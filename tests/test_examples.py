"""Examples must keep running end-to-end (the reference's example/ scripts
are exercised by CI the same way — SURVEY §2.7 runtime_functions.sh), and
the training ones must hit NUMERIC floors — round-2 verdict #6: parsing
the printed accuracy, not just the string, so a wrong-but-running model
fails."""
import os
import re
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parse_metric(out, pattern):
    m = re.search(pattern, out)
    assert m, f"metric {pattern!r} not printed:\n{out}"
    return float(m.group(1))


def _run(script, *args, timeout=280):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"{script} failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout + r.stderr


def test_train_mnist_gluon(tmp_path):
    # explicit empty data dir pins the synthetic fallback (hermetic: never
    # trains on a host's real MNIST download); the printed accuracy is
    # parsed and gated — 3 epochs on the separable synthetic set must
    # clear 0.9 (a broken loss/optimizer lands near 0.1)
    out = _run("train_mnist.py", "--epochs", "3", "--batch-size", "256",
               "--data-dir", str(tmp_path))
    acc = _parse_metric(out, r"final accuracy:\s*([0-9.]+)")
    assert acc >= 0.9, f"MNIST example accuracy {acc} below 0.9 floor"


def test_train_nmt_token_accuracy_floor():
    # reversal-task NMT: vocab 16 / seq 6 reaches ~1.0 greedy-decode
    # token accuracy in 300 steps (calibrated; chance is ~0.08) — the
    # 0.6 floor fails any wrong loss/teacher-forcing/decode regression
    out = _run("train_nmt.py", "--steps", "300", "--units", "32",
               "--batch-size", "32", "--num-layers", "1",
               "--vocab", "16", "--seq-len", "6")
    acc = _parse_metric(out, r"greedy-decode token accuracy:\s*([0-9.]+)")
    assert acc >= 0.6, f"NMT token accuracy {acc} below 0.6 floor"


def test_train_ssd_map_floor():
    # round-4 verdict #10: every driver-config example carries a numeric
    # gate. 60 steps on the painted-box synthetic set reach mAP 1.0
    # (calibrated); 0.6 fails any matcher/loss/decoder regression while
    # staying far from flakiness
    out = _run("train_ssd.py", "--steps", "60", "--batch-size", "8",
               "--data-shape", "64", timeout=420)
    val = _parse_metric(out, r"mAP:\s*([0-9.]+)")
    assert val >= 0.6, f"SSD example mAP {val} below 0.6 floor"
    final_loss = _parse_metric(out, r"final loss=([0-9.]+)")
    assert final_loss < 2.5, f"SSD final loss {final_loss} above 2.5"


def test_train_faster_rcnn_loss_decreases():
    # joint RPN+RCNN loss on the painted-box synthetic set, read on the
    # example's four held-out batches before and after training: 12.20 →
    # 3.7-6.7 in 60 steps (calibrated on nine runs, PR 26: this tree and
    # its parent, --lr 5e-4 moved by parts in a million; after 30 steps
    # it is 7.2-11.3, too close to call). With --lr 0 it stays at 12.20
    # and the gate fails. The lines printed along the way are fresh
    # batches whose loss swings 6-17 untrained, and a change of rounding
    # in the seventh digit moves every one of them after the sixth step:
    # the gate this replaces (best of steps 10, 20, 29 under 0.7 x step 0)
    # passed with --lr 0 and flipped with such a change
    out = _run("train_faster_rcnn.py", "--steps", "60",
               "--image-size", "96", timeout=420)
    m = re.search(r"held-out loss\s+([0-9.]+)\s+->\s+([0-9.]+)", out)
    assert m, out
    before, after = float(m.group(1)), float(m.group(2))
    assert after < 0.7 * before, out


def test_pretrain_bert_mlm_loss_floor():
    # tiny BERT memorizes the fixed synthetic batch: mlm_loss ~0.014 in
    # 150 steps (calibrated; ln(512) ≈ 6.2 at init)
    out = _run("pretrain_bert.py", "--vocab-size", "512",
               "--batch-size", "16", "--seq-length", "32",
               "--num-layers", "2", "--units", "64", "--num-heads", "4",
               "--hidden-size", "128", "--steps", "150", "--lr", "3e-3",
               "--no-bf16", timeout=280)
    final = _parse_metric(out, r"final mlm_loss=([0-9.]+)")
    assert final < 0.5, f"BERT example mlm loss {final} above 0.5 floor"


def test_train_word_lm_perplexity_floor():
    # deterministic bigram-chain grammar (vocab 50, chance ppl 50):
    # the 2-layer LSTM reaches ppl ~1.01 in 8 epochs (calibrated) — a 5.0
    # gate fails any RNN/embedding/BPTT regression
    out = _run("train_word_lm.py", "--epochs", "8", "--tokens", "20000",
               "--lr", "5e-3", timeout=280)
    ppl = _parse_metric(out, r"final perplexity=([0-9.]+)")
    assert ppl < 5.0, f"word-LM perplexity {ppl} above the 5.0 gate"


def test_train_imagenet_memorizes():
    # resnet18 on one fixed synthetic batch: loss → ~0 in 60 steps
    # (calibrated) — gates the ShardedTrainer + vision-zoo + SGD path
    out = _run("train_imagenet.py", "--network", "resnet18_v1",
               "--batch-size", "16", "--num-classes", "10",
               "--image-shape", "3,32,32", "--steps-per-epoch", "60",
               "--epochs", "1", "--lr", "0.05", "--no-bf16", timeout=420)
    final = _parse_metric(out, r"final loss=([0-9.]+)")
    assert final < 0.5, f"imagenet example loss {final} above 0.5 floor"


def test_train_dcgan_matches_data_statistics():
    """DCGAN (adversarial family, ref: example/gan/dcgan.py): after a
    short run the generator's pixel-mean map must approach the data's
    radial structure (GAN losses oscillate, so the gate is on sample
    statistics), and both players must still be in the game (neither
    loss collapsed to 0)."""
    out = _run("train_dcgan.py", "--steps", "150")
    # anchor to the FINAL summary line — the per-step logs also contain
    # d_loss/g_loss and re.search would read step 0 otherwise
    l1 = _parse_metric(out, r"pixel-mean-map L1\s*([0-9.]+)")
    d_loss = _parse_metric(
        out, r"pixel-mean-map L1\s*[0-9.]+\s+d_loss\s*([0-9.]+)")
    g_loss = _parse_metric(
        out, r"pixel-mean-map L1\s*[0-9.]+\s+d_loss\s*[0-9.]+\s*"
             r"g_loss\s*([0-9.]+)")
    assert l1 < 0.12, f"generated stats L1 {l1} too far from data"
    assert d_loss > 0.05, "discriminator collapsed (training broken)"
    assert g_loss > 0.05, "generator loss collapsed (D gave up)"


def test_train_vae_elbo_floor():
    """VAE (generative family, ref: example/autoencoder): reconstruction
    must get tight on the blob distribution, the KL must stay in a sane
    band (collapse -> ~0; blowup -> huge), and prior samples must carry
    the data's spatial statistics."""
    out = _run("train_vae.py", "--steps", "400", timeout=420)
    rec = _parse_metric(out, r"final rec\s*([0-9.]+)")
    kl = _parse_metric(out, r"final rec\s*[0-9.]+\s+kl\s*([0-9.]+)")
    l1 = _parse_metric(out, r"prior-sample L1\s*([0-9.]+)")
    assert rec < 0.05, f"reconstruction MSE {rec} too high"
    assert 0.5 < kl < 100, f"KL {kl} collapsed or blew up"
    # calibrated: healthy run lands ~0.03; a decoder whose prior samples
    # collapse to the background constant scores ~0.19 — 0.1 separates
    # them with margin on both sides
    assert l1 < 0.1, f"prior samples L1 {l1} far from data statistics"
