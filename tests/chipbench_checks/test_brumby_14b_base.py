"""The brumby_14b_base configuration: its file against the catalog row it was
copied from, the parameter counts at the published widths (from the shapes:
nothing is allocated), the operation count, the retention's least work for
its share of the roofline, and the cell's runner rehearsed on the CPU at a
small size, with the configuration's own comparison: every layer of the
reference on the system's own input to it, the control at 3 mantissa bits,
the planted faults."""
import copy
import importlib.util
import os

import jax
import numpy as np
import pytest

from mxnet_tpu.gluon.model_zoo import brumby

from chipbench import layer_metrics, manifest, reduce_trace, run as bench_run
from chipbench.models import brumby_14b_base as bm
from chipbench.runners import train

PEAKS = manifest.load_peaks("TPU v5 lite")
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
BENCH = manifest.load_manifest()
CONFIG = manifest.load_config(BENCH, "brumby_14b_base")
ARGS = CONFIG["args"]
CELL = "brumby_14b_base.fused_bs1_seq8192"
MINE = {"retention_scan_ms_per_step", "retention_rest_ms_per_step",
        "retention_roofline"}
TRAFFIC = manifest.load_traffic("fused_k4_bs1_seq8192")

# the ``config`` of the row "Brumby-14B-Base" in the model-configs guide's
# architectures.jsonl (source_url https://huggingface.co/manifestai/
# Brumby-14B-Base/blob/main/config.json), copied key for key
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "vocab_size": 18992}
# what may never be cut: a hidden or intermediate size, a head size, and here
# the counts of heads too (the layers are held whole)
WIDTHS = ("hidden_size", "intermediate_size", "head_dim",
          "num_attention_heads", "num_key_value_heads")

SMALL = dict(
    ARGS, vocab_size=128, hidden_size=64, intermediate_size=96,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, chunk_size=8, init_sigma=0.1, compute_dtype=None,
    master_dtype=None,
    optimizer_params=dict(ARGS["optimizer_params"], learning_rate=1e-3))
SMALL_TRAFFIC = dict(TRAFFIC, seq=24, batch_per_chip=2, k=2)


def test_published_is_the_catalog_row():
    assert CONFIG["published"] == CATALOG
    entry = manifest.by_name(BENCH["configs"], "brumby_14b_base",
                             "configuration")
    assert CONFIG["source"] == entry["source"] \
        == "https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/" \
           "config.json"
    # one cell, on the traffic file that is there (Nemotron's)
    cell = manifest.by_name(BENCH["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("brumby_14b_base", "fused_k4_bs1_seq8192", 1)
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == "brumby_14b_base"] == [CELL]
    assert "system's own input" in CONFIG["comparison"]
    assert CONFIG["compare"].endswith(".compare")
    assert CONFIG["reference"].endswith(".reference_kept")
    assert CONFIG["reference_samples"] == 1


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_every_key_runs_as_published_but_the_reduced_ones(key):
    want = REDUCED.get(key, CATALOG[key])
    # at the top level for the driver's check, under args for the builder
    assert CONFIG[key] == want and ARGS[key] == want
    assert (key in CONFIG["reduced"]) == (key in REDUCED)


def test_reduced_names_no_width_and_the_cut_is_at_the_floors():
    entry = manifest.by_name(BENCH["configs"], "brumby_14b_base",
                             "configuration")
    assert CONFIG["reduced"] == sorted(REDUCED) == entry["reduced"]
    assert not set(CONFIG["reduced"]) & set(WIDTHS)
    assert not any(key.endswith(("_dim", "_rank")) for key in REDUCED)
    counts = ARGS["published_counts"]
    assert counts == CONFIG["published_counts"] == {
        key: CATALOG[key] for key in ("num_hidden_layers", "vocab_size")}
    # one period is one layer; four layers and an eighth of the vocabulary
    # are the guide's floors
    assert ARGS["num_hidden_layers"] == 4
    assert 8 * ARGS["vocab_size"] == counts["vocab_size"] == 151936
    assert "one of 8 chips" in CONFIG["deployment"]
    # what config.json does not carry is the builder's, and said
    assert ARGS["chunk_size"] == 1024 and ARGS["retention_eps"] == 1e-6
    assumed = " ".join(CONFIG["assumed"])
    for word in ("from memory", "p = 2", "log sigmoid", "1e-6", "q_norm",
                 "rotate-half", "chunk_size 1024", "8 bytes a parameter",
                 "Normal(0.02)", "shifted by one"):
        assert word in assumed, word


@pytest.fixture(scope="module")
def shapes():
    """``{name: count}`` of the net's parameters at the published widths:
    built, never initialized, so nothing is allocated."""
    net = brumby.brumby(**{key: ARGS[key] for key in bm.MODEL_KEYS})
    params = net.collect_params()
    assert all(p._data is None and p.grad_req != "null"
               for p in params.values())
    return {name: int(np.prod(p.shape)) for name, p in params.items()}


def count(shapes, *parts):
    return sum(n for name, n in shapes.items()
               if all(part in name for part in parts))


@pytest.mark.parametrize("parts, want", [
    (("layer0_",), 330_352_896),                # a layer
    (("layer0_mixer_q_weight",), 5120 * 5120),
    (("layer0_mixer_k_weight",), 1024 * 5120),
    (("layer0_mixer_v_weight",), 1024 * 5120),
    (("layer0_mixer_g_weight",), 8 * 5120),     # a gate a key/value head
    (("layer0_", "bias"), 0),                   # no bias anywhere
    (("layer0_mixer_o_weight",), 5120 * 5120),
    (("layer0_mixer_", "norm_gamma"), 2 * 128),  # one weight a head size
    (("layer0_mlp_", "weight"), 3 * 5120 * 17408),
    (("layer0_", "norm_gamma"), 2 * 5120 + 2 * 128),
    (("_layers_",), 4 * 330_352_896),
    (("embed_weight",), 18992 * 5120),          # an eighth of 151936 rows
    (("head_weight",), 18992 * 5120),           # untied
    (("",), 1_515_894_784),                     # the cut
], ids=lambda v: "_".join(v).strip("_") or "all" if isinstance(v, tuple)
    else None)
def test_parameter_counts_at_published_widths(shapes, parts, want):
    assert count(shapes, *[p.replace("layer0_", "hybriddecoderlayer0_")
                           for p in parts]) == want


def test_state_is_eight_bytes_a_parameter_and_clears_the_floor(shapes):
    # bf16 weight, gradient and two Adam moments; no separate compute copy
    assert ARGS["compute_dtype"] == ARGS["master_dtype"] == "bfloat16"
    assert ARGS["optimizer"] == "adamw"
    assert sum(shapes.values()) == 1_515_894_784
    assert round(1_515_894_784 * 8 / 1e9, 2) == 12.13
    assert 1_515_894_784 * 8 / 16e9 > 0.25
    assert "1,515,894,784" in CONFIG["deployment"]


def test_operation_count_comes_from_the_shapes():
    flops = bm.flops_per_sample(ARGS, TRAFFIC)
    macs = bm.product_macs_per_token(ARGS, TRAFFIC["seq"])
    assert flops == 6 * sum(macs.values()) * 8192 == 80_809_071_476_736
    assert macs == {
        "mlp": 4 * 267_386_880, "retention_proj": 4 * 62_955_520,
        "retention": 4 * 56_364_032, "head": 97_239_040}
    # a layer's 773.4 MFLOP a token forward, 112.7 of them the retention's
    layer = 2 * (macs["mlp"] + macs["retention_proj"] + macs["retention"]) / 4
    assert round(layer / 1e6, 1) == 773.4
    assert round(2 * macs["retention"] / 4 / 1e6, 1) == 112.7
    # the state read and its update at the symmetric second power, 8256 x
    # (128 + 1), and the causal triangle of a chunk of 1024
    assert bm.retention_macs_per_token(ARGS, 8192) \
        == 40 * 8256 * 129 + 8 * 8256 * 129 + 40 * 2 * 512 * 128
    shares = {k: round(100 * v / sum(macs.values()), 1)
              for k, v in macs.items()}
    assert shares == {"mlp": 65.1, "retention_proj": 15.3, "retention": 13.7,
                      "head": 5.9}
    # nothing grows with the sequence but the triangle, up to a chunk
    assert bm.product_macs_per_token(ARGS, 32768) == macs
    assert bm.retention_macs_per_token(ARGS, 512) \
        == 48 * 8256 * 129 + 40 * 2 * 256 * 128


def reader():
    spec = importlib.util.spec_from_file_location(
        "retention_roofline", os.path.join(
            layer_metrics.HERE, "retention_roofline.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_least_work_of_the_retention_and_its_share_of_the_roofline():
    operations = bm.retention_operations(ARGS, 1, 8192)
    assert operations == 3 * 2 * 56_364_032 * 8192 * 4
    arrays = 8192 * (2 * 40 + 2 * 8) * 128 * 2
    states = 7 * 8 * 8256 * 129 * 4
    assert bm.retention_bytes(ARGS, 1, 8192) == 2 * (arrays + states) * 4
    # 11.08 TFLOP at 197 TFLOP/s is 56.2 ms, the bytes need 4.3 ms: the
    # operations bound the least time, and two sequences need twice of both
    assert operations / PEAKS["bf16_flops_per_s"] \
        == pytest.approx(56.25e-3, rel=1e-3)
    assert bm.retention_bytes(ARGS, 1, 8192) / PEAKS["hbm_bytes_per_s"] \
        == pytest.approx(4.3e-3, rel=2e-2)
    assert bm.retention_operations(ARGS, 2, 8192) == 2 * operations
    assert bm.retention_bytes(ARGS, 1, 512) == 2 * (512 * 96 * 128 * 2) * 4
    share = reader().roofline(bm, ARGS, 1, 8192, 562.5, PEAKS)
    assert share == pytest.approx(10.0, abs=0.01)
    # two sequences a step need twice the time
    assert reader().roofline(bm, ARGS, 2, 8192, 1125.0, PEAKS) \
        == pytest.approx(share)


def test_the_metric_files_are_the_cells_alone():
    specs = layer_metrics.for_cell(CELL)
    assert MINE <= set(specs)
    for other in ("granite_4_0_h_micro.fused_bs1_seq4096",
                  "nemotron_3_nano_30b_a3b.fused_bs1_seq8192"):
        assert not MINE & set(layer_metrics.for_cell(other))
    assert specs["retention_scan_ms_per_step"]["scopes"] == ["retention.scan"]
    assert specs["retention_rest_ms_per_step"]["scopes"] == ["retention"]
    assert specs["retention_roofline"]["model"] == bm.__name__
    assert {specs[name]["layer"] for name in MINE} \
        == {"power retention mixer (ops)"}
    for entry in BENCH["per_layer"]:
        if entry["name"] in MINE:
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "train_samples_per_s_per_chip"
    # a run without a live net or a trace reads nothing and does not raise
    live, bm.LIVE[:] = list(bm.LIVE), []
    try:
        assert reader().read({"window_s": 1.0},
                             specs["retention_roofline"], {"chips": 1}) is None
    finally:
        bm.LIVE[:] = live


def test_the_same_seed_gives_the_same_weights_and_batch():
    mesh = __import__("mxnet_tpu").parallel.make_mesh(
        {"data": 1}, devices=jax.devices()[:1])
    seed = 2 ** 31 + 9
    x, y = bm.make_batch(SMALL, {"seq": 8}, 2, np.random.default_rng(seed))
    again = bm.make_batch(SMALL, {"seq": 8}, 2, np.random.default_rng(seed))
    assert (x == again[0]).all() and (y == again[1]).all()
    assert (x[:, 1:] == y[:, :-1]).all() and x.max() < SMALL["vocab_size"]
    assert bm.BATCH == [(2, 8)]
    # the issue's traffic: uniform over the rows held, from the first row on
    wide, _ = bm.make_batch(SMALL, {"seq": 4096}, 2, np.random.default_rng(1))
    assert len(np.unique(wide[:, :32])) > 16
    assert set(np.unique(wide)) == set(range(SMALL["vocab_size"]))
    logits = [bm.reference_logits(bm.build(SMALL, mesh, seed)[0], x)
              for _ in range(2)]
    assert (logits[0] == logits[1]).all() and np.abs(logits[0]).max() > 0
    checks = [bm.state_check_inputs(SMALL, 24, seed) for _ in range(2)]
    assert all((a == b).all() for a, b in zip(*checks))


def test_timed_run_of_the_cell_at_a_small_size():
    config = dict(CONFIG, args=SMALL)
    facts = train.run(config, SMALL_TRAFFIC, jax.devices()[:1], 2 ** 31 + 11,
                      0.5)
    assert facts["correct"] is True and facts["failed"] == 0
    assert facts["checks"]["last_loss"] < facts["checks"]["first_loss"]
    assert "forward" not in facts["checks"]
    assert facts["values"]["flops_per_sample"] == bm.flops_per_sample(
        SMALL, SMALL_TRAFFIC)


def fake_device_plane(monkeypatch):
    """A CPU trace has no device plane: put one op under the first host
    annotation, so that the traced path runs to its end (as
    ``test_train_runner.py`` does)."""
    real = reduce_trace.read_planes

    def read(path):
        annotations = real(path)[1]
        name, start, end = annotations[0]
        return {0: [("fusion.1", "fusion", start, (start + end) / 2)]}, \
            annotations, [], []
    monkeypatch.setattr(reduce_trace, "read_planes", read)


CONDITIONS = {"layer_error", "layer_rms_error", "state_path_error",
              "state_zeroed_breaks_it"}


def test_traced_run_of_the_cell_goes_through_compare(tmp_path, monkeypatch,
                                                     capsys):
    fake_device_plane(monkeypatch)
    config = dict(CONFIG, args=SMALL, reference_samples=2)
    facts = train.run(config, SMALL_TRAFFIC, jax.devices()[:1], 5, 0.3,
                      str(tmp_path / "trace"))
    forward = facts["checks"]["forward"]
    assert facts["correct"] is True and forward["ok"]
    assert forward["samples"] == 2 and forward["compared"] == 2 * 24 * 128
    assert forward["share"] < 1e-4              # float32 at this size
    conditions = forward["conditions"]
    assert set(conditions) == CONDITIONS
    assert all(c["ok"] for c in conditions.values())
    assert conditions["layer_error"]["value"] < 1e-4
    assert conditions["layer_rms_error"]["value"] < 1e-4
    assert conditions["state_path_error"]["value"] < 1e-4
    assert conditions["state_zeroed_breaks_it"]["value"] > 0.1
    assert conditions["state_path_error"]["limit"] == bm.STATE_LIMIT \
        == conditions["state_zeroed_breaks_it"]["limit"]
    assert (conditions["layer_error"]["limit"],
            conditions["layer_rms_error"]["limit"]) \
        == (bm.LAYER_LIMIT, bm.LAYER_RMS_LIMIT)
    said = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("chipbench: retention {")]
    printed = __import__("json").loads(said[0][len("chipbench: retention "):])
    # one reading a layer, and the plain end-to-end distance for the record
    assert len(printed["layers"]) == SMALL["num_hidden_layers"]
    assert 0 <= printed["end_to_end_share"] < 1e-4
    cell = manifest.by_name(BENCH["workloads"], CELL, "cell")
    line = bench_run.result_line(copy.deepcopy(BENCH), cell, facts, PEAKS,
                                 DEVICE, 1.5, True)
    assert line["correct"] is True
    assert {"mfu", "device_busy_ms_per_step", "device_idle_share",
            "compile_s"} <= set(line["metrics"])
    # the scope metrics need a device trace (there is none on a CPU) and are
    # left out, not raised
    assert not MINE & set(line["metrics"])


@pytest.fixture(scope="module")
def small():
    """``(kept, tokens, logits, hidden)``: a small net's float32 forward as a
    system would hand it to the comparison."""
    import mxnet_tpu as mx
    mesh = mx.parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    net, _ = bm.build(SMALL, mesh, 11)
    tokens, _ = bm.make_batch(SMALL, {"seq": 24}, 1, np.random.default_rng(3))
    logits, *hidden = [out.asnumpy() for out in net(mx.nd.array(tokens))]
    return bm.reference_kept(net, tokens), tokens, logits, hidden


def test_the_reference_a_stage_at_a_time_is_the_plain_reference(small):
    kept, tokens, logits, hidden = small
    assert set(kept) == {"params", "args", "seed", "logits"}
    assert len(hidden) == SMALL["num_hidden_layers"]
    assert hidden[0].shape == (1, 24, SMALL["hidden_size"])
    plain_outs, plain_logits = bm.staged_reference(
        kept["params"], kept["args"], tokens)
    assert (plain_logits == kept["logits"]).all()
    # on its own outputs the staged reference is the plain one
    outs, staged_logits = bm.staged_reference(
        kept["params"], kept["args"], tokens, hidden=plain_outs)
    assert all((a == b).all() for a, b in zip(outs, plain_outs))
    assert (staged_logits == plain_logits).all()
    errors = bm.stage_errors(kept, tokens, logits, hidden)
    assert all(e["max"] < 1e-5 and e["rms"] < 1e-5 for e in errors["layers"])
    assert errors["max_abs_error"] < 1e-5 * errors["max_abs_reference"]
    assert errors["end_to_end_share"] < 1e-5


def test_a_fault_in_one_layer_is_that_layers_alone(small):
    """Every stage reads the system's own input, so a layer that is wrong
    fails its own stage and the ones after it are judged on what they got."""
    kept, tokens, logits, hidden = small
    faulty = [h.copy() for h in hidden]
    faulty[0][0, 5] *= 1.5                      # one row of the first layer
    errors = bm.stage_errors(kept, tokens, logits, faulty)["layers"]
    assert errors[0]["max"] > 0.1
    # the second layer's output was computed from the sound row: it differs
    # from the reference on the faulty one, and that shows there too
    assert errors[1]["max"] > 1e-3
    said = bm.judged(kept, tokens, logits, faulty, (0.0, 1.0))
    assert said["conditions"]["layer_error"]["ok"] is False
    # a fault in the head alone is the runner's to see: the logits compared
    # are the reference's head on the system's last hidden state
    said = bm.judged(kept, tokens, logits * 1.1, hidden, (0.0, 1.0))
    assert all(c["ok"] for c in said["conditions"].values())
    assert said["max_abs_error"] > 0.05 * said["max_abs_reference"]
    assert train.bounded(1, said["max_abs_error"], said["max_abs_reference"],
                         True)["ok"] is False


def test_rounding_helper_and_the_control(small, capsys):
    import jax.numpy as jnp
    values = np.random.default_rng(0).standard_normal(4096) \
        .astype(np.float32) * 3
    as_bf16 = np.asarray(jnp.asarray(values).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    assert (np.asarray(bm.rounded(jnp.asarray(values), 7)) == as_bf16).all()
    coarse = np.asarray(bm.rounded(jnp.asarray(values), 3))
    assert 0.02 < np.abs(coarse / values - 1).max() <= 2.0 ** -4
    assert bm.rounded(values, None) is values
    kept, tokens, logits, hidden = small
    # the reference itself at bfloat16's 7 bits passes, at 3 bits it does not
    fine = bm.control(kept, tokens, bits=7)
    assert all(c["ok"] for c in fine["conditions"].values())
    assert train.bounded(1, fine["max_abs_error"], fine["max_abs_reference"],
                         True)["ok"]
    coarse = bm.control(kept, tokens)
    failed = [name for name, c in coarse["conditions"].items() if not c["ok"]]
    assert "layer_rms_error" in failed
    assert coarse["conditions"]["layer_rms_error"]["value"] \
        > 4 * fine["conditions"]["layer_rms_error"]["value"]
    assert capsys.readouterr().out.count("chipbench: retention {") == 2


def test_compare_fails_by_either_condition_of_the_state_path(small,
                                                             monkeypatch):
    """An operator that carried nothing passes the forward at seeded gates
    and fails the first condition; one that is exact everywhere fails the
    second if the fault it plants breaks nothing."""
    kept, tokens, logits, hidden = small
    monkeypatch.setattr(train, "system_outputs",
                        lambda *a, **k: [logits] + hidden)
    honest = bm.compare(kept, None, SMALL, tokens, tokens)
    assert set(honest["conditions"]) == CONDITIONS
    assert all(c["ok"] for c in honest["conditions"].values())
    assert honest["samples"] == 1 and honest["compared"] == 24 * 128

    real = bm.operator_outputs
    monkeypatch.setattr(bm, "operator_outputs",
                        lambda *a: real(*a[:5], carry=False))
    faulty = bm.compare(kept, None, SMALL, tokens, tokens)["conditions"]
    assert faulty["state_path_error"]["ok"] is False
    assert faulty["state_path_error"]["value"] \
        == faulty["state_zeroed_breaks_it"]["value"] > bm.STATE_LIMIT
    assert faulty["layer_error"]["ok"] and faulty["layer_rms_error"]["ok"]

    monkeypatch.setattr(bm, "operator_outputs",
                        lambda *a: real(*a[:5], carry=True))
    blind = bm.compare(kept, None, SMALL, tokens, tokens)["conditions"]
    assert blind["state_path_error"]["ok"] is True
    assert blind["state_zeroed_breaks_it"]["ok"] is False
