"""The lfm2_24b_a2b configuration: its file against the catalog row it was
copied from, the parameter counts at the published widths (from the shapes:
nothing is allocated) and the deployment's count, the operation count, the
least work behind the short convolution's share of its roofline, the router's
load from the layers' counters, and the cell's runner rehearsed on the CPU at a
small size, with the configuration's own comparison."""
import copy
import json

import jax
import numpy as np
import pytest

from mxnet_tpu.gluon.model_zoo import lfm2_moe

from chipbench import layer_metrics, manifest, reduce_trace, run as bench_run
from chipbench.layer_metrics import expert_load, router_load, shape_roofline
from chipbench.models import lfm2_24b_a2b as lm
from chipbench.runners import train

PEAKS = manifest.load_peaks("TPU v5 lite")
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
BENCH = manifest.load_manifest()
CONFIG = manifest.load_config(BENCH, "lfm2_24b_a2b")
ARGS = CONFIG["args"]
CELL = "lfm2_24b_a2b.fused_bs2_seq8192"
TRAFFIC = manifest.load_traffic("fused_k4_bs2_seq8192")

# the ``config`` of the row "LFM2-24B-A2B" in the model-configs guide's
# architectures.jsonl (source_url https://huggingface.co/LiquidAI/
# LFM2-24B-A2B/blob/main/config.json), copied key for key
PUBLISHED_TYPES = ["full_attention" if i % 4 == 2 else "conv"
                   for i in range(40)]
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": PUBLISHED_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
# one leading dense conv layer, then published layers 2-9
REDUCED = {"layer_types": ["conv"] + ["full_attention", "conv", "conv",
                                      "conv"] * 2,
           "num_dense_layers": 1, "num_experts": 8, "num_hidden_layers": 9,
           "vocab_size": 8192}
# what may never be cut: a hidden, intermediate or head size, the experts a
# token, the convolution's taps, the key/value heads
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_experts_per_tok", "conv_L_cache", "num_attention_heads",
          "num_key_value_heads")

SMALL = dict(
    ARGS, vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    num_experts=8, init_sigma=0.1, compute_dtype=None, master_dtype=None,
    published_counts=dict(ARGS["published_counts"], num_experts=16),
    optimizer_params=dict(ARGS["optimizer_params"], learning_rate=1e-3))
SMALL_TRAFFIC = dict(TRAFFIC, seq=24, k=2)


def test_published_is_the_catalog_row():
    assert CONFIG["published"] == CATALOG
    entry = manifest.by_name(BENCH["configs"], "lfm2_24b_a2b", "configuration")
    assert CONFIG["source"] == entry["source"]
    assert CONFIG["compare"].endswith(".compare")
    assert CONFIG["reference"].endswith(".reference_kept")
    assert CONFIG["reference_samples"] == 1


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_every_key_runs_as_published_but_the_reduced_ones(key):
    want = REDUCED.get(key, CATALOG[key])
    # at the top level, where the configuration is checked against its
    # source, and under args, where build() reads it
    assert CONFIG[key] == want and ARGS[key] == want
    assert (key in CONFIG["reduced"]) == (key in REDUCED)


def test_reduced_names_no_width_and_the_cut_keeps_the_floors():
    entry = manifest.by_name(BENCH["configs"], "lfm2_24b_a2b", "configuration")
    assert CONFIG["reduced"] == sorted(REDUCED) == entry["reduced"]
    assert not set(CONFIG["reduced"]) & set(WIDTHS)
    assert not any(key.endswith(("_dim", "_rank")) for key in REDUCED)
    counts = ARGS["published_counts"]
    assert counts == CONFIG["published_counts"] == {
        key: CATALOG[key] for key in ("layer_types", "num_dense_layers",
                                      "num_experts", "num_hidden_layers",
                                      "vocab_size")}
    # one of 8 chips sharing each layer: an eighth of the experts (8, the
    # floor) and of the vocabulary; the leading dense layers once, then two
    # whole periods of the published pattern (layers 2-9): 8 expert layers,
    # attention at 1 in 4 among them
    assert 8 * ARGS["num_experts"] == counts["num_experts"]
    assert 8 * ARGS["vocab_size"] == counts["vocab_size"]
    assert ARGS["num_experts"] >= 8 and ARGS["first_expert"] == 0
    assert lm.experts_held(ARGS) == ((0, 8), 64)
    types = ARGS["layer_types"]
    assert types[:ARGS["num_dense_layers"]] == PUBLISHED_TYPES[:1]
    assert types[1:] == PUBLISHED_TYPES[2:10]
    assert len(types) - ARGS["num_dense_layers"] == 8 >= 4
    assert types[1:].count("full_attention") * 4 == len(types) - 1
    assert "one of 8 chips" in CONFIG["deployment"]
    assert ARGS["expert_bias_update_rate"] == 1e-3


@pytest.fixture(scope="module")
def shapes():
    """``{name: (count, trainable)}`` of the net's parameters at the
    published widths: built, never initialized, so nothing is allocated."""
    held, width = lm.experts_held(ARGS)
    net = lfm2_moe.lfm2_moe(
        experts_held=held, return_routes=True, num_experts=width,
        expert_bias_update_rate=ARGS["expert_bias_update_rate"],
        **{key: ARGS[key] for key in lm.MODEL_KEYS})
    params = net.collect_params()
    assert all(p._data is None for p in params.values())
    return {name: (int(np.prod(p.shape)), p.grad_req != "null")
            for name, p in params.items()}


def count(shapes, *parts, buffers=False):
    return sum(n for name, (n, trainable) in shapes.items()
               if all(part in name for part in parts)
               and (buffers or trainable))


@pytest.mark.parametrize("parts, want", [
    (("layer0_",), 89_139_200),                 # the dense conv layer
    (("layer0_mixer_in_",), 2048 * 6144),
    (("layer0_mixer_conv_weight",), 2048 * 3),
    (("layer0_mixer_out_",), 2048 * 2048),
    (("layer0_mlp_", "weight"), 3 * 2048 * 11776),
    (("layer2_",), 92_416_000),                 # an expert conv layer
    (("layer1_",), 86_118_528),                 # an expert attention layer
    (("layer1_mixer_q_weight",), 2048 * 2048),
    (("layer1_mixer_k_weight",), 2048 * 512),
    (("layer1_mixer_q_norm",), 64),
    (("layer1_mlp_router_weight",), 64 * 2048),
    (("layer1_mlp_expert_w",), 8 * 3 * 2048 * 1536),
    (("_mixer_conv_weight",), 7 * 2048 * 3),
    (("embed_weight",), 8192 * 2048),           # an eighth of 65536, tied
    (("final_norm",), 2048),
    (("",), 832_651_520),                       # the cut
], ids=lambda v: "_".join(v).strip("_") or "all" if isinstance(v, tuple)
    else None)
def test_parameter_counts_at_published_widths(shapes, parts, want):
    assert count(shapes, *parts) == want


def test_the_deployment_states_the_count_of_the_built_model(shapes):
    total = count(shapes, "")
    assert total == sum(lm.parameter_counts(ARGS).values()) == 832_651_520
    assert total == 89_139_200 + 6 * 92_416_000 + 2 * 86_118_528 \
        + 8192 * 2048 + 2048
    assert "832,651,520" in CONFIG["deployment"]
    # bf16 weight, gradient and two Adam moments: 8 bytes, 6.66 GB
    assert ARGS["compute_dtype"] == ARGS["master_dtype"] == "bfloat16"
    assert ARGS["optimizer"] == "adamw"
    assert round(total * 8 / 1e9, 2) == 6.66 and "6.66 GB" in CONFIG[
        "deployment"]
    # besides them, each expert layer's bias, its router's counts of all 64
    # experts and the held experts' counters
    assert count(shapes, "", buffers=True) - total == 8 * (64 + 64 + 8 + 1)


def test_operation_count_comes_from_the_shapes():
    flops = lm.flops_per_sample(ARGS, TRAFFIC)
    macs = lm.product_macs_per_token(ARGS, TRAFFIC["seq"])
    assert flops == 6 * sum(macs.values()) * 8192 == 14_740_327_759_872
    assert macs == {
        "shortconv_proj": 117_440_512, "dense_mlp": 72_351_744,
        "routed_experts": 37_748_736, "attention": 33_554_432,
        "attention_proj": 20_971_520, "head": 16_777_216,
        "router": 1_048_576}
    shares = {part: round(100 * n / sum(macs.values()), 1)
              for part, n in macs.items()}
    assert shares == {"shortconv_proj": 39.2, "dense_mlp": 24.1,
                      "routed_experts": 12.6, "attention": 11.2,
                      "attention_proj": 7.0, "head": 5.6, "router": 0.3}
    # the routed experts: the share a uniform router sends here, 4 x 8 / 64
    # of a token's pairs
    assert macs["routed_experts"] == 8 * 0.5 * 3 * 2048 * 1536


def test_the_cell_takes_two_whole_sequences_a_step():
    assert TRAFFIC == {"runner": "train", "mode": "fused", "k": 4,
                       "batch_per_chip": 2, "seq": 8192,
                       "mesh": {"data": "all"}, "trace_dispatches": 2}
    cell = manifest.by_name(BENCH["workloads"], CELL, "cell")
    assert cell["chips"] == 1 and cell["traffic"] == "fused_k4_bs2_seq8192"
    # each held expert sees 16384 x 4 / 64 = 1024 rows a step on average
    tokens = TRAFFIC["batch_per_chip"] * TRAFFIC["seq"]
    assert tokens * ARGS["num_experts_per_tok"] / 64 == 1024


def test_least_work_of_the_short_convolution():
    ops = lm.short_conv_operations(ARGS, 2, 8192)
    assert ops == 7 * 2 * 8192 * 2048 * (7 + 15)
    moved = lm.short_conv_bytes(ARGS, 2, 8192)
    # B, C, x in and y out forward; dy, B, C, x in and three gradients out
    # backward; the taps once each way; 7 conv layers
    assert moved == 7 * (11 * 2 * 8192 * 2048 * 2 + 2 * 2048 * 3 * 2)
    # 5.17 GB at 819 GB/s is 6.3 ms; the operations need 0.03 ms: bytes
    # bound it
    share = shape_roofline.roofline(ops, moved, 20.0, PEAKS)
    assert share == pytest.approx(31.5, abs=0.1)
    spec = layer_metrics.load_all()["short_conv_roofline"]
    assert getattr(lm, spec["operations"]) is lm.short_conv_operations
    assert getattr(lm, spec["bytes"]) is lm.short_conv_bytes
    assert spec["scopes"] == ["shortconv.conv"]


def test_the_routers_load_over_all_its_experts(monkeypatch):
    load = [{"rows": [1024] * 64, "steps": 1}] * 7 \
        + [{"rows": [2048, 0] + [1024] * 62, "steps": 1}]
    assert expert_load.max_over_mean(load) == pytest.approx(2.0)
    # the router's counts of every expert layer, from the layers' counters
    spec = layer_metrics.load_all()["router_load_max_over_mean"]
    monkeypatch.setattr(expert_load, "load_of_live_net", lambda model: [
        dict(said, router_rows=said["rows"]) for said in load])
    assert router_load.read(None, spec, {}) == pytest.approx(2.0)
    # a program without the counters (an older commit, or Nemotron's and
    # DeepSeek-V2's frozen bias) gives nothing
    monkeypatch.setattr(expert_load, "load_of_live_net", lambda model: load)
    assert router_load.read(None, spec, {}) is None
    monkeypatch.setattr(expert_load, "load_of_live_net", lambda model: None)
    assert router_load.read(None, spec, {}) is None


def test_the_same_seed_gives_the_same_weights_and_batch():
    mesh = __import__("mxnet_tpu").parallel.make_mesh(
        {"data": 1}, devices=jax.devices()[:1])
    seed = 2 ** 31 + 9
    x, y = lm.make_batch(SMALL, {"seq": 8}, 2, np.random.default_rng(seed))
    again = lm.make_batch(SMALL, {"seq": 8}, 2, np.random.default_rng(seed))
    assert (x == again[0]).all() and (y == again[1]).all()
    assert (x[:, 1:] == y[:, :-1]).all() and x.max() < SMALL["vocab_size"]
    logits = [lm.reference_logits(lm.build(SMALL, mesh, seed)[0], x)
              for _ in range(2)]
    assert (logits[0] == logits[1]).all() and np.abs(logits[0]).max() > 0


def test_timed_run_of_the_cell_at_a_small_size():
    config = dict(CONFIG, args=SMALL)
    facts = train.run(config, SMALL_TRAFFIC, jax.devices()[:1], 2 ** 31 + 11,
                      0.5)
    assert facts["correct"] is True and facts["failed"] == 0
    assert facts["checks"]["last_loss"] < facts["checks"]["first_loss"]
    assert "forward" not in facts["checks"]
    assert facts["values"]["flops_per_sample"] == lm.flops_per_sample(
        SMALL, SMALL_TRAFFIC)


def fake_device_plane(monkeypatch):
    """A CPU trace has no device plane: put one op under the first host
    annotation, so that the traced path runs to its end."""
    real = reduce_trace.read_planes

    def read(path):
        annotations = real(path)[1]
        name, start, end = annotations[0]
        return {0: [("fusion.1", "fusion", start, (start + end) / 2)]}, \
            annotations, [], []
    monkeypatch.setattr(reduce_trace, "read_planes", read)


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
    assert set(conditions) == {"routes_differ_outside_margin",
                               "routes_inside_margin", "held_pairs_computed",
                               "bias_moved_by_the_rule"}
    assert all(c["ok"] for c in conditions.values())
    assert conditions["routes_differ_outside_margin"]["value"] == 0.0
    assert conditions["held_pairs_computed"]["value"] == 0.0
    assert conditions["bias_moved_by_the_rule"]["value"] == 0.0
    out = capsys.readouterr().out
    assert "chipbench: routes " in out and "chipbench: bias_step " in out
    cell = manifest.by_name(BENCH["workloads"], CELL, "cell")
    line = bench_run.result_line(copy.deepcopy(BENCH), cell, facts, PEAKS,
                                 DEVICE, 1.5, True)
    assert line["correct"] is True
    assert {"mfu", "device_busy_ms_per_step", "device_idle_share",
            "custom_call_ms_per_step", "compile_s"} <= set(line["metrics"])
    # the scope metrics and the roofline need a device trace (there is none
    # on a CPU) and are left out, not raised
    mine = {"short_conv_ms_per_step", "short_conv_rest_ms_per_step",
            "short_conv_roofline"}
    assert mine | {"router_load_max_over_mean"} <= set(
        layer_metrics.for_cell(CELL))
    assert not mine & set(line["metrics"])
    # the router's load is read from the layers' counters: the step of the
    # comparison's, the warm-up's and the window's
    assert line["metrics"]["router_load_max_over_mean"]["value"] >= 1
    load = expert_load.load_of_live_net(lm)
    assert len(load) == 4 and all(said["steps"] == 9 for said in load)
    assert all(sum(said["router_rows"]) == 9 * 2 * 24 * 4 for said in load)
    for other in BENCH["workloads"]:
        if other["name"] != CELL:
            assert not (mine | {"router_load_max_over_mean"}) & set(
                layer_metrics.for_cell(other["name"]))


@pytest.fixture(scope="module")
def compared():
    """A net, its batch, what ``reference`` keeps and the system's own
    outputs, for comparisons fed other routes."""
    import mxnet_tpu as mx
    mesh = mx.parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    net, trainer = lm.build(SMALL, mesh, 7)
    x, y = lm.make_batch(SMALL, SMALL_TRAFFIC, 2, np.random.default_rng(7))
    net(mx.nd.array(x[:1]))
    kept = lm.reference_kept(net, x[:2])
    trainer.prepare(x[:1])
    return kept, trainer, x, y, train.system_outputs(trainer, SMALL, x, y)


def compare_with(monkeypatch, compared, outputs):
    kept, trainer, x, y, _ = compared
    monkeypatch.setattr(train, "system_outputs",
                        lambda *args, **kwargs: outputs)
    return lm.compare(kept, trainer, SMALL, x, y)


def test_compare_passes_the_systems_own_outputs(monkeypatch, compared,
                                                capsys):
    said = compare_with(monkeypatch, compared, compared[-1])
    assert said["samples"] == 2 and said["compared"] == 2 * 24 * 128
    assert all(c["ok"] for c in said["conditions"].values())
    assert said["max_abs_error"] < 1e-4 * said["max_abs_reference"]
    # the step moved the biases of the experts off the mean
    step = capsys.readouterr().out.split("chipbench: bias_step ")[1]
    step = json.loads(step.splitlines()[0])
    assert step["layers"] == 4 and 0 < step["moved"] <= 4 * 16
    assert said["conditions"]["bias_moved_by_the_rule"]["value"] == 0.0


def plant(monkeypatch, fault):
    """Put ``fault`` into the expert layers' balancing step, for the next
    program traced."""
    from mxnet_tpu.gluon.contrib import nn as contrib_nn
    move, count = contrib_nn.moved_bias, contrib_nn.balance_counts
    name, fn = {
        "frozen": ("moved_bias", lambda bias, counts, rate: bias),
        "twice": ("moved_bias", lambda bias, counts, rate: move(
            move(bias, counts, rate), counts, rate)),
        "wrong_sign": ("moved_bias", lambda bias, counts, rate: move(
            bias, counts, -rate)),
        "counts_doubled": ("balance_counts", lambda routes, experts: 2 * count(
            routes, experts)),
    }[fault]
    monkeypatch.setattr(contrib_nn, name, fn)


def test_a_frozen_bias_is_not_correct_through_the_runners_check(monkeypatch):
    """The planted control of the bias condition: a system whose bias never
    moves passes every other condition and the logits' bound, and the
    runner's check of the configuration's comparison comes out not ok."""
    import mxnet_tpu as mx
    plant(monkeypatch, "frozen")
    mesh = mx.parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    net, trainer = lm.build(SMALL, mesh, 13)
    x, y = lm.make_batch(SMALL, SMALL_TRAFFIC, 2, np.random.default_rng(13))
    net(mx.nd.array(x[:1]))
    kept = lm.reference_kept(net, x[:2])
    trainer.prepare(x[:1])
    check = train.configured_check(dict(CONFIG, args=SMALL,
                                        reference_samples=2),
                                   kept, trainer, SMALL, x, y)
    assert check["ok"] is False and check["share"] < 1e-4
    bias = check["conditions"].pop("bias_moved_by_the_rule")
    assert bias["ok"] is False and bias["value"] > 0
    assert all(c["ok"] for c in check["conditions"].values())


@pytest.mark.parametrize("fault", ["twice", "wrong_sign", "counts_doubled"])
def test_a_bias_step_off_its_rule_fails_the_bias_condition(monkeypatch,
                                                           fault):
    import mxnet_tpu as mx
    plant(monkeypatch, fault)
    mesh = mx.parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    _, trainer = lm.build(SMALL, mesh, 17)
    x, y = lm.make_batch(SMALL, SMALL_TRAFFIC, 2, np.random.default_rng(17))
    trainer.prepare(x[:1])
    said = lm.bias_step(trainer, SMALL, x, y)
    assert said["ok"] is False and said["value"] > 0


def test_an_expert_far_below_the_cut_fails_the_first_condition(
        monkeypatch, compared):
    kept, _, x, _, outputs = compared
    params, cfg, samples = kept
    _, own, scores = lm.forward_at(params, cfg, samples)
    s = scores[0].reshape(-1, 16)
    # the token of the first expert layer whose 4th and 5th scores lie
    # farthest apart: its last chosen expert gives way to its 5th
    ranked = np.sort(s, -1)
    token = int(np.argmax(ranked[:, -4] - ranked[:, -5]))
    fifth = int(np.argsort(-s[token], kind="stable")[4])
    routes = outputs[1].copy().reshape(-1, 4)
    routes[token, -1] = fifth
    bent = [outputs[0], routes.reshape(outputs[1].shape)] + list(outputs[2:])
    said = compare_with(monkeypatch, compared, bent)
    first = said["conditions"]["routes_differ_outside_margin"]
    assert first["ok"] is False and first["value"] == pytest.approx(1 / 192)


def test_rows_that_were_not_computed_fail_the_third_condition(monkeypatch,
                                                              compared):
    outputs = list(compared[-1])
    outputs[-1] = outputs[-1].copy()
    outputs[-1][0, 0] -= 1              # one pair dropped
    said = compare_with(monkeypatch, compared, outputs)
    third = said["conditions"]["held_pairs_computed"]
    assert third["ok"] is False and third["value"] == 1.0
    assert said["conditions"]["routes_differ_outside_margin"]["ok"] is True


def test_coarser_scores_put_more_tokens_inside_the_margin(monkeypatch,
                                                          compared):
    """The control the chip run makes at full size, in small: a router whose
    scores are rounded (here to 3 mantissa bits, since at this size and in
    float32 bfloat16 moves no score near another) moves them by far more
    than this float32 program does, and the share inside the margin says
    so; its choice is still the order of its own scores, so the first
    condition holds."""
    import jax.numpy as jnp
    from jax import lax
    outputs = list(compared[-1])
    honest = compare_with(monkeypatch, compared, outputs)
    for layer in range(4):
        rounded = np.asarray(lax.reduce_precision(
            jnp.asarray(outputs[5 + layer]), exponent_bits=8,
            mantissa_bits=3))
        outputs[5 + layer] = rounded
        outputs[1 + layer] = lm.own_choice(rounded, 4).astype(np.int32)
    said = compare_with(monkeypatch, compared, outputs)
    conditions = said["conditions"]
    assert conditions["routes_differ_outside_margin"]["ok"] is True
    assert conditions["routes_inside_margin"]["value"] \
        > 5 * honest["conditions"]["routes_inside_margin"]["value"] + 0.05


def test_the_control_in_the_nearest_precision_below_is_not_correct(compared):
    """The reference with every product's operands at 3 mantissa bits (the
    nearest precision below bfloat16), put through the same judgement, lies
    farther from the float32 reference than the runner's 3e-2 of the largest
    logit allows."""
    kept = compared[0]
    said = lm.control(kept)
    assert said["samples"] == 2 and said["compared"] == 2 * 24 * 128
    share = said["max_abs_error"] / said["max_abs_reference"]
    assert share > train.FORWARD_TOLERANCE
    assert said["conditions"]["held_pairs_computed"]["ok"] is True
