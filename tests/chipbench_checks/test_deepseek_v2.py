"""The deepseek_v2 configuration: its file against the catalog row it was
copied from, the parameter counts at the published widths (from the shapes:
nothing is allocated) and the deployment's count, the operation count, the
least work behind the two shares of a roofline, and the cell's runner
rehearsed on the CPU at a small size, with the configuration's own
comparison."""
import copy

import jax
import numpy as np
import pytest

from mxnet_tpu.gluon.model_zoo import deepseek_v2

from chipbench import layer_metrics, manifest, reduce_trace, run as bench_run
from chipbench.layer_metrics import expert_load
from chipbench.models import deepseek_v2 as dm
from chipbench.runners import train

PEAKS = manifest.load_peaks("TPU v5 lite")
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
BENCH = manifest.load_manifest()
CONFIG = manifest.load_config(BENCH, "deepseek_v2")
ARGS = CONFIG["args"]
CELL = "deepseek_v2.fused_bs1_seq8192"
TRAFFIC = manifest.load_traffic("fused_k4_bs1_seq8192")

# the ``config`` of the row "DeepSeek-V2" in the model-configs guide's
# architectures.jsonl (source_url https://huggingface.co/deepseek-ai/
# DeepSeek-V2/blob/main/config.json), copied key for key
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
    "n_routed_experts": 160, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 3, "topk_method": "group_limited_greedy",
    "v_head_dim": 128, "vocab_size": 102400}
REDUCED = {"n_routed_experts": 10, "num_attention_heads": 8,
           "num_hidden_layers": 5, "vocab_size": 12800}
# what may never be cut: a hidden, intermediate, latent or head size, the
# experts a token, the router's groups
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok", "n_group",
          "topk_group", "n_shared_experts", "num_key_value_heads")

SMALL = dict(
    ARGS, vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=3, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
    num_attention_heads=2, n_routed_experts=8, n_group=4, topk_group=2,
    num_experts_per_tok=3, init_sigma=0.1, compute_dtype=None,
    master_dtype=None,
    published_counts=dict(ARGS["published_counts"], n_routed_experts=16,
                          num_attention_heads=4),
    rope_scaling=dict(ARGS["rope_scaling"],
                      original_max_position_embeddings=8, factor=4),
    optimizer_params=dict(ARGS["optimizer_params"], learning_rate=1e-3),
    lr_warmup_steps=0)
SMALL_TRAFFIC = dict(TRAFFIC, seq=24, batch_per_chip=2, k=2)


def test_published_is_the_catalog_row():
    assert CONFIG["published"] == CATALOG
    entry = manifest.by_name(BENCH["configs"], "deepseek_v2", "configuration")
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
    entry = manifest.by_name(BENCH["configs"], "deepseek_v2", "configuration")
    assert CONFIG["reduced"] == sorted(REDUCED) == entry["reduced"]
    assert not set(CONFIG["reduced"]) & set(WIDTHS)
    assert not any(key.endswith(("_dim", "_rank")) for key in REDUCED)
    counts = ARGS["published_counts"]
    assert counts == CONFIG["published_counts"] == {
        key: CATALOG[key] for key in ("n_routed_experts", "num_attention_heads",
                                      "num_hidden_layers", "vocab_size")}
    # one of 16 chips sharing each layer: a sixteenth of the experts and of
    # the heads; an eighth of the vocabulary; the dense layer and four
    # expert layers after it
    assert 16 * ARGS["n_routed_experts"] == counts["n_routed_experts"]
    assert 16 * ARGS["num_attention_heads"] == counts["num_attention_heads"]
    assert 8 * ARGS["vocab_size"] == counts["vocab_size"]
    assert ARGS["n_routed_experts"] >= 8 and ARGS["first_expert"] == 0
    assert ARGS["first_head"] == 0
    assert dm.experts_held(ARGS) == ((0, 10), 160)
    assert dm.heads_held(ARGS) == ((0, 8), 128)
    assert dm.expert_layers(ARGS) == [1, 2, 3, 4]
    assert "one of 16 chips" in CONFIG["deployment"]


@pytest.fixture(scope="module")
def shapes():
    """``{name: (count, trainable)}`` of the net's parameters at the
    published widths: built, never initialized, so nothing is allocated."""
    held, width = dm.experts_held(ARGS)
    heads, published = dm.heads_held(ARGS)
    net = deepseek_v2.deepseek_v2(
        experts_held=held, heads_held=heads, return_routes=True,
        n_routed_experts=width, num_attention_heads=published,
        **{key: ARGS[key] for key in dm.MODEL_KEYS})
    params = net.collect_params()
    assert all(p._data is None for p in params.values())
    return {name: (int(np.prod(p.shape)), p.grad_req != "null")
            for name, p in params.items()}


def count(shapes, *parts, buffers=False):
    return sum(n for name, (n, trainable) in shapes.items()
               if all(part in name for part in parts)
               and (buffers or trainable))


@pytest.mark.parametrize("parts, want", [
    (("layer0_attention_",), 19_466_240),       # MLA at 8 heads
    (("layer0_attention_q_a_weight",), 5120 * 1536),
    (("layer0_attention_q_b_",), 1536 * 8 * 192),
    (("layer0_attention_kv_a_weight",), 5120 * 576),
    (("layer0_attention_kv_b_",), 512 * 8 * 256),
    (("layer0_attention_o_",), 8 * 128 * 5120),
    (("layer0_mlp_", "weight"), 188_743_680),   # the dense layer
    (("layer1_mlp_router_",), 819_200),
    (("layer1_mlp_expert_w",), 10 * 23_592_960),
    (("layer1_mlp_shared_",), 47_185_920),
    (("_attention_",), 5 * 19_466_240),
    (("_mlp_expert_w",), 943_718_400),
    (("_mlp_shared_",), 188_743_680),
    (("embed_weight",), 12800 * 5120),          # an eighth of 102400 rows
    (("head_weight",), 12800 * 5120),           # untied
    (("",), 1_552_942_080),                     # the cut
], ids=lambda v: "_".join(v).strip("_") or "all" if isinstance(v, tuple)
    else None)
def test_parameter_counts_at_published_widths(shapes, parts, want):
    assert count(shapes, *parts) == want


def test_the_deployment_states_the_count_of_the_built_model(shapes):
    total = count(shapes, "")
    assert total == sum(dm.parameter_counts(ARGS).values()) == 1_552_942_080
    assert "1,552,942,080" in CONFIG["deployment"]
    # bf16 weight, gradient and two Adam moments: 8 bytes, 12.42 GB
    assert ARGS["compute_dtype"] == ARGS["master_dtype"] == "bfloat16"
    assert ARGS["optimizer"] == "adamw"
    assert round(total * 8 / 1e9, 2) == 12.42 and "12.42 GB" in CONFIG[
        "deployment"]
    # besides them, the routers' frozen bias and the expert layers' counters
    assert count(shapes, "", buffers=True) - total == 4 * (160 + 10 + 1)


def test_operation_count_comes_from_the_shapes():
    flops = dm.flops_per_sample(ARGS, TRAFFIC)
    macs = dm.product_macs_per_token(ARGS, TRAFFIC["seq"])
    assert flops == 6 * sum(macs.values()) * 8192 == 31_036_507_422_720
    assert macs == {
        "mla_proj": 97_320_960, "mla_attention": 52_428_800,
        "router": 3_276_800, "shared_experts": 188_743_680,
        "routed_experts": 35_389_440, "dense_mlp": 188_743_680,
        "head": 65_536_000}
    # the routed experts: the share a uniform router sends here, 6 x 10 /
    # 160 of a token's pairs
    assert macs["routed_experts"] == 4 * 0.375 * 3 * 5120 * 1536
    shorter = dm.product_macs_per_token(ARGS, 4096)
    assert shorter["mla_attention"] * 2 == macs["mla_attention"]


def test_least_work_of_the_attention_core():
    ops = dm.attention_operations(ARGS, 1, 8192)
    assert ops == 3 * 2 * 8 * (8192 * 8193 // 2) * 320 * 5
    moved = dm.attention_bytes(ARGS, 1, 8192)
    assert moved == 2 * 8192 * 8 * (2 * 192 + 2 * 128) * 2 * 5
    from chipbench.layer_metrics import shape_roofline
    # 2.58 TFLOP at 197 TFLOP/s is 13.1 ms; the bytes need 0.8 ms
    share = shape_roofline.roofline(ops, moved, 50.0, PEAKS)
    assert share == pytest.approx(26.2, abs=0.1)
    spec = layer_metrics.load_all()["mla_attention_roofline"]
    assert getattr(dm, spec["operations"]) is dm.attention_operations
    assert getattr(dm, spec["bytes"]) is dm.attention_bytes


def test_useful_work_of_the_gated_grouped_products():
    rows = 4 * 3072.0           # 8192 x 6 x 10 / 160 in each of four layers
    assert dm.expert_product_operations(rows, ARGS) \
        == rows * 3 * 2 * 5120 * 1536 * 3
    weights = 4 * 10 * 3 * 5120 * 1536 * 2
    assert dm.expert_product_bytes(rows, ARGS, 4) \
        == 3 * weights + 3 * rows * (2 * 5120 + 3 * 1536) * 2
    load = [{"rows": [307] * 10, "steps": 1}] * 4
    share = expert_load.roofline(load, dm, ARGS, 30.0, PEAKS)
    # 1.74 TFLOP at 197 TFLOP/s is 8.8 ms, the bytes need 7.0 ms
    assert share == pytest.approx(29.4, abs=0.1)


def test_held_rows_over_a_uniform_routers():
    from chipbench.layer_metrics import held_rows
    # 8192 x 6 x 10 / 160 = 3072 rows a layer a step from a uniform router
    load = [{"rows": [307] * 10, "steps": 1}] * 3 \
        + [{"rows": [614] * 10, "steps": 2}]
    assert held_rows.over_uniform(load, ARGS, 8192) \
        == pytest.approx(4 * 3070 / (4 * 3072))
    load[0] = {"rows": [0] * 9 + [6144], "steps": 1}
    assert held_rows.over_uniform(load, ARGS, 8192) \
        == pytest.approx((6144 + 3 * 3070) / (4 * 3072))
    assert expert_load.max_over_mean(load) == pytest.approx(10.0)


def test_the_papers_schedule_and_clipping():
    """DeepSeek-V2's optimizer from its first step (arXiv:2405.04434 sec.
    3.1.2): AdamW 0.9 / 0.95, wd 0.1, a linear warm-up from 0 to 2.4e-4 over
    2000 steps, times 0.316 twice, global-norm clipping at 1.0."""
    assert ARGS["optimizer"] == "adamw"
    assert ARGS["optimizer_params"] == {"learning_rate": 2.4e-4, "beta1": 0.9,
                                        "beta2": 0.95, "wd": 0.1}
    assert ARGS["lr_warmup_steps"] == 2000 and ARGS["clip_norm"] == 1.0
    assert ARGS["lr_decay_factor"] == 0.316
    first, second = ARGS["lr_decay_steps"]
    mesh = __import__("mxnet_tpu").parallel.make_mesh(
        {"data": 1}, devices=jax.devices()[:1])
    small = dict(SMALL, optimizer_params=ARGS["optimizer_params"],
                 lr_warmup_steps=ARGS["lr_warmup_steps"])
    _, trainer = dm.build(small, mesh, 3)
    lr = trainer._optimizer.lr_scheduler
    assert lr(1) == pytest.approx(1.2e-7) and lr(80) == pytest.approx(9.6e-6)
    assert lr(2000) == lr(first - 1) == pytest.approx(2.4e-4)
    assert lr(first) == pytest.approx(2.4e-4 * 0.316)
    assert lr(second) == pytest.approx(2.4e-4 * 0.316 ** 2)
    assert trainer._guard_cfg.clip_norm == 1.0
    assert trainer._guard_cfg.mode == "deferred"


def test_the_same_seed_gives_the_same_weights_and_batch():
    mesh = __import__("mxnet_tpu").parallel.make_mesh(
        {"data": 1}, devices=jax.devices()[:1])
    seed = 2 ** 31 + 9
    x, y = dm.make_batch(SMALL, {"seq": 8}, 2, np.random.default_rng(seed))
    again = dm.make_batch(SMALL, {"seq": 8}, 2, np.random.default_rng(seed))
    assert (x == again[0]).all() and (y == again[1]).all()
    assert (x[:, 1:] == y[:, :-1]).all() and x.max() < SMALL["vocab_size"]
    logits = [dm.reference_logits(dm.build(SMALL, mesh, seed)[0], x)
              for _ in range(2)]
    assert (logits[0] == logits[1]).all() and np.abs(logits[0]).max() > 0


def test_timed_run_of_the_cell_at_a_small_size():
    config = dict(CONFIG, args=SMALL)
    facts = train.run(config, SMALL_TRAFFIC, jax.devices()[:1], 2 ** 31 + 11,
                      0.5)
    assert facts["correct"] is True and facts["failed"] == 0
    assert facts["checks"]["last_loss"] < facts["checks"]["first_loss"]
    assert "forward" not in facts["checks"]
    assert facts["values"]["flops_per_sample"] == dm.flops_per_sample(
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
                               "routes_inside_margin",
                               "drops_differ_outside_margin",
                               "held_pairs_computed"}
    assert all(c["ok"] for c in conditions.values())
    assert conditions["routes_differ_outside_margin"]["value"] == 0.0
    assert conditions["drops_differ_outside_margin"]["value"] == 0.0
    assert conditions["held_pairs_computed"]["value"] == 0.0
    assert "chipbench: routes " in capsys.readouterr().out
    cell = manifest.by_name(BENCH["workloads"], CELL, "cell")
    line = bench_run.result_line(copy.deepcopy(BENCH), cell, facts, PEAKS,
                                 DEVICE, 1.5, True)
    assert line["correct"] is True
    assert {"mfu", "device_busy_ms_per_step", "device_idle_share",
            "custom_call_ms_per_step", "compile_s"} <= set(line["metrics"])
    # the scope metrics and both rooflines need a device trace (there is
    # none on a CPU) and are left out, not raised
    mine = {"mla_attention_ms_per_step", "mla_rest_ms_per_step",
            "group_route_ms_per_step", "mla_attention_roofline",
            "gated_experts_roofline", "expert_dispatch_combine_ms_per_step"}
    # the held experts' load is read from the layers' counters
    counted = {"held_expert_load_max_over_mean", "held_rows_over_uniform"}
    assert mine | counted <= set(layer_metrics.for_cell(CELL))
    assert not mine & set(line["metrics"])
    assert counted <= set(line["metrics"])
    assert line["metrics"]["held_rows_over_uniform"]["value"] > 0
    assert line["metrics"]["held_expert_load_max_over_mean"]["value"] >= 1
    assert not (mine | counted) & set(layer_metrics.for_cell(
        "nemotron_3_nano_30b_a3b.fused_bs1_seq8192"))
    load = expert_load.load_of_live_net(dm)
    assert len(load) == 2 and all(said["steps"] > 0 for said in load)


@pytest.fixture(scope="module")
def compared():
    """A net, its batch, what ``reference`` keeps and the system's own
    outputs, for comparisons fed other routes."""
    import mxnet_tpu as mx
    mesh = mx.parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    net, trainer = dm.build(SMALL, mesh, 7)
    x, y = dm.make_batch(SMALL, SMALL_TRAFFIC, 2, np.random.default_rng(7))
    net(mx.nd.array(x[:1]))
    kept = dm.reference_kept(net, x[:2])
    trainer.prepare(x[:1])
    return kept, trainer, x, y, train.system_outputs(trainer, SMALL, x, y)


def compare_with(monkeypatch, compared, outputs):
    kept, trainer, x, y, _ = compared
    monkeypatch.setattr(train, "system_outputs",
                        lambda *args, **kwargs: outputs)
    return dm.compare(kept, trainer, SMALL, x, y)


def test_compare_passes_the_systems_own_outputs(monkeypatch, compared):
    said = compare_with(monkeypatch, compared, compared[-1])
    assert said["samples"] == 2 and said["compared"] == 2 * 24 * 128
    assert all(c["ok"] for c in said["conditions"].values())
    assert said["max_abs_error"] < 1e-4 * said["max_abs_reference"]


def test_an_expert_from_a_group_not_kept_fails_the_first_condition(
        monkeypatch, compared):
    kept, _, x, _, outputs = compared
    params, cfg, samples = kept
    _, own, scores = dm.forward_at(params, cfg, samples)
    s = scores[0].reshape(-1, 16)
    # the token of the first expert layer whose kept groups lie farthest
    # above the others: its last chosen expert gives way to the best expert
    # of a group the reference did not keep
    best = s.reshape(-1, 4, 4).max(-1)
    ranked = np.sort(best, -1)
    token = int(np.argmax(ranked[:, -2] - ranked[:, -3]))
    groups = dm.own_choice(s, cfg)[1][token]
    outside = [e for e in range(16) if e // 4 not in groups]
    stranger = max(outside, key=lambda e: s[token, e])
    routes = outputs[1].copy().reshape(-1, 3)
    routes[token, -1] = stranger
    bent = [outputs[0], routes.reshape(outputs[1].shape)] + list(outputs[2:])
    said = compare_with(monkeypatch, compared, bent)
    first = said["conditions"]["routes_differ_outside_margin"]
    assert first["ok"] is False and first["value"] == pytest.approx(1 / 96)


def test_rows_that_were_not_computed_fail_the_third_condition(monkeypatch,
                                                              compared):
    outputs = list(compared[-1])
    outputs[-1] = outputs[-1].copy()
    outputs[-1][0, 0] -= 1              # one pair dropped
    said = compare_with(monkeypatch, compared, outputs)
    third = said["conditions"]["held_pairs_computed"]
    assert third["ok"] is False and third["value"] == 1.0
    assert said["conditions"]["routes_differ_outside_margin"]["ok"] is True


def test_a_pair_dropped_in_place_of_another_fails_the_drop_condition(
        monkeypatch, compared):
    """The budget keeps the pairs of largest score: a system that dropped
    the held pair of largest score, far from the cut, and computed one the
    reference drops is caught, and the routes' own condition is not."""
    kept, _, x, _, outputs = compared
    params, cfg, samples = kept
    routes = outputs[1].copy().reshape(-1)
    assert (routes < 0).any()           # the small batch is over budget
    scores = dm.forward_at(params, cfg, samples)[2][0].reshape(-1, 16)
    affinity = np.take_along_axis(scores, outputs[1].reshape(-1, 3) % 16,
                                  -1).reshape(-1)
    held = (routes >= 4) & (routes < 12)
    best = int(np.argmax(np.where(held, affinity, -np.inf)))
    routes[best] -= 16
    lowest_dropped = int(np.argmin(np.where(routes < 0, affinity, np.inf)))
    if lowest_dropped != best:
        routes[lowest_dropped] += 16
    bent = [outputs[0], routes.reshape(outputs[1].shape)] + list(outputs[2:])
    said = compare_with(monkeypatch, compared, bent)
    drops = said["conditions"]["drops_differ_outside_margin"]
    assert drops["ok"] is False and drops["value"] > 0
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
    for layer in (0, 1):
        rounded = np.asarray(lax.reduce_precision(
            jnp.asarray(outputs[3 + layer]), exponent_bits=8,
            mantissa_bits=3))
        outputs[3 + layer] = rounded
        outputs[1 + layer] = dm.own_choice(
            rounded.reshape(-1, 16), SMALL)[0].reshape(
                outputs[1 + layer].shape).astype(np.int32)
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
    said = dm.control(kept)
    assert said["samples"] == 2 and said["compared"] == 2 * 24 * 128
    share = said["max_abs_error"] / said["max_abs_reference"]
    assert share > train.FORWARD_TOLERANCE
    assert said["conditions"]["held_pairs_computed"]["ok"] is True

