"""The nemotron_3_nano_30b_a3b configuration: its file against the catalog
row it was copied from, the parameter counts at the published widths (from
the shapes: nothing is allocated), the operation count, and the cell's runner
rehearsed on the CPU at a small size, with the configuration's own
comparison."""
import copy

import jax
import numpy as np
import pytest

from mxnet_tpu.gluon.model_zoo import nemotron_h

from chipbench import layer_metrics, manifest, reduce_trace, run as bench_run
from chipbench.layer_metrics import expert_load
from chipbench.models import nemotron_3_nano_30b_a3b as nm
from chipbench.runners import train

PEAKS = manifest.load_peaks("TPU v5 lite")
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
BENCH = manifest.load_manifest()
CONFIG = manifest.load_config(BENCH, "nemotron_3_nano_30b_a3b")
ARGS = CONFIG["args"]
CELL = "nemotron_3_nano_30b_a3b.fused_bs1_seq8192"
TRAFFIC = manifest.load_traffic("fused_k4_bs1_seq8192")

# the ``config`` of the row "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16" in the
# model-configs guide's architectures.jsonl (source_url https://huggingface.co/
# nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json), copied
# key for key
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = {"hybrid_override_pattern": "MEMEM*EME", "n_routed_experts": 16,
           "num_hidden_layers": 9, "vocab_size": 16384}
# what may never be cut: a hidden, intermediate, state or projection size, a
# head size, an expansion factor, the experts a token
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "moe_shared_expert_intermediate_size", "head_dim", "mamba_head_dim",
          "ssm_state_size", "expand", "num_experts_per_tok", "conv_kernel",
          "chunk_size", "n_groups", "mamba_num_heads", "num_attention_heads",
          "num_key_value_heads")

SMALL = dict(
    ARGS, vocab_size=128, hidden_size=64, hybrid_override_pattern="ME*E",
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
    n_groups=2, chunk_size=8, n_routed_experts=4,
    published_counts=dict(ARGS["published_counts"], n_routed_experts=16),
    num_experts_per_tok=3, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, init_sigma=0.1,
    compute_dtype=None, master_dtype=None,
    optimizer_params=dict(ARGS["optimizer_params"], learning_rate=1e-3))
SMALL_TRAFFIC = dict(TRAFFIC, seq=24, batch_per_chip=2, k=2)


def test_published_is_the_catalog_row():
    assert CONFIG["published"] == CATALOG
    entry = manifest.by_name(BENCH["configs"], "nemotron_3_nano_30b_a3b",
                             "configuration")
    assert CONFIG["source"] == entry["source"]
    assert CONFIG["compare"].endswith(".compare")
    assert CONFIG["reference"].endswith(".reference_kept")
    assert CONFIG["reference_samples"] == 1


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_every_key_runs_as_published_but_the_reduced_ones(key):
    want = REDUCED.get(key, CATALOG[key])
    # at the top level for the driver's check, under args for the builder
    assert CONFIG[key] == want and ARGS[key] == want
    assert (key in CONFIG["reduced"]) == (key in REDUCED)


def test_reduced_names_no_width_and_the_cut_is_one_whole_period():
    entry = manifest.by_name(BENCH["configs"], "nemotron_3_nano_30b_a3b",
                             "configuration")
    assert CONFIG["reduced"] == sorted(REDUCED) == entry["reduced"]
    assert not set(CONFIG["reduced"]) & set(WIDTHS)
    assert not any(key.endswith(("_dim", "_rank")) for key in REDUCED)
    # the first nine letters: every kind of layer, 4 : 4 : 1 for 23 : 23 : 6
    pattern = ARGS["hybrid_override_pattern"]
    assert pattern == CATALOG["hybrid_override_pattern"][:9]
    assert len(pattern) == ARGS["num_hidden_layers"] >= 5
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) \
        == (4, 4, 1)
    published = CATALOG["hybrid_override_pattern"]
    assert (published.count("M"), published.count("E"),
            published.count("*")) == (23, 23, 6)
    # one of 8 chips that share each layer: an eighth of the experts and of
    # the vocabulary, which are the guide's floors
    counts = ARGS["published_counts"]
    assert counts == CONFIG["published_counts"] == {
        key: CATALOG[key] for key in ("n_routed_experts",
                                      "num_hidden_layers", "vocab_size")}
    assert 8 * ARGS["n_routed_experts"] == counts["n_routed_experts"] == 128
    assert 8 * ARGS["vocab_size"] == counts["vocab_size"] == 131072
    assert ARGS["n_routed_experts"] >= 8 and ARGS["first_expert"] == 0
    assert nm.experts_held(ARGS) == ((0, 16), 128)
    # the mixer's inner width is heads x head size (expand is not read)
    assert ARGS["mamba_num_heads"] * ARGS["mamba_head_dim"] == 4096
    assert "one of 8 chips" in CONFIG["deployment"]


@pytest.fixture(scope="module")
def shapes():
    """``{name: (count, trainable)}`` of the net's parameters at the
    published widths: built, never initialized, so nothing is allocated."""
    held, width = nm.experts_held(ARGS)
    net = nemotron_h.nemotron_h(
        experts_held=held, return_routes=True, n_routed_experts=width,
        **{key: ARGS[key] for key in nm.MODEL_KEYS})
    params = net.collect_params()
    assert all(p._data is None for p in params.values())
    return {name: (int(np.prod(p.shape)), p.grad_req != "null")
            for name, p in params.items()}


def count(shapes, *parts, counters=False):
    return sum(n for name, (n, trainable) in shapes.items()
               if all(part in name for part in parts)
               and (counters or trainable or name.endswith("router_bias")))


@pytest.mark.parametrize("parts, want", [
    (("layer0_",), 38_744_896),                 # a Mamba-2 layer
    (("layer0_mixer_in_",), 2688 * 10304),
    (("layer0_mixer_conv_",), 6144 * 4 + 6144),
    (("layer0_mixer_out_",), 4096 * 2688),
    (("layer5_",), 23_399_040),                 # the attention layer
    (("layer5_mixer_k_",), 2688 * 256),
    (("layer1_mixer_router_",), 128 * 2688 + 128),
    (("layer1_mixer_shared_",), 2 * 2688 * 3712),
    (("layer1_mixer_expert_w",), 16 * 9_977_856),
    (("layer1_",), 20_302_592 + 16 * 9_977_856),    # an expert layer
    (("_layers_",), 898_171_776),               # one period, 4 + 4 + 1
    (("embed_weight",), 16384 * 2688),          # an eighth of 131072 rows
    (("head_weight",), 16384 * 2688),           # untied
    (("",), 986_254_848),                       # the cut
], ids=lambda v: "_".join(v).strip("_") or "all" if isinstance(v, tuple)
    else None)
def test_parameter_counts_at_published_widths(shapes, parts, want):
    assert count(shapes, *parts) == want


def test_state_is_eight_bytes_a_parameter_and_clears_the_floor(shapes):
    # bf16 weight, gradient and two Adam moments; no separate compute copy
    assert ARGS["compute_dtype"] == ARGS["master_dtype"] == "bfloat16"
    assert ARGS["optimizer"] == "adamw"
    assert 986_254_848 * 8 / 16e9 > 0.25
    # besides them, the expert layers' int32 counters: 16 rows and one step
    assert count(shapes, "", counters=True) - 986_254_848 == 4 * 17
    assert "986,254,848" in CONFIG["deployment"]


def test_operation_count_comes_from_the_shapes():
    flops = nm.flops_per_sample(ARGS, TRAFFIC)
    macs = nm.product_macs_per_token(ARGS, TRAFFIC["seq"])
    assert flops == 6 * sum(macs.values()) * 8192 == 18371454173184
    assert macs == {
        "mamba_proj": 154_828_800, "mamba_scan": 6_815_744,
        "attention_proj": 23_396_352, "attention": 33_554_432,
        "router": 1_376_256, "shared_experts": 79_822_848,
        "routed_experts": 29_933_568, "head": 44_040_192}
    # the routed experts: the share a uniform router sends here, 6 x 16 / 128
    # of a token's pairs
    assert macs["routed_experts"] == 4 * 0.75 * 2 * 2688 * 1856
    # attention grows with the sequence, nothing else does
    shorter = nm.product_macs_per_token(ARGS, 4096)
    assert shorter["attention"] * 2 == macs["attention"]
    assert {k: v for k, v in shorter.items() if k != "attention"} \
        == {k: v for k, v in macs.items() if k != "attention"}


def test_useful_work_of_the_grouped_products():
    rows = 4 * 6144.0           # 8192 x 6 x 16 / 128 in each of four layers
    assert nm.expert_product_operations(rows, ARGS) \
        == rows * 2 * 2 * 2688 * 1856 * 3
    weights = 4 * 16 * 2 * 2688 * 1856 * 2
    assert nm.expert_product_bytes(rows, ARGS, 4) \
        == 3 * weights + 3 * rows * 2 * (2688 + 1856) * 2
    load = [{"rows": [384] * 16, "steps": 1}] * 4
    share = expert_load.roofline(load, nm, ARGS, 10.0, PEAKS)
    # 1.47 TFLOP at 197 TFLOP/s is 7.5 ms, the bytes need 5.7 ms
    assert share == pytest.approx(74.7, abs=0.1)
    assert expert_load.max_over_mean(load) == 1.0
    assert expert_load.max_over_mean(
        [{"rows": [10, 30], "steps": 2}, {"rows": [5, 5], "steps": 2}]) == 1.5


def test_the_same_seed_gives_the_same_weights_and_batch():
    mesh = __import__("mxnet_tpu").parallel.make_mesh(
        {"data": 1}, devices=jax.devices()[:1])
    seed = 2 ** 31 + 9
    x, y = nm.make_batch(SMALL, {"seq": 8}, 2, np.random.default_rng(seed))
    again = nm.make_batch(SMALL, {"seq": 8}, 2, np.random.default_rng(seed))
    assert (x == again[0]).all() and (y == again[1]).all()
    assert (x[:, 1:] == y[:, :-1]).all() and x.max() < SMALL["vocab_size"]
    logits = [nm.reference_logits(nm.build(SMALL, mesh, seed)[0], x)
              for _ in range(2)]
    assert (logits[0] == logits[1]).all() and np.abs(logits[0]).max() > 0


def test_timed_run_of_the_cell_at_a_small_size():
    config = dict(CONFIG, args=SMALL)
    facts = train.run(config, SMALL_TRAFFIC, jax.devices()[:1], 2 ** 31 + 11,
                      0.5)
    assert facts["correct"] is True and facts["failed"] == 0
    assert facts["checks"]["last_loss"] < facts["checks"]["first_loss"]
    assert "forward" not in facts["checks"]
    assert facts["values"]["flops_per_sample"] == nm.flops_per_sample(
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
                               "routes_inside_margin", "held_pairs_computed"}
    assert all(c["ok"] for c in conditions.values())
    assert conditions["routes_differ_outside_margin"]["value"] == 0.0
    assert conditions["held_pairs_computed"]["value"] == 0.0
    assert conditions["routes_inside_margin"]["limit"] <= 0.25
    assert "chipbench: routes " in capsys.readouterr().out
    cell = manifest.by_name(BENCH["workloads"], CELL, "cell")
    line = bench_run.result_line(copy.deepcopy(BENCH), cell, facts, PEAKS,
                                 DEVICE, 1.5, True)
    assert line["correct"] is True
    assert {"mfu", "device_busy_ms_per_step", "device_idle_share",
            "custom_call_ms_per_step", "compile_s"} <= set(line["metrics"])
    # the counters went through the compiled steps: the reader that needs no
    # trace finds them; the scope metrics need a device trace (there is none
    # on a CPU) and are left out, not raised
    assert line["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    assert "moe_experts_roofline" not in line["metrics"]
    mine = {"moe_experts_ms_per_step", "moe_route_ms_per_step",
            "moe_shared_ms_per_step", "moe_experts_roofline",
            "expert_load_max_over_mean"}
    assert mine <= set(layer_metrics.for_cell(CELL))
    assert not mine & set(layer_metrics.for_cell(
        "granite_4_0_h_micro.fused_bs1_seq4096"))
    load = expert_load.load_of_live_net(nm)
    assert len(load) == 2 and all(said["steps"] > 0 for said in load)


class FakeTrainer:
    """What ``compare`` reads of a trainer, with outputs that can be bent."""

    def __init__(self, outputs):
        self.outputs = outputs


@pytest.fixture(scope="module")
def compared():
    """A net, its batch, what ``reference`` keeps and the system's own
    outputs, for comparisons fed other routes."""
    import mxnet_tpu as mx
    mesh = mx.parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    net, trainer = nm.build(SMALL, mesh, 7)
    x, y = nm.make_batch(SMALL, SMALL_TRAFFIC, 2, np.random.default_rng(7))
    net(mx.nd.array(x[:1]))
    kept = nm.reference_kept(net, x[:2])
    trainer.prepare(x[:1])
    return kept, trainer, x, y, train.system_outputs(trainer, SMALL, x, y)


def compare_with(monkeypatch, compared, outputs):
    kept, trainer, x, y, _ = compared
    monkeypatch.setattr(train, "system_outputs",
                        lambda *args, **kwargs: outputs)
    return nm.compare(kept, trainer, SMALL, x, y)


def test_compare_passes_the_systems_own_outputs(monkeypatch, compared):
    said = compare_with(monkeypatch, compared, compared[-1])
    assert said["samples"] == 2 and said["compared"] == 2 * 24 * 128
    assert all(c["ok"] for c in said["conditions"].values())
    assert said["max_abs_error"] < 1e-4 * said["max_abs_reference"]


def test_an_expert_swapped_outside_the_margin_fails_the_first_condition(
        monkeypatch, compared):
    kept, _, x, _, outputs = compared
    params, cfg, samples = kept
    _, own, scores = nm.forward_at(params, cfg, samples)
    # the token of the first expert layer whose cut is widest: its last
    # chosen expert gives way to the lowest-scored of all
    s = scores[0].reshape(-1, 16)
    ranked = np.sort(s, -1)
    token = int(np.argmax(ranked[:, -3] - ranked[:, 0]))
    routes = outputs[1].copy().reshape(-1, 3)
    lowest = int(np.argmin(s[token]))
    assert lowest not in routes[token]
    routes[token, np.argmin(s[token, routes[token]])] = lowest
    bent = [outputs[0], routes.reshape(outputs[1].shape)] + list(outputs[2:])
    assert len(bent) == 6
    said = compare_with(monkeypatch, compared, bent)
    first = said["conditions"]["routes_differ_outside_margin"]
    assert first["ok"] is False and first["value"] == pytest.approx(1 / 96)
    # the rows computed no longer are the pairs those routes name
    assert said["conditions"]["held_pairs_computed"]["ok"] is False \
        or lowest >= 4 and routes[token].max() >= 4


def test_rows_that_were_not_computed_fail_the_third_condition(monkeypatch,
                                                              compared):
    outputs = list(compared[-1])
    outputs[-1] = outputs[-1].copy()
    outputs[-1][0, 0] -= 1              # one pair dropped
    said = compare_with(monkeypatch, compared, outputs)
    third = said["conditions"]["held_pairs_computed"]
    assert third["ok"] is False and third["value"] == 1.0
    assert said["conditions"]["routes_differ_outside_margin"]["ok"] is True


def test_scores_rounded_to_bfloat16_put_many_more_tokens_inside_the_margin(
        monkeypatch, compared):
    """The control the chip run makes at full size, in small: a router that
    rounds its scores to bfloat16 moves them by far more than this float32
    program does, and the share inside the margin says so; its choice is
    still the order of its own scores, so the first condition holds."""
    import jax.numpy as jnp
    outputs = list(compared[-1])
    honest = compare_with(monkeypatch, compared, outputs)
    for layer in (0, 1):
        rounded = np.asarray(jnp.asarray(outputs[3 + layer], jnp.bfloat16)
                             .astype(jnp.float32))
        outputs[3 + layer] = rounded
        outputs[1 + layer] = nm.own_choice(rounded, 3).astype(np.int32)
    said = compare_with(monkeypatch, compared, outputs)
    conditions = said["conditions"]
    assert conditions["routes_differ_outside_margin"]["ok"] is True
    assert conditions["routes_inside_margin"]["value"] \
        > 5 * honest["conditions"]["routes_inside_margin"]["value"] + 0.05


def test_margin_is_the_measured_movement_and_conditions_are_per_token():
    scores = np.array([[0.9, 0.8, 0.5, 0.1], [0.9, 0.8, 0.7, 0.1]])
    system = scores + np.array([[0.0, -0.2, 0.2, 0.0], [0.0, 0.0, 0.0, 0.0]])
    margins = nm.margin_of(system, scores)
    assert margins[0, 1] == pytest.approx(0.2) and margins[1, 1] == 2.0 ** -23
    own = nm.own_choice(scores, 2)
    assert own.tolist() == [[0, 1], [0, 1]]
    # token 0: experts 1 and 2 changed places within what they moved by
    differ, inside = nm.route_conditions(own, scores, margins,
                                         nm.own_choice(system, 2))
    assert differ.tolist() == [True, False] and inside.tolist() == [True, False]
    # a choice that is not the order of the system's own scores lies outside
    differ, inside = nm.route_conditions(own, scores, margins,
                                         np.array([[0, 2], [0, 3]]))
    assert differ.tolist() == [True, True] and inside.tolist() == [True, False]
    needed = nm.needed_margin(own, np.array([[0, 2], [0, 3]]), scores,
                              margins)
    assert needed["gap"] == pytest.approx(0.7)
