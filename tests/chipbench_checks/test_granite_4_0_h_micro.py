"""The granite-4.0-h-micro configuration: its file against the catalog row it
was copied from, the parameter counts at the published widths (from the
shapes: nothing is allocated), the operation count, and the cell's runner
rehearsed on the CPU at a small size."""
import copy

import jax
import numpy as np
import pytest

from mxnet_tpu.gluon.model_zoo import granite_hybrid

from chipbench import manifest, reduce_trace, run as bench_run
from chipbench.models import granite_4_0_h_micro as gm
from chipbench.runners import train

PEAKS = manifest.load_peaks("TPU v5 lite")
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
BENCH = manifest.load_manifest()
CONFIG = manifest.load_config(BENCH, "granite_4_0_h_micro")
ARGS = CONFIG["args"]
CELL = "granite_4_0_h_micro.fused_bs1_seq4096"
TRAFFIC = manifest.load_traffic("fused_k4_bs1_seq4096")

# the ``config`` of the row "granite-4.0-h-micro" in the model-configs
# guide's architectures.jsonl (source_url https://huggingface.co/ibm-granite/
# granite-4.0-h-micro/blob/main/config.json), copied key for key
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": ["attention" if i in (5, 15, 25, 35) else "mamba"
                    for i in range(40)],
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}
REDUCED = {"num_hidden_layers": 10, "vocab_size": 25088}

SMALL = dict(ARGS, vocab_size=128, hidden_size=64,
             shared_intermediate_size=96, num_hidden_layers=3,
             layer_types=["mamba", "attention", "mamba"],
             num_attention_heads=8, num_key_value_heads=2, mamba_n_heads=4,
             mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
             compute_dtype=None, master_dtype=None,
             optimizer_params=dict(ARGS["optimizer_params"],
                                   learning_rate=1e-3))
SMALL_TRAFFIC = dict(TRAFFIC, seq=24, batch_per_chip=2, k=2)


def test_published_is_the_catalog_row():
    assert CONFIG["published"] == CATALOG
    assert CONFIG["source"] == manifest.by_name(
        BENCH["configs"], "granite_4_0_h_micro", "configuration")["source"]


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_every_key_runs_as_published_but_the_reduced_ones(key):
    want = REDUCED.get(key, CATALOG[key])
    # at the top level for the driver's check, under args for the builder
    assert CONFIG[key] == want and ARGS[key] == want
    assert (key in CONFIG["reduced"]) == (key in REDUCED)


def test_reduced_names_no_width_and_the_cut_is_one_whole_period():
    assert CONFIG["reduced"] == sorted(REDUCED) == manifest.by_name(
        BENCH["configs"], "granite_4_0_h_micro", "configuration")["reduced"]
    kinds = gm.layer_types(ARGS)
    assert kinds == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    # the published ratio, nine to one, in every period of the source
    assert all(CATALOG["layer_types"][i:i + 10] == kinds
               for i in range(0, 40, 10))
    assert ARGS["vocab_size"] * 4 == CATALOG["vocab_size"]
    assert ARGS["vocab_size"] * 8 >= CATALOG["vocab_size"]     # the floor
    assert ARGS["mamba_n_heads"] * ARGS["mamba_d_head"] \
        == ARGS["mamba_expand"] * ARGS["hidden_size"]


@pytest.fixture(scope="module")
def shapes():
    """Parameter shapes of the net at the published widths: built, never
    initialized, so nothing is allocated."""
    net = granite_hybrid.granite_hybrid(
        layer_types=gm.layer_types(ARGS),
        intermediate_size=ARGS["shared_intermediate_size"],
        **{key: ARGS[key] for key in gm.MODEL_KEYS})
    params = net.collect_params()
    assert all(p._data is None for p in params.values())
    return {name: int(np.prod(p.shape)) for name, p in params.items()}


def count(shapes, *parts):
    return sum(n for name, n in shapes.items()
               if all(part in name for part in parts))


@pytest.mark.parametrize("parts, want", [
    (("layer0_mixer_",), 25_847_232),           # a Mamba-2 mixer
    (("layer0_mixer_in_",), 2048 * 8512),
    (("layer0_mixer_conv_",), 4352 * 4 + 4352),
    (("layer0_mixer_out_",), 4096 * 2048),
    (("layer5_mixer_",), 10_485_760),           # the attention mixer
    (("layer0_mlp_in_",), 2048 * 16384),
    (("layer0_mlp_", "weight"), 50_331_648),    # the shared MLP
    (("layer0_",), 76_182_976),                 # a Mamba layer
    (("layer5_",), 60_821_504),                 # the attention layer
    (("_layers_",), 746_468_288),               # one period, 9 + 1
    (("embed_weight",), 51_380_224),            # a quarter of 100352 rows
    (("",), 797_850_560),                       # the cut
], ids=lambda v: "_".join(v).strip("_") or "all" if isinstance(v, tuple)
    else None)
def test_parameter_counts_at_published_widths(shapes, parts, want):
    assert count(shapes, *parts) == want


def test_state_is_eight_bytes_a_parameter_and_clears_the_floor():
    # bf16 weight, gradient and two Adam moments; no separate compute copy
    assert ARGS["compute_dtype"] == ARGS["master_dtype"] == "bfloat16"
    assert ARGS["optimizer"] == "adamw"
    assert 797_850_560 * 8 / 16e9 > 0.25


def test_operation_count_comes_from_the_shapes():
    flops = gm.flops_per_sample(ARGS, TRAFFIC)
    assert flops == 20_278_419_652_608
    macs = gm.product_macs_per_token(ARGS, TRAFFIC["seq"])
    # 6 x parameters-in-products x tokens + attention; the scan's own
    # products (2.3% of the whole) are what the count has over it
    in_products = 797_850_560 - (9 * (4352 * 5 + 3 * 64 + 4096)
                                 + 21 * 2048)
    usual = 6 * in_products * 4096 + 6 * macs["attention"] * 4096
    assert 0 < flops / usual - 1 < 0.03
    assert macs["mamba_scan"] / sum(macs.values()) == pytest.approx(
        0.0232, abs=1e-3)
    # the head keeps its published share: a quarter of the vocabulary with
    # a quarter of the depth
    whole = gm.product_macs_per_token(dict(
        ARGS, num_hidden_layers=40, vocab_size=100352), 4096)
    assert macs["head"] / sum(macs.values()) == pytest.approx(
        whole["head"] / sum(whole.values()), rel=0.01)
    # attention grows with the sequence, the scan does not
    longer = gm.product_macs_per_token(ARGS, 8192)
    assert longer["attention"] == 2 * macs["attention"]
    assert longer["mamba_scan"] == macs["mamba_scan"]


def test_the_same_seed_gives_the_same_weights_and_batch():
    mesh = __import__("mxnet_tpu").parallel.make_mesh(
        {"data": 1}, devices=jax.devices()[:1])
    seed = 2 ** 31 + 9
    x, y = gm.make_batch(SMALL, {"seq": 8}, 2, np.random.default_rng(seed))
    again = gm.make_batch(SMALL, {"seq": 8}, 2, np.random.default_rng(seed))
    assert (x == again[0]).all() and (y == again[1]).all()
    assert (x[:, 1:] == y[:, :-1]).all() and x.max() < SMALL["vocab_size"]
    logits = [gm.reference_logits(gm.build(SMALL, mesh, seed)[0], x)
              for _ in range(2)]
    assert (logits[0] == logits[1]).all() and np.abs(logits[0]).max() > 0


def test_timed_run_of_the_cell_at_a_small_size():
    config = dict(CONFIG, args=SMALL)
    facts = train.run(config, SMALL_TRAFFIC, jax.devices()[:1], 2 ** 31 + 11,
                      0.5)
    assert facts["correct"] is True and facts["failed"] == 0
    assert facts["checks"]["last_loss"] < facts["checks"]["first_loss"]
    assert facts["values"]["flops_per_sample"] == gm.flops_per_sample(
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


def test_traced_run_of_the_cell_reads_the_new_metrics(tmp_path, monkeypatch):
    fake_device_plane(monkeypatch)
    config = dict(CONFIG, args=SMALL, reference_samples=2)
    facts = train.run(config, SMALL_TRAFFIC, jax.devices()[:1], 5, 0.3,
                      str(tmp_path / "trace"))
    forward = facts["checks"]["forward"]
    assert forward["ok"] and forward["samples"] == 2
    assert forward["share"] < 1e-4              # float32 at this size
    cell = manifest.by_name(BENCH["workloads"], CELL, "cell")
    bench = copy.deepcopy(BENCH)
    line = bench_run.result_line(bench, cell, facts, PEAKS, DEVICE, 1.5, True)
    assert line["correct"] is True
    # the cell's own metrics are asked for; what the reader finds depends
    # on the trace (test_device_scopes.py), and here there is no .xplane.pb
    # under the checkout's .chipbench_trace, so it may find nothing
    assert {"mfu", "device_busy_ms_per_step", "device_idle_share",
            "custom_call_ms_per_step", "compile_s"} <= set(line["metrics"])
    from chipbench import layer_metrics
    assert {"ssd_ms_per_step", "mamba_rest_ms_per_step", "scoped_share"} \
        <= set(layer_metrics.for_cell(CELL))
    assert "ssd_ms_per_step" not in layer_metrics.for_cell(
        "bert_12_768_12.fused_bs128_seq128")
