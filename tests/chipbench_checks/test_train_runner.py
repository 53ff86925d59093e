"""The train runner and the result line, rehearsed on the CPU with a toy
configuration and traffic of this directory's own (``tiny_model.py``,
``data/tiny_*.json``): files added, none of ``chipbench/`` edited. Nothing
here describes a topology or touches libtpu."""
import copy
import json
import os
import subprocess
import sys

import jax
import pytest

from chipbench import manifest, reduce_trace, run as bench_run
from chipbench.runners import train

HERE = os.path.dirname(os.path.abspath(__file__))
FACT_KEYS = {"correct", "attempted", "failed", "checks", "setup_end",
             "values", "spans", "trace", "memory_peak_bytes"}
PEAKS = manifest.load_peaks("TPU v5 lite")
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def load(name):
    return manifest.load_json(os.path.join(HERE, "data", name + ".json"))


@pytest.fixture
def config(monkeypatch):
    monkeypatch.syspath_prepend(HERE)       # "tiny_model.build" resolves
    return load("tiny_mlp")


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


@pytest.mark.parametrize("mix", ["tiny_fused", "tiny_loop",
                                 "tiny_loop_ahead"])
def test_timed_run_hands_back_the_facts(config, mix):
    traffic = load(mix)
    facts = train.run(config, traffic, jax.devices()[:1], 2 ** 31 + 11, 0.3)
    assert set(facts) == FACT_KEYS
    assert facts["correct"] is True and facts["failed"] == 0
    checks = facts["checks"]
    per_call = traffic["k"] if traffic["mode"] == "fused" else 1
    assert facts["attempted"] == checks["calls"] * per_call > 0
    assert checks["window_s"] >= 0.3 and checks["programs_in_window"] == 0
    values = facts["values"]
    assert values["train_samples_per_s_per_chip"] == pytest.approx(
        facts["attempted"] * traffic["batch_per_chip"] / checks["window_s"])
    assert ("step_ms_p95" in values) == (traffic["mode"] == "loop")
    assert facts["spans"]["dispatch"]["count"] == checks["calls"]
    assert facts["trace"] is None


@pytest.mark.parametrize("mix", ["tiny_fused", "tiny_loop",
                                 "tiny_loop_ahead"])
def test_traced_run_gives_the_contract_line(config, mix, tmp_path,
                                            monkeypatch):
    fake_device_plane(monkeypatch)
    traffic = load(mix)
    facts = train.run(config, traffic, jax.devices()[:1], 5, 0.3,
                      str(tmp_path / "trace"))
    assert facts["checks"]["forward"]["ok"]
    assert facts["checks"]["forward"]["share"] < 1e-5      # float32 toy
    want = (traffic["trace_dispatches"] * traffic["k"]
            if traffic["mode"] == "fused" else traffic["trace_steps"])
    assert facts["attempted"] == facts["values"]["steps_traced"] == want
    # a cell is an entry of BENCHMARK.json and nothing more
    bench = copy.deepcopy(manifest.load_manifest())
    cell = {"name": "tiny." + mix, "config": "tiny_mlp", "traffic": mix,
            "chips": 1, "why": "CPU rehearsal"}
    bench["workloads"].append(cell)
    line = bench_run.result_line(bench, cell, facts, PEAKS, DEVICE, 1.5, True)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes", "busy_s", "window_s"}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # every per-layer metric that exists in all cells is read
    assert {"compile_s", "cache_misses", "host_dispatch_ms", "data_wait_ms",
            "tier_kernel_dispatches", "tier_fallbacks", "mfu",
            "device_busy_ms_per_step", "device_idle_share",
            "custom_call_ms_per_step", "program_runs_per_step"} \
        == set(line["metrics"])
    assert "step_ms_p50" not in line["metrics"]     # the tail cell's alone
    assert "collective_ms_per_step" not in line["metrics"]
    for reading in line["metrics"].values():
        assert set(reading) == {"value", "unit"}
    json.dumps(line)


def test_timed_line_has_the_end_to_end_metrics_of_the_cell(config):
    facts = train.run(config, load("tiny_loop"), jax.devices()[:1], 5, 0.2)
    bench = manifest.load_manifest()
    tail_cell = manifest.by_name(bench["workloads"],
                                 "bert_12_768_12.loop_bs128_seq128", "cell")
    line = bench_run.result_line(bench, tail_cell, facts, PEAKS, DEVICE, 2.0,
                                 False)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["metrics"]) == {"train_samples_per_s_per_chip",
                                    "step_ms_p95", "setup_s"}
    assert line["metrics"]["setup_s"] == {"value": 2.0, "unit": "s"}
    other = manifest.by_name(bench["workloads"], "resnet50_v1.fused_bs256",
                             "cell")
    line = bench_run.result_line(bench, other, facts, PEAKS, DEVICE, 2.0,
                                 False)
    assert set(line["metrics"]) == {"train_samples_per_s_per_chip", "setup_s"}


def test_a_loss_that_is_not_finite_is_not_correct(config):
    config["args"]["learning_rate"] = 1e30
    facts = train.run(config, load("tiny_fused"), jax.devices()[:1], 5, 0.2)
    assert facts["checks"]["losses_finite"] is False
    assert facts["correct"] is False


def test_a_program_compiled_inside_the_window_is_not_correct(config,
                                                             monkeypatch):
    real = train.fused_window

    def compiles_first(*args, **kwargs):
        jax.jit(lambda v: v * 3 + 1)(jax.numpy.arange(7)).block_until_ready()
        return real(*args, **kwargs)
    monkeypatch.setattr(train, "fused_window", compiles_first)
    facts = train.run(config, load("tiny_fused"), jax.devices()[:1], 5, 0.2)
    assert facts["checks"]["programs_in_window"] >= 1
    assert facts["checks"]["losses_finite"] and facts["checks"]["losses_fell"]
    assert facts["correct"] is False


def test_the_chip_peak_is_live_arrays_plus_what_programs_reserve():
    # as a v5e reported after a BERT-base run: 0.95 GB live, 6.6 GB reserved
    stats = {"bytes_in_use": 953405440, "peak_bytes_in_use": 953433088,
             "bytes_reserved": 6604849152, "peak_bytes_reserved": 6604849152,
             "bytes_limit": 16909336064}
    assert train.peak_bytes(stats) == 953433088 + 6604849152
    assert train.peak_bytes({"peak_bytes_in_use": 7}) == 7
    assert train.peak_bytes({}) == 0


def test_losses_fell_looks_at_each_batch():
    assert train.losses_fell([(0, 2.0), (1, 3.0), (0, 1.0), (1, 2.5)])
    assert not train.losses_fell([(0, 2.0), (1, 3.0), (0, 1.0), (1, 3.5)])
    assert not train.losses_fell([(0, 2.0)])        # nothing to compare


def test_percentile_is_nearest_rank():
    values = sorted(float(v) for v in range(1, 101))
    assert train.percentile(values, 95) == 95.0
    assert train.percentile(values, 50) == 50.0
    assert train.percentile([7.0], 95) == 7.0


def test_without_a_tpu_the_command_refuses():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "resnet50_v1.fused_bs256", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    lines = [ln for ln in done.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1 and lines[0].startswith(
        "chipbench: error no_accelerator")
    assert "metrics" not in done.stdout
