"""The measurement entry points never hide a missing device.

bench.py, benchmarks/scaling.py and chip_smoke.py are one process each, the
one that owns the chip. Without a TPU, or when the body fails, they exit
non-zero and print ONE parseable error line — never a CPU number under a
device metric's name, never ``"ok": true``. The suite runs on the CPU, so
"no device" is simply what it sees.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json_lines(text):
    out = []
    for line in text.splitlines():
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def _run(script, *argv, cwd=REPO):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_diagnostic_shape():
    d = bench._diagnostic("no_accelerator", "JAX found a cpu")
    assert d["metric"] == bench.METRIC
    assert d["value"] is None and d["vs_baseline"] is None
    assert d["error"] == "no_accelerator"
    json.dumps(d)                       # serializable


def test_no_device_is_nonzero_exit_and_one_error_line(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    rc = bench.main()
    assert rc != 0
    lines = [r for r in _json_lines(capsys.readouterr().out)
             if "metric" in r]
    assert len(lines) == 1
    assert lines[0]["error"] == "no_accelerator"
    assert lines[0]["metric"] == bench.METRIC and lines[0]["value"] is None


def test_body_failure_is_nonzero_exit_and_one_error_line(monkeypatch,
                                                         capsys):
    def boom():
        raise RuntimeError("compiler said no")
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setattr(bench, "_run_body", boom)
    rc = bench.main()
    assert rc != 0
    lines = [r for r in _json_lines(capsys.readouterr().out)
             if "metric" in r]
    assert len(lines) == 1
    assert lines[0]["error"] == "bench_failed"
    assert "compiler said no" in lines[0]["detail"]
    assert lines[0]["value"] is None


@pytest.mark.parametrize("script", ["bench.py", "benchmarks/scaling.py"])
def test_on_a_cpu_backend_the_device_metric_is_refused(script):
    """The whole script, as the driver runs it: a CPU backend gets an
    error, not a small CPU job printed under the device metric's name."""
    out = _run(os.path.join(REPO, script))
    assert out.returncode != 0, out.stdout[-500:]
    lines = [r for r in _json_lines(out.stdout) if "metric" in r]
    assert len(lines) == 1, out.stdout[-500:]
    assert lines[0]["error"] == "no_accelerator"
    assert lines[0]["value"] is None
    assert not lines[0].get("vs_baseline")


def test_chip_smoke_without_a_chip_fails_and_prints_no_result():
    out = _run(os.path.join(REPO, "chip_smoke.py"))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no accelerator" in out.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run(str(tmp_path / "chip_smoke.py"), cwd=str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_unknown_chip_has_no_peaks_and_no_ratio():
    """A ratio against a guessed peak reads like a measurement: a device
    the table does not know is an error, not a default."""
    from mxnet_tpu import MXNetError, runtime

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    with pytest.raises(MXNetError, match="no published peaks"):
        runtime.device_peaks(Dev())
    Dev.device_kind = "TPU v5 lite"
    assert runtime.device_peaks(Dev())["hbm_bytes_per_s"] == 819e9


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: the code sets no other directory.
    Unset: ``<checkout>/.jax_cache``, a fixed path."""
    import jax
    from mxnet_tpu import runtime
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert runtime.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == was    # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert runtime.enable_compile_cache() == want
        assert runtime.enable_compile_cache() == want          # and stays
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_contexts_resolve_to_their_own_platform_or_raise():
    import mxnet_tpu as mx
    assert mx.cpu().jax_device.platform == "cpu"
    assert mx.cpu(999).jax_device.platform == "cpu"   # the host is one memory
    with pytest.raises(mx.MXNetError, match="no TPU"):
        mx.tpu().jax_device
    assert mx.num_tpus() == 0


def test_children_that_would_fight_over_the_chips_are_refused(monkeypatch):
    """One process for each chip: subprocess replicas and local launcher
    workers get no device of their own, so on a host with chips more than
    one that may use the TPU — or any, once this process holds them — is
    refused; children pinned to the CPU always start."""
    from mxnet_tpu.diagnostics import guard
    cpu, free = {"JAX_PLATFORMS": "cpu"}, {}
    guard.check_chip_children([free, free], "no chips here")    # no-op
    monkeypatch.setattr(guard, "local_tpu_chips", lambda: 4)
    guard.check_chip_children([cpu, cpu, cpu], "cpu workers")
    guard.check_chip_children([None, None], "inherits the suite's cpu pin")
    guard.check_chip_children([free, cpu], "one taker")
    with pytest.raises(RuntimeError, match="every chip of this host"):
        guard.check_chip_children([free, free], "two takers")
