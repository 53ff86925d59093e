"""Benchmark of record: ResNet-50 training throughput (images/sec/chip).

Runs the flagship training step — the full fused SPMD program (forward,
softmax-CE loss, backward, SGD-momentum update) — on the TPU this process
holds and reports steady-state throughput, per BASELINE.md's measurement
protocol.

``vs_baseline`` is measured / governing-ceiling, where the ceiling is
BASELINE.md's physics-derived HBM bound (59 GB/step of intrinsic traffic at
the chip's peak bandwidth — the binding constraint for RN50-bs256 on one
v5e; the 50%-MFU arithmetic ceiling is ≈8000 img/s and not binding). The
bandwidth comes from ``mxnet_tpu.runtime.DEVICE_PEAKS``, keyed by
``device_kind``; a chip that table does not know is an error, and the base
of the ratio is printed beside it.

One process, which owns the chip: no probe child, no body child. A device
number is only ever measured on a device — without a TPU, or when the body
fails, the script prints ONE JSON line with ``error`` set and ``value``
null and exits non-zero. It never shrinks the job to fit a CPU.

Prints ONE JSON line: {"metric", "value", "unit", "device", "vs_baseline",
"baseline", ...} on success, {"metric", "value": null, "error", "detail"}
otherwise.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time
import traceback

import numpy as np

from mxnet_tpu import runtime

METRIC = "resnet50_train_images_per_sec_per_chip"
BATCH = 256
STEP_HBM_BYTES = 59e9      # BASELINE.md: intrinsic traffic of one bs256 step
STEPS_PER_DISPATCH = 10    # run_steps(k): one program, k optimizer steps
DISPATCHES = 8             # per timed window
WINDOWS = 3


def _emit(obj: dict) -> None:
    sys.stdout.flush()
    print(json.dumps(obj), flush=True)


def _diagnostic(error: str, detail: str) -> dict:
    return {"metric": METRIC, "value": None, "unit": "images/sec/chip",
            "vs_baseline": None, "error": error, "detail": detail}


def _parse_pallas_flag(argv) -> str | None:
    """``--pallas {on,off,auto}`` (or ``--pallas=X``): A/B switch for the
    guarded custom-kernel tier (docs/pallas.md). Returns the mode or
    None; the caller exports it as MXNET_TPU_PALLAS, the knob a deployment
    would set."""
    for i, arg in enumerate(argv):
        if arg.startswith("--pallas="):
            return arg.split("=", 1)[1].strip().lower()
        if arg == "--pallas":
            # a trailing flag with no value must be the structured
            # bad_flag diagnostic, not a silent default-auto A/B leg
            return (argv[i + 1].strip().lower() if i + 1 < len(argv)
                    else "")
    return None


def _run_body() -> dict:
    """The benchmark itself; returns the result record."""
    from mxnet_tpu import gluon, observability, pallas, parallel
    from mxnet_tpu.diagnostics import get_journal
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.guardrails import GuardConfig

    runtime.enable_compile_cache()
    j = get_journal()
    j.set_phase("body_setup")
    devices = runtime.tpu_devices(METRIC)
    dev, device = devices[0], runtime.device_record(devices)
    peaks = runtime.device_peaks(dev)        # unknown chip: an error
    ceiling = BATCH / (STEP_HBM_BYTES / peaks["hbm_bytes_per_s"])

    net = vision.resnet50_v1()
    net.initialize()
    mesh = parallel.make_mesh({"data": len(devices)})
    # bf16 master weights+momentum: −0.6 GB/step of optimizer traffic on
    # an HBM-bound step (docs/perf_notes.md round 3); convergence-gated
    # against fp32 masters in tests/test_convergence.py.
    # deferred-mode guard: the fused finiteness check + in-program skip
    # counters ride the measured step (so the artifact's throughput IS
    # the guarded number) with zero per-step host reads — skipped_steps
    # below is the one report-time fetch (docs/guardrails.md)
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        mesh=mesh, compute_dtype="bfloat16", master_dtype="bfloat16",
        guard=GuardConfig(mode="deferred"))

    x_host = np.random.randn(BATCH, 3, 224, 224).astype(np.float32)
    y_host = np.random.randint(0, 1000, (BATCH,))
    # stage the batch on device once — the input pipeline's double-buffered
    # prefetch (SURVEY §2.5 #34 TPU equivalent) keeps steady-state steps free
    # of host→device transfers, which is what we measure here
    trainer.prepare(x_host[:1])
    x = trainer._shard_batch_arg(x_host)
    y = trainer._shard_batch_arg(y_host)

    # K steps per dispatch (lax.scan inside one program) so host dispatch
    # never gates the measurement — the same program a production input
    # pipeline would run. Each window ends in a block_until_ready.
    k = STEPS_PER_DISPATCH
    j.set_phase("body_compile_warm")
    t0 = time.perf_counter()
    trainer.run_steps(x, y, num_steps=k).wait_to_read()     # compile+warm
    compile_warm_s = time.perf_counter() - t0
    j.set_phase("body_measure")
    rates = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(DISPATCHES):
            loss = trainer.run_steps(x, y, num_steps=k)
        loss.wait_to_read()
        dt = time.perf_counter() - t0
        rates.append(BATCH * DISPATCHES * k / dt / len(devices))
    value = statistics.median(rates)

    # telemetry provenance (docs/observability.md): compile counts/times
    # and step-phase p50/p95 ride the artifact
    obs = observability.snapshot()
    comp = observability.compile_stats(obs)
    print(f"bench: compiles={comp['compiles']} "
          f"total={comp['total_ms']}ms by_site={comp['by_site']}",
          file=sys.stderr)
    return {
        "metric": METRIC,
        "value": round(value, 2),
        "unit": f"images/sec/chip (batch={BATCH}, bf16)",
        "device": device,
        "windows": [round(r, 2) for r in rates],
        "compile_warm_s": round(compile_warm_s, 1),
        "vs_baseline": round(value / ceiling, 4),
        "baseline": {"images_per_sec_per_chip": round(ceiling, 1),
                     "what": f"HBM bound: {STEP_HBM_BYTES / 1e9:g} GB/step "
                             f"at {peaks['hbm_bytes_per_s'] / 1e9:g} GB/s"},
        # per-op kernel-tier provenance (docs/pallas.md): which tier
        # each custom-kernel dispatch chose while building the measured
        # program, and why any fallback happened — an A/B number must
        # say which tier produced it
        "pallas": {"mode": pallas.mode(), "ops": pallas.tier_provenance()},
        # guardrail accounting (docs/guardrails.md): the fused guard's
        # in-program skip counter, fetched once at report time — a
        # non-zero count means the measured window trained on fewer
        # steps than dispatched
        "skipped_steps": int(trainer.skipped_steps),
        "observability": obs,
    }


def main() -> int:
    pallas_mode = _parse_pallas_flag(sys.argv)
    if pallas_mode is not None:
        if pallas_mode not in ("on", "off", "auto"):
            _emit(_diagnostic("bad_flag",
                              f"--pallas must be on|off|auto, got "
                              f"{pallas_mode!r}"))
            return 2
        os.environ["MXNET_TPU_PALLAS"] = pallas_mode
    try:
        result = _run_body()
    except runtime.NoAccelerator as e:
        _emit(_diagnostic("no_accelerator", str(e)))
        return 3
    except Exception as e:
        traceback.print_exc()
        _emit(_diagnostic("bench_failed", f"{type(e).__name__}: {e}"))
        return 1
    _emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
