#!/usr/bin/env python
"""Scaling-efficiency harness: per-chip training throughput over a ladder
of mesh sizes on one host's chips (ROADMAP S6: 1, 2, 4 on a four-chip
host), and the ratio of the largest mesh's per-chip rate to the smallest's.

One process, which owns every chip it measures. Same artifact contract as
bench.py: ONE JSON line on stdout — the result, or ``error`` set and
``value`` null with a non-zero exit when JAX finds no TPU, a requested
mesh size exceeds the visible devices, or the body fails. A device number
is never taken on a CPU.

``--artifact PATH`` additionally writes the full result — per-mesh-size
throughput ladder, scaling efficiency, **elastic / cohort metadata**
(``elastic.elastic_metadata()``: world shape, the MXTPU_* env wiring) and
the ``observability.snapshot()`` compile/step-phase provenance — as a JSON
document:

     python benchmarks/scaling.py --network resnet50_v1 --sizes 1,2,4 \
         --artifact chiprun_out/scaling.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

METRIC = "scaling_efficiency"


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _diagnostic(error: str, detail: str) -> dict:
    return {"metric": METRIC, "value": None, "target": 0.9,
            "error": error, "detail": detail}


def _write_artifact(path, doc) -> None:
    if not path:
        return
    from mxnet_tpu.resilience import atomic
    with atomic.atomic_write(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"scaling: artifact -> {path}", file=sys.stderr)


def measure(n_chips, batch_per_chip, steps, warmup, network, classes,
            image, bf16):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    devices = jax.devices()[:n_chips]
    mesh = parallel.make_mesh({"data": n_chips}, devices=devices)
    net = vision.get_model(network, classes=classes)
    net.initialize(mx.init.Xavier())
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh, compute_dtype="bfloat16" if bf16 else None)
    batch = batch_per_chip * n_chips
    x_host = np.random.randn(batch, 3, image, image).astype(np.float32)
    y_host = np.random.randint(0, classes, (batch,))
    trainer.prepare(x_host[:1])
    x = trainer._shard_batch_arg(x_host)
    y = trainer._shard_batch_arg(y_host)
    for _ in range(warmup):
        trainer.step(x, y).wait_to_read()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(x, y)
    loss.wait_to_read()
    dt = time.perf_counter() - t0
    return batch * steps / dt / n_chips


def _parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--network", default="resnet50_v1")
    p.add_argument("--classes", type=int, default=1000)
    p.add_argument("--image", type=int, default=224)
    p.add_argument("--batch-per-chip", type=int, default=128)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--sizes", default=None,
                   help="comma list of mesh sizes (default: 1,2,4,… up to "
                        "visible devices)")
    p.add_argument("--no-bf16", dest="bf16", action="store_false",
                   default=True)
    p.add_argument("--artifact", default=None,
                   help="also write the full result (ladder + elastic/"
                        "cohort metadata + observability snapshot) to "
                        "this path")
    return p.parse_args(argv)


def _run_body(args):
    """Returns ``(result or diagnostic, exit code)``."""
    from mxnet_tpu import elastic, observability, runtime

    runtime.enable_compile_cache()
    devices = runtime.tpu_devices(METRIC)
    n = len(devices)
    if args.sizes:
        sizes = [int(s) for s in args.sizes.split(",") if s]
        missing = [s for s in sizes if s > n]
        if missing:
            # an explicitly-requested size the hardware can't provide
            # must fail LOUDLY: silently clamping would let the gate
            # "pass" with base==max (a vacuous efficiency of 1.0)
            return _diagnostic(
                "insufficient_devices",
                f"requested mesh sizes {missing} exceed the {n} visible "
                f"devices — refusing to fake the scaling ladder"), 3
    else:
        sizes = [s for s in (1, 2, 4, 8, 16, 32, 64) if s <= n]
    results = {}
    for s in sizes:
        per_chip = measure(s, args.batch_per_chip, args.steps,
                           args.warmup, args.network, args.classes,
                           args.image, args.bf16)
        results[s] = per_chip
        print(json.dumps({"chips": s,
                          "images_per_sec_per_chip": round(per_chip, 2)}),
              file=sys.stderr, flush=True)
    base = results[sizes[0]]
    return {
        "metric": METRIC,
        "value": round(results[sizes[-1]] / base, 4),
        "target": 0.9,
        "base_chips": sizes[0], "max_chips": sizes[-1],
        "network": args.network, "bf16": bool(args.bf16),
        "batch_per_chip": args.batch_per_chip,
        "device": runtime.device_record(devices),
        "ladder": {str(s): round(v, 2) for s, v in results.items()},
        # cohort/elastic provenance (docs/elastic.md): world shape +
        # env wiring, so a pod-slice artifact records which cohort ran
        "elastic": elastic.elastic_metadata(),
        "observability": observability.snapshot(),
    }, 0


def main() -> int:
    from mxnet_tpu.runtime import NoAccelerator
    args = _parse_args()
    try:
        doc, rc = _run_body(args)
    except NoAccelerator as e:
        doc, rc = _diagnostic("no_accelerator", str(e)), 3
    except Exception as e:
        traceback.print_exc()
        doc, rc = _diagnostic("scaling_failed",
                              f"{type(e).__name__}: {e}"), 1
    _emit(doc)
    _write_artifact(args.artifact, doc)
    return rc


if __name__ == "__main__":
    sys.exit(main())
