#!/usr/bin/env python
"""Rematerialization / master-dtype experiment matrix for the RN50 step.

docs/perf_notes.md (round 2) measured the ResNet-50 train step as
HBM-bandwidth-bound: ~59 GB/step intrinsic traffic, MXU ~74% idle. The two
untried bandwidth levers are:

  - activation rematerialization (``ShardedTrainer(remat=...)`` →
    ``jax.checkpoint``): stop saving forward activations, recompute them in
    backward — trades idle MXU FLOPs for HBM writes+reads;
  - bf16 master weights (``master_dtype="bfloat16"``): halve the
    weight/momentum read+write traffic of the fused update.

This probe measures the full fused train step (fwd+bwd+SGD-mom update) for
each config with bench.py's k-steps-per-dispatch scanned program, so that
host dispatch never gates the measurement.

Usage: PYTHONPATH=. python benchmarks/remat_probe.py [--batch 256]
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def measure(config_name, batch, on_tpu, **trainer_kw):
    import jax
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.resnet50_v1() if on_tpu else vision.resnet18_v1()
    net.initialize()
    mesh = parallel.make_mesh({"data": len(jax.devices())})
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        mesh=mesh, compute_dtype="bfloat16" if on_tpu else None, **trainer_kw)
    x_host = np.random.randn(batch, 3, 224 if on_tpu else 32,
                             224 if on_tpu else 32).astype(np.float32)
    y_host = np.random.randint(0, 1000, (batch,))
    # stage the batch on device ONCE: re-uploading per dispatch would
    # put the host->device link into the measurement
    trainer._prepare((x_host,))
    x = trainer._shard_batch_arg(x_host)
    y = trainer._shard_batch_arg(y_host)

    # bench.py's methodology: N back-to-back ASYNC dispatches of a k-step
    # scanned program, ONE hard sync at the end (dispatch overlaps
    # compute), best of 3 windows.
    k = 10 if on_tpu else 2
    dispatches = 8 if on_tpu else 2
    windows = 3
    np.asarray(trainer.run_steps(x, y, num_steps=k).asnumpy())   # compile
    best = None
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(dispatches):
            loss = trainer.run_steps(x, y, num_steps=k)
        np.asarray(loss.asnumpy())
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    per_step = best / (dispatches * k)
    img_s = batch / per_step
    print(f"{config_name:<28} {per_step * 1e3:8.1f} ms/step "
          f"{img_s:8.0f} img/s", flush=True)
    return per_step, img_s


def main():
    from mxnet_tpu import runtime
    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--configs", nargs="+", default=None)
    args = ap.parse_args()

    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    batch = args.batch or (256 if on_tpu else 8)
    print(f"platform={jax.devices()[0].platform} batch={batch}", flush=True)

    matrix = {
        "baseline": {},
        "remat_full": {"remat": "full"},
        "remat_dots": {"remat": "dots"},
        "bf16_master": {"master_dtype": "bfloat16"},
        "bf16_master+remat_full": {"master_dtype": "bfloat16",
                                   "remat": "full"},
    }
    names = args.configs or list(matrix)
    results = {}
    for name in names:
        results[name] = measure(name, batch, on_tpu, **matrix[name])
    base = results.get("baseline")
    if base:
        for name, (t, r) in results.items():
            print(f"{name:<28} speedup vs baseline: {base[0] / t:.3f}x")


if __name__ == "__main__":
    main()
