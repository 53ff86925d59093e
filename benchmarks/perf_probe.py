"""Piecewise ResNet-50 step profiler — where does the step time go?

Every sub-program (forward, forward+backward, the full fused train step)
is measured as a k-iteration ``lax.scan``, serialized by a carry
data-dependency, so that one dispatch covers k iterations; the timed call
ends in ``block_until_ready`` and the time per iteration is t / k. MFU is
against the chip's published bf16 peak (``mxnet_tpu.runtime.DEVICE_PEAKS``);
without a TPU, or on a chip that table does not know, the probe refuses.

Usage: python benchmarks/perf_probe.py [--batch 256 512]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCAN_K = 10


def _timed(call, iters=3):
    best = None
    for _ in range(iters):
        t0 = time.perf_counter()
        call()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def _scan_time(jit_fn, args):
    """jit_fn(k)(args...) -> scalar; returns seconds per inner iteration."""
    import jax
    f = jit_fn(SCAN_K)
    jax.block_until_ready(f(*args))              # compile + warm
    return _timed(lambda: jax.block_until_ready(f(*args))) / SCAN_K


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, nargs="+", default=[256])
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from mxnet_tpu import gluon, parallel, runtime
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import functional_apply

    runtime.enable_compile_cache()
    dev = runtime.tpu_devices("perf_probe (step time, MFU)")[0]
    peak = runtime.device_peaks(dev)["bf16_flops_per_s"]
    fwd_flops = 4.1e9                     # RN50 @224, per image
    print(f"platform={dev.platform} kind={dev.device_kind} "
          f"devices={len(jax.devices())}")

    for batch in args.batch:
        net = vision.resnet50_v1()
        net.initialize()
        mesh = parallel.make_mesh({"data": len(jax.devices())})
        trainer = parallel.ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "wd": 1e-4},
            mesh=mesh, compute_dtype="bfloat16")
        x_host = np.random.randn(batch, 3, 224, 224).astype(np.float32)
        y_host = np.random.randint(0, 1000, (batch,))
        trainer.prepare(x_host[:1])
        x = trainer._shard_batch_arg(x_host)
        y = trainer._shard_batch_arg(y_host)
        tr = [p._data[0]._data for p in trainer._trainable]
        aux = [p._data[0]._data for p in trainer._aux]

        cdt = jnp.bfloat16

        def cast_all(ws):
            return [w.astype(cdt) if jnp.issubdtype(w.dtype, jnp.floating)
                    else w for w in ws]

        def fwd_once(tr_, aux_, x_):
            outs, _, _ = functional_apply(
                net, jax.random.PRNGKey(0), tr_, aux_, [x_],
                training=True)   # training mode: batch stats, like the step
            return outs[0]

        def make_fwd(k):
            def run(tr_, aux_, x_):
                tr_ = cast_all(tr_)
                aux_ = cast_all(aux_)
                x_ = x_.astype(cdt)

                def body(c, _):
                    out = fwd_once(tr_, aux_, x_ + c * 1e-30)
                    return jnp.mean(out).astype(x_.dtype), None
                c, _ = jax.lax.scan(body, jnp.zeros((), x_.dtype),
                                    None, length=k)
                return c
            return jax.jit(run)

        def loss_of(tr_, aux_, x_, y_):
            outs, _, _ = functional_apply(
                net, jax.random.PRNGKey(0), tr_, aux_, [x_], training=True)
            logits = outs[0].astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            nll = lse - jnp.take_along_axis(
                logits, y_[:, None], axis=-1)[:, 0]
            return jnp.mean(nll)

        def make_grad(k):
            def run(tr_, aux_, x_, y_):
                tr_ = cast_all(tr_)
                aux_ = cast_all(aux_)
                x_ = x_.astype(cdt)

                def body(c, _):
                    g = jax.grad(loss_of)(
                        [w + (c * 1e-30).astype(w.dtype) for w in tr_],
                        aux_, x_, y_)
                    return jnp.mean(g[0]).astype(jnp.float32), None
                c, _ = jax.lax.scan(body, jnp.zeros(()), None, length=k)
                return c
            return jax.jit(run)

        t_fwd = _scan_time(make_fwd, (tr, aux, x))
        t_grad = _scan_time(make_grad, (tr, aux, x, y))

        # full fused train step: trainer.run_steps is the same scan
        def full_step():
            trainer.run_steps(x, y, num_steps=SCAN_K).wait_to_read()
        full_step()              # compile + warm
        t_step = _timed(full_step) / SCAN_K

        n = len(jax.devices())

        def rep(name, t, mult):
            ips = batch / t / n
            mfu = mult * fwd_flops * ips / peak
            print(f"  batch={batch:4d} {name:12s} {t*1e3:8.2f} ms  "
                  f"{ips:8.0f} img/s/chip  MFU={mfu*100:5.1f}%")
        rep("forward", t_fwd, 1)
        rep("fwd+bwd", t_grad, 3)
        rep("full step", t_step, 3)


if __name__ == "__main__":
    main()
