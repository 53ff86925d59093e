#!/usr/bin/env python
"""Pallas conv-epilogue probe (VERDICT r4 Weak #8 / Next #7).

Round-4 analysis pinned RN50 at 2686 img/s vs a 3550 HBM ceiling and
attributed the residual ~24% to XLA's conv-fusion bandwidth efficiency
(625/819 GB/s), declaring it "not framework-reachable". This probe tests
the one named candidate lever: fusing the BN-scale + residual-add + relu
epilogue of a stage-3/4 bottleneck conv into a hand Pallas kernel, vs
letting XLA fuse the same ops into its conv consumer.

Two timed variants on the stage-3 3x3 shape (N=64, 14x14, C=256, bf16):
  xla     conv -> scale*x+bias -> +res -> relu, one jit (XLA fuses)
  pallas  conv under jit, epilogue as ONE Pallas VMEM pass

If the Pallas variant wins, part of the 24% is reclaimable and the next
step is widening the epilogue; if it loses or ties, the round-4 claim
gains evidence (the epilogue is already fused; the gap lives inside the
conv itself). Either outcome goes to docs/perf_notes.md.

CPU: runs a tiny interpret-mode correctness check only (no timing claim).
Prints one JSON line per variant.
"""
from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _block(n, cap):
    """Largest divisor of n that is <= cap (grid must tile n exactly —
    a floor-divided grid would leave the remainder rows unwritten)."""
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1


def epilogue_pallas(y, scale, bias, res, interpret=False):
    """relu(y * scale + bias + res) in one VMEM pass over (R, C) rows."""
    from jax.experimental import pallas as pl

    r, c = y.shape
    br = _block(r, 512)
    bc = _block(c, 256)

    def kernel(y_ref, s_ref, b_ref, res_ref, o_ref):
        x = y_ref[...].astype(jnp.float32)
        out = x * s_ref[...] + b_ref[...] + res_ref[...].astype(jnp.float32)
        o_ref[...] = jnp.maximum(out, 0.0).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(r // br, c // bc),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                  pl.BlockSpec((1, bc), lambda i, j: (0, j)),
                  pl.BlockSpec((1, bc), lambda i, j: (0, j)),
                  pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), y.dtype),
        interpret=interpret,
    )(y, scale, bias, res)


def main():
    from mxnet_tpu import runtime
    runtime.enable_compile_cache()
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        n, h, w, cin, cout = 64, 14, 14, 256, 256
        steps, reps = 30, 3
    else:
        n, h, w, cin, cout = 2, 14, 14, 128, 128
        steps, reps = 2, 1

    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    x = jnp.asarray(rng.randn(n, h, w, cin), dtype=dt)
    k = jnp.asarray(rng.randn(3, 3, cin, cout) * 0.05, dtype=dt)
    scale = jnp.asarray(rng.rand(1, cout) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(1, cout) * 0.1, jnp.float32)
    res = jnp.asarray(rng.randn(n, h, w, cout), dtype=dt)

    conv = functools.partial(
        jax.lax.conv_general_dilated, window_strides=(1, 1),
        padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)

    @jax.jit
    def step_xla(x, k, scale, bias, res):
        y = conv(x, k)
        y = y * scale.reshape(1, 1, 1, -1) + bias.reshape(1, 1, 1, -1)
        return jnp.maximum(y + res.astype(jnp.float32), 0.0).astype(x.dtype)

    @jax.jit
    def step_pallas(x, k, scale, bias, res):
        y = conv(x, k).astype(x.dtype)
        flat = y.reshape(-1, y.shape[-1])
        out = epilogue_pallas(flat, scale, bias,
                              res.reshape(-1, res.shape[-1]),
                              interpret=not on_tpu)
        return out.reshape(y.shape)

    # correctness first (fp32 reference)
    a = np.asarray(step_xla(x, k, scale, bias, res), np.float32)
    b = np.asarray(step_pallas(x, k, scale, bias, res), np.float32)
    err = float(np.abs(a - b).max())
    tol = 0.1 if on_tpu else 1e-3        # bf16 conv accumulate reorder
    if err > tol:
        print(json.dumps({"metric": "conv_epilogue_probe",
                          "error": "mismatch", "max_err": err}))
        return 1

    results = {}
    for name, fn in [("xla", step_xla), ("pallas", step_pallas)]:
        fn(x, k, scale, bias, res).block_until_ready()
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(steps):
                out = fn(x, k, scale, bias, res)
            out.block_until_ready()
            dtm = (time.perf_counter() - t0) / steps
            best = dtm if best is None else min(best, dtm)
        results[name] = best
        # ms + ratio only: a GB/s figure from whole-step time would
        # attribute conv time to the epilogue and mislead perf_notes
        print(json.dumps({
            "metric": f"conv_epilogue_{name}_ms", "value": round(best * 1e3, 3),
            "unit": f"ms/step ({platform}, {n}x{h}x{w}x{cin}->{cout})",
        }))
    print(json.dumps({
        "metric": "conv_epilogue_pallas_speedup",
        "value": round(results["xla"] / results["pallas"], 4),
        "unit": "x (xla_ms / pallas_ms; >1 means pallas wins)",
        "max_err": err,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
