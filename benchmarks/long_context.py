#!/usr/bin/env python
"""Long-context attention throughput ladder (SURVEY §5.7 — the net-new
TPU capability: blockwise/Pallas flash attention for sequences far past
the reference's ~512-token BucketingModule ceiling).

Measures one BERT-style self-attention layer (fused QKV projection +
``_contrib_fused_self_attention`` + output projection) forward+backward
across a sequence ladder on the available device. Short sequences route
to the fused dense path; S > 1024 engages the streaming flash kernel
(Pallas on TPU hardware, blockwise jnp elsewhere), whose memory is O(S)
instead of O(S²) — the dense scores tensor for S=32k at batch 1/head 12
would alone be 12·32768² fp32 ≈ 48 GB, past HBM.

Methodology: bench.py's staged-batch, k-step-scan, best-of-3-windows
timing (see docs/perf_notes.md "Measurement pitfalls").

Usage: PYTHONPATH=. python benchmarks/long_context.py
       [--seqs 512 2048 8192 16384 32768] [--units 768] [--heads 12]
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def measure(seq, units, heads, on_tpu):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.contrib import _fused_self_attention

    tokens = 16384 if on_tpu else 2048      # constant work per config
    batch = max(1, tokens // seq)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, seq, units) * 0.02, dtype)
    w_qkv = jnp.asarray(rng.randn(units, 3 * units) * 0.02, dtype)
    w_out = jnp.asarray(rng.randn(units, units) * 0.02, dtype)

    def layer(x, w_qkv, w_out):
        qkv = x @ w_qkv                      # the full QKV projection
        out = _fused_self_attention(qkv, heads=heads, causal=True,
                                    block_size=1024)
        out = out @ w_out
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad = jax.grad(layer, argnums=(0, 1, 2))

    k = 8 if on_tpu else 2

    @jax.jit
    def steps(x, w_qkv, w_out):
        def body(c, _):
            g_x, g_qkv, g_out = grad(c, w_qkv, w_out)
            return c - 1e-6 * g_x.astype(c.dtype), jnp.sum(
                g_qkv.astype(jnp.float32)) + jnp.sum(
                g_out.astype(jnp.float32))
        c, s = jax.lax.scan(body, x, jnp.arange(k))
        return s[-1]

    np.asarray(steps(x, w_qkv, w_out))      # compile + warm
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(4 if on_tpu else 1):
            s = steps(x, w_qkv, w_out)
        np.asarray(s)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    n_disp = 4 if on_tpu else 1
    tok_s = batch * seq * n_disp * k / best
    print(f"S={seq:<6} batch={batch:<3} {best / (n_disp * k) * 1e3:9.2f} "
          f"ms/step {tok_s:12.0f} tokens/s fwd+bwd", flush=True)
    return tok_s


def main():
    from mxnet_tpu import runtime
    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=int, nargs="+", default=None)
    ap.add_argument("--units", type=int, default=None)
    ap.add_argument("--heads", type=int, default=None)
    args = ap.parse_args()
    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    # CPU smoke crosses the s > 1024 threshold too, so the streaming
    # blockwise path (the point of this benchmark) is exercised off-TPU
    seqs = args.seqs or ([512, 2048, 8192, 16384, 32768] if on_tpu
                         else [256, 2048])
    units = args.units or (768 if on_tpu else 64)
    heads = args.heads or (12 if on_tpu else 4)
    print(f"platform={jax.devices()[0].platform} units={units} "
          f"heads={heads} (constant tokens/config; causal)", flush=True)
    for s in seqs:
        measure(s, units, heads, on_tpu)


if __name__ == "__main__":
    main()
