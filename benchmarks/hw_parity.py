#!/usr/bin/env python
"""Hardware parity sweep: run the §4 consistency check (the reference's
check_consistency / test_operator_gpu.py pattern — CPU is the oracle for
the accelerator) against the REAL chip.

For each op in the sweep: compute on the TPU via the normal dispatch
path, recompute the same op with numpy/CPU math, and compare at
dtype-appropriate tolerance. Covers the compute core the models lean on:
conv/dense/norms/softmax/attention/reductions + a fused train step.

Usage: PYTHONPATH=. python benchmarks/hw_parity.py
Prints PASS/FAIL per op and a summary line.
"""
from __future__ import annotations

import numpy as np


def main():
    from mxnet_tpu import runtime
    runtime.enable_compile_cache()
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd

    platform = jax.devices()[0].platform
    print(f"platform={platform}")
    rng = np.random.RandomState(0)
    results = []

    def check(name, got, want, rtol=2e-2, atol=2e-3):
        got = np.asarray(got)
        want = np.asarray(want)
        ok = np.allclose(got, want, rtol=rtol, atol=atol)
        err = float(np.max(np.abs(got - want) /
                           (np.abs(want) + atol))) if got.size else 0.0
        results.append((name, ok, err))
        print(f"{'PASS' if ok else 'FAIL'} {name:<28} max rel err "
              f"{err:.2e}", flush=True)

    # dense / conv / norm cores
    x = rng.randn(32, 64).astype(np.float32)
    w = rng.randn(128, 64).astype(np.float32)
    b = rng.randn(128).astype(np.float32)
    check("FullyConnected",
          mx.nd.FullyConnected(nd.array(x), nd.array(w), nd.array(b),
                               num_hidden=128).asnumpy(),
          x @ w.T + b, rtol=1e-3, atol=1e-4)

    xc = rng.randn(4, 8, 16, 16).astype(np.float32)
    wc = rng.randn(12, 8, 3, 3).astype(np.float32)
    got = mx.nd.Convolution(nd.array(xc), nd.array(wc),
                            kernel=(3, 3), num_filter=12,
                            no_bias=True).asnumpy()
    # NUMPY oracle (a lax conv would run on the same device under the
    # same precision config — tautological): sliding windows + einsum
    win = np.lib.stride_tricks.sliding_window_view(
        xc, (3, 3), axis=(2, 3))             # (N, C, OH, OW, 3, 3)
    want = np.einsum("nchwij,ocij->nohw", win, wc)
    check("Convolution3x3", got, want, rtol=1e-3, atol=1e-4)

    xb = (rng.randn(16, 8, 6, 6) * 3 + 5).astype(np.float32)
    g1 = np.abs(rng.randn(8).astype(np.float32)) + 0.5
    b1 = rng.randn(8).astype(np.float32)
    with autograd.record(train_mode=True):
        out, bm, bv = mx.nd.BatchNorm(
            nd.array(xb), nd.array(g1), nd.array(b1),
            nd.array(np.zeros(8, np.float32)),
            nd.array(np.zeros(8, np.float32)),
            fix_gamma=False, output_mean_var=True)
    mu = xb.mean(axis=(0, 2, 3), keepdims=True)
    var = xb.var(axis=(0, 2, 3), keepdims=True)
    want = (xb - mu) / np.sqrt(var + 1e-3) * g1.reshape(1, -1, 1, 1) \
        + b1.reshape(1, -1, 1, 1)
    check("BatchNorm(train)", out.asnumpy(), want, rtol=1e-2, atol=1e-3)

    xl = rng.randn(8, 32).astype(np.float32)
    gl = np.ones(32, np.float32)
    bl = np.zeros(32, np.float32)
    mu = xl.mean(-1, keepdims=True)
    sd = np.sqrt(xl.var(-1, keepdims=True) + 1e-5)
    check("LayerNorm",
          mx.nd.LayerNorm(nd.array(xl), nd.array(gl),
                          nd.array(bl)).asnumpy(),
          (xl - mu) / sd, rtol=1e-3, atol=1e-4)

    s = rng.randn(6, 40).astype(np.float32) * 4
    e = np.exp(s - s.max(-1, keepdims=True))
    check("softmax", mx.nd.softmax(nd.array(s)).asnumpy(),
          e / e.sum(-1, keepdims=True), rtol=1e-3, atol=1e-5)
    check("logsumexp",
          mx.nd.logsumexp(nd.array(s), axis=-1).asnumpy(),
          np.log(np.exp(s - s.max(-1, keepdims=True))
                 .sum(-1)) + s.max(-1), rtol=1e-4, atol=1e-4)

    # fused attention vs dense oracle
    B, S, H, D = 2, 64, 4, 16
    qkv = rng.randn(B, S, 3 * H * D).astype(np.float32) * 0.3
    got = mx.nd.contrib.fused_self_attention(
        nd.array(qkv), heads=H, causal=True).asnumpy()
    q = qkv[:, :, :H * D].reshape(B, S, H, D)
    k = qkv[:, :, H * D:2 * H * D].reshape(B, S, H, D)
    v = qkv[:, :, 2 * H * D:].reshape(B, S, H, D)
    sc = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    mask = np.triu(np.full((S, S), -1e30), 1)
    sc = sc + mask
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * D)
    check("fused_self_attention", got, want, rtol=1e-2, atol=1e-3)

    # one fused train step: loss must match a CPU-computed reference
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    xs = rng.randn(8, 10).astype(np.float32)
    ys = rng.randint(0, 4, (8,))
    with autograd.record():
        outp = net(nd.array(xs))
        loss = gluon.loss.SoftmaxCrossEntropyLoss()(outp,
                                                    nd.array(ys))
    w1 = net[0].weight.data().asnumpy()
    b1_ = net[0].bias.data().asnumpy()
    w2 = net[1].weight.data().asnumpy()
    b2_ = net[1].bias.data().asnumpy()
    h = np.maximum(xs @ w1.T + b1_, 0)
    logits = h @ w2.T + b2_
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True))
                 .sum(-1)) + logits.max(-1)
    want_loss = lse - logits[np.arange(8), ys]
    check("train-step loss", loss.asnumpy(), want_loss,
          rtol=1e-3, atol=1e-4)

    # backward parity: autograd gradients vs hand-derived numpy math
    # (the reference's GPU tier checks both directions — SURVEY §4)
    xg = nd.array(x)
    wg = nd.array(w)
    xg.attach_grad()
    wg.attach_grad()
    ct = rng.randn(32, 128).astype(np.float32)
    with autograd.record():
        o = mx.nd.FullyConnected(xg, wg, nd.array(b), num_hidden=128)
        lo = (o * nd.array(ct)).sum()
    lo.backward()
    check("FC dL/dx", xg.grad.asnumpy(), ct @ w, rtol=1e-3, atol=1e-4)
    check("FC dL/dw", wg.grad.asnumpy(), ct.T @ x, rtol=1e-3, atol=1e-4)

    xcg = nd.array(xc)
    xcg.attach_grad()
    ctc = rng.randn(4, 12, 14, 14).astype(np.float32)
    with autograd.record():
        oc = mx.nd.Convolution(xcg, nd.array(wc), kernel=(3, 3),
                               num_filter=12, no_bias=True)
        lc = (oc * nd.array(ctc)).sum()
    lc.backward()
    # numpy dL/dx: full-correlation of cotangent with flipped kernels
    pad_ct = np.zeros((4, 12, 18, 18), np.float32)
    pad_ct[:, :, 2:16, 2:16] = ctc
    wflip = wc[:, :, ::-1, ::-1]
    win_ct = np.lib.stride_tricks.sliding_window_view(
        pad_ct, (3, 3), axis=(2, 3))
    want_dx = np.einsum("nohwij,ocij->nchw", win_ct, wflip)
    check("conv dL/dx", xcg.grad.asnumpy(), want_dx,
          rtol=1e-3, atol=1e-4)

    n_fail = sum(not ok for _, ok, _ in results)
    print(f"hw_parity: {len(results) - n_fail}/{len(results)} ops match "
          f"the CPU oracle on {platform}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
