#!/usr/bin/env python
"""DCGAN (ref: example/gan/dcgan.py — the reference zoo's adversarial
family): Conv2DTranspose generator vs Conv2D discriminator, alternating
adam steps, trained here on a synthetic structured-image distribution so
the example is self-contained and CI-gateable.

TPU notes: both players train through ShardedTrainer-style fused steps?
No — GANs alternate two optimizers over two parameter sets with the
OTHER player frozen, which maps naturally onto two eager autograd loops
over hybridized blocks (each forward is one compiled program); the
batch-level compute dominates, so the two-dispatch structure costs ~0 on
real shapes.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if "jax" not in sys.modules and not os.environ.get("JAX_PLATFORMS") and \
        "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["JAX_PLATFORMS"] = "cpu"

import mxnet_tpu as mx                                   # noqa: E402
from mxnet_tpu import autograd, gluon                    # noqa: E402


def build_generator(ngf=16, nz=16):
    net = gluon.nn.HybridSequential()
    net.add(
        gluon.nn.Dense(ngf * 2 * 4 * 4, use_bias=False),
        gluon.nn.HybridLambda(lambda F, x: F.reshape(x, (-1, 32, 4, 4))),
        gluon.nn.Conv2DTranspose(ngf, 4, strides=2, padding=1,
                                 use_bias=False),        # 8x8
        gluon.nn.Activation("relu"),
        gluon.nn.Conv2DTranspose(1, 4, strides=2, padding=1,
                                 use_bias=False),        # 16x16
        gluon.nn.Activation("tanh"))
    return net


def build_discriminator(ndf=16):
    net = gluon.nn.HybridSequential()
    net.add(
        gluon.nn.Conv2D(ndf, 4, strides=2, padding=1),   # 8x8
        gluon.nn.LeakyReLU(0.2),
        gluon.nn.Conv2D(ndf * 2, 4, strides=2, padding=1),  # 4x4
        gluon.nn.LeakyReLU(0.2),
        gluon.nn.Dense(1))
    return net


def real_batch(rng, n, size=16):
    """Structured 'real' images: soft blobs at random positions — a
    distribution with spatial statistics a generator must actually match
    (pure noise would let any G pass)."""
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    cx = rng.uniform(4, size - 4, (n, 1, 1))
    cy = rng.uniform(4, size - 4, (n, 1, 1))
    r2 = (xx[None] - cx) ** 2 + (yy[None] - cy) ** 2
    img = np.exp(-r2 / 8.0) * 2.0 - 1.0                 # in [-1, 1)
    return img[:, None].astype(np.float32)


def main():
    mx.runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--nz", type=int, default=16)
    ap.add_argument("--lr", type=float, default=2e-3)
    args = ap.parse_args()

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    gen, dis = build_generator(nz=args.nz), build_discriminator()
    gen.initialize(mx.init.Normal(0.05))
    dis.initialize(mx.init.Normal(0.05))
    gen.hybridize()
    dis.hybridize()
    gt = gluon.Trainer(gen.collect_params(), "adam",
                       {"learning_rate": args.lr, "beta1": 0.5})
    dt = gluon.Trainer(dis.collect_params(), "adam",
                       {"learning_rate": args.lr, "beta1": 0.5})
    bce = gluon.loss.SigmoidBinaryCrossEntropyLoss()
    ones = mx.nd.ones((args.batch,))
    zeros = mx.nd.zeros((args.batch,))

    t0 = time.time()
    g_last = d_last = None
    for step in range(args.steps):
        real = mx.nd.array(real_batch(rng, args.batch))
        z = mx.nd.array(rng.randn(args.batch, args.nz).astype(np.float32))
        # D step: real -> 1, fake -> 0 (G frozen: fake is a constant here)
        fake = gen(z).detach()
        with autograd.record():
            d_loss = (bce(dis(real).reshape(-1), ones)
                      + bce(dis(fake).reshape(-1), zeros)).mean()
        d_loss.backward()
        dt.step(args.batch)
        # G step: fool D (D frozen: its params get no trainer.step)
        with autograd.record():
            g_loss = bce(dis(gen(z)).reshape(-1), ones).mean()
        g_loss.backward()
        gt.step(args.batch)
        g_last, d_last = float(g_loss.asscalar()), float(d_loss.asscalar())
        if step % 50 == 0:
            print(f"step {step:4d}  d_loss {d_last:.3f}  g_loss {g_last:.3f}")

    # gate: the generated pixel-mean map matches the data's radial
    # structure far better than the init did (GAN losses oscillate, so
    # gate on sample statistics instead)
    z = mx.nd.array(rng.randn(256, args.nz).astype(np.float32))
    fake_mean = gen(z).asnumpy().mean(axis=0)[0]
    real_mean = real_batch(rng, 256).mean(axis=0)[0]
    err = float(np.abs(fake_mean - real_mean).mean())
    print(f"pixel-mean-map L1 {err:.4f}  d_loss {d_last:.3f} "
          f"g_loss {g_last:.3f}  {time.time()-t0:.1f}s")
    return {"mean_map_l1": err, "d_loss": d_last, "g_loss": g_last}


if __name__ == "__main__":
    main()
