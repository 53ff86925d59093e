#!/usr/bin/env python
"""Variational autoencoder (ref: example/autoencoder + the VAE idiom the
reference zoo ships): conv encoder → reparameterized latent → deconv
decoder, trained with the ELBO (reconstruction + KL) under one
hybridized program per player-free step — the generative-family
counterpart to train_dcgan.py's adversarial one.

Synthetic blob images (same distribution as the DCGAN example) keep it
hermetic; the CI gate is reconstruction error + a finite, shrinking KL.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if "jax" not in sys.modules and not os.environ.get("JAX_PLATFORMS") and \
        "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["JAX_PLATFORMS"] = "cpu"

import mxnet_tpu as mx                                   # noqa: E402
from mxnet_tpu import autograd, gluon                    # noqa: E402
from train_dcgan import real_batch                       # noqa: E402
# (one shared data distribution — the cross-example L1 gates compare)


class VAE(gluon.HybridBlock):
    def __init__(self, nz=8, nf=16):
        super().__init__()
        self._nz = nz
        with self.name_scope():
            self.enc = gluon.nn.HybridSequential()
            self.enc.add(
                gluon.nn.Conv2D(nf, 4, strides=2, padding=1),       # 8x8
                gluon.nn.Activation("relu"),
                gluon.nn.Conv2D(nf * 2, 4, strides=2, padding=1),   # 4x4
                gluon.nn.Activation("relu"),
                gluon.nn.Dense(2 * nz))
            self.dec = gluon.nn.HybridSequential()
            self.dec.add(
                gluon.nn.Dense(nf * 2 * 4 * 4, activation="relu"),
                gluon.nn.HybridLambda(
                    lambda F, x: F.reshape(x, (-1, nf * 2, 4, 4))),
                gluon.nn.Conv2DTranspose(nf, 4, strides=2, padding=1),
                gluon.nn.Activation("relu"),
                gluon.nn.Conv2DTranspose(1, 4, strides=2, padding=1),
                gluon.nn.Activation("tanh"))

    def hybrid_forward(self, F, x, eps):
        h = self.enc(x)
        mu = F.slice_axis(h, axis=1, begin=0, end=self._nz)
        logvar = F.slice_axis(h, axis=1, begin=self._nz, end=2 * self._nz)
        z = mu + F.exp(0.5 * logvar) * eps      # reparameterization
        return self.dec(z), mu, logvar



def main():
    mx.runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--nz", type=int, default=8)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--kl-weight", type=float, default=5e-3)
    args = ap.parse_args()

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    net = VAE(nz=args.nz)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})

    t0 = time.time()
    rec = kl = None
    for step in range(args.steps):
        x = mx.nd.array(real_batch(rng, args.batch))
        eps = mx.nd.array(rng.randn(args.batch, args.nz)
                          .astype(np.float32))
        with autograd.record():
            xh, mu, logvar = net(x, eps)
            rec_l = ((xh - x) ** 2).mean()
            kl_l = (-0.5 * (1 + logvar - mu * mu -
                            mx.nd.exp(logvar))).sum(axis=1).mean()
            loss = rec_l + args.kl_weight * kl_l
        loss.backward()
        trainer.step(args.batch)
        rec, kl = float(rec_l.asscalar()), float(kl_l.asscalar())
        if step % 50 == 0:
            print(f"step {step:4d}  rec {rec:.4f}  kl {kl:.2f}")

    # generative check: decode pure prior samples and compare their
    # pixel-mean map to the data's (same gate family as the DCGAN example)
    z = mx.nd.array(rng.randn(256, args.nz).astype(np.float32))
    gen = net.dec(z).asnumpy().mean(axis=0)[0]
    real_mean = real_batch(rng, 256).mean(axis=0)[0]
    l1 = float(np.abs(gen - real_mean).mean())
    print(f"final rec {rec:.4f}  kl {kl:.2f}  prior-sample L1 {l1:.4f}  "
          f"{time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
