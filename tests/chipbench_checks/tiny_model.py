"""A toy configuration of this directory's own, for rehearsing the runners
on the CPU: added as files, with no edit to ``chipbench/``. Never a cell."""
from __future__ import annotations

import numpy as np


def build(args, mesh, seed):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel

    mx.random.seed(seed % (2 ** 31 - 1))
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(args["hidden"], activation="relu"),
            gluon.nn.Dense(args["classes"]))
    net.initialize()
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": args["learning_rate"]}, mesh=mesh,
        compute_dtype=args["compute_dtype"])
    return net, trainer


def make_batch(args, traffic, batch, rng):
    x = rng.standard_normal((batch, args["features"]), dtype=np.float32)
    return x, rng.integers(0, args["classes"], (batch,))


def flops_per_sample(args, traffic):
    return 3 * 2 * (args["features"] * args["hidden"]
                    + args["hidden"] * args["classes"])


def reference_logits(net, x):
    w1, b1, w2, b2 = (p.data().asnumpy().astype(np.float32) for p in (
        net[0].weight, net[0].bias, net[1].weight, net[1].bias))
    h = np.maximum(np.asarray(x, np.float32) @ w1.T + b1, 0.0)
    return h @ w2.T + b2
