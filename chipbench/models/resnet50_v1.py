"""ResNet-50 v1 (He et al., arXiv:1512.03385, Table 1, 50-layer; the Gluon
v1 variant strides in the first 1x1 of a stage): the trainer as a user
builds it (``bench.py``'s construction), a seeded batch, the operation
count from the shapes, and a plain float32 forward to hold the system to.
"""
from __future__ import annotations

import numpy as np

from . import reference_device

def build(args, mesh, seed):
    """``(net, trainer)``; parameters are drawn from ``seed``."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.guardrails import GuardConfig

    mx.random.seed(seed % (2 ** 31 - 1))
    net = vision.resnet50_v1(classes=args["classes"])
    net.initialize()
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), args["optimizer"],
        optimizer_params=dict(args["optimizer_params"]),
        mesh=mesh, compute_dtype=args["compute_dtype"],
        master_dtype=args["master_dtype"],
        guard=GuardConfig(mode=args["guard_mode"]))
    return net, trainer


def make_batch(args, traffic, batch, rng):
    """Seeded host batch ``(images float32, labels)``."""
    size = args["image_size"]
    x = rng.standard_normal((batch, 3, size, size), dtype=np.float32)
    y = rng.integers(0, args["classes"], (batch,))
    return x, y


def conv_macs(size, stages, channels):
    """Multiply-accumulates of one image's forward pass, from the layer
    shapes alone: 7x7/2 stem, 3x3/2 max-pool, four stages of bottlenecks
    (1x1 with the stage's stride, 3x3, 1x1, and a strided 1x1 projection on
    the first block of a stage)."""
    hw = (size + 2 * 3 - 7) // 2 + 1
    macs = hw * hw * 3 * channels[0] * 49
    hw = (hw + 2 - 3) // 2 + 1
    for i, blocks in enumerate(stages):
        cin, cout = channels[i], channels[i + 1]
        mid = cout // 4
        for b in range(blocks):
            stride = 2 if (b == 0 and i > 0) else 1
            out = (hw - 1) // stride + 1
            macs += out * out * (cin * mid + 9 * mid * mid + mid * cout)
            if b == 0:
                macs += out * out * cin * cout
            cin, hw = cout, out
    return macs


def flops_per_sample(args, traffic):
    """Training operations for one image: forward, and twice that for the
    backward pass (gradients of activations and of weights); a
    multiply-accumulate is two operations. Batch-norm, pooling and the
    elementwise work are left out, as model utilization is defined."""
    macs = conv_macs(args["image_size"], args["stages"], args["channels"]) \
        + args["channels"][-1] * args["classes"]
    return 3 * 2 * macs


def reference_logits(net, x):
    """Logits of ``x`` (N, 3, H, W) in predict mode, in straightforward
    float32 ``jax.numpy`` on the host CPU with the net's parameters as they
    are now. Batch normalisation uses the running statistics."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def val(param):
        return jnp.asarray(param.data().asnumpy().astype(np.float32))

    def conv(h, layer, stride, pad):
        return lax.conv_general_dilated(
            h, val(layer.weight), (stride, stride), [(pad, pad)] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=lax.Precision.HIGHEST)

    def bn(h, layer):
        shape = (1, -1, 1, 1)
        inv = lax.rsqrt(val(layer.running_var) + layer._epsilon)
        return ((h - val(layer.running_mean).reshape(shape))
                * (inv * val(layer.gamma)).reshape(shape)
                + val(layer.beta).reshape(shape))

    def bottleneck(h, block, stride):
        b = block.body
        out = jax.nn.relu(bn(conv(h, b[0], stride, 0), b[1]))
        out = jax.nn.relu(bn(conv(out, b[2], 1, 1), b[3]))
        out = bn(conv(out, b[4], 1, 0), b[5])
        if block.downsample is not None:
            d = block.downsample
            h = bn(conv(h, d[0], stride, 0), d[1])
        return jax.nn.relu(out + h)

    with reference_device():
        f = net.features
        h = jnp.asarray(np.asarray(x, np.float32))
        h = jax.nn.relu(bn(conv(h, f[0], 2, 3), f[1]))
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
        # features: stem conv, bn, relu, max-pool, the stages, global pool
        for i, stage in enumerate(f[j] for j in range(4, len(f) - 1)):
            for b, block in enumerate(stage):
                h = bottleneck(h, block, 2 if (b == 0 and i > 0) else 1)
        h = jnp.mean(h, axis=(2, 3))
        out = jnp.dot(h, val(net.output.weight).T,
                      precision=lax.Precision.HIGHEST) \
            + val(net.output.bias)
        return np.asarray(out)
