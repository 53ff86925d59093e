"""BERT-base (Devlin et al., arXiv:1810.04805, L=12 H=768 A=12) with the
masked-LM head over every position: the trainer as
``benchmarks/bert.py:build_trainer`` builds it, a seeded batch, the
operation count from the shapes, and a plain float32 forward to hold the
system to.
"""
from __future__ import annotations

import numpy as np

from . import reference_device


def build(args, mesh, seed):
    """``(net, trainer)``; parameters are drawn from ``seed``. ``net`` maps
    tokens (B, S) to MLM logits (B, S, vocab)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import bert

    mx.random.seed(seed % (2 ** 31 - 1))
    inner = bert.get_bert_model(
        args["model_name"], vocab_size=args["vocab_size"],
        max_length=args["max_length"], dropout=args["dropout"],
        use_pooler=False, use_classifier=False,
        num_layers=args["num_layers"], units=args["units"],
        hidden_size=args["hidden_size"], num_heads=args["num_heads"])
    inner.initialize(mx.init.Normal(args["init_sigma"]))

    class MLMWrapper(gluon.HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def hybrid_forward(self, F, tokens):
            # logits stay 3-D (B, S, V), as in benchmarks/bert.py
            _, mlm = self.inner(tokens)
            return mlm

    net = MLMWrapper(inner)
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), args["optimizer"],
        dict(args["optimizer_params"]),
        mesh=mesh, compute_dtype=args["compute_dtype"],
        master_dtype=args["master_dtype"])
    return net, trainer


def make_batch(args, traffic, batch, rng):
    """Seeded random tokens; the labels are the tokens."""
    toks = rng.integers(0, args["vocab_size"], (batch, traffic["seq"]))
    return toks, toks


def flops_per_sample(args, traffic):
    """Training operations for one sequence, from the shapes: every matrix
    multiplication of the forward pass (QKV, attention scores and mix,
    output projection, FFN, the MLM head's transform and its vocabulary
    projection), two operations a multiply-accumulate, and twice the
    forward again for the backward pass. Embedding lookups, softmax,
    normalisation and GELU are left out, as model utilization is defined.
    Within 2% of the usual 6 x 110e6 x tokens."""
    u, f, s = args["units"], args["hidden_size"], traffic["seq"]
    per_token = args["num_layers"] * (4 * u * u + 2 * u * f + 2 * s * u) \
        + u * u + u * args["vocab_size"]
    return 3 * 2 * per_token * s


def reference_logits(net, tokens):
    """MLM logits of ``tokens`` (N, S) in predict mode (no dropout), in
    straightforward float32 ``jax.numpy`` on the host CPU with the net's
    parameters as they are now. Post-layer-norm cells, erf GELU, fused QKV
    weight in q, k, v column blocks with heads contiguous inside each."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST

    def val(param):
        return jnp.asarray(param.data().asnumpy().astype(np.float32))

    def dense(h, layer):
        return jnp.dot(h, val(layer.weight).T, precision=hi) \
            + val(layer.bias)

    def ln(h, layer):
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean((h - mu) ** 2, axis=-1, keepdims=True)
        return (h - mu) * lax.rsqrt(var + layer._epsilon) \
            * val(layer.gamma) + val(layer.beta)

    def gelu(h):
        return 0.5 * h * (1.0 + lax.erf(h / np.sqrt(2.0)))

    with reference_device():
        m = net.inner
        toks = np.asarray(tokens)
        n, s = toks.shape
        h = val(m.word_embed.weight)[toks] + val(m.position_weight)[:s]
        h = ln(h, m.embed_layer_norm)
        for cell in m.encoder.transformer_cells:
            att = cell.attention
            heads = att._num_heads
            qkv = dense(h, att.qkv)
            q, k, v = (t.reshape(n, s, heads, -1)
                       for t in jnp.split(qkv, 3, axis=-1))
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) \
                * q.shape[-1] ** -0.5
            mix = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                             v, precision=hi).reshape(n, s, -1)
            h = ln(h + dense(mix, att.proj), cell.ln1)
            ffn = dense(gelu(dense(h, cell.ffn.ffn_1)), cell.ffn.ffn_2)
            h = ln(h + ffn, cell.ln2)
        d = m.decoder
        h = ln(gelu(dense(h, d[0])), d[2])
        return np.asarray(dense(h, d[3]))
