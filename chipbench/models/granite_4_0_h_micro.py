"""granite-4.0-h-micro (ibm-granite, ``model_type`` ``granitemoehybrid``):
Mamba-2 layers with a grouped-query attention layer at index 5 of every ten,
a shared SiLU-gated MLP in every layer, a tied head. The trainer as a user
builds it (``gluon.model_zoo.granite_hybrid`` -> ``ShardedTrainer``), a
seeded batch of next-token pairs, the operation count from the shapes, and
the plain float32 reference: the recurrence step by step, attention as a
full masked softmax. The reference shares no function with ``mxnet_tpu/ops``;
its sizes and multipliers come from the configuration's ``args`` (kept on
the net by ``build``), its weights from the net's parameters.

The equations, with the configuration's keys in brackets:
``h0 = E[tokens] * embedding_multiplier``; each layer ``h += residual_multiplier
* mixer(RMSNorm(h))`` then ``h += residual_multiplier * MLP(RMSNorm(h))``;
``MLP(x) = W_out(silu(g) * u)``, ``[g, u] = split(W_in x)``; logits
``= RMSNorm(h_L) E^T / logits_scaling``. Attention: 32 query heads and 8
key/value heads of 64, no bias, no rotary, causal softmax of ``q k^T *
attention_multiplier``. Mamba-2: ``[z | xBC | dt] = W_in x``; ``xBC <-
silu(conv(xBC))``, depthwise, kernel 4, left-padded; ``dt <- softplus(dt +
dt_bias)``, ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t
x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm(y * silu(z))``; ``W_out``.
"""
from __future__ import annotations

import numpy as np

from . import reference_device

# the keys of ``args`` that shape the model, as ``granite_hybrid`` names them
MODEL_KEYS = ("vocab_size", "hidden_size", "num_attention_heads",
              "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
              "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
              "mamba_chunk_size", "rms_norm_eps", "attention_multiplier",
              "embedding_multiplier", "residual_multiplier", "logits_scaling")

# build() keeps the newest (net, trainer) here: a per-layer metric that
# joins the trace with the compiled programs (layer_metrics/device_scopes.py)
# reads them after the runner has returned and dropped its own references
LIVE = []


def layer_types(args):
    return list(args["layer_types"][:args["num_hidden_layers"]])


def build(args, mesh, seed):
    """``(net, trainer)``; parameters are drawn from ``seed``. ``net`` maps
    tokens (B, S) to logits (B, S, vocab)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import granite_hybrid

    class SeededNormal(mx.init.Initializer):
        """``Normal(sigma)`` drawn as float32 from a generator of its own:
        a fifth of the time ``mx.init.Normal`` (float64 from numpy's legacy
        generator) takes over 800 million parameters."""

        def __init__(self, sigma, seed):
            super().__init__(sigma=sigma)
            self.sigma, self.rng = sigma, np.random.default_rng(seed)

        def _init_weight(self, desc, arr):
            self._set(arr, self.sigma * self.rng.standard_normal(
                arr.shape, dtype=np.float32))

    mx.random.seed(seed % (2 ** 31 - 1))    # the mixers' own initializers
    net = granite_hybrid.granite_hybrid(
        recompute=args["recompute"], layer_types=layer_types(args),
        intermediate_size=args["shared_intermediate_size"],
        **{key: args[key] for key in MODEL_KEYS})
    net.initialize(SeededNormal(args["init_sigma"], seed))
    net.chipbench_args = dict(args)
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), args["optimizer"],
        dict(args["optimizer_params"]), mesh=mesh,
        compute_dtype=args["compute_dtype"],
        master_dtype=args["master_dtype"])
    LIVE[:] = [(net, trainer)]
    return net, trainer


def make_batch(args, traffic, batch, rng):
    """Seeded uniform tokens; the label of a position is the next token."""
    toks = rng.integers(0, args["vocab_size"], (batch, traffic["seq"] + 1))
    return toks[:, :-1], toks[:, 1:]


def product_macs_per_token(args, seq):
    """Multiply-accumulates of the forward pass for one token, by part:
    every product with a weight, attention's two (halved: a causal row
    reads half the keys on average), the scan's four for each chunk (the
    chunk's (Q, Q) matrices whole, as the chunked algorithm defines them)
    and the head."""
    u, f = args["hidden_size"], args["shared_intermediate_size"]
    heads, kv = args["num_attention_heads"], args["num_key_value_heads"]
    head_dim = u // heads
    h, p = args["mamba_n_heads"], args["mamba_d_head"]
    g, n = args["mamba_n_groups"], args["mamba_d_state"]
    q = args["mamba_chunk_size"]
    inner = h * p
    kinds = layer_types(args)
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    return {
        "mlp": len(kinds) * (u * 2 * f + f * u),
        "mamba_proj": n_mamba * (u * (2 * inner + 2 * g * n + h) + inner * u),
        # C B^T, (scores) x, B^T x into the chunk's state, C (state)
        "mamba_scan": n_mamba * (q * g * n + q * inner + 2 * n * inner),
        "attention_proj": n_attn * (2 * u * heads * head_dim
                                    + 2 * u * kv * head_dim),
        "attention": n_attn * (2 * seq * heads * head_dim // 2),
        "head": u * args["vocab_size"],
    }


def flops_per_sample(args, traffic):
    """Training operations for one sequence, from the shapes: every product
    of the forward pass, two operations a multiply-accumulate, and twice
    the forward again for the backward pass. Embedding lookups, the
    convolution (4 taps), softmax, normalisation, gates and the scan's
    elementwise work are left out, as model utilization is defined; so is
    the recomputation of each layer in the backward pass."""
    seq = traffic["seq"]
    return 3 * 2 * sum(product_macs_per_token(args, seq).values()) * seq


# -- the plain float32 reference ---------------------------------------------

def reference_params(net, read=None):
    """The net's parameters as float32 ``jax.numpy`` arrays on the
    reference's device, by role: ``{"embed", "final_norm", "layers": [...]}``.
    Dense weights are (out, in), as the program stores them. ``read(param)``
    replaces the value taken from each parameter (a test reads gradients
    into the same structure)."""
    import jax.numpy as jnp

    def val(param):
        if read is not None:
            return read(param)
        return jnp.asarray(param.data().asnumpy().astype(np.float32))

    def layer(block):
        mixer = block.mixer
        out = {"input_norm": val(block.input_norm.gamma),
               "mlp_norm": val(block.mlp_norm.gamma),
               "mlp_in": val(block.mlp.w_in.weight),
               "mlp_out": val(block.mlp.w_out.weight)}
        if hasattr(mixer, "A_log"):
            out["mamba"] = {
                "in": val(mixer.in_proj.weight),
                "conv_weight": val(mixer.conv_weight),
                "conv_bias": val(mixer.conv_bias),
                "dt_bias": val(mixer.dt_bias), "A_log": val(mixer.A_log),
                "D": val(mixer.D), "norm": val(mixer.norm_gamma),
                "out": val(mixer.out_proj.weight)}
        else:
            out["attention"] = {name: val(getattr(mixer, name + "_proj").weight)
                                for name in "qkvo"}
        return out

    with reference_device():
        return {"embed": val(net.embed_weight),
                "final_norm": val(net.final_norm.gamma),
                "layers": [layer(block) for block in net.layers]}


def _forward(params, cfg, tokens):
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps = cfg["rms_norm_eps"]

    def linear(x, w):                   # w is (out, in)
        return jnp.einsum("...i,oi->...o", x, w, precision=hi)

    def rms_norm(x, gamma):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma

    def silu(x):
        return x / (1.0 + jnp.exp(-x))

    def attention(x, w):
        b, s, _ = x.shape
        heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        q = linear(x, w["q"]).reshape(b, s, heads, -1)
        k = linear(x, w["k"]).reshape(b, s, kv, -1)
        v = linear(x, w["v"]).reshape(b, s, kv, -1)
        # each key/value head serves heads // kv consecutive query heads
        k = jnp.repeat(k, heads // kv, axis=2)
        v = jnp.repeat(v, heads // kv, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) \
            * cfg["attention_multiplier"]
        future = jnp.arange(s)[None, :] > jnp.arange(s)[:, None]
        scores = jnp.where(future, -jnp.inf, scores)
        mix = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                         precision=hi)
        return linear(mix.reshape(b, s, -1), w["o"])

    def mamba(x, w):
        bsz, s, _ = x.shape
        h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
        g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
        taps = cfg["mamba_d_conv"]
        inner = h * p
        z, xbc, dt = jnp.split(linear(x, w["in"]),
                               [inner, 2 * inner + 2 * g * n], axis=-1)
        padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        xbc = silu(w["conv_bias"] + sum(
            padded[:, j:j + s] * w["conv_weight"][:, j] for j in range(taps)))
        xs, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
        xs = xs.reshape(bsz, s, h, p)
        # a group's B and C serve h // g consecutive heads
        b = jnp.repeat(b.reshape(bsz, s, g, n), h // g, axis=2)
        c = jnp.repeat(c.reshape(bsz, s, g, n), h // g, axis=2)
        dt = jnp.logaddexp(dt + w["dt_bias"], 0.0)              # softplus
        a = -jnp.exp(w["A_log"])

        def position(state, at):        # state (B, H, P, N)
            x_t, b_t, c_t, dt_t = at
            state = jnp.exp(dt_t * a)[..., None, None] * state \
                + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
            y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=hi) \
                + w["D"][:, None] * x_t
            return state, y_t

        _, y = lax.scan(position, jnp.zeros((bsz, h, p, n), jnp.float32),
                        tuple(jnp.moveaxis(t, 1, 0) for t in (xs, b, c, dt)))
        y = jnp.moveaxis(y, 0, 1).reshape(bsz, s, inner)
        return linear(rms_norm(y * silu(z), w["norm"]), w["out"])

    def mlp(x, w_in, w_out):
        gate, up = jnp.split(linear(x, w_in), 2, axis=-1)
        return linear(silu(gate) * up, w_out)

    m = cfg["residual_multiplier"]
    h = params["embed"][jnp.asarray(tokens)] * cfg["embedding_multiplier"]
    for w in params["layers"]:
        x = rms_norm(h, w["input_norm"])
        h = h + m * (mamba(x, w["mamba"]) if "mamba" in w
                     else attention(x, w["attention"]))
        h = h + m * mlp(rms_norm(h, w["mlp_norm"]), w["mlp_in"], w["mlp_out"])
    return linear(rms_norm(h, params["final_norm"]), params["embed"]) \
        / cfg["logits_scaling"]


def reference_logits(net, tokens):
    """Logits of ``tokens`` (N, S) in plain float32 ``jax.numpy`` with the
    net's parameters as they are now."""
    with reference_device():
        return np.asarray(_forward(reference_params(net), net.chipbench_args,
                                   np.asarray(tokens)))


def reference_loss_and_grads(net, tokens, labels):
    """``(loss, grads)`` of the mean next-token cross entropy, by autodiff of
    the plain forward; ``grads`` has the structure of
    :func:`reference_params`."""
    import jax
    import jax.numpy as jnp

    labels = np.asarray(labels)

    def loss_of(params):
        logp = jax.nn.log_softmax(
            _forward(params, net.chipbench_args, np.asarray(tokens)), -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, jnp.asarray(labels)[..., None], -1))

    with reference_device():
        loss, grads = jax.value_and_grad(loss_of)(reference_params(net))
        return float(loss), jax.tree_util.tree_map(np.asarray, grads)
