"""Brumby-14B-Base (manifestai, ``model_type`` ``brumby``): a pre-norm
decoder whose mixer is power retention (gated linear attention of degree 2),
a SiLU-gated MLP in every layer, an untied head. The trainer as a user builds
it (``gluon.model_zoo.brumby`` -> ``ShardedTrainer``), a seeded batch of
next-token pairs, the operation count from the shapes, the retention's least
operations and bytes for its share of the roofline, the plain float32
reference, and the comparison (``compare``: every layer of the reference on
the system's own input to that layer, the head on the system's last hidden
state, and the operator's state path at the cell's length on gates that
remember). The reference shares no function with ``mxnet_tpu/ops``; its sizes
come from the configuration's ``args`` (kept on the net by ``build``), its
weights from the net's parameters.

The equations, with the configuration's keys in brackets. ``h0 = E[tokens]``;
each layer ``h += Retention(RMSNorm(h))`` then ``h += W_out(silu(g) * u)``,
``[g, u] = split(W_in RMSNorm(h))`` (eps ``rms_norm_eps``, weight after the
normalisation); logits = ``RMSNorm(h_L) W_head^T``.

Retention: ``q = x W_q`` (``num_attention_heads`` of ``head_dim``), ``k = x
W_k``, ``v = x W_v`` (``num_key_value_heads``), ``gamma = x W_g`` (one a
key/value head); ``q <- rope(RMSNorm_d(q))``, ``k <- rope(RMSNorm_d(k))``
(one weight of ``head_dim`` each; rotate-half over the whole head, base
``rope_theta``); ``log g = log sigmoid(gamma)``; query head ``h`` reads
key/value head and gate ``h // (heads / kv heads)``;

    a[t, s] = (q_t . k_s)^2 / head_dim * exp(sum_{s < r <= t} log g_r), s <= t
    y_t     = sum_s a[t, s] v_s / (sum_s a[t, s] + retention_eps)

and ``W_o`` over the heads of ``y``. The reference computes exactly this
``a[t, s]`` form, a block of queries at a time: no chunk, no state.
"""
from __future__ import annotations

import functools
import json

import numpy as np

# the keys of ``args`` that shape the model, as ``brumby`` names them
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
              "chunk_size", "retention_eps")

# build() keeps the newest (net, trainer) here: a per-layer metric that
# joins the trace with the compiled programs (layer_metrics/device_scopes.py)
# reads them after the runner has returned and dropped its own references;
# make_batch() keeps the newest batch's (samples, positions) beside them, for
# the retention's share of the roofline (layer_metrics/retention_roofline.py)
LIVE = []
BATCH = []


def build(args, mesh, seed):
    """``(net, trainer)``; parameters are drawn from ``seed``. ``net`` maps
    tokens (B, S) to logits (B, S, vocab) and, after them, the residual
    stream as each layer leaves it (``compare`` reads both out of one run of
    the compiled forward; the training program returns neither)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import brumby, nemotron_h

    class SeededNormal(mx.init.Initializer):
        """``Normal(sigma)`` drawn as float32 from a generator of its own
        (``mx.init.Normal`` draws float64 from numpy's legacy generator,
        five times slower over a billion and a half parameters)."""

        def __init__(self, sigma, seed):
            super().__init__(sigma=sigma)
            self.sigma, self.rng = sigma, np.random.default_rng(seed)

        def _init_weight(self, desc, arr):
            self._set(arr, self.sigma * self.rng.standard_normal(
                arr.shape, dtype=np.float32))

    net = brumby.brumby(recompute=args["recompute"],
                        output_hidden_states=True,
                        **{key: args[key] for key in MODEL_KEYS})
    net.initialize(SeededNormal(args["init_sigma"], seed))
    net.chipbench_args = dict(args)
    net.chipbench_seed = seed
    trainer = parallel.ShardedTrainer(
        net, nemotron_h.FirstOutputLoss(gluon.loss.SoftmaxCrossEntropyLoss()),
        args["optimizer"], dict(args["optimizer_params"]), mesh=mesh,
        compute_dtype=args["compute_dtype"],
        master_dtype=args["master_dtype"])
    LIVE[:] = [(net, trainer)]
    return net, trainer


def make_batch(args, traffic, batch, rng):
    """Seeded uniform tokens over the rows held; the label of a position is
    the next token."""
    toks = rng.integers(0, args["vocab_size"], (batch, traffic["seq"] + 1))
    BATCH[:] = [(batch, traffic["seq"])]
    return toks[:, :-1], toks[:, 1:]


def retention_macs_per_token(args, seq):
    """The least multiply-accumulates one layer's retention needs for one
    token, whatever computes it: the state of a key/value head is the
    symmetric second power of the key, ``d (d + 1) / 2`` rows, by the value's
    ``d`` columns and one more for the normaliser; every query head reads it
    once a token and every key/value head adds to it once a token; inside a
    chunk a causal row reads half the chunk's keys on average, two products
    (``q k^T`` and the weights times ``v``)."""
    heads, kv = args["num_attention_heads"], args["num_key_value_heads"]
    dim = args["head_dim"]
    state = dim * (dim + 1) // 2 * (dim + 1)
    rows = min(args["chunk_size"], seq)
    return heads * state + kv * state + heads * 2 * (rows // 2) * dim


def product_macs_per_token(args, seq):
    """Multiply-accumulates of the forward pass for one token, by part:
    every product with a weight, the retention at the least work that
    computes it (:func:`retention_macs_per_token`), the head."""
    u, f = args["hidden_size"], args["intermediate_size"]
    heads, kv = args["num_attention_heads"], args["num_key_value_heads"]
    dim, layers = args["head_dim"], args["num_hidden_layers"]
    return {
        "mlp": layers * 3 * u * f,
        "retention_proj": layers * (2 * u * heads * dim + 2 * u * kv * dim
                                    + u * kv),         # q, o; k, v; gate
        "retention": layers * retention_macs_per_token(args, seq),
        "head": u * args["vocab_size"],
    }


def flops_per_sample(args, traffic):
    """Training operations for one sequence, from the shapes: every product
    of the forward pass, two operations a multiply-accumulate, and twice the
    forward again for the backward pass. Lookups, normalisation, rotary, the
    gates, the decays and the recomputation of each layer in the backward
    pass are left out, as model utilization is defined; the retention counts
    its least work, so a program that expands the second power in full, or
    computes a chunk's scores whole, does operations that are not counted."""
    seq = traffic["seq"]
    return 3 * 2 * sum(product_macs_per_token(args, seq).values()) * seq


# -- the retention's useful work, for its share of the roofline ---------------

def retention_operations(args, batch, seq):
    """Operations one training step needs in the retention operator over all
    layers: :func:`retention_macs_per_token`, two operations a
    multiply-accumulate, forward and twice that backward. The forward pass a
    recomputed layer makes again is not useful work (as
    ``moe_experts_roofline`` counts the grouped products)."""
    return 3 * 2 * retention_macs_per_token(args, seq) * batch * seq \
        * args["num_hidden_layers"]


def retention_bytes(args, batch, seq, itemsize=2):
    """Bytes one training step has to move for them: q, k, v and y of each
    layer once each way (read or written forward, their cotangents
    backward), and the float32 states at the chunk boundaries once each way
    (written forward, read backward)."""
    heads, kv = args["num_attention_heads"], args["num_key_value_heads"]
    dim = args["head_dim"]
    rows = min(args["chunk_size"], seq)
    boundaries = max(-(-seq // rows) - 1, 0)
    arrays = batch * seq * (2 * heads + 2 * kv) * dim * itemsize
    states = batch * boundaries * kv * dim * (dim + 1) // 2 * (dim + 1) * 4
    return 2 * (arrays + states) * args["num_hidden_layers"]


# -- the plain float32 reference ---------------------------------------------

QUERY_BLOCK = 512       # 8192 x 8192 x 40 float32 scores would be 10.7 GB


def reference_params(net, read=None):
    """The net's parameters as float32 host arrays of their own, by role.
    Dense weights are (out, in), as the program stores them. ``read(param)`` replaces the
    value taken from each parameter (a test reads gradients into the same
    structure)."""
    def val(param):
        if read is not None:
            return read(param)
        # a copy: prepare() casts and moves the parameter itself
        return np.array(param.data().asnumpy(), dtype=np.float32)

    def layer(block):
        mixer = block.mixer
        return {"input_norm": val(block.input_norm.gamma),
                "q": val(mixer.q_proj.weight), "k": val(mixer.k_proj.weight),
                "v": val(mixer.v_proj.weight), "g": val(mixer.g_proj.weight),
                "o": val(mixer.o_proj.weight),
                "q_norm": val(mixer.q_norm.gamma),
                "k_norm": val(mixer.k_norm.gamma),
                "mlp_norm": val(block.mlp_norm.gamma),
                "mlp_in": val(block.mlp.w_in.weight),
                "mlp_out": val(block.mlp.w_out.weight)}

    return {"embed": val(net.embed_weight),
            "final_norm": val(net.final_norm.gamma),
            "head": val(net.head_weight),
            "layers": [layer(block) for block in net.layers]}


def rounded(x, bits):
    """``x`` to the nearest float of ``bits`` explicit mantissa bits, the
    exponent left free (7: bfloat16; 3: an 8-bit float whose every value has
    a scale of its own); ``None``: as it is. The control of the comparison
    rounds the operands of the reference's products with it."""
    import jax.numpy as jnp
    if bits is None:
        return x
    mantissa, exponent = jnp.frexp(x)           # 0.5 <= |mantissa| < 1
    steps = 2.0 ** (bits + 1)
    return jnp.ldexp(jnp.round(mantissa * steps) / steps, exponent)


def retention_reference(q, k, v, log_g, eps, block=QUERY_BLOCK, bits=None):
    """The ``a[t, s]`` form in float32: ``q`` (B, S, H, d), ``k`` (B, S, G,
    d), ``v`` (B, S, G, dv), ``log_g`` (B, S, G) -> ``y`` (B, S, H, dv). A
    block of queries at a time against every key up to the block's end. The
    decay between two rows is a difference of running sums taken from the
    block's first row, forwards inside the block and backwards before it, so
    that nearby rows differ by small numbers however long the sequence.
    ``bits``: the products' operands (q, k, v and the weights) rounded to
    that many mantissa bits (:func:`rounded`)."""
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    seq, heads, dim = q.shape[1:]
    share = heads // k.shape[2]
    q, k, v = (rounded(t, bits) for t in (q, k, v))
    # each key/value head and its gate serve heads // G consecutive query heads
    k, v, log_g = (jnp.repeat(t, share, axis=2) for t in (k, v, log_g))
    out = []
    for start in range(0, seq, block):
        end = min(start + block, seq)
        inside = jnp.cumsum(log_g[:, start:end], axis=1)
        run = inside                                        # (B, end, H)
        if start:
            before = log_g[:, :start] - lax.cumsum(log_g[:, :start], axis=1,
                                                   reverse=True)
            run = jnp.concatenate([before, inside], axis=1)
        run = jnp.moveaxis(run, 1, 2)                       # (B, H, end)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:end], k[:, :end],
                            precision=hi)
        decay = run[:, :, start:end, None] - run[:, :, None, :]
        past = jnp.arange(end)[None, :] <= jnp.arange(start, end)[:, None]
        a = jnp.where(past, jnp.square(scores) / dim
                      * jnp.exp(jnp.where(past, decay, 0.0)), 0.0)
        num = jnp.einsum("bhqk,bkhd->bqhd", rounded(a, bits), v[:, :end],
                         precision=hi)
        den = jnp.moveaxis(jnp.sum(a, -1), 1, 2)            # (B, q, H)
        out.append(num / (den[..., None] + eps))
    return jnp.concatenate(out, axis=1)


def rotary_reference(x, theta):
    """Rotate-half rotary over the whole last axis of ``x`` (B, S, heads, d):
    coordinates ``i`` and ``i + d/2`` turn by ``position * theta^(-2i/d)``."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _linear(x, w, bits=None):           # w is (out, in)
    import jax.numpy as jnp
    from jax import lax
    return jnp.einsum("...i,oi->...o", rounded(x, bits), rounded(w, bits),
                      precision=lax.Precision.HIGHEST)


def _rms_norm(x, gamma, eps):
    import jax.numpy as jnp
    from jax import lax
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def _layer(cfg, w, h, bits=None):
    """One decoder layer: retention, then the gated MLP. ``bits``: every
    product's operands rounded (:func:`rounded`), for the control."""
    import jax
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    linear = functools.partial(_linear, bits=bits)
    b, s, _ = h.shape
    x = _rms_norm(h, w["input_norm"], eps)
    q = _rms_norm(linear(x, w["q"]).reshape(b, s, heads, -1), w["q_norm"],
                  eps)
    k = _rms_norm(linear(x, w["k"]).reshape(b, s, kv, -1), w["k_norm"], eps)
    v = linear(x, w["v"]).reshape(b, s, kv, -1)
    log_g = jax.nn.log_sigmoid(linear(x, w["g"]))
    y = retention_reference(rotary_reference(q, cfg["rope_theta"]),
                            rotary_reference(k, cfg["rope_theta"]), v, log_g,
                            cfg["retention_eps"], bits=bits)
    h = h + linear(y.reshape(b, s, -1), w["o"])
    gate, up = jnp.split(linear(_rms_norm(h, w["mlp_norm"], eps),
                                w["mlp_in"]), 2, axis=-1)
    return h + linear(gate / (1.0 + jnp.exp(-gate)) * up, w["mlp_out"])


def _head(cfg, final_norm, head, h, bits=None):
    return _linear(_rms_norm(h, final_norm, cfg["rms_norm_eps"]), head, bits)


def _forward(params, cfg, tokens):
    import jax.numpy as jnp
    h = jnp.asarray(params["embed"])[jnp.asarray(tokens)]
    for w in params["layers"]:
        h = _layer(cfg, w, h)
    return _head(cfg, params["final_norm"], params["head"], h)


def staged_reference(params, cfg, tokens, hidden=None, bits=None):
    """``(what each layer puts out, logits)`` of ``tokens`` (N, S) in plain
    float32, one compiled layer at a time with that layer's weights alone
    moved to JAX's default device. ``hidden`` ``None``: the plain forward,
    every layer on the output of the one before. ``hidden`` given (the
    system's residual stream as each layer left it): the first layer on the
    reference's own embedding, every later layer on the **system's** output
    of the layer before, the head on the system's last. ``bits``: every
    product's operands rounded (:func:`rounded`)."""
    import jax
    layer = jax.jit(functools.partial(_layer, cfg, bits=bits))
    h = params["embed"][np.asarray(tokens)]
    outs = []
    for i, w in enumerate(params["layers"]):
        outs.append(np.asarray(layer(w, h)))
        h = outs[-1] if hidden is None \
            else np.asarray(hidden[i]).astype(np.float32)
    logits = jax.jit(functools.partial(_head, cfg, bits=bits))(
        params["final_norm"], params["head"], h)
    return outs, np.asarray(logits)


def reference_logits(net, tokens):
    """Logits of ``tokens`` (N, S) in plain float32 with the net's parameters
    as they are now. It runs on JAX's default device: on the chip (every
    product asks for ``Precision.HIGHEST``, which keeps float32 there) the
    cell's 26 TFLOP take seconds where the host's CPU would take minutes,
    and the runner calls it before ``prepare()`` puts the trainer's state on
    the chip, so a layer's 1.3 GB of float32 weights find room."""
    return staged_reference(reference_params(net), net.chipbench_args,
                            tokens)[1]


def reference_kept(net, x):
    """What ``compare`` needs from before the cast: the float32 parameters
    (host copies), the plain reference's logits of the samples (end to end,
    for the record), the sizes and the seed."""
    params = reference_params(net)
    return {"params": params, "args": dict(net.chipbench_args),
            "seed": net.chipbench_seed,
            "logits": staged_reference(params, net.chipbench_args, x)[1]}


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

    loss, grads = jax.jit(jax.value_and_grad(loss_of))(
        jax.tree_util.tree_map(jnp.asarray, reference_params(net)))
    return float(loss), jax.tree_util.tree_map(np.asarray, grads)


# -- the comparison -----------------------------------------------------------
#
# What is compared, and why not the logits end to end. A seeded gate is
# sigmoid of a zero-mean logit, about 0.5, so a row of this model averages two
# or three values under weights (q . k)^2 / d, whose relative error is twice
# the product's: a retention puts out three times the rounding its input
# carries in, and four layers end at 3.2% (relative root mean square) and
# 6.8-12.7% of the largest logit in bfloat16. The float32 reference itself,
# with nothing but its weights rounded to the stated bfloat16, lies 5.0-5.4%
# from itself (PERF.md sec. 6, PR 34; three seeds on a v5e): no program in the
# stated precision is within the runner's 3% end to end, and what end to end
# measures is the function's conditioning. So the reference is evaluated a
# stage at a time **at the system's own input to that stage**, as a routed
# configuration's is at the system's routes: layer 0 on the reference's own
# embedding of the tokens, layer i on the residual stream the system's layer
# i - 1 put out, the final norm and head on the system's last. Every stage's
# output is held to the reference's (``layer_error``, ``layer_rms_error``;
# the head's logits by the runner's own bound), so by induction every logit
# is covered, with rounding counted once a stage and not compounded. The end
# to end distance is still measured and printed (``end_to_end_share``).

# ``layer_error``: largest |system - reference| over one layer's output, as a
# share of the largest |reference| there, worst layer. ``layer_rms_error``:
# root mean square of the difference over that of the reference, worst layer.
# Read on a v5e at the published widths (PERF.md sec. 6, PR 34; six seeds):
# the bfloat16 program 3.6e-2 to 5.5e-2 and 1.31e-2 to 1.33e-2 (the first
# layer, whose input is the embedding alone; the others 0.49e-2 to 0.64e-2);
# the control, the reference chain with every product's operands at 3
# mantissa bits put through this same comparison, 2.5e-1 to 3.2e-1 and
# 1.67e-1 (and 3.7e-2 to 4.0e-2 at the head, past the runner's 3e-2). The
# root mean square is the steady one (it moves in the third digit from seed
# to seed) and its limit lies a factor of 3.4 and 3.7 from the two readings;
# the largest entry is a backstop against a fault in a few rows, with more
# room above (2.7) than below (1.7), since a first row damped by
# retention_eps can move one head's output there by itself.
LAYER_LIMIT = 0.15
LAYER_RMS_LIMIT = 0.045

# Largest |operator - a[t, s] form| over the check's outputs, as a share of
# the largest |a[t, s] form|. Read on a v5e (PERF.md sec. 6, PR 34), the
# operator at (1, 8192, 10, 128), chunk 1024: in bfloat16 3.29e-3 to 5.49e-3
# on seven seeds; with q, k and v rounded to an 8-bit float (4 exponent bits,
# 3 of mantissa: the nearest precision below) 6.81e-2 to 2.42e-1 on five;
# with no state carried 0.93 to 1.29. The limit lies between the first two,
# a factor of 3.4 to 3.6 from either.
STATE_LIMIT = 0.02
STATE_CHECK_GROUPS = 2      # key/value heads in the check, with their share
SLOWEST, FASTEST = -0.25, -4.0      # log-gates, in units of 1 / chunk


def state_check_inputs(cfg, seq, seed):
    """Seeded inputs of the operator's own check at the cell's length, chunk
    and head sizes, for ``STATE_CHECK_GROUPS`` key/value heads and the query
    heads they serve: rows of unit mean square for q and k (what the head
    norms hand the operator), standard normal values, and log-gates between
    ``FASTEST / chunk`` and ``SLOWEST / chunk``, so that a row still weighs
    an eighth or so a whole chunk later and the state carries most of what a
    chunk's first rows put out."""
    rng = np.random.default_rng([seed, 34])
    chunk = cfg["chunk_size"]
    rows = max(-(-seq // chunk), 2) * chunk
    share = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    dim = cfg["head_dim"]

    def unit(heads):
        t = rng.standard_normal((1, rows, heads, dim), dtype=np.float32)
        return t / np.sqrt(np.mean(t * t, -1, keepdims=True))

    q, k = unit(STATE_CHECK_GROUPS * share), unit(STATE_CHECK_GROUPS)
    v = rng.standard_normal((1, rows, STATE_CHECK_GROUPS, dim),
                            dtype=np.float32)
    log_g = rng.uniform(FASTEST, SLOWEST, (1, rows, STATE_CHECK_GROUPS)) \
        .astype(np.float32) / chunk
    return q, k, v, log_g


def operator_outputs(cfg, q, k, v, log_g, carry=True):
    """The system's operator (``_contrib_power_retention`` as registered,
    compiled, operands in the configuration's compute dtype) on the check's
    inputs, in float32. ``carry=False`` plants the fault the check must see:
    every chunk is given to the operator as a sequence of its own, so no
    state crosses a boundary."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import ops

    retention = ops.get("_contrib_power_retention").fn
    chunk = cfg["chunk_size"]
    dtype = jnp.dtype(cfg.get("compute_dtype") or "float32")
    q, k, v = (jnp.asarray(t).astype(dtype) for t in (q, k, v))
    log_g = jnp.asarray(log_g)
    if not carry:
        q, k, v, log_g = (t.reshape((-1, chunk) + t.shape[2:])
                          for t in (q, k, v, log_g))
    y = jax.jit(functools.partial(
        retention, chunk_size=chunk, eps=cfg["retention_eps"]))(
            q, k, v, log_g)
    return np.asarray(y.astype(jnp.float32)).reshape(1, -1, *y.shape[2:])


def state_check(cfg, seq, seed):
    """``(with the state, with the state zeroed)``: how far the system's
    operator lies from the ``a[t, s]`` form on :func:`state_check_inputs`,
    as a share of the form's largest output. The form gets the operands as
    the operator gets them, rounded to the compute dtype."""
    import jax
    import jax.numpy as jnp

    q, k, v, log_g = state_check_inputs(cfg, seq, seed)
    dtype = jnp.dtype(cfg.get("compute_dtype") or "float32")
    as_given = [np.asarray(jnp.asarray(t).astype(dtype).astype(jnp.float32))
                for t in (q, k, v)]
    want = np.asarray(jax.jit(functools.partial(
        retention_reference, eps=cfg["retention_eps"]))(*as_given, log_g))
    scale = np.abs(want).max()
    return tuple(
        float(np.abs(operator_outputs(cfg, q, k, v, log_g, carry) - want)
              .max() / scale) for carry in (True, False))


def stage_errors(kept, tokens, logits, hidden):
    """A system's ``logits`` (N, S, vocab) and ``hidden`` (what each of its
    layers put out, (N, S, hidden) each) against the float32 reference a
    stage at a time on the system's own inputs (:func:`staged_reference`):
    ``layers`` (one ``{"max", "rms"}`` a layer), the head's
    ``max_abs_error`` and ``max_abs_reference``, and ``end_to_end_share``,
    the distance of the logits from the plain reference's as the runner
    reckons a share, for the record. The stages run on the host
    (``reference_device``): the runner calls ``compare`` after ``prepare()``
    has put the trainer's state on the chip, and a layer's 1.3 GB of float32
    weights beside it would stand in the peak the run reports (read on a
    v5e: ``peak_bytes_in_use`` 11.5 GB where the training step's is 9.2;
    PERF.md sec. 6, PR 34)."""
    from . import reference_device

    with reference_device():
        outs, head = staged_reference(kept["params"], kept["args"], tokens,
                                      hidden)
    layers = []
    for out, got in zip(outs, hidden):
        diff = np.asarray(got).astype(np.float32) - out
        layers.append({
            "max": float(np.abs(diff).max() / np.abs(out).max()),
            "rms": float(np.sqrt(np.mean(np.square(diff, dtype=np.float64))
                                 / np.mean(np.square(out,
                                                     dtype=np.float64))))})
    logits = np.asarray(logits).astype(np.float32)
    plain = kept["logits"]
    return {"layers": layers,
            "max_abs_error": float(np.abs(logits - head).max()),
            "max_abs_reference": float(np.abs(head).max()),
            "end_to_end_share": float(np.abs(logits - plain).max()
                                      / np.abs(plain).max())}


def judged(kept, tokens, logits, hidden, state):
    """What ``compare`` returns for a system that put out ``logits`` and
    ``hidden`` and whose operator read ``state`` = (error with the state,
    error with the state zeroed) in :func:`state_check`."""
    errors = stage_errors(kept, tokens, logits, hidden)
    carried, zeroed = state
    layer_max = max(e["max"] for e in errors["layers"])
    layer_rms = max(e["rms"] for e in errors["layers"])
    print("chipbench: retention " + json.dumps(
        {"layers": errors["layers"],
         "end_to_end_share": errors["end_to_end_share"],
         "head_share": errors["max_abs_error"] / errors["max_abs_reference"],
         "state_path_error": carried, "state_zeroed_error": zeroed,
         "limits": {"layer": LAYER_LIMIT, "layer_rms": LAYER_RMS_LIMIT,
                    "state": STATE_LIMIT}}, sort_keys=True), flush=True)
    staged = "the float32 reference's layer on the system's own input to " \
             "that layer (layer 0: on the reference's embedding)"
    return {
        "samples": len(logits), "compared": int(np.asarray(logits).size),
        "max_abs_error": errors["max_abs_error"],
        "max_abs_reference": errors["max_abs_reference"],
        "conditions": {
            "layer_error": {
                "value": layer_max, "limit": LAYER_LIMIT,
                "ok": layer_max <= LAYER_LIMIT,
                "why": "largest difference between what a layer of the "
                       "system put out and " + staged + ", as a share of "
                       "the reference's largest entry there, worst layer: "
                       "one layer's rounding, a sequence's first row "
                       "damped by retention_eps included; the logits "
                       "compared are the reference's final norm and head "
                       "on the system's last hidden state"},
            "layer_rms_error": {
                "value": layer_rms, "limit": LAYER_RMS_LIMIT,
                "ok": layer_rms <= LAYER_RMS_LIMIT,
                "why": "root mean square of that difference over the "
                       "reference's, worst layer: what one layer does to "
                       "rounding in the bulk of its rows"},
            "state_path_error": {
                "value": carried, "limit": STATE_LIMIT,
                "ok": carried <= STATE_LIMIT,
                "why": "largest difference between the system's operator "
                       "and the float32 a[t, s] form, as a share of the "
                       "form's largest output, at the cell's length, chunk "
                       "and head sizes for two key/value heads and their "
                       "query heads, on seeded log-gates of -4 to -0.25 a "
                       "chunk (memory of several chunks): rounding of the "
                       "products' operands to the compute dtype, no more"},
            "state_zeroed_breaks_it": {
                "value": zeroed, "limit": STATE_LIMIT,
                "ok": zeroed > STATE_LIMIT,
                "why": "the same with every chunk given to the operator as "
                       "a sequence of its own, so that nothing is carried "
                       "across a boundary: it has to break the limit, or "
                       "the first condition holds of nothing"},
        },
    }


def compare(kept, trainer, args, x, y):
    """The system's forward, one run of its one compiled program (the logits
    and, after them, what each layer put out), against the reference a stage
    at a time on the system's own inputs, and the operator's state path at
    the cell's length: seeded gates forget within a few tokens, so the
    forward alone would pass an operator that carried nothing from chunk to
    chunk."""
    from chipbench.runners import train

    n = len(kept["logits"])
    logits, *hidden = train.system_outputs(trainer, args, x, y, rows=n)
    return judged(kept, np.asarray(x)[:n], logits, hidden,
                  state_check(kept["args"], np.asarray(x).shape[1],
                              kept["seed"]))


def control(kept, tokens, bits=3):
    """The comparison's control: the reference itself, end to end with every
    product's operands rounded to ``bits`` mantissa bits (3: the nearest
    precision below the stated bfloat16's 7), put through :func:`judged` as
    if it were the system (its operator's state path taken as sound). It has
    to come out not ``ok``."""
    hidden, logits = staged_reference(kept["params"], kept["args"], tokens,
                                      bits=bits)
    return judged(kept, tokens, logits, hidden, (0.0, 1.0))
