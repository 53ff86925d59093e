"""LFM2-24B-A2B (``model_type`` ``lfm2_moe``): gated short-convolution layers
beside grouped-query attention with head norms and rotary, a leading dense
layer, then routed experts chosen by a sigmoid router whose balancing bias
moves every step; a head tied to the embedding. The trainer as a user builds
it (``gluon.model_zoo.lfm2_moe`` -> ``ShardedTrainer``), a seeded batch of
next-token pairs, the operation count from the shapes, the short
convolution's least operations and bytes for its share of the roofline, the
plain float32 reference, and the comparison a routed model needs
(``compare``: the reference at the system's routes, the routes held to the
reference's own, and the balancing bias after one training step held to its
rule). The reference shares no function with ``mxnet_tpu/ops``;
its sizes come from the configuration's ``args`` (kept on the net by
``build``), its weights from the net's parameters.

The equations, with the configuration's keys in brackets. ``h0 =
E[tokens]``; each layer ``h += op(RMSNorm(h))`` then ``h += ffn(RMSNorm(h))``
(eps ``norm_eps``, weight after the normalisation); logits =
``RMSNorm(h_L) E^T`` (tied).

``op`` by ``layer_types``. ``conv``: ``[B | C | x] = W_in h``; ``u = B * x``;
``v[t] = sum_{j < K} w[:, j] * u[t + j - (K - 1)]`` (``conv_L_cache`` K,
zeros before the sequence, depthwise, no bias); ``W_out(C * v)``.
``full_attention``: ``num_attention_heads`` query and ``num_key_value_heads``
key/value heads of ``hidden_size / num_attention_heads``; ``q = RMSNorm_q(x
W_q)``, ``k = RMSNorm_k(x W_k)`` over each head; rotary over the whole head,
rotate-half, base ``rope_parameters.rope_theta``; causal softmax of ``q k^T /
sqrt(d)``; key/value head j serves the query heads ``j * share ..``;
``W_o``. No bias anywhere.

``ffn``: the first ``num_dense_layers`` ``W_out(silu(g) * u)``, ``[g | u] =
W_in x`` (``intermediate_size``). After them: ``s = sigmoid(W_r x)`` over all
``published_counts.num_experts`` in float32; chosen = the
``num_experts_per_tok`` largest of ``s + b`` (``b`` the balancing bias: it
chooses and does not weigh); ``w_e = routed_scaling_factor * s_e / (sum of
the chosen s + 1e-6)`` (``norm_topk_prob``); ``f_e(x) = W2_e (silu(g) *
u)``, ``[g | u] = W1_e x`` (``moe_intermediate_size``); ``ffn(x) = sum over
the chosen e **that this chip holds** of w_e f_e(x)`` (the chip holds experts
``first_expert ..`` as many as ``num_experts`` says; what the others would
add is left out here as it is in the program). After each training step
``b += expert_bias_update_rate * sign(mean(c) - c)``, ``c`` the pairs sent
to each expert in that step.
"""
from __future__ import annotations

import json

import numpy as np

from . import reference_device
from .deepseek_v2 import _gated, _linear, _product
from .nemotron_3_nano_30b_a3b import margin_of, needed_margin, own_choice, \
    route_conditions

# the keys of ``args`` that shape the model, as ``lfm2_moe`` names them (the
# count of experts is the published one: ``build`` says which this chip
# holds)
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "layer_types", "num_dense_layers",
              "num_attention_heads", "num_key_value_heads",
              "num_experts_per_tok", "conv_L_cache", "conv_bias", "norm_eps",
              "rope_parameters", "norm_topk_prob", "routed_scaling_factor",
              "use_expert_bias")

# build() keeps the newest (net, trainer) here: a per-layer metric that
# joins the trace with the compiled programs (layer_metrics/device_scopes.py)
# or reads the expert layers' counters (layer_metrics/router_load.py) reads
# them after the runner has returned and dropped its own references;
# make_batch() keeps the newest batch's (samples, positions) beside them, for
# the short convolution's share of the roofline
# (layer_metrics/shape_roofline.py)
LIVE = []
BATCH = []


def experts_held(args):
    """``(first, count)`` of the experts this chip computes, and the width
    of the router they are chosen among."""
    return (args["first_expert"], args["num_experts"]), \
        args["published_counts"]["num_experts"]


def build(args, mesh, seed):
    """``(net, trainer)``; parameters are drawn from ``seed``. ``net`` maps
    tokens (B, S) to ``[logits (B, S, vocab), the routes of each expert
    layer (B, S, 4), the scores of each (B, S, 64), the rows each held
    expert computed (8, 8)]``."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import lfm2_moe, nemotron_h

    class SeededNormal(mx.init.Initializer):
        """``Normal(sigma)`` drawn as float32 from a generator of its own
        (``mx.init.Normal`` draws float64 from numpy's legacy generator,
        five times slower over most of a billion parameters); the norms'
        weights keep their ones."""

        def __init__(self, sigma, seed):
            super().__init__(sigma=sigma)
            self.sigma, self.rng = sigma, np.random.default_rng(seed)

        def _init_weight(self, desc, arr):
            self._set(arr, self.sigma * self.rng.standard_normal(
                arr.shape, dtype=np.float32))

    mx.random.seed(seed % (2 ** 31 - 1))    # the convolutions' initializer
    held, width = experts_held(args)
    net = lfm2_moe.lfm2_moe(
        experts_held=held, return_routes=True, recompute=args["recompute"],
        expert_bias_update_rate=args["expert_bias_update_rate"],
        num_experts=width, **{key: args[key] for key in MODEL_KEYS})
    net.initialize(SeededNormal(args["init_sigma"], seed))
    net.chipbench_args = dict(args)
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


def _kinds(args):
    """``(conv layers, attention layers, routed layers, dense layers)``."""
    types = args["layer_types"]
    routed = len(types) - args["num_dense_layers"]
    return types.count("conv"), types.count("full_attention"), routed, \
        args["num_dense_layers"]


def parameter_counts(args):
    """Trainable parameters of the cut, by part, from the sizes alone."""
    u, heads, kv = (args["hidden_size"], args["num_attention_heads"],
                    args["num_key_value_heads"])
    d = u // heads
    convs, attns, routed, dense = _kinds(args)
    (_, held), width = experts_held(args)
    return {
        "short_convs": convs * (u * 3 * u + u * args["conv_L_cache"] + u * u),
        "attention": attns * (2 * u * heads * d + 2 * u * kv * d + 2 * d),
        "dense_mlp": dense * 3 * u * args["intermediate_size"],
        "routers": routed * width * u,
        "routed_experts": routed * held * 3 * u
        * args["moe_intermediate_size"],
        "embedding": args["vocab_size"] * u,        # the head is tied
        "norms": 2 * (convs + attns) * u + u,
    }


def product_macs_per_token(args, seq):
    """Multiply-accumulates of the forward pass for one token, by part:
    every product with a weight, attention's two (a causal row reads half
    the keys on average), the head. The routed experts count the share of a
    token's pairs that a uniform router sends to the experts held:
    ``num_experts_per_tok`` x held / all."""
    u, heads, kv = (args["hidden_size"], args["num_attention_heads"],
                    args["num_key_value_heads"])
    d = u // heads
    convs, attns, routed, dense = _kinds(args)
    (_, held), width = experts_held(args)
    return {
        "shortconv_proj": convs * (u * 3 * u + u * u),
        "dense_mlp": dense * 3 * u * args["intermediate_size"],
        "routed_experts": routed * args["num_experts_per_tok"] * held * 3
        * u * args["moe_intermediate_size"] // width,
        "attention": attns * 2 * heads * d * seq // 2,
        "attention_proj": attns * (2 * u * heads * d + 2 * u * kv * d),
        "head": u * args["vocab_size"],
        "router": routed * width * u,
    }


def flops_per_sample(args, traffic):
    """Training operations for one sequence, from the shapes: every product
    of the forward pass, two operations a multiply-accumulate, and twice the
    forward again for the backward pass. Lookups, the convolution's taps and
    gates, softmax, normalisation, rotary, the ordering and gathering of
    routed rows and the recomputation of each layer in the backward pass are
    left out, as model utilization is defined."""
    seq = traffic["seq"]
    return 3 * 2 * sum(product_macs_per_token(args, seq).values()) * seq


# -- the short convolution's least work, for its share of the roofline -------

def short_conv_operations(args, batch, seq):
    """Operations one training step needs in the gate, the convolution and
    the gate of every ``conv`` layer, per token and channel: forward ``u = B
    * x`` (1), the ``K`` taps (``2K - 1``), ``C * v`` (1); backward the
    cotangents of ``C`` and ``v`` (2), of ``u`` through the taps (``2K -
    1``), of the taps' weights (``2K``), of ``B`` and ``x`` (2). The forward
    a recomputed layer makes again is not useful work."""
    taps = args["conv_L_cache"]
    per_element = (2 * taps + 1) + (4 * taps + 3)
    return _kinds(args)[0] * batch * seq * args["hidden_size"] * per_element


def short_conv_bytes(args, batch, seq, itemsize=2):
    """Bytes one training step has to move for it, from the shapes alone:
    forward ``B``, ``C`` and ``x`` read and ``y`` written once; backward
    ``dy``, ``B``, ``C`` and ``x`` read and their three gradients written
    once; the taps' weights read and their gradient written once."""
    u, taps = args["hidden_size"], args["conv_L_cache"]
    arrays = 11 * batch * seq * u * itemsize
    return _kinds(args)[0] * (arrays + 2 * u * taps * itemsize)


# -- the plain float32 reference ---------------------------------------------

def reference_params(net, read=None):
    """The net's parameters as float32 ``jax.numpy`` arrays on the
    reference's device, by role, and each expert layer's balancing bias
    (``bias``, a buffer: no gradient). Dense weights are (out, in), the
    experts' (expert, in, out), as the program stores them. ``read(param)``
    replaces the value taken from each trainable parameter (a test reads
    gradients into the same structure; ``bias`` is then left out)."""
    import jax.numpy as jnp

    def val(param):
        if read is not None:
            return read(param)
        return jnp.asarray(param.data().asnumpy().astype(np.float32))

    def layer(kind, block):
        op, ffn = block.mixer, block.mlp
        out = {"operator_norm": val(block.input_norm.gamma),
               "ffn_norm": val(block.mlp_norm.gamma)}
        if kind == "conv":
            out["conv"] = {"in": val(op.in_proj.weight),
                           "weight": val(op.conv_weight),
                           "out": val(op.out_proj.weight)}
        else:
            out["attention"] = {
                "q": val(op.q_proj.weight), "k": val(op.k_proj.weight),
                "v": val(op.v_proj.weight), "o": val(op.o_proj.weight),
                "q_norm": val(op.q_norm.gamma),
                "k_norm": val(op.k_norm.gamma)}
        if hasattr(ffn, "router_weight"):
            out["experts"] = {"router": val(ffn.router_weight),
                              "w1": val(ffn.expert_w1),
                              "w2": val(ffn.expert_w2)}
            if read is None:
                out["experts"]["bias"] = val(ffn.router_bias)
        else:
            out["mlp"] = {"in": val(ffn.w_in.weight),
                          "out": val(ffn.w_out.weight)}
        return out

    kinds = net.chipbench_args["layer_types"]
    with reference_device():
        return {"embed": val(net.embed_weight),
                "final_norm": val(net.final_norm.gamma),
                "layers": [layer(kind, block) for kind, block in zip(
                    kinds, net.layers._children.values())]}


QUERY_BLOCK = 512       # 8192 x 8192 x 32 float32 scores would be 8.6 GB


def _rms_norm(cfg, x, gamma):
    import jax.numpy as jnp
    from jax import lax
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                         + cfg["norm_eps"]) * gamma


def reference_short_conv(cfg, w, x, bits=None):
    """The gated short convolution of ``x`` (B, S, hidden), its
    convolution written as the sum over its taps."""
    import jax.numpy as jnp
    u = cfg["hidden_size"]
    taps, s = cfg["conv_L_cache"], x.shape[1]
    bcx = _linear(x, w["in"], bits)
    b, c, xs = bcx[..., :u], bcx[..., u:2 * u], bcx[..., 2 * u:]
    padded = jnp.pad(b * xs, ((0, 0), (taps - 1, 0), (0, 0)))
    v = sum(w["weight"][:, j] * padded[:, j:j + s] for j in range(taps))
    return _linear(c * v, w["out"], bits)


def _rotary(cfg, x):
    """Rotate-half rotary over the whole last axis of ``x`` (B, S, n, d) at
    positions 0..S-1: coordinates i and i + d/2 turn by ``s *
    theta^(-2i/d)``, the angles in float64."""
    import jax.numpy as jnp
    d = x.shape[-1]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    inv_freq = theta ** (-2.0 * np.arange(d // 2) / d)
    angles = np.arange(x.shape[1])[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[:, None, :]
    lo, up = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - up * sin, up * cos + lo * sin], -1)


def reference_attention(cfg, w, x, bits=None):
    """Grouped-query attention of ``x`` (B, S, hidden) with head norms and
    rotary, causal, a block of queries at a time."""
    import jax
    import jax.numpy as jnp
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b, s, _ = x.shape
    q = _rms_norm(cfg, _linear(x, w["q"], bits).reshape(b, s, heads, -1),
                  w["q_norm"])
    k = _rms_norm(cfg, _linear(x, w["k"], bits).reshape(b, s, kv, -1),
                  w["k_norm"])
    v = _linear(x, w["v"], bits).reshape(b, s, kv, -1)
    q, k = _rotary(cfg, q), _rotary(cfg, k)
    # each key/value head serves heads // kv consecutive query heads
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    scale = q.shape[-1] ** -0.5
    mixed = []
    for start in range(0, s, QUERY_BLOCK):          # in blocks of queries
        end = min(start + QUERY_BLOCK, s)
        scores = _product("bqhd,bkhd->bhqk", q[:, start:end], k[:, :end],
                          bits) * scale
        future = jnp.arange(end)[None, :] > jnp.arange(start, end)[:, None]
        scores = jnp.where(future, -jnp.inf, scores)
        mixed.append(_product("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                              v[:, :end], bits))
    return _linear(jnp.concatenate(mixed, 1).reshape(b, s, -1), w["o"], bits)


def reference_experts(cfg, w, x, chosen=None, bits=None):
    """``(y, scores)``: the expert layer of ``x`` (B, S, hidden), the experts
    held being ``first_expert ..`` as many as ``w`` holds; the chosen
    experts given ((B, S, k), concrete) or, ``None``, the reference's own
    (the largest of the scores plus ``w["bias"]``, 0 where absent).
    ``scores``: the router's (B, S, experts). ``bits``: every product's
    operands rounded (:func:`_product`)."""
    import jax
    import jax.numpy as jnp
    first, top = cfg["first_expert"], cfg["num_experts_per_tok"]
    bsz, s, u = x.shape
    flat = x.reshape(-1, u)
    scores = jax.nn.sigmoid(_linear(flat, w["router"], bits))
    if chosen is None:
        chosen = own_choice(np.asarray(scores) + np.asarray(
            w.get("bias", 0.0)), top)
    chosen = np.asarray(chosen).reshape(-1, top)
    picked = jnp.take_along_axis(scores, jnp.asarray(chosen), axis=-1)
    weights = cfg["routed_scaling_factor"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-6) \
        if cfg["norm_topk_prob"] else cfg["routed_scaling_factor"] * picked
    y = jnp.zeros_like(flat)
    for e in range(first, first + w["w1"].shape[0]):    # the experts held
        mask = chosen == e
        rows = np.nonzero(mask.any(-1))[0]
        if not rows.size:
            continue
        weight = jnp.sum(jnp.where(jnp.asarray(mask[rows]), weights[rows],
                                   0.0), -1)
        hidden = _gated(_product("ri,if->rf", flat[rows], w["w1"][e - first],
                                 bits))
        y = y.at[rows].add(weight[:, None] * _product(
            "rf,fo->ro", hidden, w["w2"][e - first], bits))
    return y.reshape(bsz, s, u), scores.reshape(bsz, s, -1)


def _forward(params, cfg, tokens, routes=None, bits=None):
    """``(logits, scores)``: the forward pass with the experts of each
    expert layer given (``routes``: one (B, S, k) integer array a layer,
    concrete) or, where ``routes`` is ``None``, chosen by the reference
    itself. The weights of the chosen come from the reference's own float32
    scores at those experts either way. ``scores``: each expert layer's (B,
    S, experts) scores. ``bits``: every product's operands rounded
    (:func:`_product`)."""
    import jax.numpy as jnp
    routes = iter(routes) if routes is not None else None
    all_scores = []
    h = params["embed"][jnp.asarray(tokens)]
    for w in params["layers"]:
        x = _rms_norm(cfg, h, w["operator_norm"])
        if "conv" in w:
            h = h + reference_short_conv(cfg, w["conv"], x, bits)
        else:
            h = h + reference_attention(cfg, w["attention"], x, bits)
        x = _rms_norm(cfg, h, w["ffn_norm"])
        if "experts" in w:
            y, scores = reference_experts(
                cfg, w["experts"], x, None if routes is None else next(routes),
                bits)
            all_scores.append(scores)
        else:
            y = _linear(_gated(_linear(x, w["mlp"]["in"], bits)),
                        w["mlp"]["out"], bits)
        h = h + y
    return _linear(_rms_norm(cfg, h, params["final_norm"]), params["embed"],
                   bits), all_scores


def biases(params):
    """Each expert layer's balancing bias, as numpy."""
    return [np.asarray(w["experts"].get("bias", 0.0))
            for w in params["layers"] if "experts" in w]


def forward_at(params, cfg, tokens, routes=None, bits=None):
    """``(logits, own, scores)`` as numpy arrays: the logits with the chosen
    experts given (``None``: the reference's own), and for each expert layer
    the reference's own choice (B, S, k) and its float32 scores (B, S,
    experts); ``bits``: every product's operands rounded (only with the
    reference's own choice)."""
    import jax
    tokens = np.asarray(tokens)
    with reference_device():
        if routes is None:      # the choice needs the scores' values
            logits, scores = _forward(params, cfg, tokens, bits=bits)
        else:                   # one program: the routes are constants of it
            routes = [np.asarray(r) for r in routes]
            logits, scores = jax.jit(
                lambda p: _forward(p, cfg, tokens, routes))(params)
        scores = [np.asarray(s) for s in scores]
        own = [own_choice(s + b, cfg["num_experts_per_tok"])
               for s, b in zip(scores, biases(params))]
        return np.asarray(logits), own, scores


def reference_logits(net, tokens):
    """Logits of ``tokens`` (N, S) in plain float32 with the net's parameters
    as they are now and the reference's own choice of experts."""
    return forward_at(reference_params(net), net.chipbench_args, tokens)[0]


def reference_kept(net, x):
    """What ``compare`` needs from before the cast: the float32 parameters
    and biases, the sizes and the samples."""
    return reference_params(net), dict(net.chipbench_args), np.asarray(x)


def reference_loss_and_grads(net, tokens, labels, routes):
    """``(loss, grads)`` of the mean next-token cross entropy at the given
    ``routes``, by autodiff of the plain forward; ``grads`` has the
    structure of :func:`reference_params` without the biases."""
    import jax
    import jax.numpy as jnp

    labels = np.asarray(labels)
    routes = [np.asarray(r) for r in routes]
    cfg = net.chipbench_args

    def loss_of(params):
        logits = _forward(params, cfg, np.asarray(tokens), routes)[0]
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, jnp.asarray(labels)[..., None], -1))

    params = reference_params(net)
    for w in params["layers"]:
        w.get("experts", {}).pop("bias", None)  # the routes are given
    with reference_device():
        # one program: the routes are constants of it
        loss, grads = jax.jit(jax.value_and_grad(loss_of))(params)
        return float(loss), jax.tree_util.tree_map(np.asarray, grads)


# -- the comparison -----------------------------------------------------------

# Share of token-layers that may lie inside the margin (of which the first
# condition says nothing). Read on a v5e (PERF.md sec. 6): the cell's
# program 0.1660 to 0.1713 on eight seeds (by layer 0.12 to 0.21: the error
# carried in grows with depth); a router that rounds its product and its
# scores to bfloat16 (`lax.reduce_precision`) 0.2136 to 0.2187 on four of
# those seeds; the reference in the nearest precision below (`control`, 3
# mantissa bits) 0.884 on one, with `share` 0.225. The limit lies between
# the sound readings and both controls: 0.024 above the highest sound
# reading, 0.019 under the lowest of the bfloat16 router.
INSIDE_LIMIT = 0.195


def compare(kept, trainer, args, x, y):
    """The system's logits against the reference's **at the system's
    routes**; the routes held to the reference's own (the largest of the
    float32 scores plus the bias) wherever those lie farther apart than the
    system's scores were moved; the rows the expert layers computed against
    the pairs their routes sent to the experts held; then one training step
    of the system, its balancing biases and router counts held to the rule
    (:func:`bias_step`)."""
    from chipbench.runners import train
    said = judged(kept, train.system_outputs(trainer, args, x, y))
    said["conditions"]["bias_moved_by_the_rule"] = bias_step(trainer, args,
                                                             x, y)
    return said


def expert_layers(net):
    """The net's routed-expert layers, in the order of its routes."""
    return [layer.mlp for layer in net.layers._children.values()
            if hasattr(layer.mlp, "router_weight")]


def bias_step(trainer, args, x, y):
    """One compiled training step of the system on ``x, y`` (it leaves the
    trainer a step further on), and each expert layer's balancing bias and
    router counts after it against the rule applied here to the routes that
    step chose: ``c`` the pairs sent to each of all the experts, the bias
    ``b + rate * sign(mean(c) - c)`` in float32, the counter ``router_rows``
    grown by ``c``. A bias left as it was, moved twice (once for each trace
    of a recomputed forward) or the wrong way, or counts not added, differ."""
    layers = expert_layers(trainer._block)
    _, width = experts_held(args)

    def counters():
        return ([layer.router_bias.data().asnumpy() for layer in layers],
                [np.zeros(width, np.int64) if layer.router_rows is None
                 else layer.router_rows.data().asnumpy().astype(np.int64)
                 for layer in layers])

    bias0, rows0 = counters()
    trainer.step(x, y)
    routes = [out.asnumpy() for out in trainer.last_outputs[1:1 + len(layers)]]
    bias1, rows1 = counters()
    rate = np.float32(args["expert_bias_update_rate"])
    wrong = moved = 0
    for b0, r0, b1, r1, chosen in zip(bias0, rows0, bias1, rows1, routes):
        c = np.bincount(np.mod(chosen, width).reshape(-1), minlength=width)
        load = c.astype(np.float32)
        want = (b0 + rate * np.sign(load.mean(dtype=np.float32) - load)
                ).astype(b0.dtype)
        wrong += int(np.sum(b1 != want)) + int(np.sum(r1 - r0 != c))
        moved += int(np.sum(b1 != b0))
    print("chipbench: bias_step " + json.dumps(
        {"layers": len(layers), "moved": moved, "wrong": wrong,
         "bias_max": float(max(np.abs(b).max() for b in bias1))},
        sort_keys=True), flush=True)
    return {
        "value": float(wrong), "limit": 0.0, "ok": wrong == 0,
        "why": "entries of the expert layers' balancing biases and router "
               "counts that differ, after one compiled training step of the "
               "system on the batch, from b + rate * sign(mean(c) - c) in "
               "float32 and the counts grown by c, c the pairs the routes of "
               "that step sent to each of all the experts (counted here): a "
               "bias left as it was, moved twice or the wrong way, or counts "
               "not added"}


def control(kept, bits=3):
    """The comparison's control: the reference itself with every product's
    operands rounded to ``bits`` mantissa bits (3: the nearest precision
    below the configuration's bfloat16), its own routes and scores, and as
    many rows computed as its routes send to the experts held, put through
    :func:`judged` as if it were the system. It has to come out not
    ``ok``."""
    params, cfg, samples = kept
    logits, own, scores = forward_at(params, cfg, samples, bits=bits)
    return judged(kept, [logits] + own + scores
                  + [np.stack([held_pairs(r, cfg) for r in own])])


def held_pairs(chosen, cfg):
    """The pairs of ``chosen`` that name each expert held here."""
    (first, held), _ = experts_held(cfg)
    local = np.asarray(chosen).reshape(-1).astype(np.int64) - first
    return np.bincount(local[(local >= 0) & (local < held)], minlength=held)


def judged(kept, outputs):
    """What :func:`compare` returns for a system whose one forward put out
    ``outputs``: ``[logits, routes of each expert layer, their scores, the
    rows each held expert computed]``, over a batch whose first samples are
    the kept ones."""
    params, cfg, samples = kept
    n = len(samples)
    logits = outputs[0][:n].astype(np.float32)
    layers = (len(outputs) - 2) // 2
    routes = outputs[1:1 + layers]
    system_scores = outputs[1 + layers:1 + 2 * layers]
    computed = outputs[-1]
    reference, own, scores = forward_at(params, cfg, samples,
                                        [r[:n] for r in routes])

    _, width = experts_held(cfg)
    top = cfg["num_experts_per_tok"]
    tokens = outside = inside_count = 0
    per_layer = []
    for mine, theirs, s, moved, bias in zip(own, routes, scores,
                                            system_scores, biases(params)):
        # the choice is of the scores plus the bias, which both sides add
        # alike: the margin is the scores' own
        ranked = s.reshape(-1, width) + bias
        mine = mine.reshape(-1, top)
        theirs = theirs[:n].reshape(-1, top)
        margins = margin_of(moved[:n].reshape(-1, width), s.reshape(-1, width))
        differ, inside = route_conditions(mine, ranked, margins, theirs)
        tokens += differ.size
        outside += int(np.sum(differ & ~inside))
        inside_count += int(np.sum(inside))
        per_layer.append({
            "differ": float(differ.mean()), "inside": float(inside.mean()),
            "differ_outside": int(np.sum(differ & ~inside)),
            "needed": needed_margin(mine, theirs, ranked, margins),
            "moved_max": float(margins.max())})

    # nothing dropped: every pair of the routes that names an expert held
    # here was computed, over the whole batch
    landed = np.stack([held_pairs(r, cfg) for r in routes])
    dropped = int(np.abs(landed - np.asarray(computed)).sum())
    print("chipbench: routes " + json.dumps(
        {"layers": per_layer, "landed": landed.sum(-1).tolist(),
         "computed": np.asarray(computed).sum(-1).tolist(),
         "share": float(np.max(np.abs(logits - reference))
                        / np.max(np.abs(reference)))},
        sort_keys=True), flush=True)
    outside_share = outside / tokens
    inside_share = inside_count / tokens
    return {
        "samples": n, "compared": logits.size,
        "max_abs_error": np.max(np.abs(logits - reference)),
        "max_abs_reference": np.max(np.abs(reference)),
        "conditions": {
            "routes_differ_outside_margin": {
                "value": outside_share, "limit": 0.0,
                "ok": outside_share == 0.0,
                "why": "share of token-layers whose chosen experts differ "
                       "from the float32 reference's own (the largest "
                       "scores plus the balancing bias) although the "
                       "reference's values at the cut lie farther apart "
                       "than the system's scores of that token were moved "
                       "by bfloat16 rounding, measured in the same forward "
                       "(margin_of)"},
            "routes_inside_margin": {
                "value": inside_share, "limit": INSIDE_LIMIT,
                "ok": inside_share <= INSIDE_LIMIT,
                "why": "share of token-layers whose values at the cut lie "
                       "inside that margin, of which the first condition "
                       "says nothing: it bounds how far the scores moved"},
            "held_pairs_computed": {
                "value": float(dropped), "limit": 0.0, "ok": dropped == 0,
                "why": "pairs of the system's routes that name an expert "
                       "held here, less the rows the expert layers "
                       "computed, summed over layers and experts as "
                       "absolute differences: nothing is dropped"},
        },
    }
