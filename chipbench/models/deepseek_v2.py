"""DeepSeek-V2 (``model_type`` ``deepseek_v2``): multi-head latent attention
(MLA) with YaRN rotary, a leading dense layer, then routed-expert layers with
a softmax router whose choice is group-limited, gated routed experts and
gated shared experts; an untied head. The trainer as a user builds it
(``gluon.model_zoo.deepseek_v2`` -> ``ShardedTrainer``), a seeded batch of
next-token pairs, the operation count from the shapes, the least operations
and bytes of the attention core and of the gated grouped products for their
shares of the roofline, the plain float32 reference, and the comparison a
routed model needs (``compare``: the reference at the system's routes, the
routes held to the reference's own through both stages of the choice). The
reference shares no function with ``mxnet_tpu/ops``; its sizes come from the
configuration's ``args`` (kept on the net by ``build``), its weights from the
net's parameters.

The equations, with the configuration's keys in brackets. ``h0 =
E[tokens]``; each layer ``h += MLA(RMSNorm(h))`` then ``h += FFN(RMSNorm(h))``
(eps ``rms_norm_eps``, weight after the normalisation); logits =
``RMSNorm(h_L) W_head^T``.

MLA, for each head held: ``c_q = RMSNorm(W_DQ x)`` (``q_lora_rank``), ``[q_nope
| q_pe] = (W_UQ c_q)_h`` (``qk_nope_head_dim`` | ``qk_rope_head_dim``);
``[c_kv | k_pe] = W_DKV x`` (``kv_lora_rank`` | ``qk_rope_head_dim``), ``[k_nope
| v] = (W_UKV RMSNorm(c_kv))_h`` (``qk_nope_head_dim`` | ``v_head_dim``);
``q_pe``, ``k_pe`` (one vector for all heads) turned by rotary at positions
0..S-1, rotate-half, YaRN frequencies (:func:`yarn_frequencies`);
``score = [q_nope | q_pe] . [k_nope | k_pe] * (nope + rope)^-1/2 * m^2``, ``m
= 0.1 mscale_all_dim ln(factor) + 1``; causal softmax times ``v``; the heads
held side by side times ``W_O``.

FFN: layer 0 (``first_k_dense_replace``) ``W_out(silu(g) * u)``, ``[g | u] =
W_in x`` (``intermediate_size``). After it: ``s = softmax(W_r x)`` over all
``published_counts.n_routed_experts`` experts in float32; group scores the
largest ``s`` of each of ``n_group`` runs of consecutive experts; the experts
of the ``topk_group`` best groups kept; chosen = the ``num_experts_per_tok``
largest kept scores; ``w_e = routed_scaling_factor * s_e``
(``norm_topk_prob`` false); ``f_e(x) = W2_e (silu(W1g_e x) * W1u_e x)``
(``moe_intermediate_size``); ``FFN(x) = sum over the chosen e **that this
chip holds** of w_e f_e(x) + f_shared(x)``, the shared expert the same form at
``n_shared_experts * moe_intermediate_size`` (the chip holds experts
``first_expert ..`` and heads ``first_head ..``: what the others would add
is left out here as it is in the program).
"""
from __future__ import annotations

import functools
import json
import math

import numpy as np

from . import reference_device
from .brumby_14b_base import rounded

# the keys of ``args`` that shape the model, as ``deepseek_v2`` names them
# (the counts of experts and heads are the published ones: ``build`` says
# which this chip holds)
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "n_shared_experts", "num_experts_per_tok",
              "first_k_dense_replace", "moe_layer_freq", "n_group",
              "topk_group", "norm_topk_prob", "routed_scaling_factor",
              "scoring_func", "rms_norm_eps", "rope_theta", "rope_scaling",
              "topk_method", "hidden_act", "attention_bias",
              "tie_word_embeddings")

# build() keeps the newest (net, trainer) here: a per-layer metric that
# joins the trace with the compiled programs (layer_metrics/device_scopes.py)
# or reads the expert layers' counters (layer_metrics/expert_load.py) reads
# them after the runner has returned and dropped its own references;
# make_batch() keeps the newest batch's (samples, positions) beside them, for
# the attention core's share of the roofline (layer_metrics/shape_roofline.py)
# and the held experts' rows over a uniform router's
# (layer_metrics/held_rows.py)
LIVE = []
BATCH = []


def experts_held(args):
    """``(first, count)`` of the experts this chip computes, and the width
    of the router they are chosen among."""
    return (args["first_expert"], args["n_routed_experts"]), \
        args["published_counts"]["n_routed_experts"]


def heads_held(args):
    """``(first, count)`` of the attention heads this chip computes, and
    the published count."""
    return (args["first_head"], args["num_attention_heads"]), \
        args["published_counts"]["num_attention_heads"]


def expert_layers(args):
    """Indices of the layers whose feed-forward part is routed experts."""
    return [i for i in range(args["num_hidden_layers"])
            if i >= args["first_k_dense_replace"]
            and i % args["moe_layer_freq"] == 0]


def build(args, mesh, seed):
    """``(net, trainer)``; parameters are drawn from ``seed``. ``net`` maps
    tokens (B, S) to ``[logits (B, S, vocab), the routes of each expert
    layer (B, S, 6), the scores of each (B, S, 160), the rows each held
    expert computed (4, 10)]``."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import deepseek_v2, nemotron_h
    from mxnet_tpu.guardrails import GuardConfig

    class SeededNormal(mx.init.Initializer):
        """``Normal(sigma)`` drawn as float32 from a generator of its own
        (``mx.init.Normal`` draws float64 from numpy's legacy generator,
        five times slower over a billion and a half parameters); the norms'
        weights keep their ones."""

        def __init__(self, sigma, seed):
            super().__init__(sigma=sigma)
            self.sigma, self.rng = sigma, np.random.default_rng(seed)

        def _init_weight(self, desc, arr):
            self._set(arr, self.sigma * self.rng.standard_normal(
                arr.shape, dtype=np.float32))

    held, width = experts_held(args)
    heads, published = heads_held(args)
    net = deepseek_v2.deepseek_v2(
        experts_held=held, heads_held=heads, return_routes=True,
        recompute=args["recompute"], balance_alphas=args["balance_alphas"],
        capacity_factor=args["capacity_factor"], n_routed_experts=width,
        num_attention_heads=published,
        **{key: args[key] for key in MODEL_KEYS})
    net.initialize(SeededNormal(args["init_sigma"], seed))
    net.chipbench_args = dict(args)
    # the paper's schedule from its first step: a linear warm-up from 0, then
    # the peak, multiplied by the factor at each decay step
    schedule = mx.lr_scheduler.MultiFactorScheduler(
        step=args["lr_decay_steps"], factor=args["lr_decay_factor"],
        base_lr=args["optimizer_params"]["learning_rate"],
        warmup_steps=args["lr_warmup_steps"])
    trainer = parallel.ShardedTrainer(
        net, nemotron_h.FirstOutputLoss(gluon.loss.SoftmaxCrossEntropyLoss()),
        args["optimizer"],
        dict(args["optimizer_params"], lr_scheduler=schedule), mesh=mesh,
        compute_dtype=args["compute_dtype"],
        master_dtype=args["master_dtype"],
        guard=GuardConfig(clip_norm=args["clip_norm"], mode="deferred"))
    LIVE[:] = [(net, trainer)]
    return net, trainer


def make_batch(args, traffic, batch, rng):
    """Seeded uniform tokens over the rows held; the label of a position is
    the next token."""
    toks = rng.integers(0, args["vocab_size"], (batch, traffic["seq"] + 1))
    BATCH[:] = [(batch, traffic["seq"])]
    return toks[:, :-1], toks[:, 1:]


def parameter_counts(args):
    """Trainable parameters of the cut, by part, from the sizes alone."""
    u, heads = args["hidden_size"], args["num_attention_heads"]
    nope, rope = args["qk_nope_head_dim"], args["qk_rope_head_dim"]
    vd, qr, kr = args["v_head_dim"], args["q_lora_rank"], args["kv_lora_rank"]
    f, width = args["moe_intermediate_size"], experts_held(args)[1]
    layers, routed = args["num_hidden_layers"], len(expert_layers(args))
    attention = (u * qr + qr + qr * heads * (nope + rope) + u * (kr + rope)
                 + kr + kr * heads * (nope + vd) + heads * vd * u)
    return {
        "attention": layers * attention,
        "routed_experts": routed * args["n_routed_experts"] * 3 * u * f,
        "shared_experts": routed * 3 * u * args["n_shared_experts"] * f,
        "routers": routed * width * u,
        "dense_mlp": (layers - routed) * 3 * u * args["intermediate_size"],
        "embedding_and_head": 2 * args["vocab_size"] * u,
        "norms": 2 * layers * u + u,
    }


def product_macs_per_token(args, seq):
    """Multiply-accumulates of the forward pass for one token, by part:
    every product with a weight, attention's two (a causal row reads half
    the keys on average: 192-wide scores and 128-wide values for each head
    held), the head. The routed experts count the share of a token's pairs
    that a uniform router sends to the experts held: ``num_experts_per_tok``
    x held / all."""
    u, heads = args["hidden_size"], args["num_attention_heads"]
    nope, rope = args["qk_nope_head_dim"], args["qk_rope_head_dim"]
    vd, qr, kr = args["v_head_dim"], args["q_lora_rank"], args["kv_lora_rank"]
    f = args["moe_intermediate_size"]
    (_, held), width = experts_held(args)
    layers, routed = args["num_hidden_layers"], len(expert_layers(args))
    return {
        "mla_proj": layers * (u * qr + qr * heads * (nope + rope)
                              + u * (kr + rope) + kr * heads * (nope + vd)
                              + heads * vd * u),
        "mla_attention": layers * heads * (nope + rope + vd) * seq // 2,
        "router": routed * width * u,
        "shared_experts": routed * 3 * u * args["n_shared_experts"] * f,
        "routed_experts": routed * args["num_experts_per_tok"] * held
        * 3 * u * f // width,
        "dense_mlp": (layers - routed) * 3 * u * args["intermediate_size"],
        "head": u * args["vocab_size"],
    }


def flops_per_sample(args, traffic):
    """Training operations for one sequence, from the shapes: every product
    of the forward pass, two operations a multiply-accumulate, and twice the
    forward again for the backward pass. Lookups, softmax, normalisation,
    rotary, gates, the ordering and gathering of routed rows and the
    recomputation of each layer in the backward pass are left out, as model
    utilization is defined."""
    seq = traffic["seq"]
    return 3 * 2 * sum(product_macs_per_token(args, seq).values()) * seq


# -- the attention core's least work, for its share of the roofline ----------

def attention_operations(args, batch, seq):
    """Operations one training step needs in the attention core over all
    layers: for every head held the causal triangle, ``seq (seq + 1) / 2``
    pairs, of a ``nope + rope``-wide score and a ``v_head_dim``-wide
    weighted value, two operations a multiply-accumulate, forward and twice
    that backward. The forward a recomputed layer makes again is not useful
    work, nor what a padded value head adds."""
    width = args["qk_nope_head_dim"] + args["qk_rope_head_dim"] \
        + args["v_head_dim"]
    pairs = seq * (seq + 1) // 2
    return 3 * 2 * args["num_attention_heads"] * pairs * width * batch \
        * args["num_hidden_layers"]


def attention_bytes(args, batch, seq, itemsize=2):
    """Bytes one training step has to move for it: q, k (``nope + rope``
    wide a head), v and the output (``v_head_dim``) of each layer once each
    way (read or written forward, their cotangents backward)."""
    qk = args["qk_nope_head_dim"] + args["qk_rope_head_dim"]
    arrays = batch * seq * args["num_attention_heads"] \
        * (2 * qk + 2 * args["v_head_dim"]) * itemsize
    return 2 * arrays * args["num_hidden_layers"]


# -- the gated grouped products' useful work, for their share of the roofline

def expert_product_operations(rows, args):
    """Operations that ``rows`` (token, expert) rows routed to held experts
    need in one training step: three matrices a gated expert (gate and up
    into it, down out of it), two operations a multiply-accumulate, forward
    and twice that backward. Recomputed and padded rows are not useful
    work."""
    return rows * 3 * 2 * args["hidden_size"] \
        * args["moe_intermediate_size"] * 3


def expert_product_bytes(rows, args, layers, itemsize=2):
    """Bytes one training step has to move for those products: each layer's
    held experts' three matrices read forward and read again backward, their
    gradients written once; each row read and written by both products
    forward (in, the gate's and up's outputs, the hidden row in, out), and
    backward the same rows' cotangents and the rows kept for the weights'
    gradients."""
    u, f = args["hidden_size"], args["moe_intermediate_size"]
    weights = layers * args["n_routed_experts"] * 3 * u * f * itemsize
    a_row = (2 * u + 3 * f) * itemsize
    return 3 * weights + 3 * rows * a_row


# -- the plain float32 reference ---------------------------------------------

def yarn_frequencies(args):
    """The ``qk_rope_head_dim / 2`` inverse frequencies of the rotary part,
    float64: ``theta_i = rope_theta^(-2i/d)``, and with YaRN ``theta_i (1 -
    r_i) + theta_i / factor r_i`` with ``r_i = clip((i - lo) / (hi - lo),
    0, 1)``, ``lo = floor(c(beta_fast))``, ``hi = ceil(c(beta_slow))``,
    ``c(n) = d ln(original_max_position_embeddings / (2 pi n)) / (2 ln
    rope_theta)``; and the factor on cos and sin."""
    d, base = args["qk_rope_head_dim"], float(args["rope_theta"])
    theta = base ** (-2.0 * np.arange(d // 2) / d)
    scaling = args.get("rope_scaling")
    if not scaling:
        return theta, 1.0
    factor = scaling["factor"]

    def c(n):
        return d * math.log(scaling["original_max_position_embeddings"]
                            / (2 * math.pi * n)) / (2 * math.log(base))

    lo = max(math.floor(c(scaling["beta_fast"])), 0)
    hi = min(math.ceil(c(scaling["beta_slow"])), d - 1)
    r = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return theta * (1 - r) + theta / factor * r, \
        mscale(factor, scaling["mscale"]) \
        / mscale(factor, scaling["mscale_all_dim"])


def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def score_scale(args):
    """What the reference multiplies a score by."""
    scale = (args["qk_nope_head_dim"] + args["qk_rope_head_dim"]) ** -0.5
    scaling = args.get("rope_scaling")
    if scaling:
        scale *= mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def reference_params(net, read=None):
    """The net's parameters as float32 ``jax.numpy`` arrays on the
    reference's device, by role. Dense weights are (out, in), the experts'
    (expert, in, out), as the program stores them. ``read(param)`` replaces
    the value taken from each parameter (a test reads gradients into the
    same structure)."""
    import jax.numpy as jnp

    def val(param):
        if read is not None:
            return read(param)
        return jnp.asarray(param.data().asnumpy().astype(np.float32))

    def layer(block):
        a, mlp = block.attention, block.mlp
        out = {"input_norm": val(block.input_norm.gamma),
               "mlp_norm": val(block.mlp_norm.gamma),
               "attention": {
                   "q_a": val(a.q_a_proj.weight),
                   "q_a_norm": val(a.q_a_norm.gamma),
                   "q_b": val(a.q_b_proj.weight),
                   "kv_a": val(a.kv_a_proj.weight),
                   "kv_a_norm": val(a.kv_a_norm.gamma),
                   "kv_b": val(a.kv_b_proj.weight),
                   "o": val(a.o_proj.weight)}}
        if hasattr(mlp, "router_weight"):
            out["experts"] = {
                "router": val(mlp.router_weight),
                "w1": val(mlp.expert_w1), "w2": val(mlp.expert_w2),
                "shared_in": val(mlp.shared.w_in.weight),
                "shared_out": val(mlp.shared.w_out.weight)}
        else:
            out["mlp"] = {"in": val(mlp.w_in.weight),
                          "out": val(mlp.w_out.weight)}
        return out

    with reference_device():
        return {"embed": val(net.embed_weight),
                "final_norm": val(net.final_norm.gamma),
                "head": val(net.head_weight),
                "layers": [layer(block) for block in net.layers]}


QUERY_BLOCK = 512       # 8192 x 8192 x 8 float32 scores would be 2.1 GB


def own_choice(scores, cfg):
    """The reference's own choice from float32 ``scores`` (T, E), in numpy:
    the ``topk_group`` groups of largest maximum (``n_group`` runs of
    consecutive experts), then the ``num_experts_per_tok`` largest scores
    of their experts, largest first, the lower index first among equals, as
    ``lax.top_k`` orders them. Returns ``(chosen (T, k), groups (T,
    topk_group))``."""
    scores = np.asarray(scores)
    t, e = scores.shape
    n_group, top_group = cfg["n_group"], cfg["topk_group"]
    best = scores.reshape(t, n_group, -1).max(-1)
    groups = np.argsort(-best, axis=-1, kind="stable")[:, :top_group]
    kept = np.zeros((t, n_group), bool)
    np.put_along_axis(kept, groups, True, axis=-1)
    masked = np.where(np.repeat(kept, e // n_group, axis=-1), scores,
                      -np.inf)
    chosen = np.argsort(-masked, axis=-1,
                        kind="stable")[:, :cfg["num_experts_per_tok"]]
    return chosen, groups


def budget_of(cfg, tokens):
    """The pairs the experts held here may compute in a forward over
    ``tokens`` tokens: ``capacity_factor`` times their share, rounded up
    (DeepSeek-V2's device-level dropping; ``None`` without it)."""
    factor = cfg.get("capacity_factor") or 0
    if not factor:
        return None
    (_, held), width = experts_held(cfg)
    top = cfg["num_experts_per_tok"]
    return min(math.ceil(factor * tokens * top * held / width), tokens * top)


def reference_drop(cfg, chosen, scores):
    """``chosen`` (T, k) with the pairs past the budget of the experts held
    marked dropped (id less the router's width), in numpy: of the pairs
    naming a held expert, those of largest score (``scores`` (T, E)) are
    kept, the lower pair first among equals. Unchanged without a
    ``capacity_factor``."""
    chosen = np.asarray(chosen)
    budget = budget_of(cfg, chosen.shape[0])
    if budget is None:
        return chosen
    (first, held), width = experts_held(cfg)
    flat = chosen.reshape(-1)
    here = (flat >= first) & (flat < first + held)
    affinity = np.take_along_axis(np.asarray(scores), chosen, -1).reshape(-1)
    ranked = np.argsort(-np.where(here, affinity, -np.inf), kind="stable")
    kept = np.zeros(flat.shape, bool)
    kept[ranked[:budget]] = True
    return np.where(here & ~kept, flat - width, flat).reshape(chosen.shape)


def _product(spec, a, b, bits=None):
    """``jnp.einsum(spec, a, b)`` at ``Precision.HIGHEST``, both operands
    first rounded to ``bits`` mantissa bits (``None``: as they are; the
    control of the comparison rounds to 3, the nearest precision below the
    configuration's bfloat16)."""
    import jax.numpy as jnp
    from jax import lax
    return jnp.einsum(spec, rounded(a, bits), rounded(b, bits),
                      precision=lax.Precision.HIGHEST)


def _linear(x, w, bits=None):           # w is (out, in)
    return _product("...i,oi->...o", x, w, bits)


def _rms_norm(cfg, x, gamma):
    import jax.numpy as jnp
    from jax import lax
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                         + cfg["rms_norm_eps"]) * gamma


def _gated(h):
    """``silu(g) * u`` of the two halves of the last axis."""
    import jax.numpy as jnp
    g, u = jnp.split(h, 2, axis=-1)
    return g / (1.0 + jnp.exp(-g)) * u


def _rotary(cfg, x):
    """Rotate-half rotary over the last axis of ``x`` (B, S, n, rope) at
    positions 0..S-1 with :func:`yarn_frequencies`."""
    import jax.numpy as jnp
    inv_freq, ratio = yarn_frequencies(cfg)
    half = cfg["qk_rope_head_dim"] // 2
    angles = np.arange(x.shape[1])[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles) * ratio, jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angles) * ratio, jnp.float32)[:, None, :]
    lo, up = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - up * sin, up * cos + lo * sin], -1)


def reference_attention(cfg, w, x, bits=None):
    """Latent attention of ``x`` (B, S, hidden) over the heads whose
    weights ``w`` holds (their count from the shapes), causal, a block of
    queries at a time; ``bits``: every product's operands rounded
    (:func:`_product`)."""
    import jax
    import jax.numpy as jnp

    linear = functools.partial(_linear, bits=bits)
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    kv_rank = cfg["kv_lora_rank"]
    heads = w["q_b"].shape[0] // (nope + rope)
    b, s, _ = x.shape
    q = linear(_rms_norm(cfg, linear(x, w["q_a"]), w["q_a_norm"]),
               w["q_b"]).reshape(b, s, heads, nope + rope)
    kv = linear(x, w["kv_a"])
    k_pe = _rotary(cfg, kv[..., kv_rank:].reshape(b, s, 1, rope))
    kv = linear(_rms_norm(cfg, kv[..., :kv_rank], w["kv_a_norm"]),
                w["kv_b"]).reshape(b, s, heads, -1)
    q = jnp.concatenate([q[..., :nope], _rotary(cfg, q[..., nope:])], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, heads, rope))], -1)
    v = kv[..., nope:]
    scale = score_scale(cfg)
    mixed = []
    for start in range(0, s, QUERY_BLOCK):          # in blocks of queries
        end = min(start + QUERY_BLOCK, s)
        scores = _product("bqhd,bkhd->bhqk", q[:, start:end], k[:, :end],
                          bits) * scale
        future = jnp.arange(end)[None, :] > jnp.arange(start, end)[:, None]
        scores = jnp.where(future, -jnp.inf, scores)
        mixed.append(_product("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                              v[:, :end], bits))
    return linear(jnp.concatenate(mixed, 1).reshape(b, s, -1), w["o"])


def reference_experts(cfg, w, x, chosen=None, bits=None):
    """``(y, scores)``: the expert layer of ``x`` (B, S, hidden), the experts
    held being ``first_expert ..`` as many as ``w`` holds; the chosen
    experts given ((B, S, k), concrete) or, ``None``, the reference's own
    (:func:`own_choice`). ``scores``: the router's (B, S, experts).
    ``bits``: every product's operands rounded (:func:`_product`). A pair
    marked dropped (id less the router's width: :func:`reference_drop`)
    adds nothing."""
    import jax
    import jax.numpy as jnp

    linear = functools.partial(_linear, bits=bits)
    first, top = cfg["first_expert"], cfg["num_experts_per_tok"]
    bsz, s, u = x.shape
    flat = x.reshape(-1, u)
    scores = jax.nn.softmax(linear(flat, w["router"]), -1)
    if chosen is None:
        own = np.asarray(scores)
        chosen = reference_drop(cfg, own_choice(own, cfg)[0], own)
    chosen = np.asarray(chosen).reshape(-1, top)
    picked = jnp.take_along_axis(
        scores, jnp.asarray(chosen % scores.shape[-1]), axis=-1)
    weights = cfg["routed_scaling_factor"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-20) \
        if cfg["norm_topk_prob"] else cfg["routed_scaling_factor"] * picked
    y = linear(_gated(linear(flat, w["shared_in"])), w["shared_out"])
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
    itself (:func:`own_choice`). The weights of the chosen come from the
    reference's own float32 scores at those experts either way.
    ``scores``: each expert layer's (B, S, experts) scores. ``bits``:
    every product's operands rounded (:func:`_product`)."""
    import jax.numpy as jnp

    linear = functools.partial(_linear, bits=bits)
    routes = iter(routes) if routes is not None else None
    all_scores = []
    h = params["embed"][jnp.asarray(tokens)]
    for w in params["layers"]:
        h = h + reference_attention(cfg, w["attention"],
                                    _rms_norm(cfg, h, w["input_norm"]), bits)
        x = _rms_norm(cfg, h, w["mlp_norm"])
        if "experts" in w:
            y, scores = reference_experts(
                cfg, w["experts"], x, None if routes is None else next(routes),
                bits)
            all_scores.append(scores)
        else:
            y = linear(_gated(linear(x, w["mlp"]["in"])), w["mlp"]["out"])
        h = h + y
    return linear(_rms_norm(cfg, h, params["final_norm"]), params["head"]), \
        all_scores


def forward_at(params, cfg, tokens, routes=None, bits=None):
    """``(logits, own, scores)`` as numpy arrays: the logits with the chosen
    experts given (``None``: the reference's own), and for each expert layer
    the reference's own choice (B, S, k), the pairs past the budget marked
    dropped (:func:`reference_drop`), and its float32 scores (B, S,
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
        flat = [s.reshape(-1, s.shape[-1]) for s in scores]
        own = [reference_drop(cfg, own_choice(s, cfg)[0], s).reshape(
            shaped.shape[:-1] + (-1,)) for s, shaped in zip(flat, scores)]
        return np.asarray(logits), own, scores


def reference_logits(net, tokens):
    """Logits of ``tokens`` (N, S) in plain float32 with the net's parameters
    as they are now and the reference's own choice of experts."""
    return forward_at(reference_params(net), net.chipbench_args, tokens)[0]


def reference_kept(net, x):
    """What ``compare`` needs from before the cast: the float32 parameters,
    the sizes and the samples."""
    return reference_params(net), dict(net.chipbench_args), np.asarray(x)


def reference_balance(cfg, routes, scores):
    """The balance losses of one expert layer at the given ``routes`` (B, S,
    k), concrete, and the reference's ``scores`` (B, S, experts), each
    sequence's terms written out as the paper has them (arXiv:2405.04434
    sec. 2.2.3: expert-, device- and communication-level, the devices being
    the router's groups), averaged over the batch; 0 without
    ``balance_alphas``."""
    import jax.numpy as jnp
    alphas = cfg.get("balance_alphas")
    if not alphas:
        return 0.0
    bsz, seq, top = routes.shape
    experts, groups = scores.shape[-1], cfg["n_group"]
    size = experts // groups
    total = 0.0
    chosen = np.asarray(routes) % experts      # a dropped pair was chosen
    for b in range(bsz):
        count = np.bincount(chosen[b].reshape(-1), minlength=experts)
        f = experts / (top * seq) * count
        p = jnp.mean(scores[b], 0)
        sent = np.zeros(groups)
        for token in chosen[b]:
            for g in set(int(e) // size for e in token):
                sent[g] += 1
        for g in range(groups):
            mine = slice(g * size, (g + 1) * size)
            p_group = jnp.sum(p[mine])
            total = total + alphas[1] * np.mean(f[mine]) * p_group \
                + alphas[2] * groups / (cfg["topk_group"] * seq) * sent[g] \
                * p_group
        total = total + alphas[0] * jnp.sum(f * p)
    return total / bsz


def reference_loss_and_grads(net, tokens, labels, routes):
    """``(loss, grads)`` of the mean next-token cross entropy, plus the
    balance losses, at the given ``routes``, by autodiff of the plain
    forward; ``grads`` has the structure of :func:`reference_params`."""
    import jax
    import jax.numpy as jnp

    labels = np.asarray(labels)
    routes = [np.asarray(r) for r in routes]
    cfg = net.chipbench_args

    def loss_of(params):
        logits, scores = _forward(params, cfg, np.asarray(tokens), routes)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, jnp.asarray(labels)[..., None], -1)) + sum(
                reference_balance(cfg, r, s) for r, s in zip(routes, scores))

    with reference_device():
        # one program: the routes are constants of it
        loss, grads = jax.jit(jax.value_and_grad(loss_of))(
            reference_params(net))
        return float(loss), jax.tree_util.tree_map(np.asarray, grads)


# -- the comparison -----------------------------------------------------------

# Share of token-layers that may lie inside the margin at either stage of the
# choice (of which the first condition says nothing). Read on a v5e (PERF.md
# sec. 6): the cell's program 0.1426 to 0.1478 on five seeds (by layer
# 0.11, 0.14, 0.15, 0.18: the error carried in grows with depth); the
# reference in the nearest precision below (`control`, 3 mantissa bits) at
# the cell's size and load 0.832 (`share` 0.129); a router that rounds its
# product and its softmax to bfloat16 (`lax.reduce_precision`) 0.1852 and
# 0.1875 on two of the sound seeds. The limit lies between the sound
# readings and both controls, 0.017 above the highest sound reading.
INSIDE_LIMIT = 0.165


def margin_of(system_scores, scores):
    """How far each of the system's scores lies from the reference's float32
    score of the same token and expert, **as measured** in the run that is
    compared (the system's scores are outputs of the same compiled forward
    as its routes and logits): the bfloat16 rounding of the router's input
    and weight, and of every layer below, moved them by that much. A floor
    of one float32 rounding of the score keeps ties inside. Two scores
    farther apart than the sum of their margins cannot have changed
    places."""
    scores = np.asarray(scores, np.float64)
    moved = np.abs(np.asarray(system_scores, np.float64) - scores)
    return np.maximum(moved, np.abs(scores) * 2.0 ** -23)


def group_margin_of(system_scores, scores, n_group):
    """The same for each group's score, the largest of its experts' (the
    first stage of the choice): how far the system's group maximum lies
    from the reference's, with the same floor."""
    t, e = np.shape(scores)
    mine = np.asarray(scores, np.float64).reshape(t, n_group, -1).max(-1)
    theirs = np.asarray(system_scores, np.float64).reshape(
        t, n_group, -1).max(-1)
    return np.maximum(np.abs(theirs - mine), np.abs(mine) * 2.0 ** -23)


def _uncertain(values, margins, chosen):
    """Per row: whether the lowest a chosen value can fall to is not above
    the highest another candidate (finite value, not chosen) can rise to."""
    low = np.where(chosen, values - margins, np.inf).min(-1)
    others = ~chosen & np.isfinite(values)
    return low <= np.where(others, values + margins, -np.inf).max(-1)


def route_conditions(scores, system_scores, system, cfg):
    """For one expert layer, over the tokens (flat): ``(differ, inside,
    inside_groups, inside_experts)``. ``differ``: the system's chosen set is
    not the reference's own. A token lies inside the margin where the
    reference's choice of groups could have gone otherwise (its lowest
    chosen group maximum and its highest other one within the sum of their
    measured margins) or, inside the groups it chose, its choice of experts
    (the same of its 6th and 7th scores there)."""
    scores = np.asarray(scores)
    t, e = scores.shape
    n_group = cfg["n_group"]
    own, groups = own_choice(scores, cfg)
    margins = margin_of(system_scores, scores)
    in_group = np.zeros((t, n_group), bool)
    np.put_along_axis(in_group, groups, True, axis=-1)
    inside_groups = _uncertain(
        scores.reshape(t, n_group, -1).max(-1),
        group_margin_of(system_scores, scores, n_group), in_group)
    candidates = np.where(np.repeat(in_group, e // n_group, axis=-1),
                          scores, -np.inf)
    chosen = np.zeros((t, e), bool)
    np.put_along_axis(chosen, own, True, axis=-1)
    inside_experts = _uncertain(candidates, margins, chosen)
    differ = (np.sort(np.asarray(system), -1) != np.sort(own, -1)).any(-1)
    return differ, inside_groups | inside_experts, inside_groups, \
        inside_experts


def drop_conditions(scores, system_scores, system, cfg):
    """For one expert layer, over the pairs (flat) of the system's choice
    that name an expert held here: ``(differ, inside)``. ``differ``: the
    system kept a pair that the budget, ranked by the reference's float32
    scores, drops, or dropped one that it keeps. A pair lies inside the
    margin where its score and the lowest score kept lie within the sum of
    its measured margin and the largest of the others' (:func:`margin_of`):
    farther apart, the two cannot have changed places."""
    system = np.asarray(system)
    width = scores.shape[-1]
    chosen = system % width
    (first, held), _ = experts_held(cfg)
    here = ((chosen >= first) & (chosen < first + held)).reshape(-1)
    budget = budget_of(cfg, len(chosen))
    if budget is None or not here.any():
        return np.zeros(int(here.sum()), bool), np.ones(int(here.sum()), bool)
    theirs = (system >= 0).reshape(-1)[here]
    mine = (reference_drop(cfg, chosen, scores) >= 0).reshape(-1)[here]
    affinity = np.take_along_axis(scores, chosen, -1).reshape(-1)[here]
    margins = np.take_along_axis(margin_of(system_scores, scores), chosen,
                                 -1).reshape(-1)[here]
    cut = np.sort(affinity)[::-1][min(budget, affinity.size) - 1]
    return theirs != mine, \
        np.abs(affinity - cut) <= margins + margins.max()


def compare(kept, trainer, args, x, y):
    """The system's logits against the reference's **at the system's
    routes**; the routes held to the reference's own wherever, at both
    stages of the choice, the float32 scores lie farther apart than the
    system's scores were moved; and the rows the expert layers computed
    against the pairs their routes sent to the experts held."""
    from chipbench.runners import train
    return judged(kept, train.system_outputs(trainer, args, x, y))


def control(kept, bits=3):
    """The comparison's control: the reference itself with every product's
    operands rounded to ``bits`` mantissa bits (3: the nearest precision
    below the configuration's bfloat16), its own routes and scores, and as
    many rows computed as its routes send to the experts held, put through
    :func:`judged` as if it were the system. It has to come out not
    ``ok``."""
    params, cfg, samples = kept
    logits, own, scores = forward_at(params, cfg, samples, bits=bits)
    (first, held), _ = experts_held(cfg)

    def rows(chosen):
        local = chosen.reshape(-1).astype(np.int64) - first
        return np.bincount(local[(local >= 0) & (local < held)],
                           minlength=held)

    return judged(kept, [logits] + own + scores
                  + [np.stack([rows(r) for r in own])])


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
    reference, _, scores = forward_at(params, cfg, samples,
                                      [r[:n] for r in routes])

    (first, held), width = experts_held(cfg)
    top = cfg["num_experts_per_tok"]
    tokens = outside = inside_count = pairs = drops_outside = 0
    per_layer = []
    for s, theirs, moved in zip(scores, routes, system_scores):
        s = s.reshape(-1, width)
        theirs = theirs[:n].reshape(-1, top)
        moved = moved[:n].reshape(-1, width)
        # a pair dropped past the budget (id less the width) was chosen
        differ, inside, by_group, by_expert = route_conditions(
            s, moved, theirs % width, cfg)
        tokens += differ.size
        outside += int(np.sum(differ & ~inside))
        inside_count += int(np.sum(inside))
        kept_differ, kept_inside = drop_conditions(s, moved, theirs, cfg)
        pairs += kept_differ.size
        drops_outside += int(np.sum(kept_differ & ~kept_inside))
        per_layer.append({
            "differ": float(differ.mean()), "inside": float(inside.mean()),
            "inside_groups": float(by_group.mean()),
            "inside_experts": float(by_expert.mean()),
            "differ_outside": int(np.sum(differ & ~inside)),
            "dropped": int(np.sum(theirs < 0)),
            "drops_differ": int(np.sum(kept_differ)),
            "moved_max": float(np.abs(moved - s).max())})

    # every pair of the routes that names an expert held here, and was not
    # dropped past the budget (a negative id), was computed, over the batch
    def pairs_held(chosen):
        local = chosen.reshape(-1).astype(np.int64) - first
        return np.bincount(local[(local >= 0) & (local < held)],
                           minlength=held)

    landed = np.stack([pairs_held(r) for r in routes])
    dropped = int(np.abs(landed - np.asarray(computed)).sum())
    print("chipbench: routes " + json.dumps(
        {"layers": per_layer, "landed": landed.sum(-1).tolist(),
         "computed": np.asarray(computed).sum(-1).tolist(),
         "share": float(np.max(np.abs(logits - reference))
                        / np.max(np.abs(reference)))},
        sort_keys=True), flush=True)
    outside_share = outside / tokens
    inside_share = inside_count / tokens
    drops_share = drops_outside / max(pairs, 1)
    return {
        "samples": n, "compared": logits.size,
        "max_abs_error": np.max(np.abs(logits - reference)),
        "max_abs_reference": np.max(np.abs(reference)),
        "conditions": {
            "routes_differ_outside_margin": {
                "value": outside_share, "limit": 0.0,
                "ok": outside_share == 0.0,
                "why": "share of token-layers whose chosen experts differ "
                       "from the float32 reference's own although, at both "
                       "stages of the group-limited choice (the best groups, "
                       "then the best experts of those groups), the "
                       "reference's scores at the cut lie farther apart "
                       "than the system's scores of that token were moved "
                       "by bfloat16 rounding, measured in the same forward "
                       "(margin_of, group_margin_of)"},
            "routes_inside_margin": {
                "value": inside_share, "limit": INSIDE_LIMIT,
                "ok": inside_share <= INSIDE_LIMIT,
                "why": "share of token-layers whose scores at either cut lie "
                       "inside that margin, of which the first condition "
                       "says nothing: it bounds how far the scores moved"},
            "drops_differ_outside_margin": {
                "value": drops_share, "limit": 0.0,
                "ok": drops_share == 0.0,
                "why": "share of the pairs naming an expert held here whose "
                       "fate at the device's budget (kept, or dropped as "
                       "DeepSeek-V2 trains: the budget's worth of largest "
                       "score kept) differs from the one the reference's "
                       "float32 scores give them, although the pair's score "
                       "and the lowest one kept lie farther apart than the "
                       "system's scores were moved (drop_conditions)"},
            "held_pairs_computed": {
                "value": float(dropped), "limit": 0.0, "ok": dropped == 0,
                "why": "pairs of the system's routes that name an expert "
                       "held here and were kept within the device's budget, "
                       "less the rows the expert layers computed, summed "
                       "over layers and experts as absolute differences: "
                       "every pair kept is computed"},
        },
    }
