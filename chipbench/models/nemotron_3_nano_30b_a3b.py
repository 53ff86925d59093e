"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` ``nemotron_h``): Mamba-2
layers, routed-expert layers with a shared expert, grouped-query attention
without any position embedding, each layer one part; an untied head. The
trainer as a user builds it (``gluon.model_zoo.nemotron_h`` ->
``ShardedTrainer``), a seeded batch of next-token pairs, the operation count
from the shapes, the plain float32 reference, and the comparison a routed
model needs (``compare``: chipbench/README.md, "A configuration's own
comparison"). The reference shares no function with ``mxnet_tpu/ops``; its
sizes come from the configuration's ``args`` (kept on the net by ``build``),
its weights from the net's parameters.

The equations, with the configuration's keys in brackets. ``h0 = E[tokens]``;
each layer ``h += mixer(RMSNorm(h))`` (eps ``layer_norm_epsilon``), the mixer
by the layer's letter in ``hybrid_override_pattern``; logits =
``RMSNorm(h_L) W_head^T``.

``M``: ``[z | xBC | dt] = W_in x``; ``xBC <- silu(conv(xBC))``, depthwise,
kernel ``conv_kernel``, left-padded, with bias; ``dt <- softplus(dt +
dt_bias)``, ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t
x_t B_t^T``, ``y_t = S_t C_t + D x_t``, a group of B and C serving
``mamba_num_heads / n_groups`` consecutive heads; ``y <- RMSNorm(y *
silu(z))`` with the mean square taken over each of the ``n_groups`` runs of
channels apart; ``W_out``.

``*``: ``num_attention_heads`` query heads and ``num_key_value_heads``
key/value heads of ``head_dim``, no bias, no rotary, causal softmax of ``q
k^T / sqrt(head_dim)``.

``E``: ``s = sigmoid(W_r x)``; chosen = the ``num_experts_per_tok`` largest
of ``s + b`` (``b`` = 0 here); ``w_e = routed_scaling_factor * s_e / (sum of
the chosen s + 1e-20)``; ``f_e(x) = W2_e relu(W1_e x)^2``; ``mixer(x) = sum
over the chosen e **that this chip holds** of w_e f_e(x) + f_shared(x)``
(the chip holds experts ``first_expert .. first_expert + n_routed_experts -
1`` of ``published_counts.n_routed_experts``; what the others would add is
left out here as it is in the program).
"""
from __future__ import annotations

import json

import numpy as np

from . import reference_device

# the keys of ``args`` that shape the model, as ``nemotron_h`` names them
MODEL_KEYS = ("vocab_size", "hidden_size", "hybrid_override_pattern",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
              "n_groups", "conv_kernel", "chunk_size", "num_experts_per_tok",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
              "mlp_hidden_act", "layer_norm_epsilon")

# build() keeps the newest (net, trainer) here: a per-layer metric that
# joins the trace with the compiled programs (layer_metrics/device_scopes.py)
# or reads the expert layers' counters (layer_metrics/expert_load.py) reads
# them after the runner has returned and dropped its own references
LIVE = []


def experts_held(args):
    """``(first, count)`` of the experts this chip computes, and the width
    of the router they are chosen among."""
    return (args["first_expert"], args["n_routed_experts"]), \
        args["published_counts"]["n_routed_experts"]


def build(args, mesh, seed):
    """``(net, trainer)``; parameters are drawn from ``seed``. ``net`` maps
    tokens (B, S) to ``[logits (B, S, vocab), the routes of each expert
    layer (B, S, 6), the scores of each (B, S, 128), the rows each held
    expert computed (4, 16)]``."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import nemotron_h

    class SeededNormal(mx.init.Initializer):
        """``Normal(sigma)`` drawn as float32 from a generator of its own
        (``mx.init.Normal`` draws float64 from numpy's legacy generator,
        five times slower over a billion parameters)."""

        def __init__(self, sigma, seed):
            super().__init__(sigma=sigma)
            self.sigma, self.rng = sigma, np.random.default_rng(seed)

        def _init_weight(self, desc, arr):
            self._set(arr, self.sigma * self.rng.standard_normal(
                arr.shape, dtype=np.float32))

    mx.random.seed(seed % (2 ** 31 - 1))    # the mixers' own initializers
    held, width = experts_held(args)
    net = nemotron_h.nemotron_h(
        experts_held=held, return_routes=True, recompute=args["recompute"],
        n_routed_experts=width, **{key: args[key] for key in MODEL_KEYS})
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
    return toks[:, :-1], toks[:, 1:]


def product_macs_per_token(args, seq):
    """Multiply-accumulates of the forward pass for one token, by part:
    every product with a weight, attention's two (halved: a causal row reads
    half the keys on average), the scan's four for each chunk, the head. The
    routed experts count the share of a token's pairs that a uniform router
    sends to the experts held: ``num_experts_per_tok`` x held / all."""
    u = args["hidden_size"]
    heads, kv = args["num_attention_heads"], args["num_key_value_heads"]
    head_dim = args["head_dim"]
    h, p = args["mamba_num_heads"], args["mamba_head_dim"]
    g, n, q = args["n_groups"], args["ssm_state_size"], args["chunk_size"]
    inner = h * p
    (_, held), width = experts_held(args)
    pattern = args["hybrid_override_pattern"]
    n_mamba, n_attn = pattern.count("M"), pattern.count("*")
    n_moe = pattern.count("E")
    shared = args["n_shared_experts"] \
        * args["moe_shared_expert_intermediate_size"]
    return {
        "mamba_proj": n_mamba * (u * (2 * inner + 2 * g * n + h) + inner * u),
        # C B^T, (scores) x, B^T x into the chunk's state, C (state)
        "mamba_scan": n_mamba * (q * g * n + q * inner + 2 * n * inner),
        "attention_proj": n_attn * (2 * u * heads * head_dim
                                    + 2 * u * kv * head_dim),
        "attention": n_attn * (2 * seq * heads * head_dim // 2),
        "router": n_moe * width * u,
        "shared_experts": n_moe * 2 * u * shared,
        "routed_experts": n_moe * args["num_experts_per_tok"] * held
        * 2 * u * args["moe_intermediate_size"] // width,
        "head": u * args["vocab_size"],
    }


def flops_per_sample(args, traffic):
    """Training operations for one sequence, from the shapes: every product
    of the forward pass, two operations a multiply-accumulate, and twice the
    forward again for the backward pass. Lookups, the convolution's four
    taps, softmax, normalisation, gates, the scan's elementwise work, the
    ordering and gathering of routed rows and the recomputation of each
    layer in the backward pass are left out, as model utilization is
    defined."""
    seq = traffic["seq"]
    return 3 * 2 * sum(product_macs_per_token(args, seq).values()) * seq


# -- the grouped products' useful work, for their share of the roofline ------

def expert_product_operations(rows, args):
    """Operations that ``rows`` (token, expert) rows routed to held experts
    need in one training step: two products a row (into the expert and out
    of it), two operations a multiply-accumulate, forward and twice that
    backward. Recomputed and padded rows are not useful work."""
    return rows * 2 * 2 * args["hidden_size"] \
        * args["moe_intermediate_size"] * 3


def expert_product_bytes(rows, args, layers, itemsize=2):
    """Bytes one training step has to move for those products: each layer's
    held experts' weights read forward and read again backward, their
    gradients written once; each row read and written by both products
    forward (in, hidden out, hidden in, out), and backward the same rows'
    cotangents and the rows kept for the weights' gradients."""
    u, f = args["hidden_size"], args["moe_intermediate_size"]
    weights = layers * args["n_routed_experts"] * 2 * u * f * itemsize
    a_row = 2 * (u + f) * itemsize
    return 3 * weights + 3 * rows * a_row


# -- the plain float32 reference ---------------------------------------------

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

    def layer(kind, block):
        mixer = block.mixer
        out = {"norm": val(block.norm.gamma)}
        if kind == "M":
            out["mamba"] = {
                "in": val(mixer.in_proj.weight),
                "conv_weight": val(mixer.conv_weight),
                "conv_bias": val(mixer.conv_bias),
                "dt_bias": val(mixer.dt_bias), "A_log": val(mixer.A_log),
                "D": val(mixer.D), "norm": val(mixer.norm_gamma),
                "out": val(mixer.out_proj.weight)}
        elif kind == "*":
            out["attention"] = {name: val(getattr(mixer, name + "_proj").weight)
                                for name in "qkvo"}
        else:
            out["experts"] = {
                "router": val(mixer.router_weight),
                "w1": val(mixer.expert_w1), "w2": val(mixer.expert_w2),
                "shared_in": val(mixer.shared.w_in.weight),
                "shared_out": val(mixer.shared.w_out.weight)}
        return out

    pattern = net.chipbench_args["hybrid_override_pattern"]
    with reference_device():
        return {"embed": val(net.embed_weight),
                "final_norm": val(net.final_norm.gamma),
                "head": val(net.head_weight),
                "layers": [layer(kind, block) for kind, block in zip(
                    pattern, net.layers._children.values())]}


QUERY_BLOCK = 512       # 8192 x 8192 x 32 float32 scores would be 8.6 GB


def _forward(params, cfg, tokens, routes=None):
    """``(logits, scores)``: the forward pass with the experts of each
    expert layer given (``routes``: one (B, S, k) integer array a layer,
    concrete) or, where ``routes`` is ``None``, chosen by the reference
    itself. The weights of the chosen come from the reference's own float32
    scores at those experts either way. ``scores``: each expert layer's (B,
    S, experts) scores, from which a caller reads the reference's own
    choice."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    eps = cfg["layer_norm_epsilon"]
    (first, held), _ = experts_held(cfg)
    top = cfg["num_experts_per_tok"]

    def linear(x, w):                   # w is (out, in)
        return jnp.einsum("...i,oi->...o", x, w, precision=hi)

    def rms_norm(x, gamma):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma

    def silu(x):
        return x / (1.0 + jnp.exp(-x))

    def relu2(x):
        return jnp.square(jnp.maximum(x, 0.0))

    def attention(x, w):
        b, s, _ = x.shape
        heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        q = linear(x, w["q"]).reshape(b, s, heads, -1)
        k = linear(x, w["k"]).reshape(b, s, kv, -1)
        v = linear(x, w["v"]).reshape(b, s, kv, -1)
        # each key/value head serves heads // kv consecutive query heads
        k = jnp.repeat(k, heads // kv, axis=2)
        v = jnp.repeat(v, heads // kv, axis=2)
        scale = cfg["head_dim"] ** -0.5
        mixed = []
        for start in range(0, s, QUERY_BLOCK):      # in blocks of queries
            end = min(start + QUERY_BLOCK, s)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:end],
                                k[:, :end], precision=hi) * scale
            future = jnp.arange(end)[None, :] \
                > jnp.arange(start, end)[:, None]
            scores = jnp.where(future, -jnp.inf, scores)
            mixed.append(jnp.einsum(
                "bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v[:, :end],
                precision=hi))
        return linear(jnp.concatenate(mixed, 1).reshape(b, s, -1), w["o"])

    def mamba(x, w):
        bsz, s, _ = x.shape
        h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
        g, n = cfg["n_groups"], cfg["ssm_state_size"]
        taps = cfg["conv_kernel"]
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
        gated = jnp.moveaxis(y, 0, 1).reshape(bsz, s, inner) * silu(z)
        # the gated norm, group by group: each run of inner // g channels
        # has a mean square of its own
        width = inner // g
        normed = jnp.concatenate([
            gated[..., i * width:(i + 1) * width] * lax.rsqrt(jnp.mean(
                jnp.square(gated[..., i * width:(i + 1) * width]), -1,
                keepdims=True) + eps) for i in range(g)], -1)
        return linear(normed * w["norm"], w["out"])

    def feed_forward(x, w_in, w_out):
        return linear(relu2(linear(x, w_in)), w_out)

    def experts(x, w, chosen):
        bsz, s, u = x.shape
        flat = x.reshape(-1, u)
        scores = jax.nn.sigmoid(linear(flat, w["router"]))
        if chosen is None:              # b = 0: the bias chooses nothing
            chosen = np.argsort(-np.asarray(scores), axis=-1,
                                kind="stable")[:, :top]
        chosen = np.asarray(chosen).reshape(-1, top)
        picked = jnp.take_along_axis(scores, jnp.asarray(chosen), axis=-1)
        weights = cfg["routed_scaling_factor"] * picked / (
            jnp.sum(picked, -1, keepdims=True) + 1e-20) \
            if cfg["norm_topk_prob"] else \
            cfg["routed_scaling_factor"] * picked
        y = feed_forward(flat, w["shared_in"], w["shared_out"])
        for e in range(first, first + held):    # the experts held, one by one
            mask = chosen == e
            rows = np.nonzero(mask.any(-1))[0]
            if not rows.size:
                continue
            weight = jnp.sum(jnp.where(jnp.asarray(mask[rows]),
                                       weights[rows], 0.0), -1)
            hidden = relu2(jnp.einsum("ri,if->rf", flat[rows],
                                      w["w1"][e - first], precision=hi))
            y = y.at[rows].add(weight[:, None] * jnp.einsum(
                "rf,fo->ro", hidden, w["w2"][e - first], precision=hi))
        return y.reshape(bsz, s, u), scores.reshape(bsz, s, -1)

    routes = iter(routes) if routes is not None else None
    all_scores = []
    h = params["embed"][jnp.asarray(tokens)]
    for w in params["layers"]:
        x = rms_norm(h, w["norm"])
        if "mamba" in w:
            h = h + mamba(x, w["mamba"])
        elif "attention" in w:
            h = h + attention(x, w["attention"])
        else:
            y, scores = experts(x, w["experts"],
                                None if routes is None else next(routes))
            all_scores.append(scores)
            h = h + y
    return linear(rms_norm(h, params["final_norm"]), params["head"]), \
        all_scores


def own_choice(scores, top):
    """The ``top`` largest of each row of float32 ``scores``, largest first,
    the lower index first among equals, as ``lax.top_k`` orders them."""
    return np.argsort(-np.asarray(scores), axis=-1, kind="stable")[..., :top]


def forward_at(params, cfg, tokens, routes=None):
    """``(logits, own, scores)`` as numpy arrays: the logits with the chosen
    experts given (``None``: the reference's own), and for each expert layer
    the reference's own choice (B, S, k) and its float32 scores (B, S,
    experts)."""
    import jax
    tokens = np.asarray(tokens)
    with reference_device():
        if routes is None:      # the choice needs the scores' values
            logits, scores = _forward(params, cfg, tokens)
        else:                   # one program: the routes are constants of it
            routes = [np.asarray(r) for r in routes]
            logits, scores = jax.jit(
                lambda p: _forward(p, cfg, tokens, routes))(params)
        scores = [np.asarray(s) for s in scores]
        return np.asarray(logits), \
            [own_choice(s, cfg["num_experts_per_tok"]) for s in scores], \
            scores


def reference_logits(net, tokens):
    """Logits of ``tokens`` (N, S) in plain float32 with the net's parameters
    as they are now and the reference's own choice of experts."""
    return forward_at(reference_params(net), net.chipbench_args, tokens)[0]


def reference_kept(net, x):
    """What ``compare`` needs from before the cast: the float32 parameters,
    the sizes and the samples."""
    return reference_params(net), dict(net.chipbench_args), np.asarray(x)


def reference_loss_and_grads(net, tokens, labels, routes):
    """``(loss, grads)`` of the mean next-token cross entropy at the given
    ``routes``, by autodiff of the plain forward; ``grads`` has the
    structure of :func:`reference_params`."""
    import jax
    import jax.numpy as jnp

    labels = np.asarray(labels)
    routes = [np.asarray(r) for r in routes]

    def loss_of(params):
        logits = _forward(params, net.chipbench_args, np.asarray(tokens),
                          routes)[0]
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, jnp.asarray(labels)[..., None], -1))

    with reference_device():
        # one program: the routes are constants of it
        loss, grads = jax.jit(jax.value_and_grad(loss_of))(
            reference_params(net))
        return float(loss), jax.tree_util.tree_map(np.asarray, grads)


# -- the comparison -----------------------------------------------------------

# Share of token-layers that may lie inside the margin (of which the first
# condition says nothing). Read on a v5e (PERF.md sec. 6, PR 32): the cell's
# program 0.1777 to 0.1825 on five seeds (by layer 0.12, 0.17, 0.20, 0.22:
# the error carried in grows with depth); a router that rounds its product
# and its scores to bfloat16 (`lax.reduce_precision`: XLA elides a plain cast
# and back) 0.2809, 0.2846 and 0.2807 on three of those seeds, 0.24 to 0.32 by
# layer; grouped products with both operands rounded to an 8-bit float (4
# exponent bits, 3 of mantissa) 0.65 to 0.68, and 0.22 to 0.23 of the largest
# logit against the runner's 0.03. The limit lies between the first two, five
# hundredths from either.
INSIDE_LIMIT = 0.23


def margin_of(system_scores, scores):
    """How far each of the system's scores lies from the reference's float32
    score of the same token and expert, **as measured** in the run that is
    compared: the system's scores are outputs of the same compiled forward
    as its routes and logits. Two things move a score, and the difference
    holds both: the system rounds the router's input and weight to bfloat16
    (a relative 2**-9 each: some 1.7e-3 of a logit's spread at width
    2688), and its input carries the bfloat16 rounding of every layer below
    (read on a v5e: two to four times that, growing with depth; a worst-case
    bound on either would put every token inside the margin, and a bound in
    standard deviations leaves one token-layer in thousands outside it by
    chance). A floor of one float32 rounding of a score keeps ties inside.
    Two scores farther apart than the sum of their margins cannot have
    changed places: the system's are then in the reference's order."""
    scores = np.asarray(scores, np.float64)
    moved = np.abs(np.asarray(system_scores, np.float64) - scores)
    return np.maximum(moved, 2.0 ** -23)


def route_conditions(own, scores, margins, system):
    """For one expert layer: which tokens' chosen sets differ from the
    reference's own, and which lie inside the margin at the cut (the lowest
    a chosen score can fall to is not above the highest another can rise
    to). All arrays are over the tokens, flat."""
    chosen = np.zeros(scores.shape, bool)
    np.put_along_axis(chosen, own, True, axis=-1)
    inside = (np.where(chosen, scores - margins, np.inf).min(-1)
              <= np.where(chosen, -np.inf, scores + margins).max(-1))
    differ = (np.sort(system, -1) != np.sort(own, -1)).any(-1)
    return differ, inside


def needed_margin(own, system, scores, margins):
    """Among the tokens whose chosen sets differ: the largest distance, in
    the reference's scores, between the reference's cut (its last chosen
    expert) and the lowest-scored expert the system chose in its place, and
    the largest such distance as a share of the two experts' margins. What
    the margin has to cover, for PERF.md."""
    cut = own[:, -1:]
    low = np.take_along_axis(system, np.argmin(np.take_along_axis(
        scores, system, -1), -1)[:, None], -1)
    gap = (np.take_along_axis(scores, cut, -1)
           - np.take_along_axis(scores, low, -1))[:, 0]
    room = (np.take_along_axis(margins, cut, -1)
            + np.take_along_axis(margins, low, -1))[:, 0]
    differ = (np.sort(system, -1) != np.sort(own, -1)).any(-1)
    if not differ.any():
        return {"gap": 0.0, "gap_over_margin": 0.0}
    return {"gap": float(gap[differ].max()),
            "gap_over_margin": float((gap[differ] / room[differ]).max())}


def compare(kept, trainer, args, x, y):
    """The system's logits against the reference's **at the system's
    routes**; the routes held to the reference's own wherever the float32
    scores lie farther apart than the system's scores were moved; and the
    rows the expert layers computed against the pairs their routes sent to
    the experts held."""
    from chipbench.runners import train

    params, cfg, samples = kept
    n = len(samples)
    outputs = train.system_outputs(trainer, args, x, y)
    logits = outputs[0][:n].astype(np.float32)
    layers = (len(outputs) - 2) // 2
    routes = outputs[1:1 + layers]
    system_scores = outputs[1 + layers:1 + 2 * layers]
    computed = outputs[-1]
    reference, own, scores = forward_at(
        params, cfg, samples, [r[:n] for r in routes])

    (first, held), width = experts_held(cfg)
    top = cfg["num_experts_per_tok"]
    tokens = outside = inside_count = 0
    per_layer = []
    for mine, theirs, s, moved in zip(own, routes, scores, system_scores):
        s = s.reshape(-1, width)
        mine = mine.reshape(-1, top)
        theirs = theirs[:n].reshape(-1, top)
        margins = margin_of(moved[:n].reshape(-1, width), s)
        differ, inside = route_conditions(mine, s, margins, theirs)
        tokens += differ.size
        outside += int(np.sum(differ & ~inside))
        inside_count += int(np.sum(inside))
        slope = s * (1.0 - s)
        per_layer.append({
            "differ": float(differ.mean()), "inside": float(inside.mean()),
            "differ_outside": int(np.sum(differ & ~inside)),
            "needed": needed_margin(mine, theirs, s, margins),
            # the scores' movement in units of the logits: root mean
            # square and largest
            "moved_rms": float(np.sqrt(np.mean((margins / slope) ** 2))),
            "moved_max": float(np.max(margins / slope))})

    # nothing dropped: every pair of the routes that names an expert held
    # here was computed, over the whole batch
    def pairs_held(chosen):
        local = chosen.reshape(-1).astype(np.int64) - first
        return np.bincount(local[(local >= 0) & (local < held)],
                           minlength=held)

    landed = np.stack([pairs_held(r) for r in routes])
    dropped = int(np.abs(landed - np.asarray(computed)).sum())
    print("chipbench: routes " + json.dumps(
        {"layers": per_layer, "landed": landed.sum(-1).tolist(),
         "computed": np.asarray(computed).sum(-1).tolist()},
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
                       "from the float32 reference's own although the "
                       "reference's scores at the cut lie farther apart "
                       "than the system's scores of that token were moved "
                       "by bfloat16 rounding of the router's input and "
                       "weight and by the error carried in from the layers "
                       "below, measured in the same forward (margin_of)"},
            "routes_inside_margin": {
                "value": inside_share, "limit": INSIDE_LIMIT,
                "ok": inside_share <= INSIDE_LIMIT,
                "why": "share of token-layers whose scores at the cut lie "
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
