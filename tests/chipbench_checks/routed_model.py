"""A routed toy of this directory's own, for rehearsing on the CPU what a
configuration's ``compare`` is for: two layers, the hidden one a
top-2-of-8 routed MLP, the routes a second output of the net. Added as
files, with no edit to ``chipbench/``. Never a cell.

``h = sum over the two chosen experts e of gate_e * relu(x @ w1[e] + b1[e])``
with ``scores = x @ router.T``, the two largest chosen, their gates a softmax
over the two; ``logits = h @ w2.T + b2``. Scores are made close on purpose:
the router's second row is its first plus ``args["router_twin"]`` (1e-4) of
noise, so in bfloat16 the two rows are one and the system takes the first of
the pair where the cut falls between them, while the float32 reference takes
whichever the noise favours: about one token in fourteen lands on another
expert, and both are right.
"""
from __future__ import annotations

import numpy as np

EXPERTS, CHOSEN = 8, 2
# Rounding of one bfloat16 value: half a unit in the last of 8 bits.
BF16 = 2.0 ** -9
# The share of tokens that may lie inside the margin. At this toy's sizes a
# fifth to a quarter do (a seventh between the twin rows, a tenth by chance);
# were it nearly all of them, the condition on the tokens outside the margin
# would hold of nothing.
INSIDE_LIMIT = 0.4


def build(args, mesh, seed):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, parallel

    class RoutedToy(gluon.HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            f, h = args["features"], args["hidden"]
            with self.name_scope():
                self.router = self.params.get("router", shape=(EXPERTS, f))
                self.w1 = self.params.get("w1", shape=(EXPERTS, f, h))
                self.b1 = self.params.get("b1", shape=(EXPERTS, h),
                                          init="zeros")
                self.head = gluon.nn.Dense(args["classes"], in_units=h)

        def hybrid_forward(self, F, x, router, w1, b1):
            import jax
            import jax.numpy as jnp
            xd, r, w, b = (a._data for a in (x, router, w1, b1))
            scores = xd @ r.T                               # (N, E)
            best, routes = jax.lax.top_k(scores, CHOSEN)
            gates = jax.nn.softmax(best.astype(jnp.float32), axis=-1)
            hidden = jax.nn.relu(
                jnp.einsum("nf,nkfh->nkh", xd, w[routes]) + b[routes])
            mixed = jnp.einsum("nk,nkh->nh", gates.astype(xd.dtype), hidden)
            return (self.head(nd.NDArray(mixed, _skip_device_put=True)),
                    nd.NDArray(routes.astype(jnp.int32),
                               _skip_device_put=True))

    class FirstOutputLoss(gluon.loss.SoftmaxCrossEntropyLoss):
        """The trainer hands a loss the list when a net has several
        outputs: the logits are the first."""

        def hybrid_forward(self, F, outputs, label, sample_weight=None):
            return super().hybrid_forward(F, outputs[0], label,
                                          sample_weight)

    mx.random.seed(seed % (2 ** 31 - 1))
    net = RoutedToy()
    net.initialize(mx.init.Normal(0.5))
    rng = np.random.default_rng(seed)
    rows = rng.standard_normal((EXPERTS, args["features"]))
    rows[1] = rows[0] + args["router_twin"] * rows[1]
    net.router.set_data(nd.array(rows.astype(np.float32)))
    trainer = parallel.ShardedTrainer(
        net, FirstOutputLoss(), "sgd",
        {"learning_rate": args["learning_rate"]}, mesh=mesh,
        compute_dtype=args["compute_dtype"],
        master_dtype=args["compute_dtype"])
    return net, trainer


def make_batch(args, traffic, batch, rng):
    x = rng.standard_normal((batch, args["features"]), dtype=np.float32)
    return x, rng.integers(0, args["classes"], (batch,))


def flops_per_sample(args, traffic):
    f, h = args["features"], args["hidden"]
    return 3 * 2 * (f * EXPERTS + CHOSEN * f * h + h * args["classes"])


def float32_parameters(net):
    return {name: p.data().asnumpy().astype(np.float32) for name, p in (
        ("router", net.router), ("w1", net.w1), ("b1", net.b1),
        ("w2", net.head.weight), ("b2", net.head.bias))}


def scores_of(params, x):
    return np.asarray(x, np.float32) @ params["router"].T


def choose(scores):
    """The ``CHOSEN`` largest of each row, largest first, as ``top_k``
    orders them."""
    return np.argsort(-scores, axis=-1, kind="stable")[:, :CHOSEN]


def logits_at(params, x, routes):
    """The forward pass in float32 with the experts given."""
    x = np.asarray(x, np.float32)
    best = np.take_along_axis(scores_of(params, x), routes, axis=-1)
    gates = np.exp(best - best.max(axis=-1, keepdims=True))
    gates /= gates.sum(axis=-1, keepdims=True)
    hidden = np.maximum(
        np.einsum("nf,nkfh->nkh", x, params["w1"][routes])
        + params["b1"][routes], 0.0)
    return np.einsum("nk,nkh->nh", gates, hidden) @ params["w2"].T \
        + params["b2"]


def reference_logits(net, x):
    """The plain check's reference: float32 throughout, its own routes."""
    params = float32_parameters(net)
    return logits_at(params, x, choose(scores_of(params, x)))


def reference_kept(net, x):
    """What ``compare`` needs from before the cast: the float32 parameters
    and the samples."""
    return float32_parameters(net), np.asarray(x, np.float32)


def margin_of(params, x):
    """How far bfloat16 can move each score: the system rounds ``x`` and the
    router to bfloat16 (a relative ``BF16`` each, so ``2 * BF16`` a
    product, summed in float32) and the score once more. Two scores closer
    than the sum of their margins may change places, no others."""
    x = np.asarray(x, np.float32)
    products = np.abs(x) @ np.abs(params["router"]).T
    return 2 * BF16 * products + BF16 * np.abs(scores_of(params, x))


def compare(kept, trainer, args, x, y):
    """The system's logits against the reference's **at the system's
    routes**, and the routes held to the reference's own: equal as sets
    wherever the float32 scores leave no doubt."""
    from chipbench.runners import train

    params, samples = kept
    logits, routes = train.system_outputs(trainer, args, x, y,
                                          rows=len(samples))
    logits = logits.astype(np.float32)
    reference = logits_at(params, samples, routes)

    scores = scores_of(params, samples)
    own = choose(scores)
    margin = margin_of(params, samples)
    chosen = np.zeros(scores.shape, bool)
    np.put_along_axis(chosen, own, True, axis=-1)
    # the lowest a chosen score can fall, the highest another can rise
    inside = (np.where(chosen, scores - margin, np.inf).min(axis=-1)
              <= np.where(chosen, -np.inf, scores + margin).max(axis=-1))
    differ = np.sort(routes, axis=-1) != np.sort(own, axis=-1)
    differ = differ.any(axis=-1)
    outside = float(np.mean(differ & ~inside))
    inside = float(np.mean(inside))
    return {
        "samples": len(samples), "compared": logits.size,
        "max_abs_error": np.max(np.abs(logits - reference)),
        "max_abs_reference": np.max(np.abs(reference)),
        "conditions": {
            "routes_differ_outside_margin": {
                "value": outside, "limit": 0.0, "ok": outside == 0.0,
                "why": "share of tokens whose chosen experts differ from "
                       "the float32 reference's although the scores at the "
                       "cut lie farther apart than bfloat16 can move them"},
            "routes_inside_margin": {
                "value": inside, "limit": INSIDE_LIMIT,
                "ok": inside <= INSIDE_LIMIT,
                "why": "share of tokens whose scores at the cut lie inside "
                       "the margin, of which the first condition says "
                       "nothing"},
        },
    }
