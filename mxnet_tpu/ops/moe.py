"""Routed-expert ops for a layer that holds a share of the experts (expert
parallelism's layer on one of its chips). No reference analog: MXNet 1.x has
no expert layer; ``gluon.contrib.nn.RoutedExperts`` is the block over them.

- ``_contrib_moe_route``: scores over **all** the experts in float32
  (sigmoid of ``x W_r^T``, or its softmax over the experts), the ``top_k``
  largest of ``score + bias`` (the bias chooses and does not weigh), the
  chosen scores divided by their sum plus ``norm_eps`` and scaled. With
  ``n_group`` > 1 the choice is group-limited: the experts fall into
  ``n_group`` runs of equal length, a group's score is the largest of its
  experts', and only the experts of the ``topk_group`` best groups can be
  chosen. With ``capacity_factor`` > 0 the choice is dropped past this
  device's budget, as DeepSeek-V2 trains:
  of the pairs whose expert is one of the ``experts_held`` held here, the
  ``device_budget`` of largest score are kept and the others marked
  dropped (their id less E: no expert computes them, and E added gives
  the choice back). Returns the weights (..., k) in float32, the expert
  ids (..., k) in int32 and the scores (..., E) in float32.
- ``_contrib_moe_experts``: for the (token, expert) pairs whose expert is one
  of the ``H`` held here (ids ``first_expert .. first_expert + H - 1``): the
  pairs ordered by expert, their rows gathered, two grouped products with
  ``relu^2`` between (or, ``gated``, a first product of twice the width and
  ``silu(g) * u`` of its two halves), each row weighed and added back to its
  token.
  A pair whose expert lives elsewhere adds nothing. **Nothing is dropped**:
  shapes are static, so the rows are gathered into a buffer with room for
  every pair that can land here: ``capacity`` rows where the router has
  dropped the pairs past it, else two sizes, a quarter of the pairs and all
  of them, of which ``lax.cond`` runs the small one whenever the pairs
  really routed here fit it (a uniform router sends an eighth of the pairs
  to an eighth of the experts). The rows past the real count cost the
  buffer's gather and nothing in the products, whose kernel skips the
  tiles past the groups' end. Returns the partial
  result (..., U) in x's dtype and the rows of each held expert that were
  computed (H,) in int32: the rows routed to it, counted where the buffer
  is filled, so that a buffer too small would show.

The grouped product is the kernel tier's ``grouped_matmul``
(``pallas/kernels.py``: the library's megablox kernel on a TPU,
``lax.ragged_dot`` elsewhere). Gathering the rows and adding them back are
gathers in both directions (``custom_vjp``: the order is a permutation, so
the transpose of a gather is a gather through its inverse); everything else
is autodiff's.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..base import MXNetError, RECOMPUTE_KEEP
from .registry import OpParam, register

MOE_COUNT_METRIC = "mxnet_tpu_moe_layers_traced_total"

_F32 = jnp.float32

# rows of the gather buffer are a multiple of this, the grouped kernel's
# largest row tile
ROW_TILE = 512



def _count_traced_layer(experts, held, top_k, rows, grouped, expert):
    """One expert layer traced into a program: trace-time only, so a
    compiled step never counts. ``expert``: the form between the grouped
    products (``relu2``, ``swiglu``)."""
    from ..observability.metrics import default_registry
    default_registry().counter(
        MOE_COUNT_METRIC, "routed-expert layers traced into a program",
        ("experts", "held", "top_k", "rows", "grouped", "expert")).labels(
            experts=str(experts), held=str(held), top_k=str(top_k),
            rows=str(rows), grouped=grouped, expert=expert).inc()


def _group_limited(ranked, n_group, topk_group):
    """``ranked`` (..., E) with the experts outside the ``topk_group`` groups
    of largest maximum set to -inf: the first stage of a group-limited
    choice (groups of ``E / n_group`` consecutive experts; among equal
    maxima the lower group first, as ``lax.top_k`` orders them)."""
    grouped = ranked.reshape(ranked.shape[:-1] + (n_group, -1))
    _, best = lax.top_k(jnp.max(grouped, axis=-1), topk_group)
    kept = jnp.sum(jax.nn.one_hot(best, n_group, dtype=jnp.int32), -2) > 0
    return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
        ranked.shape)


def device_budget(tokens, top_k, held, experts, capacity_factor):
    """The pairs the ``held`` experts of one device may compute:
    ``capacity_factor`` times their share of the ``tokens * top_k``
    pairs, rounded up."""
    return min(math.ceil(capacity_factor * tokens * top_k * held / experts),
               tokens * top_k)


def _device_drop(ids, scores, first, held, capacity_factor):
    """``ids`` (T, k) with the pairs past the device's budget marked
    dropped (id less E): of the pairs whose expert is one of the ``held``
    from ``first`` on, those of largest score are kept (the lower pair
    first among equals, as ``lax.top_k`` orders them)."""
    experts = scores.shape[-1]
    tokens, k = ids.shape
    local = ids - first
    here = ((local >= 0) & (local < held)).reshape(-1)
    affinity = jnp.take_along_axis(scores, ids, axis=-1).reshape(-1)
    budget = device_budget(tokens, k, held, experts, capacity_factor)
    _, keep = lax.top_k(jnp.where(here, lax.stop_gradient(affinity),
                                  -jnp.inf), budget)
    kept = jnp.zeros(here.shape, bool).at[keep].set(True) & here
    return jnp.where(here & ~kept, ids.reshape(-1) - experts,
                     ids.reshape(-1)).reshape(ids.shape)


@register("_contrib_moe_route", num_inputs=3, num_outputs=3,
          params=[OpParam("top_k", int, 2),
                  OpParam("norm_topk_prob", bool, True),
                  OpParam("scaling_factor", float, 1.0),
                  OpParam("scoring", str, "sigmoid"),
                  OpParam("n_group", int, 1),
                  OpParam("topk_group", int, 1),
                  OpParam("capacity_factor", float, 0.0),
                  OpParam("first_expert", int, 0),
                  OpParam("experts_held", int, 0),
                  OpParam("norm_eps", float, 1e-20)],
          doc="Router of a routed-expert layer. Inputs: x (..., U), the "
              "router's weight (E, U), the bias of the choice (E,). Scores "
              "= sigmoid(x W^T) (scoring 'softmax': its softmax over the E "
              "experts) over all E experts in float32; chosen = the top_k "
              "largest of scores + bias, among the experts of the "
              "topk_group groups (n_group runs of E / n_group experts) "
              "whose largest scores + bias are largest when n_group > 1; "
              "weights = the chosen scores, divided by their sum plus "
              "norm_eps if norm_topk_prob, times scaling_factor. "
              "capacity_factor > 0: of the pairs naming the experts_held experts from "
              "first_expert on, those past capacity_factor times their "
              "share of the pairs, lowest score first, are dropped: their "
              "id less E. Returns (weights (..., k) float32, ids (..., k) "
              "int32, largest first, scores (..., E) float32).")
def _moe_route(x, router, bias, top_k=2, norm_topk_prob=True,
               scaling_factor=1.0, scoring="sigmoid", n_group=1,
               topk_group=1, capacity_factor=0.0, first_expert=0,
               experts_held=0, norm_eps=1e-20):
    if router.ndim != 2 or router.shape[1] != x.shape[-1]:
        raise MXNetError(f"moe_route: x (..., U) and router (E, U) "
                         f"expected, got {x.shape} and {router.shape}")
    if scoring not in ("sigmoid", "softmax"):
        raise MXNetError(f"moe_route: scoring {scoring!r}: 'sigmoid' or "
                         f"'softmax' expected")
    if router.shape[0] % n_group or not 0 < topk_group <= n_group:
        raise MXNetError(f"moe_route: {router.shape[0]} experts in "
                         f"n_group {n_group}, topk_group {topk_group}")
    logits = jnp.einsum("...u,eu->...e", x.astype(_F32), router.astype(_F32),
                        precision=lax.Precision.HIGHEST)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    ranked = scores + lax.stop_gradient(bias.astype(_F32))
    if n_group > 1:
        ranked = _group_limited(lax.stop_gradient(ranked), n_group,
                                topk_group)
    _, ids = lax.top_k(ranked, top_k)
    ids = ids.astype(jnp.int32)
    if capacity_factor > 0:
        ids = _device_drop(ids.reshape(-1, top_k),
                           scores.reshape(-1, scores.shape[-1]), first_expert,
                           experts_held, capacity_factor).reshape(ids.shape)
    # the choice is kept across HybridBlock.recompute(): scores computed again
    # in the backward pass may round otherwise and choose otherwise
    ids = checkpoint_name(ids, RECOMPUTE_KEEP)
    weights = jnp.take_along_axis(
        scores, jnp.mod(ids, scores.shape[-1]) if capacity_factor > 0
        else ids, axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + norm_eps)
    return weights * scaling_factor, ids, scores


def _by_slot(rows, at, valid, weights=None):
    """``out[t] = sum_j valid[t, j] * weights[t, j] * rows[at[t, j]]`` in
    float32, one gather of the tokens' rows a slot: on a v5e that is half the
    time of scattering the rows onto their tokens (PERF.md sec. 6, PR 32)."""
    out = jnp.zeros((at.shape[0], rows.shape[1]), _F32)
    for j in range(at.shape[1]):
        scale = valid[:, j].astype(_F32)
        if weights is not None:
            scale = scale * weights[:, j]
        out = out + rows[at[:, j]].astype(_F32) * scale[:, None]
    return out


# ``_gather_rows`` and ``_add_back`` are each other's transposes but for the
# weights; with ``order`` a permutation's head and ``at`` its inverse both
# directions are gathers, which autodiff of a gather (a scatter-add) is not.
@jax.custom_vjp
def _gather_rows(x, token, at, valid):
    """The buffer's rows: ``x[token]``."""
    return x[token]


def _gather_rows_fwd(x, token, at, valid):
    return x[token], (at, valid)


def _gather_rows_bwd(kept, g):
    at, valid = kept
    return _by_slot(g, at, valid).astype(g.dtype), None, None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@jax.custom_vjp
def _add_back(out, weights, order, here, at, valid):
    """``y[t] = sum_j valid[t, j] * weights[t, j] * out[at[t, j]]``: each
    buffer row weighed and added to its token, in float32."""
    return _by_slot(out, at, valid, weights)


def _add_back_fwd(out, weights, order, here, at, valid):
    return _by_slot(out, at, valid, weights), \
        (out, weights, order, here, at, valid)


def _add_back_bwd(kept, g):
    out, weights, order, here, at, valid = kept
    # in the buffer's order: each row's token and the weight of its pair
    per_row = g[order // weights.shape[-1]]             # (rows, U) float32
    weight = jnp.where(here, weights.reshape(-1)[order], 0.0)
    d_out = (per_row * weight[:, None]).astype(out.dtype)
    along = jnp.sum(per_row * out.astype(_F32), axis=-1)
    d_weights = jnp.where(valid, along[at], 0.0)
    return d_out, d_weights, None, None, None, None


_add_back.defvjp(_add_back_fwd, _add_back_bwd)


def _held_experts(x, weights, order, at, sizes, w1, w2, rows, gated=False):
    """The held experts' part of the result for tokens ``x`` (T, U), with a
    gather buffer of ``rows`` rows, which the caller has shown to be enough.
    ``weights`` (T, k); ``order`` (T * k,): the pairs in the order of their
    experts, those of no held expert last; ``at`` (T * k,): where each pair
    lies in that order; ``sizes`` (H,): the pairs of each held expert;
    ``gated``: ``silu(g) * u`` of the first product's two halves between the
    products, else ``relu(h)^2``."""
    from ..observability.instrument import device_scope
    from ..pallas import dispatch
    k = weights.shape[-1]
    with device_scope("moe.dispatch"):
        # the buffer takes the first `rows` pairs of the order
        landed = jnp.minimum(jnp.sum(sizes), rows)
        valid = (at < landed).reshape(-1, k)
        at = jnp.minimum(at, rows - 1).reshape(-1, k)
        order = order[:rows]
        if order.shape[0] < rows:       # a buffer rounded up to whole tiles
            order = jnp.pad(order, (0, rows - order.shape[0]))
        here = jnp.arange(rows) < landed
        gathered = _gather_rows(x, order // k, at, valid)
    with device_scope("moe.experts"):
        h = dispatch("grouped_matmul", gathered, w1, sizes)
        if gated:
            g, u = jnp.split(h.astype(_F32), 2, axis=-1)
            h = (jax.nn.silu(g) * u).astype(x.dtype)
        else:
            h = jnp.square(jax.nn.relu(h.astype(_F32))).astype(x.dtype)
        # rows past the real count are zero: the grouped product says so
        out = dispatch("grouped_matmul", h, w2, sizes)
    with device_scope("moe.combine"):
        y = _add_back(out, weights, order, here, at, valid)
    # the rows of each expert that lie inside the buffer: all of them,
    # while the buffer holds what the caller says it does
    ends = jnp.minimum(jnp.cumsum(sizes), rows)
    computed = jnp.diff(ends, prepend=0).astype(jnp.int32)
    return y.astype(x.dtype), computed


def pair_order(key):
    """``(order, at)`` of the pairs' keys (T * k,): the pairs in the order
    of their keys (stable), and where each pair lies in that order (the
    inverse permutation)."""
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    at = jnp.zeros(key.shape, jnp.int32).at[order].set(
        jnp.arange(key.shape[0], dtype=jnp.int32), unique_indices=True)
    return order, at


def _tiles(n):
    return -(-n // ROW_TILE) * ROW_TILE


def buffer_rows(pairs):
    """The two sizes of the gather buffer for ``pairs`` (token, expert)
    pairs: a quarter of them, and all of them, each rounded up to whole row
    tiles of the grouped kernel."""
    return _tiles(-(-pairs // 4)), _tiles(pairs)


@register("_contrib_moe_experts", num_inputs=5, num_outputs=2,
          params=[OpParam("first_expert", int, 0),
                  OpParam("num_experts", int, 0),
                  OpParam("gated", bool, False),
                  OpParam("capacity", int, 0)],
          doc="The held experts' part of a routed-expert layer. Inputs: x "
              "(..., U); the router's weights and ids (..., k); w1 (H, U, F) "
              "(gated: (H, U, 2F), the gate's columns first); w2 (H, F, U). "
              "The H experts held are first_expert .. first_expert + H - 1 "
              "of num_experts (0: as many as are held). Pairs whose expert "
              "is held are ordered by expert, gathered, put through w2 "
              "relu(w1 x)^2 (gated: w2 (silu(g) * u), [g | u] = w1 x) as two "
              "grouped products, weighed and added back "
              "to their tokens; other pairs add nothing; no pair is "
              "dropped (capacity > 0: the router has dropped all but that "
              "many, and the buffer has that many rows; else it holds a "
              "quarter of the pairs where they fit, else all of them). "
              "Returns (y (..., U) in x's dtype, rows (H,) "
              "int32: the rows of each held expert that were computed).")
def _moe_experts(x, weights, ids, w1, w2, first_expert=0, num_experts=0,
                 gated=False, capacity=0):
    held = w1.shape[0]
    width = w1.shape[2] // 2 if gated else w1.shape[2]
    if (w1.ndim != 3 or w2.ndim != 3 or w1.shape[1] != x.shape[-1]
            or w2.shape != (held, width, x.shape[-1])
            or gated and w1.shape[2] % 2
            or ids.shape != weights.shape
            or ids.shape[:-1] != x.shape[:-1]):
        raise MXNetError(
            f"moe_experts: x (..., U), weights and ids (..., k), w1 (H, U, "
            f"{'2F' if gated else 'F'}) and w2 (H, F, U) expected, got "
            f"{x.shape}, {weights.shape}, {ids.shape}, {w1.shape}, "
            f"{w2.shape}")
    units, k = x.shape[-1], ids.shape[-1]
    flat = x.reshape(-1, units)
    pairs = flat.shape[0] * k
    local = ids.reshape(-1).astype(jnp.int32) - first_expert
    key = jnp.where((local >= 0) & (local < held), local, held)
    small, full = buffer_rows(pairs)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    from ..observability.instrument import device_scope
    with device_scope("moe.dispatch"):
        order, at = pair_order(key)
    operands = (flat, weights.reshape(-1, k).astype(_F32), order, at, sizes,
                w1, w2)
    from ..pallas import tier_provenance
    before = tier_provenance().get("grouped_matmul", {}).get("pallas", 0)
    if capacity:
        full = _tiles(capacity)
        y, computed = _held_experts(*operands, rows=full, gated=gated)
    elif small < full:
        y, computed = lax.cond(
            jnp.sum(sizes) <= small,
            functools.partial(_held_experts, rows=small, gated=gated),
            functools.partial(_held_experts, rows=full, gated=gated),
            *operands)
    else:
        y, computed = _held_experts(*operands, rows=full, gated=gated)
    if isinstance(x, jax.core.Tracer):
        took_kernel = tier_provenance().get("grouped_matmul", {}).get(
            "pallas", 0) > before
        _count_traced_layer(num_experts or held, held, k, full,
                            "megablox_gmm" if took_kernel else "ragged_dot",
                            "swiglu" if gated else "relu2")
    return y.reshape(x.shape), computed
