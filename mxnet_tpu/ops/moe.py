"""Routed-expert ops for a layer that holds a share of the experts (expert
parallelism's layer on one of its chips). No reference analog: MXNet 1.x has
no expert layer; ``gluon.contrib.nn.RoutedExperts`` is the block over them.

- ``_contrib_moe_route``: scores over **all** the experts in float32
  (sigmoid of ``x W_r^T``), the ``top_k`` largest of ``score +
  bias`` (the bias chooses and does not weigh), the chosen scores
  normalised and scaled. Returns the weights (..., k) in float32, the
  expert ids (..., k) in int32 and the scores (..., E) in float32.
- ``_contrib_moe_experts``: for the (token, expert) pairs whose expert is one
  of the ``H`` held here (ids ``first_expert .. first_expert + H - 1``): the
  pairs ordered by expert, their rows gathered, two grouped products with
  ``relu^2`` between, each row weighed and added back to its token.
  A pair whose expert lives elsewhere adds nothing. **Nothing is dropped**:
  shapes are static, so the rows are gathered into a buffer with room for
  every pair that can land here. Two sizes of that buffer are compiled, a
  quarter of the pairs and all of them, and ``lax.cond`` runs the small one
  whenever the pairs really routed here fit it (a uniform router sends an
  eighth of the pairs to an eighth of the experts), so the rows past the real
  count cost a quarter-size buffer's gather and nothing in the products,
  whose kernel skips the tiles past the groups' end. Returns the partial
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


def _count_traced_layer(experts, held, top_k, rows, grouped):
    """One expert layer traced into a program: trace-time only, so a
    compiled step never counts."""
    from ..observability.metrics import default_registry
    default_registry().counter(
        MOE_COUNT_METRIC, "routed-expert layers traced into a program",
        ("experts", "held", "top_k", "rows", "grouped")).labels(
            experts=str(experts), held=str(held), top_k=str(top_k),
            rows=str(rows), grouped=grouped).inc()


@register("_contrib_moe_route", num_inputs=3, num_outputs=3,
          params=[OpParam("top_k", int, 2),
                  OpParam("norm_topk_prob", bool, True),
                  OpParam("scaling_factor", float, 1.0)],
          doc="Router of a routed-expert layer. Inputs: x (..., U), the "
              "router's weight (E, U), the bias of the choice (E,). Scores "
              "= sigmoid(x W^T) over all E experts in float32; chosen = "
              "the top_k largest of scores + bias; weights = the chosen "
              "scores, divided by their sum (+1e-20) if norm_topk_prob, "
              "times scaling_factor. Returns (weights (..., k) float32, "
              "ids (..., k) int32, largest first, scores (..., E) "
              "float32).")
def _moe_route(x, router, bias, top_k=2, norm_topk_prob=True,
               scaling_factor=1.0):
    if router.ndim != 2 or router.shape[1] != x.shape[-1]:
        raise MXNetError(f"moe_route: x (..., U) and router (E, U) "
                         f"expected, got {x.shape} and {router.shape}")
    logits = jnp.einsum("...u,eu->...e", x.astype(_F32), router.astype(_F32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = lax.top_k(scores + lax.stop_gradient(bias.astype(_F32)), top_k)
    # the choice is kept across HybridBlock.recompute(): scores computed again
    # in the backward pass may round otherwise and choose otherwise
    ids = checkpoint_name(ids.astype(jnp.int32), RECOMPUTE_KEEP)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
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


def _held_experts(x, weights, order, at, sizes, w1, w2, rows):
    """The held experts' part of the result for tokens ``x`` (T, U), with a
    gather buffer of ``rows`` rows, which the caller has shown to be enough.
    ``weights`` (T, k); ``order`` (T * k,): the pairs in the order of their
    experts, those of no held expert last; ``at`` (T * k,): where each pair
    lies in that order; ``sizes`` (H,): the pairs of each held expert."""
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


def buffer_rows(pairs):
    """The two sizes of the gather buffer for ``pairs`` (token, expert)
    pairs: a quarter of them, and all of them, each rounded up to whole row
    tiles of the grouped kernel."""
    def tiles(n):
        return -(-n // ROW_TILE) * ROW_TILE
    return tiles(-(-pairs // 4)), tiles(pairs)


@register("_contrib_moe_experts", num_inputs=5, num_outputs=2,
          params=[OpParam("first_expert", int, 0),
                  OpParam("num_experts", int, 0)],
          doc="The held experts' part of a routed-expert layer. Inputs: x "
              "(..., U); the router's weights and ids (..., k); w1 (H, U, F); "
              "w2 (H, F, U). The H experts held are first_expert .. "
              "first_expert + H - 1 of num_experts (0: as many as are "
              "held). Pairs whose expert is held are ordered by expert, "
              "gathered, put through w2 relu(w1 x)^2 as two grouped "
              "products, weighed and added back "
              "to their tokens; other pairs add nothing; no pair is "
              "dropped. Returns (y (..., U) in x's dtype, rows (H,) int32: "
              "the rows of each held expert that were computed).")
def _moe_experts(x, weights, ids, w1, w2, first_expert=0, num_experts=0):
    held = w1.shape[0]
    if (w1.ndim != 3 or w2.ndim != 3 or w1.shape[1] != x.shape[-1]
            or w2.shape != (held, w1.shape[2], x.shape[-1])
            or ids.shape != weights.shape
            or ids.shape[:-1] != x.shape[:-1]):
        raise MXNetError(
            f"moe_experts: x (..., U), weights and ids (..., k), w1 (H, U, "
            f"F) and w2 (H, F, U) expected, got "
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
    if small < full:
        y, computed = lax.cond(jnp.sum(sizes) <= small,
                               functools.partial(_held_experts, rows=small),
                               functools.partial(_held_experts, rows=full),
                               *operands)
    else:
        y, computed = _held_experts(*operands, rows=full)
    if isinstance(x, jax.core.Tracer):
        took_kernel = tier_provenance().get("grouped_matmul", {}).get(
            "pallas", 0) > before
        _count_traced_layer(num_experts or held, held, k, full,
                            "megablox_gmm" if took_kernel else "ragged_dot")
    return y.reshape(x.shape), computed
