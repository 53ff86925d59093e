"""In-program guard math — every function here composes under jit/pjit.

The defense against bad numerics has to live *inside* the compiled step:
a host-side ``np.isfinite`` over pulled gradients costs a device→host
round trip per step (a blocking sync that idles the chip), and on multi-host
an early return taken by one rank while its peers enter the gradient
all-reduce hangs the collective. Everything in this module is therefore
expressed as traced jnp ops:

- :func:`guard_stats` folds ONE squared-sum reduction over every
  gradient leaf into the step. The sum serves double duty: its square
  root is the global gradient norm (so global-norm clipping costs no
  second pass — :func:`clip_scale`), and a NaN/Inf anywhere in any leaf
  poisons the sum, so ``isfinite(sum)`` is the fused non-finite flag.
  Under GSPMD the gradients the update sees are already psum-reduced
  across the mesh, which makes the flag *globally agreed by
  construction*: a NaN on one shard poisons the reduction on every
  rank, and no rank can branch out of a collective because the skip is
  data-flow (:func:`select`), not control flow.
- :func:`select` realizes skip-step semantics under jit: the updated
  and previous values both exist in-program, and a ``jnp.where`` on the
  flag picks per leaf — a skipped step is bit-identical to not having
  run the optimizer at all (params, optimizer state, AND auxiliary
  state such as BatchNorm running stats).
- guard *state* (total skips, consecutive skips) is carried through the
  step as two traced i32 scalars (:func:`init_guard_state` /
  :func:`update_guard_state`) so counting skips costs zero extra host
  reads — ``lax.scan`` multi-step programs thread it for free.
- :func:`host_fetch` is the ONE sanctioned device→host read for guard
  values: a single ``jax.device_get`` of already-computed step outputs,
  never a mid-step sync. graftlint G9 flags ad-hoc host finiteness
  checks in training modules and points here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["clip_scale", "guard_stats", "host_fetch", "init_guard_state",
           "select", "update_guard_state"]


def guard_stats(grads, loss=None):
    """One fused reduction over every gradient leaf.

    Returns ``(finite, global_norm)``: a traced bool scalar that is True
    iff every element of every leaf (and ``loss``, when given) is
    finite, and the fp32 global L2 norm. The norm's squared-sum is the
    finiteness evidence — NaN/Inf propagate through the sum — so the
    guard costs exactly one all-reduce, shared with clipping.

    A finite gradient whose *square* overflows fp32 (elements beyond
    ~1.8e19) also reads as non-finite; a step with a 1e19 gradient norm
    is divergence by any definition, so the false positive is the right
    answer.
    """
    total = jnp.zeros((), jnp.float32)
    for g in jax.tree_util.tree_leaves(grads):
        g32 = jnp.asarray(g).astype(jnp.float32)
        total = total + jnp.sum(g32 * g32)
    finite = jnp.isfinite(total)
    if loss is not None:
        finite = jnp.logical_and(
            finite, jnp.isfinite(jnp.asarray(loss).astype(jnp.float32)))
    return finite, jnp.sqrt(total)


def clip_scale(global_norm, clip_norm, eps=1e-8):
    """Global-norm clip factor ``min(1, clip/(norm+eps))`` from the
    guard's already-computed norm (no second reduction pass). A
    non-finite norm yields 1.0 — the skip path owns that case, and
    scaling garbage by a NaN factor would only launder it."""
    s = jnp.minimum(clip_norm / (global_norm + eps), 1.0)
    return jnp.where(jnp.isfinite(global_norm), s, jnp.float32(1.0))


def select(finite, new, old):
    """Skip-step selection: per-leaf ``where(finite, new, old)`` over two
    matching pytrees. Works under jit/pjit/scan — the skip is data flow,
    so every rank of a collective program takes the same path."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(finite, a, b), new, old)


def init_guard_state():
    """Fresh in-program guard counters: (total_skips, consecutive_skips)
    as replicated i32 scalars."""
    return (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))


def update_guard_state(gstate, finite):
    """Fold one step's flag into the carried counters (traced)."""
    skips, consec = gstate
    bad = jnp.where(finite, 0, 1).astype(jnp.int32)
    return (skips + bad,
            jnp.where(finite, 0, consec + 1).astype(jnp.int32))


def host_fetch(*vals):
    """THE sanctioned device→host fetch for guard values.

    One ``jax.device_get`` over all requested scalars/arrays (a single
    transfer of already-materialized step outputs, never a mid-program
    sync), returned as plain Python scalars — scalar ndarrays are
    ``.item()``-ed so callers never need their own ``float()``/``bool()``
    host syncs (which graftlint G9 would rightly flag)."""
    out = []
    for v in jax.device_get(vals):
        a = np.asarray(v)
        out.append(a.item() if a.ndim == 0 else a)
    return out
