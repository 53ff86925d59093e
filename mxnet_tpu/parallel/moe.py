"""Mixture-of-Experts with expert parallelism over an ``expert`` mesh axis
(net-new capability: MXNet 1.x has no MoE dispatch — SURVEY §2.4 #32).

Two formulations behind one axis convention:

- ``moe_apply`` — dense dispatch: every device computes its expert over
  the FULL token batch, masked by the gate, combined with one ``psum``.
  O(E·tokens) compute; robust at tiny expert counts and kept as the
  parity oracle.
- ``moe_apply_topk`` — the real path (GShard/Switch shape): tokens are
  sharded over the ``expert`` axis, routed top-k with a capacity factor,
  dispatched to their experts with ``lax.all_to_all`` over ICI, computed
  at O(k·tokens/E) per device, returned with a second all-to-all, and
  combined with normalized gate weights. Dispatch/combine are one-hot
  einsums — MXU work, not gathers — and overflow tokens beyond each
  expert's capacity are dropped (zero output), with the drop fraction
  and the Switch load-balancing auxiliary loss returned for training.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..base import MXNetError

__all__ = ["moe_apply", "moe_apply_topk"]


def moe_apply(expert_fn, expert_params, gate_logits, x, mesh: Mesh = None,
              axis_name="expert"):
    """Top-1-routed mixture of experts.

    expert_fn(params_e, x) -> y       same signature for every expert
    expert_params: pytree with leaves stacked (E, ...), sharded over
        ``axis_name``
    gate_logits: (B, E) router scores (a Dense over x, computed outside)
    x: (B, D) tokens.

    Returns (B, D_out): each token processed by its argmax expert, scaled
    by the (differentiable) gate probability — Switch-transformer routing.
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    e_size = mesh.shape[axis_name]
    if gate_logits.shape[-1] != e_size:
        raise MXNetError(f"gate width {gate_logits.shape[-1]} != expert "
                         f"axis size {e_size}")
    param_spec = jax.tree_util.tree_map(lambda _: P(axis_name),
                                        expert_params)

    def body(params_local, gates, xs):
        e = lax.axis_index(axis_name)
        params_e = jax.tree_util.tree_map(lambda a: a[0], params_local)
        probs = jax.nn.softmax(gates, axis=-1)            # (B, E)
        top = jnp.argmax(probs, axis=-1)                  # (B,)
        weight = jnp.where(top == e, probs[:, e], 0.0)    # (B,)
        y = expert_fn(params_e, xs)                       # (B, D_out)
        y = y * weight[:, None].astype(y.dtype)
        return lax.psum(y, axis_name)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(param_spec, P(), P()),
                   out_specs=P())
    return fn(expert_params, gate_logits, x)


def moe_apply_topk(expert_fn, expert_params, gate_logits, x, k=2,
                   capacity_factor=1.25, mesh: Mesh = None,
                   axis_name="expert"):
    """Top-k routed MoE with all-to-all token dispatch (GShard/Switch).

    Tokens arrive sharded over ``axis_name``: ``x`` is the GLOBAL (B, D)
    batch, B divisible by the axis size E; device e owns rows
    [e*B/E, (e+1)*B/E). Each device routes its local tokens, exchanges
    them with two ``lax.all_to_all``s, and runs ONLY its own expert over
    at most k*B_local*capacity_factor tokens — per-device compute scales
    O(k·tokens/E), the property the dense formulation lacks.

    expert_fn(params_e, tokens) -> out      tokens (N, D) -> (N, D_out)
    expert_params: pytree, leaves stacked (E, ...), sharded over the axis
    gate_logits: (B, E) router scores
    k: experts per token (top-k gate probs, renormalized when k > 1)
    capacity_factor: each expert accepts ceil(k*B/E*cf) tokens; overflow
        tokens are dropped (zero contribution), first-choice slots fill
        before second-choice ones like GShard.

    Returns (y, aux_loss, stats):
      y        (B, D_out) — combined expert outputs (dropped tokens: 0)
      aux_loss scalar — E * Σ_e load_e · mean_prob_e (Switch §2.2);
               load counts all k choices, so perfect balance gives k
               (1.0 for top-1, 2.0 for the default top-2); add
               ~0.01·aux_loss to the loss
      stats    dict: 'dropped' — global fraction of (token, slot) pairs
               that overflowed capacity
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    e_size = int(mesh.shape[axis_name])
    b_global, _ = x.shape
    if gate_logits.shape[-1] != e_size:
        raise MXNetError(f"gate width {gate_logits.shape[-1]} != expert "
                         f"axis size {e_size}")
    if b_global % e_size:
        raise MXNetError(f"batch {b_global} not divisible by expert axis "
                         f"{e_size}")
    b_local = b_global // e_size
    k = int(min(k, e_size))
    capacity = max(1, math.ceil(k * b_local * capacity_factor / e_size))
    param_spec = jax.tree_util.tree_map(lambda _: P(axis_name),
                                        expert_params)

    def body(params_local, gates, xs):
        # gates/xs are the LOCAL (B_l, ...) shards
        params_e = jax.tree_util.tree_map(lambda a: a[0], params_local)
        probs = jax.nn.softmax(gates.astype(jnp.float32), axis=-1)
        top_p, top_e = lax.top_k(probs, k)               # (B_l, k)
        if k > 1:
            top_p = top_p / jnp.maximum(
                top_p.sum(-1, keepdims=True), 1e-9)

        # capacity assignment, slot-major so every token's FIRST choice
        # claims buffer space before any second choice (GShard §3.2)
        flat_e = top_e.T.reshape(-1)                     # (k*B_l,)
        onehot = jax.nn.one_hot(flat_e, e_size,
                                dtype=jnp.float32)       # (kB, E)
        pos = jnp.cumsum(onehot, axis=0) * onehot        # 1-based slot
        pos = pos.sum(-1) - 1.0                          # (kB,)
        keep = (pos < capacity).astype(jnp.float32)
        pos_c = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)

        # dispatch mask (B_l, E, C) via one-hot products (MXU einsums)
        slot_oh = jax.nn.one_hot(pos_c, capacity,
                                 dtype=jnp.float32)      # (kB, C)
        mask = (onehot * keep[:, None])[:, :, None] * slot_oh[:, None, :]
        mask = mask.reshape(k, b_local, e_size, capacity)
        dispatch = mask.sum(0)                           # (B_l, E, C)
        gate_w = top_p.T.reshape(k, b_local, 1, 1)
        combine = (mask * gate_w).sum(0)                 # (B_l, E, C)

        # route tokens out: (E, C, D) then all-to-all over the axis so
        # device e ends up with every peer's C-token buffer for expert e
        x_disp = jnp.einsum("bec,bd->ecd", dispatch,
                            xs.astype(jnp.float32)).astype(xs.dtype)
        x_recv = lax.all_to_all(x_disp, axis_name, split_axis=0,
                                concat_axis=0, tiled=True)   # (E, C, D)
        y_loc = expert_fn(params_e,
                          x_recv.reshape(e_size * capacity, -1))
        y_loc = y_loc.reshape(e_size, capacity, -1)
        y_ret = lax.all_to_all(y_loc, axis_name, split_axis=0,
                               concat_axis=0, tiled=True)    # (E, C, Do)
        y = jnp.einsum("bec,ecd->bd", combine,
                       y_ret.astype(jnp.float32)).astype(x.dtype)

        # Switch load-balancing loss over the GLOBAL batch
        load = psum_mean(onehot.reshape(k, b_local, e_size).sum(0),
                         axis_name)                      # mean over B
        importance = psum_mean(probs, axis_name)
        aux = e_size * jnp.sum(load * importance)
        # keep already ranges over all k*B_local (token, slot) pairs, so
        # its global mean IS the kept fraction
        dropped = 1.0 - psum_mean(keep[:, None], axis_name).sum()
        return y, aux, dropped

    def psum_mean(v, ax):
        return lax.psum(v.mean(axis=0), ax) / e_size

    fn = shard_map(body, mesh=mesh,
                   in_specs=(param_spec, P(axis_name), P(axis_name)),
                   out_specs=(P(axis_name), P(), P()))
    y, aux, dropped = fn(expert_params, gate_logits, x)
    return y, aux, {"dropped": dropped}
