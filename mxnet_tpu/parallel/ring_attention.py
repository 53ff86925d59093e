"""Ring attention & blockwise (flash-style) attention — long-context
sequence/context parallelism, a net-new TPU capability (SURVEY §5.7: the
reference's longest-sequence story was BucketingModule padding; ring/Ulysses
postdate MXNet 1.x but are first-class here per the task spec).

Design:

- ``blockwise_attention``: single-device memory-efficient attention; online
  softmax over key/value blocks via ``lax.scan`` with rematerialized blocks
  (``jax.checkpoint``), so sequence length is bounded by HBM not VMEM.
- ``ring_attention``: the same online-softmax accumulation where key/value
  blocks live sharded over the ``seq`` mesh axis and rotate around the ICI
  ring via ``lax.ppermute`` (one neighbor hop per step — bandwidth-optimal,
  compute overlaps the permute under XLA's latency-hiding scheduler). Runs
  under ``shard_map``; differentiable end-to-end (ppermute transposes to the
  reverse permute).

Both support causal masking with *global* positions, so causal LM training
shards cleanly over the sequence axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError


__all__ = ["blockwise_attention", "ring_attention",
           "ulysses_attention", "attention_reference"]

_NEG = -1e30


def attention_reference(q, k, v, causal=False, scale=None):
    """Plain softmax(QK^T)V — the correctness oracle (the reference's
    full-attention BERT path, SURVEY §5.7) AND the production short-KV
    path of ops.contrib flash_attention (one definition, one mask
    convention). Causal masking is bottom-right aligned (query i attends
    keys j <= i + s_kv - s_q — the decode-cache convention); softmax row
    sums accumulate in fp32 via the shared shifted_expsum core, so bf16
    inputs never materialize an fp32 score tensor. Rows whose allowed-key
    set is empty (causal with s_q > s_kv) yield zeros."""
    from ..ops.tensor import shifted_expsum
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    scores = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    mask = None
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    _, shifted, se32 = shifted_expsum(scores, axis=-1)
    w = (jnp.exp(shifted).astype(jnp.float32) / se32).astype(q.dtype)
    if mask is not None:
        w = w * mask.any(-1, keepdims=True).astype(w.dtype)
    return jnp.einsum("...qk,...kd->...qd", w, v)


def _online_block(carry, q, k_blk, v_blk, scale, mask=None):
    """One online-softmax accumulation step (the flash-attention update)."""
    o, l, m = carry
    scores = jnp.einsum("...qd,...kd->...qk", q, k_blk) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum("...qk,...kd->...qd",
                                              p, v_blk.astype(p.dtype))
    return o_new, l_new, m_new


def blockwise_attention(q, k, v, block_size=512, causal=False, scale=None):
    """Memory-efficient attention over KV blocks (inputs [..., S, D]).

    Routed through the ``mxnet_tpu.pallas`` kernel registry: the online-
    softmax kernel is the custom tier (parity-gated against
    ``attention_reference`` by tests/test_pallas.py), so it shares the
    tier's kill-switch (``MXNET_TPU_PALLAS=off`` falls back to the dense
    reference), journaled-fallback, and provenance story with every other
    hand kernel."""
    from ..pallas import dispatch
    return dispatch("blockwise_attention", q, k, v, block_size=block_size,
                    causal=causal, scale=scale)


def _blockwise_impl(q, k, v, block_size=512, causal=False, scale=None):
    """The kernel body (dispatch target — call blockwise_attention)."""
    d = q.shape[-1]
    s_k = k.shape[-2]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    block_size = min(block_size, s_k)
    while s_k % block_size:        # shrink to the nearest divisor so any
        block_size -= 1            # sequence length works (block size is a
    n_blocks = s_k // block_size   # perf knob, not a correctness contract)
    kb = jnp.moveaxis(k.reshape(k.shape[:-2] + (n_blocks, block_size, d)),
                      -3, 0)
    vb = jnp.moveaxis(v.reshape(v.shape[:-2] + (n_blocks, block_size,
                                                v.shape[-1])), -3, 0)
    s_q = q.shape[-2]
    # derive accumulators from q so their device-varying type matches under
    # shard_map (a plain zeros constant is 'unvarying' and scan rejects the
    # carry mismatch); the output's rows are as wide as v's
    zero_like_q = (q * 0).astype(jnp.float32)
    o0 = zero_like_q if v.shape[-1] == d else \
        zero_like_q[..., :1] * jnp.zeros(v.shape[-1], jnp.float32)
    l0 = zero_like_q[..., 0]
    m0 = zero_like_q[..., 0] + _NEG
    q_pos = jnp.arange(s_q)

    @jax.checkpoint
    def step(carry, inputs):
        blk_idx, k_blk, v_blk = inputs
        mask = None
        if causal:
            # bottom-right aligned, matching attention_reference and the
            # short-KV path: query i attends keys j <= i + (s_k - s_q)
            k_pos = blk_idx * block_size + jnp.arange(block_size)
            mask = q_pos[:, None] + (s_k - s_q) >= k_pos[None, :]
            mask = jnp.broadcast_to(mask, carry[0].shape[:-1]
                                    + (block_size,))
        new = _online_block(carry, q.astype(jnp.float32),
                            k_blk.astype(jnp.float32), v_blk, scale, mask)
        return new, None

    (o, l, m), _ = lax.scan(step, (o0, l0, m0),
                            (jnp.arange(n_blocks), kb, vb))
    out = (o / l[..., None]).astype(q.dtype)
    if causal and s_q > s_k:
        # bottom-right alignment leaves queries i < s_q - s_k with an
        # empty allowed-key set; zero them like attention_reference does
        # (an all-masked row otherwise softmaxes uniformly over _NEG)
        valid = (jnp.arange(s_q) + (s_k - s_q) >= 0)
        out = out * valid[:, None].astype(out.dtype)
    return out


def _ring_body(q, k, v, axis_name, causal, scale, f32=jnp.float32):
    """Per-shard ring attention: local q stays, k/v rotate over the ring."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    s_local = q.shape[-2]
    d = q.shape[-1]
    o = jnp.zeros(q.shape[:-1] + (d,), f32)
    l = jnp.zeros(q.shape[:-1], f32)
    m = jnp.full(q.shape[:-1], _NEG, f32)
    qf = q.astype(f32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_pos = idx * s_local + jnp.arange(s_local)

    for step in range(n):
        src = (idx - step) % n           # which shard this k/v came from
        mask = None
        if causal:
            k_pos = src * s_local + jnp.arange(s_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            mask = jnp.broadcast_to(mask, q.shape[:-1] + (s_local,))
        o, l, m = _online_block((o, l, m), qf, k.astype(f32), v, scale,
                                mask)
        if step != n - 1:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
    return (o / l[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh = None, axis_name="seq",
                   causal=False, scale=None, batch_axis="data",
                   head_axis=None):
    """Sequence-parallel attention over the ``axis_name`` mesh ring.

    Inputs are GLOBAL arrays [B, H, S, D]; S is sharded over ``axis_name``,
    B over ``batch_axis`` (if present in the mesh), H over ``head_axis``
    (if given). Returns the global [B, H, S, D] output with the same
    sharding. Safe to call inside jit — shard_map composes.
    """
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    d = q.shape[-1]
    scale = scale if scale is not None else float(1.0 / (d ** 0.5))
    b_ax = batch_axis if batch_axis in mesh.axis_names else None
    spec = P(b_ax, head_axis, axis_name, None)
    sh = NamedSharding(mesh, spec)
    # lay inputs out on the mesh: eager = real resharding onto the ring;
    # under jit = a sharding constraint GSPMD honors
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
    body = functools.partial(_ring_body, axis_name=axis_name, causal=causal,
                             scale=scale)
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return fn(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh = None, axis_name="seq",
                      causal=False, scale=None, batch_axis="data"):
    """Ulysses/DeepSpeed-style sequence parallelism: instead of rotating
    K/V around the ring, one ``all_to_all`` re-shards [B,H,S,D] from
    S-sharded to H-sharded, each device runs FULL attention over its head
    slice, and a second all_to_all restores S-sharding. Preferable to ring
    attention when heads ≥ shards and the sequence fits per-device memory
    (2 collectives total vs P-1 permutes). SURVEY §5.7 names this as the
    alternative design; net-new vs the reference."""
    from .mesh import current_mesh
    mesh = mesh or current_mesh()
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    p = mesh.shape[axis_name]
    if q.shape[1] % p:
        raise MXNetError(f"num_heads {q.shape[1]} must be divisible by the "
                         f"{axis_name} axis size {p}")
    if q.shape[-2] % p:
        raise MXNetError(f"sequence length {q.shape[-2]} must be divisible "
                         f"by the {axis_name} axis size {p}")
    d = q.shape[-1]
    scale = scale if scale is not None else float(1.0 / (d ** 0.5))
    b_ax = batch_axis if batch_axis in mesh.axis_names else None
    spec = P(b_ax, None, axis_name, None)
    sh = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))

    def body(q_l, k_l, v_l):
        # local: [b, H, S/p, d] → all_to_all → [b, H/p, S, d]
        def scatter(x):
            return lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

        def gather(x):
            return lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)
        qh, kh, vh = scatter(q_l), scatter(k_l), scatter(v_l)
        # blockwise kernel keeps per-device memory O(block) not O(S^2) —
        # the long-context point of sequence parallelism
        out = blockwise_attention(qh, kh, vh, causal=causal, scale=scale)
        return gather(out)

    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return fn(q, k, v)
