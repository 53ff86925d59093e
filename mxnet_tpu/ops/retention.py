"""Power retention: gated linear attention of degree 2, chunked (Manifest
AI's power attention, arXiv:2507.04239; docs/brumby.md has the equations).
No reference analog: MXNet 1.x has no linear attention.

For one query head reading key/value head and gate ``j``, with ``d`` the
head size and ``c[t]`` the running sum of the log-gates,

    a[t, s] = (q_t . k_s)^2 / d * exp(c[t] - c[s])      for s <= t, else 0
    y_t     = sum_s a[t, s] v_s / (sum_s a[t, s] + eps)

which is a recurrence over a state, because ``(u . w)^2 = phi(u) . phi(w)``
for the second tensor power ``phi``: ``S_t = g_t S_{t-1} + phi(k_t) v_t^T``,
``Z_t = g_t Z_{t-1} + k_t k_t^T`` (the normaliser's state: ``phi(q) . z =
q^T Z q``), ``y_t = (phi(q_t)^T S_t / d) / (q_t^T Z_t q_t / d + eps)``.
``_contrib_power_retention`` runs the chunked form: inside a chunk of ``C``
rows the ``a[t, s]`` form, across chunks ``S`` and ``Z``.

``phi`` is taken over the unordered pairs of coordinates (``pair_features``):
``(u . w)^2 = sum_r w_r sum_a (u_a u_{a+r}) (w_a w_{a+r})`` over the offsets
``r = 0 .. d // 2``, indices modulo ``d``, ``w_r`` = 1 where ``r`` and ``d -
r`` are the same offset and 2 elsewhere. At ``d`` = 128 that is 65 x 128 =
8320 products, each a rotation of the coordinates times the coordinates,
against 16384 for the full outer product and 8256 for the distinct pairs. The
weights (exact powers of two) go on the query's side alone, so the state is
``sum_s pairs(k_s) v_s^T``, (8320, d) in float32 for one key/value head. The
loop holds q and k with the coordinates before the rows, (d, C): a rotation
is then a shift of whole rows and the expanded operand is laid out as the
product over it reads it (with the coordinates along the lanes and float32
pair products the same loop took six times as long on a v5e: PERF.md sec. 6,
PR 34).

What is never held: the expanded queries of a sequence (8192 x 40 x 8320
bf16 = 5.5 GB) and more than one chunk's ``C x C`` scores. The loops run over
the key/value heads (``lax.map``: the heads are independent) and, inside,
over the chunks (``lax.scan`` carrying ``S`` and ``Z``); one step forms the
expanded rows of one chunk of one key/value head's query heads (5 x 1024 x
8320 bf16 = 85 MB). The chunk's body is ``jax.checkpoint``ed, so autodiff
keeps its inputs and the states at the chunk boundaries (8 x 8 x 4.3 MB a
layer at 8192 rows and chunk 1024) and recomputes the rest chunk by chunk in
the backward pass; the division by the normaliser is outside the loops, so
the recomputation makes no product the gradients do not read.

Decays, cumulative sums, exponentials, states and every accumulation are
float32 whatever the compute dtype; the operands of the products are in the
compute dtype (as ``_contrib_mamba2_ssd``). Plain ``jax.numpy`` / ``lax``
that XLA compiles: there is no kernel in the kernel tier for it yet.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..base import MXNetError
from .registry import OpParam, register

RETENTION_COUNT_METRIC = "mxnet_tpu_power_retentions_traced_total"

_F32 = jnp.float32


def _count_traced_retention(chunk, length, path):
    """One chunked retention traced into a program, by chunk size, (padded)
    sequence length and path (``xla``: the ``jax.numpy`` scan, the only one
    there is): trace-time only, so a compiled step never counts."""
    from ..observability.metrics import default_registry
    default_registry().counter(
        RETENTION_COUNT_METRIC,
        "chunked power retentions traced into a program",
        ("chunk", "length", "path")).labels(
            chunk=str(chunk), length=str(length), path=path).inc()


def pair_weights(dim):
    """``w_r`` for the offsets ``r = 0 .. dim // 2``: 1 where ``r`` and
    ``dim - r`` are one offset (0, and ``dim / 2`` of an even ``dim``), 2
    where the pair ``(a, a + r)`` stands for ``(a + r, a)`` as well."""
    r = np.arange(dim // 2 + 1)
    return np.where((2 * r) % dim == 0, 1.0, 2.0)


def pair_features(x, weighted=False):
    """``(..., d, C) -> (..., (d // 2 + 1) * d, C)``: the products ``x_a
    x_{(a + r) % d}`` of each column's coordinates for ``r = 0 .. d // 2``,
    offset by offset, times ``pair_weights`` where ``weighted``; in ``x``'s
    dtype (a product of two bfloat16 numbers rounded once). The coordinates
    lie along the second-last axis, so that an offset is a shift of whole
    rows and the result is laid out as a product over it reads it. ``sum_f
    pair_features(u, True) * pair_features(w) == (u . w)^2``."""
    dim = x.shape[-2]
    weights = pair_weights(dim) if weighted else np.ones(dim // 2 + 1)
    return jnp.concatenate(
        [(x if w == 1.0 else x * jnp.asarray(w, x.dtype))
         * jnp.roll(x, -r, axis=-2) for r, w in enumerate(weights)], axis=-2)


def _chunk(state, norm_state, q, k, v, cs):
    """One chunk of one key/value head. ``q`` (R, d, C): its query heads'
    rows, the coordinates before the rows; ``k`` (d, C), ``v`` (C, dv);
    ``cs`` (C,) float32: the log-decay from the chunk's start to each row,
    inclusive; ``state`` (F, dv) and ``norm_state`` (d, d) float32, as the
    chunk finds them. Returns the rows' numerators (R, C, dv) and
    normalisers (R, C) in float32 and both states as the chunk leaves
    them."""
    dtype = q.dtype
    dim, rows = k.shape
    inv_dim = 1.0 / dim         # the scale 1 / sqrt(d), inside the square

    # inside the chunk: the a[t, s] form
    scores = jnp.einsum("rdt,ds->rts", q, k, preferred_element_type=_F32)
    causal = jnp.tril(jnp.ones((rows, rows), bool))
    decay = jnp.exp(jnp.where(causal, cs[:, None] - cs[None, :], 0.0))
    a = jnp.where(causal, jnp.square(scores) * (inv_dim * decay), 0.0)
    num = jnp.einsum("rts,se->rte", a.astype(dtype), v,
                     preferred_element_type=_F32)
    den = jnp.sum(a, axis=-1)

    # from the rows before the chunk: the state, decayed to each row
    since_start = jnp.exp(cs) * inv_dim
    num = num + since_start[:, None] * jnp.einsum(
        "rfc,fe->rce", pair_features(q, weighted=True), state.astype(dtype),
        preferred_element_type=_F32)
    projected = jnp.einsum("ed,rdc->rec", norm_state.T.astype(dtype), q,
                           preferred_element_type=_F32)
    den = den + since_start * jnp.sum(projected * q.astype(_F32), axis=-2)

    # what the chunk leaves: both states decayed to its end, and its rows
    to_end = jnp.exp(cs[-1] - cs)
    state = jnp.exp(cs[-1]) * state + jnp.einsum(
        "fc,ce->fe", pair_features(k),
        (v.astype(_F32) * to_end[:, None]).astype(dtype),
        preferred_element_type=_F32)
    norm_state = jnp.exp(cs[-1]) * norm_state + jnp.einsum(
        "dc,ec->de", (k.astype(_F32) * to_end).astype(dtype), k,
        preferred_element_type=_F32)
    return num, den, state, norm_state


def _one_head(q, k, v, cs):
    """The chunks of one key/value head in turn: ``q`` (nc, R, d, C), ``k``
    (nc, d, C), ``v`` (nc, C, dv), ``cs`` (nc, C). Returns numerators (nc,
    R, C, dv) and normalisers (nc, R, C)."""
    dim = k.shape[-2]

    @jax.checkpoint
    def body(carry, chunk):
        num, den, state, norm_state = _chunk(*carry, *chunk)
        return (state, norm_state), (num, den)

    start = (jnp.zeros(((dim // 2 + 1) * dim, v.shape[-1]), _F32),
             jnp.zeros((dim, dim), _F32))
    return lax.scan(body, start, (q, k, v, cs))[1]


@register("_contrib_power_retention", num_inputs=4,
          params=[OpParam("chunk_size", int, 1024),
                  OpParam("eps", float, 1e-6)],
          doc="Power retention of degree 2, chunked: y_t = sum_s a[t, s] v_s "
              "/ (sum_s a[t, s] + eps), a[t, s] = (q_t . k_s)^2 / D * "
              "exp(sum of log_g over s < r <= t) for s <= t. Inputs: q (B, "
              "L, H, D), k (B, L, G, D) and v (B, L, G, Dv) with H a multiple "
              "of G (query head h reads key/value head and gate h // (H / "
              "G)), log_g (B, L, G), the log of each row's gate (<= 0). "
              "Returns y (B, L, H, Dv) in q's dtype. A length that is no "
              "multiple of chunk_size is padded at the end (the retention is "
              "causal, so the padding changes no output that is kept).")
def _power_retention(q, k, v, log_g, chunk_size=1024, eps=1e-6):
    if q.ndim != 4 or k.ndim != 4 or v.shape[:3] != k.shape[:3] \
            or q.shape[2] % k.shape[2] or q.shape[3] != k.shape[3] \
            or log_g.shape != k.shape[:3]:
        raise MXNetError(
            f"power_retention: q (B, L, H, D), k (B, L, G, D), v (B, L, G, "
            f"Dv) and log_g (B, L, G) with H a multiple of G expected, got "
            f"{q.shape}, {k.shape}, {v.shape} and {log_g.shape}")
    batch, length, heads, dim = q.shape
    groups, share = k.shape[2], heads // k.shape[2]
    rows = min(int(chunk_size), length)
    pad = -length % rows
    if pad:
        q, k, v, log_g = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, log_g))
    chunks = (length + pad) // rows
    # the log-decay from a chunk's start to each of its rows, inclusive: a
    # float32 product with the lower triangle of ones (exact terms, float32
    # sums), as the state-space scan makes its own (ops/ssm.py)
    cs = jnp.einsum("ls,bcsg->bgcl", jnp.tril(jnp.ones((rows, rows), _F32)),
                    log_g.astype(_F32).reshape(batch, chunks, rows, groups),
                    precision=lax.Precision.HIGHEST)

    def by_head(t, order):      # (B, L, n, last) -> (B * G, nc, ...)
        t = t.reshape((batch, chunks, rows, groups, -1, t.shape[-1]))
        t = jnp.transpose(t, (0, 3, 1) + order)
        return t.reshape((batch * groups, chunks) + t.shape[3:])

    # q (.., R, d, C), k (.., 1, d, C) -> (.., d, C), v (.., C, dv)
    num, den = lax.map(lambda head: _one_head(*head), (
        by_head(q, (4, 5, 2)), by_head(k, (4, 5, 2))[:, :, 0],
        by_head(v, (4, 2, 5))[:, :, 0],
        cs.reshape(batch * groups, chunks, rows)))
    y = (num / (den[..., None] + eps)).astype(q.dtype)
    # (B * G, nc, R, C, dv) -> (B, L, H, dv)
    y = y.reshape(batch, groups, chunks, share, rows, -1)
    y = jnp.transpose(y, (0, 2, 4, 1, 3, 5)).reshape(
        batch, chunks * rows, heads, -1)
    if isinstance(q, jax.core.Tracer):
        _count_traced_retention(rows, length + pad, "xla")
    return y[:, :length]
