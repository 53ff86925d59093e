"""``power_retention`` — chunked power retention as one pass over a head's
chunks with its state in fast memory.

The op (``ops/retention.py``; docs/brumby.md has the equations) computes, for
one query head reading key/value head and gate ``j``, chunk by chunk of ``C``
rows with ``cs`` the cumulative log-decay inside the chunk,

    num_t = sum_{s<=t} (q_t . k_s)^2 / d exp(cs_t - cs_s) v_s       (inside)
          + exp(cs_t) / d  phi_w(q_t)^T S                    (entering state)
    den_t = the same with 1 for v_s, and q_t^T Z q_t for the state
    S <- exp(cs_end) S + sum_s phi(k_s) (exp(cs_end - cs_s) v_s)^T
    Z <- exp(cs_end) Z + sum_s (exp(cs_end - cs_s) k_s) k_s^T
    y_t = num_t / (den_t + eps)

``phi`` is the second power over the unordered pairs of coordinates
(:func:`pair_features`): 65 x 128 = 8320 products at ``d`` = 128, so ``S`` is
(8320, 128) float32 for one key/value head. As ``jax.numpy``
(:func:`retention_reference`: what runs off the TPU and what the kernel is
held to) XLA writes the expanded rows of every (chunk, key/value head) pair
to HBM (85 MB at the Brumby cell's shape, forward, recomputed and three more
times backward) and carries both states through a ``lax.scan``.

The kernel's grid walks (batch, key/value head) in parallel and, on its two
last, sequential axes, the chunks of that head and the query heads that read
it, with ``S`` and ``Z`` in fast memory from the first chunk to the last. A
grid step is one chunk of one query head: its expanded rows are formed in
fast memory a group of offsets at a time for a block of rows (``pair_span``:
13 offsets = 1664 features, a rotation of the lanes times the coordinates
each; 256 rows) and multiplied into that slice of the state; inside the
chunk the ``a[t, s]`` form runs over row tiles and skips those above the
diagonal. The state is updated from
the chunk's keys after its last query head has read it. HBM sees q, k, v and
``cs`` once, the numerators and normalisers once, and the states each chunk
starts from once (for the backward pass).

Layout: heads side by side on the lanes, ``q`` as ``(B, L, H * d)``, so that
a head's rows are a block of one lane tile and nothing is re-laid out
outside. The weights of the pairs (1 or 2, exact) are kept on the state's
side: the kernel's state is ``w * S``, which rounds where ``S`` rounds.

Precisions are the reference's, term for term: ``cs``, the exponentials,
both states, their cotangents and every accumulation in float32; the
operands of every product, the expanded rows included (a product of two
bfloat16 numbers rounded once), in the compute dtype.

Backward (``jax.custom_vjp``; docs/brumby.md has the equations): a second
kernel over the chunks in reverse with the cotangents of both states in fast
memory, from the states the forward wrote. The expanded rows are formed
again in fast memory; the cotangent of an expanded row is folded back onto q
or k (one more rotation: the rotated rows are kept from the expansion) before
anything leaves fast memory. What the entering
state gave a row's numerator is read as the numerator less the chunk's own
part, so the state is not multiplied a second time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import register_kernel
from .ssd import _NN, _NT, _TN, _dot

_F32 = jnp.float32
_LANES = 128
_ROW_TILE = 256         # rows of a tile of the a[t, s] form, and of a fold


# ---------------------------------------------------------------------------
# the reference: the jax.numpy scan
# ---------------------------------------------------------------------------
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


def retention_reference(q, k, v, cs, chunk_size=1024, eps=1e-6):
    """The retention from its float32 cumulative log-decay on. ``q`` (B, L,
    H, D), ``k`` (B, L, G, D), ``v`` (B, L, G, Dv) with L a multiple of
    ``chunk_size``; ``cs`` (B, G, L / chunk_size, chunk_size) float32, the
    log-decay from a chunk's start to each of its rows, inclusive. Returns
    ``y`` (B, L, H, Dv) in q's dtype. ``lax.map`` over the key/value heads
    and, inside, ``lax.scan`` over the chunks (carrying both states); one
    step forms the expanded rows of one chunk of one key/value head's query
    heads. The chunk's body is ``jax.checkpoint``ed, so autodiff keeps its
    inputs and the states at the chunk boundaries and recomputes the rest
    chunk by chunk in the backward pass; the division by the normaliser is
    outside the loops, so the recomputation makes no product the gradients
    do not read."""
    batch, length, heads, _ = q.shape
    groups, share = k.shape[2], heads // k.shape[2]
    rows = int(chunk_size)
    chunks = length // rows

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
    return jnp.transpose(y, (0, 2, 4, 1, 3, 5)).reshape(
        batch, chunks * rows, heads, -1)


# ---------------------------------------------------------------------------
# tiles, from the shapes
# ---------------------------------------------------------------------------
def pair_span(dim):
    """Offsets a group of expanded features holds: the largest divisor of
    the ``dim // 2 + 1`` offsets that is at most 16 (13 of 65 at ``dim`` =
    128: 1664 features, a 1664-deep product against that slice of the
    state)."""
    offsets = dim // 2 + 1
    return max(s for s in range(1, 17) if offsets % s == 0)


def row_tile(rows):
    """Rows of a tile of the chunk's ``a[t, s]`` form: 256, or 128 where the
    chunk is no multiple of 256."""
    return _ROW_TILE if rows % _ROW_TILE == 0 else _LANES


def _offset(first, i, dim):
    """``(r, shift)`` of a group's ``i``-th offset: the rotation that brings
    coordinate ``a + r`` to ``a`` (``first`` is traced: a group of the loop
    over the offsets)."""
    r = first + i
    return r, jnp.where(r == 0, 0, dim - r)


def _pair_weight(r, dim):
    return jnp.where((r == 0) | (2 * r == dim), 1.0, 2.0).astype(_F32)


def _expand(x, first, span, phi_ref, turned_ref=None):
    """``phi_ref`` (T, span * d) <- the pair products of the rows ``x`` (T,
    d) float32 for the offsets ``first .. first + span``, offset by offset
    along the lanes: a product of two numbers of the compute dtype, rounded
    once. An offset is one rotation of the lanes; ``turned_ref`` keeps the
    rotated rows (exact in the compute dtype) for :func:`_fold`."""
    from jax.experimental.pallas import tpu as pltpu
    dim = x.shape[1]
    for i in range(span):
        _, shift = _offset(first, i, dim)
        turned = pltpu.roll(x, shift, 1)
        if turned_ref is not None:
            turned_ref[:, i * dim:(i + 1) * dim] = turned.astype(
                turned_ref.dtype)
        phi_ref[:, i * dim:(i + 1) * dim] = (x * turned).astype(phi_ref.dtype)


def _fold(x, dphi_ref, turned_ref, first, span):
    """The cotangent ``dphi_ref`` (T, span * d) of :func:`_expand`'s rows
    folded back onto the coordinates, (T, d) float32: ``dx_a = dphi[r, a]
    x_{a + r} + dphi[r, a - r] x_{a - r}``, the second term one more
    rotation."""
    from jax.experimental.pallas import tpu as pltpu
    dim = x.shape[1]
    acc = jnp.zeros(x.shape, _F32)
    for i in range(span):
        r, _ = _offset(first, i, dim)
        lanes = slice(i * dim, (i + 1) * dim)
        t = dphi_ref[:, lanes]
        acc = acc + t * turned_ref[:, lanes].astype(_F32) \
            + pltpu.roll(t * x, r, 1)
    return acc


def _row_blocks(rows):
    """The rows of a chunk in blocks of a tile: a block's expanded rows are
    multiplied while the next block's are formed."""
    step = row_tile(rows)
    return [slice(at, at + step) for at in range(0, rows, step)]


def _weights_inside(p, csc, csr, diagonal, inv_dim):
    """``(p * D / d, a)`` of a tile: ``D[t, s] = exp(cs_t - cs_s)`` under
    the causal mask where the tile lies on the diagonal, ``a = p^2 D / d``."""
    seg = csc - csr
    if diagonal:
        tri = lax.broadcasted_iota(jnp.int32, p.shape, 0) \
            >= lax.broadcasted_iota(jnp.int32, p.shape, 1)
        pd = jnp.where(tri, p * (inv_dim * jnp.exp(jnp.where(tri, seg, 0.0))),
                       0.0)
    else:
        pd = p * (inv_dim * jnp.exp(seg))
    return pd, p * pd


# A tile's work is one jitted function, traced once for the shapes of a tile
# and found again after (pallas/ssd.py says what tracing it anew cost).
@functools.partial(jax.jit, static_argnames=("diagonal",))
def _tile_forward(q, k, v, csc, csr, *, diagonal):
    """One (t, s) tile of the chunk's own part: ``q`` (T, d) rows t, ``k``
    (T, d) and ``v`` (T, dv) rows s, ``csc`` (T, 1), ``csr`` (1, T). Returns
    the tile's part of the numerators (T, dv) and normalisers (T, 1)."""
    cdt = q.dtype
    _, a = _weights_inside(_dot(q, k, _NT, cdt), csc, csr, diagonal,
                           1.0 / q.shape[1])
    return _dot(a, v, _NN, cdt), jnp.sum(a, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("diagonal",))
def _tile_backward(q, k, v, csc, csr, dnum, dden, *, diagonal):
    """The same tile backward; ``dnum`` (T, dv) and ``dden`` (T, 1) are the
    cotangents of the rows t. Returns the tile's part of the numerators
    again, of ``dq`` (rows t), ``dk`` and ``dv`` (rows s), and of ``d cs``
    down the rows t (T, 1) and along the rows s (1, T)."""
    cdt = q.dtype
    pd, a = _weights_inside(_dot(q, k, _NT, cdt), csc, csr, diagonal,
                            1.0 / q.shape[1])
    da = _dot(dnum, v, _NT, cdt) + dden             # a is 0 above the diagonal
    moved = da * a
    dp = 2.0 * da * pd
    return (_dot(a, v, _NN, cdt), _dot(dp, k, _NN, cdt),
            _dot(dp, q, _TN, cdt), _dot(a, dnum, _TN, cdt),
            jnp.sum(moved, axis=1, keepdims=True),
            -jnp.sum(moved, axis=0, keepdims=True))


def _own_column(block, ri):
    """Column ``ri`` (traced) of a (C, R) block, as (C, 1)."""
    lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == ri, block, 0.0), axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _forward_kernel(q_ref, k_ref, v_ref, csc_ref, csr_ref, num_ref, den_ref,
                    *rest, tile, span, save_states):
    from jax.experimental import pallas as pl
    if save_states:
        states_ref, norm_states_ref = rest[:2]
    state, norm_state, low, x32, from_state, weighed = rest[-6:]
    phis = rest[2 * save_states:-6]         # a buffer a block of rows
    ci, ri = pl.program_id(2), pl.program_id(3)
    cdt = q_ref.dtype
    rows, dim = q_ref.shape[1:]
    width = span * dim
    groups = (dim // 2 + 1) // span
    inv_dim = 1.0 / dim

    @pl.when((ci == 0) & (ri == 0))
    def _():
        state[...] = jnp.zeros_like(state)
        norm_state[...] = jnp.zeros_like(norm_state)

    @pl.when(ri == 0)
    def _():        # the state the chunk starts from, as its products read it
        if save_states:
            states_ref[0, 0, 0] = state[...]
            norm_states_ref[0, 0, 0] = norm_state[...]
        low[...] = state[...].astype(cdt)

    # from the rows before the chunk: the expanded rows a group of offsets at
    # a time against that slice of the state
    x32[...] = q_ref[0].astype(_F32)
    from_state[...] = jnp.zeros_like(from_state)

    def read(g, carry):
        at = pl.ds(pl.multiple_of(g * width, width), width)
        for block, phi in zip(_row_blocks(rows), phis):
            _expand(x32[block, :], g * span, span, phi)
            from_state[block, :] += _dot(phi[...], low[at, :], _NN, cdt)
        return carry

    lax.fori_loop(0, groups, read, 0)

    # inside the chunk: the a[t, s] form over the tiles under the diagonal
    norm_low = norm_state[...].astype(cdt)
    lane = lax.broadcasted_iota(jnp.int32, (tile, den_ref.shape[3]), 1)
    for ti in range(rows // tile):
        t = slice(ti * tile, (ti + 1) * tile)
        since_start = jnp.exp(csc_ref[0, 0, t, :]) * inv_dim
        num = since_start * from_state[t, :]
        projected = _dot(q_ref[0, t, :], norm_low, _NN, cdt)
        den = since_start * jnp.sum(projected * x32[t, :], axis=1,
                                    keepdims=True)
        for sj in range(ti + 1):
            s = slice(sj * tile, (sj + 1) * tile)
            n, d = _tile_forward(q_ref[0, t, :], k_ref[0, s, :],
                                 v_ref[0, s, :], csc_ref[0, 0, t, :],
                                 csr_ref[0, 0, 0, :, s], diagonal=sj == ti)
            num, den = num + n, den + d
        num_ref[0, t, :] = num
        den_ref[0, 0, t, :] = jnp.where(lane == ri, den, den_ref[0, 0, t, :])

    # what the chunk leaves, once its last query head has read the state
    @pl.when(ri == pl.num_programs(3) - 1)
    def _():
        end = csc_ref[0, 0, rows - 1:rows, :]           # (1, 1)
        # along the lanes first: (1, 1) against a tile is two broadcasts
        keep = jnp.exp(end + jnp.zeros((1, dim), _F32))
        to_end = jnp.exp(end - csc_ref[0, 0])           # (C, 1)
        x32[...] = k_ref[0].astype(_F32)
        weighed[...] = (v_ref[0].astype(_F32) * to_end).astype(cdt)

        def add(g, carry):
            added = 0.0                                     # (width, dv)
            for block, phi in zip(_row_blocks(rows), phis):
                _expand(x32[block, :], g * span, span, phi)
                added = added + _dot(phi[...], weighed[block, :], _TN, cdt)
            for i in range(span):
                r, _ = _offset(g * span, i, dim)
                at = pl.ds(pl.multiple_of(g * width + i * dim, dim), dim)
                state[at, :] = keep * state[at, :] + _pair_weight(r, dim) \
                    * added[i * dim:(i + 1) * dim]
            return carry

        lax.fori_loop(0, groups, add, 0)
        norm_state[...] = keep * norm_state[...] + _dot(
            x32[...] * to_end, k_ref[0], _TN, cdt)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _backward_kernel(q_ref, k_ref, v_ref, csc_ref, csr_ref, num_ref,
                     states_ref, norm_states_ref, dnum_ref, dden_ref,
                     dq_ref, dk_ref, dv_ref, dcsc_ref, dcsr_ref,
                     dstate, dnorm_state, low, x32, weighed, dweighed,
                     dscaled, dx, dk_acc, dv_acc, dcol, drow, *buffers,
                     tile, span):
    from jax.experimental import pallas as pl
    # a block of rows each: its expanded rows, their cotangent, its rows
    # rotated
    blocks = list(zip(_row_blocks(q_ref.shape[1]), buffers[0::3],
                      buffers[1::3], buffers[2::3]))
    ri = pl.program_id(3)
    cdt = q_ref.dtype
    rows, dim = q_ref.shape[1:]
    width = span * dim
    groups = (dim // 2 + 1) // span
    inv_dim = 1.0 / dim

    @pl.when((pl.program_id(2) == 0) & (ri == 0))
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dnorm_state[...] = jnp.zeros_like(dnorm_state)

    end = csc_ref[0, 0, rows - 1:rows, :]               # (1, 1)
    # along the lanes first: (1, 1) against a tile is two broadcasts
    keep = jnp.exp(end + jnp.zeros((1, dim), _F32))

    # the keys' side, before the chunk's first query head: dstate is the
    # cotangent of the state the chunk leaves; it becomes that of the state
    # the chunk starts from as the query heads add to it
    @pl.when(ri == 0)
    def _():
        to_end = jnp.exp(end - csc_ref[0, 0])          # (C, 1)
        k32 = k_ref[0].astype(_F32)
        v32 = v_ref[0].astype(_F32)
        x32[...] = k32
        weighed[...] = (v32 * to_end).astype(cdt)
        dweighed[...] = jnp.zeros_like(dweighed)
        dx[...] = jnp.zeros_like(dx)

        def keys(g, dkeep):
            at = pl.ds(pl.multiple_of(g * width, width), width)
            entering = states_ref[0, 0, 0, at, :]
            leaving = dstate[at, :]
            dkeep = dkeep + jnp.sum(leaving * entering, axis=0, keepdims=True)
            low[at, :] = entering.astype(cdt)
            for i in range(span):
                r, _ = _offset(g * span, i, dim)
                dscaled[i * dim:(i + 1) * dim, :] = (
                    _pair_weight(r, dim) * leaving[i * dim:(i + 1) * dim]
                ).astype(cdt)
            for block, phi, dphi, turned in blocks:
                x = x32[block, :]
                _expand(x, g * span, span, phi, turned)
                dweighed[block, :] += _dot(phi[...], dscaled[...], _NN, cdt)
                dphi[...] = _dot(weighed[block, :], dscaled[...], _NT, cdt)
                dx[block, :] += _fold(x, dphi, turned, g * span, span)
            dstate[at, :] = keep * leaving
            return dkeep

        dkeep = jnp.sum(lax.fori_loop(
            0, groups, keys, jnp.zeros((1, dstate.shape[1]), _F32)),
            axis=1, keepdims=True)
        # the normaliser's state: Z' = keep Z + (k to_end)^T k
        leaving = dnorm_state[...]
        dkeep = dkeep + jnp.sum(jnp.sum(
            leaving * norm_states_ref[0, 0, 0], axis=1, keepdims=True),
            axis=0, keepdims=True)
        dscaled_k = _dot(k_ref[0], leaving, _NT, cdt)   # d (k to_end)
        dk_acc[...] = dx[...] + dscaled_k * to_end + _dot(
            k32 * to_end, leaving, _NN, cdt)
        dnorm_state[...] = keep * leaving
        dv_acc[...] = dweighed[...] * to_end
        # to_end = exp(cs_end - cs), keep = exp(cs_end)
        moved = to_end * (
            jnp.sum(dweighed[...] * v32, axis=1, keepdims=True)
            + jnp.sum(dscaled_k * k32, axis=1, keepdims=True))
        last = lax.broadcasted_iota(jnp.int32, moved.shape, 0) == rows - 1
        dcol[...] = jnp.where(
            last, jnp.sum(moved, axis=0, keepdims=True) + dkeep * jnp.exp(end),
            0.0) - moved
        drow[...] = jnp.zeros_like(drow)

    # this query head: the chunk's own part, a tile at a time
    x32[...] = q_ref[0].astype(_F32)
    norm_low = norm_states_ref[0, 0, 0].astype(cdt)
    dnorm = jnp.zeros(dnorm_state.shape, _F32)
    for ti in range(rows // tile):
        t = slice(ti * tile, (ti + 1) * tile)
        q, q32 = q_ref[0, t, :], x32[t, :]
        dnum = dnum_ref[0, t, :]
        dden = _own_column(dden_ref[0, 0, t, :], ri)
        inside = jnp.zeros(dnum.shape, _F32)
        dq = jnp.zeros(q32.shape, _F32)
        down = jnp.zeros((tile, 1), _F32)
        for sj in range(ti + 1):
            s = slice(sj * tile, (sj + 1) * tile)
            n, dq_t, dk_s, dv_s, dc_t, dc_s = _tile_backward(
                q, k_ref[0, s, :], v_ref[0, s, :], csc_ref[0, 0, t, :],
                csr_ref[0, 0, 0, :, s], dnum, dden, diagonal=sj == ti)
            inside, dq, down = inside + n, dq + dq_t, down + dc_t
            dk_acc[s, :] += dk_s
            dv_acc[s, :] += dv_s
            drow[:, s] += dc_s
        # the normaliser from the state: since_start * q^T Z q
        since_start = jnp.exp(csc_ref[0, 0, t, :]) * inv_dim
        projected = _dot(q, norm_low, _NN, cdt)
        scaled = dden * since_start
        through = scaled * q32
        dq = dq + scaled * projected + _dot(through, norm_low, _NT, cdt)
        dnorm = dnorm + _dot(q, through, _TN, cdt)
        # what the state gave the numerator: the numerator less the chunk's
        # own part (= since_start * phi(q) S)
        from_state = num_ref[0, t, :] - inside
        dcol[t, :] += down + scaled * jnp.sum(
            projected * q32, axis=1, keepdims=True) + jnp.sum(
                dnum * from_state, axis=1, keepdims=True)
        dx[t, :] = dq
        weighed[t, :] = (dnum * since_start).astype(cdt)
    dnorm_state[...] += dnorm

    def queries(g, carry):
        at = pl.ds(pl.multiple_of(g * width, width), width)
        added = 0.0                                         # (width, dv)
        for block, phi, dphi, turned in blocks:
            x = x32[block, :]
            _expand(x, g * span, span, phi, turned)
            dphi[...] = _dot(weighed[block, :], low[at, :], _NT, cdt)
            dx[block, :] += _fold(x, dphi, turned, g * span, span)
            added = added + _dot(phi[...], weighed[block, :], _TN, cdt)
        dstate[at, :] += added
        return carry

    lax.fori_loop(0, groups, queries, 0)
    dq_ref[0] = dx[...].astype(dq_ref.dtype)

    @pl.when(ri == pl.num_programs(3) - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        dcsc_ref[0, 0] = dcol[...]
        dcsr_ref[0, 0, 0] = drow[...]


# ---------------------------------------------------------------------------
# the two calls
# ---------------------------------------------------------------------------
def _specs(dims, chunk_of):
    """Block specs of the operands both kernels share, by name; the chunk a
    grid step works on is ``chunk_of(ci)`` (forward: itself; backward: from
    the last one down). The grid is (batch, key/value head, chunk, query
    head of that key/value head)."""
    from jax.experimental import pallas as pl
    rows, share, dim, dv = dims
    features = (dim // 2 + 1) * dim
    return {
        "q": pl.BlockSpec((1, rows, dim), lambda b, g, c, r: (
            b, chunk_of(c), g * share + r)),
        "k": pl.BlockSpec((1, rows, dim),
                          lambda b, g, c, r: (b, chunk_of(c), g)),
        "v": pl.BlockSpec((1, rows, dv),
                          lambda b, g, c, r: (b, chunk_of(c), g)),
        "num": pl.BlockSpec((1, rows, dv), lambda b, g, c, r: (
            b, chunk_of(c), g * share + r)),
        "col": pl.BlockSpec((1, 1, rows, 1),
                            lambda b, g, c, r: (b, g, chunk_of(c), 0)),
        "row": pl.BlockSpec((1, 1, 1, 1, rows),
                            lambda b, g, c, r: (b, g, chunk_of(c), 0, 0)),
        "den": pl.BlockSpec((1, 1, rows, share),
                            lambda b, g, c, r: (b, g, chunk_of(c), 0)),
        "states": pl.BlockSpec((1, 1, 1, features, dv), lambda b, g, c, r: (
            b, g, chunk_of(c), 0, 0)),
        "norm_states": pl.BlockSpec((1, 1, 1, dim, dim), lambda b, g, c, r: (
            b, g, chunk_of(c), 0, 0)),
    }


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary",
                             "arbitrary"),
        vmem_limit_bytes=64 * 2 ** 20)


# The calls are jitted, as pallas/ssd.py's: every layer of a model has the
# same shapes, and the tier's staged branches are transposed twice, so one
# trace and one lowering of a kernel serve them all.
@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _forward_call(dims, interpret, save_states, q, k, v, csc, csr):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, share, dim, dv = dims
    bsz, length = q.shape[:2]
    groups, nc = k.shape[2] // dim, length // rows
    features, span, tile = (dim // 2 + 1) * dim, pair_span(dim), row_tile(rows)
    cdt = q.dtype
    sp = _specs(dims, lambda c: c)
    out_shape = [jax.ShapeDtypeStruct((bsz, length, groups * share * dv),
                                      _F32),
                 jax.ShapeDtypeStruct((bsz, groups, length, share), _F32)]
    out_specs = [sp["num"], sp["den"]]
    if save_states:
        out_shape += [
            jax.ShapeDtypeStruct((bsz, groups, nc, features, dv), _F32),
            jax.ShapeDtypeStruct((bsz, groups, nc, dim, dim), _F32)]
        out_specs += [sp["states"], sp["norm_states"]]
    return pl.pallas_call(
        functools.partial(_forward_kernel, tile=tile, span=span,
                          save_states=save_states),
        grid=(bsz, groups, nc, share),
        in_specs=[sp["q"], sp["k"], sp["v"], sp["col"], sp["row"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tile, span * dim), cdt)     # phi, a block
                        for _ in range(rows // tile)] + [
            pltpu.VMEM((features, dv), _F32),           # state
            pltpu.VMEM((dim, dim), _F32),               # norm_state
            pltpu.VMEM((features, dv), cdt),            # low
            pltpu.VMEM((rows, dim), _F32),              # x32
            pltpu.VMEM((rows, dv), _F32),               # from_state
            pltpu.VMEM((rows, dv), cdt)],               # weighed
        compiler_params=_compiler_params(), interpret=interpret,
    )(q, k, v, csc, csr)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _backward_call(dims, interpret, q, k, v, csc, csr, num, states,
                   norm_states, dnum, dden):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, share, dim, dv = dims
    nc = q.shape[1] // rows
    features, span, tile = (dim // 2 + 1) * dim, pair_span(dim), row_tile(rows)
    cdt = q.dtype
    sp = _specs(dims, lambda c: nc - 1 - c)

    def like(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype)

    return pl.pallas_call(
        functools.partial(_backward_kernel, tile=tile, span=span),
        grid=(q.shape[0], k.shape[2] // dim, nc, share),
        in_specs=[sp["q"], sp["k"], sp["v"], sp["col"], sp["row"], sp["num"],
                  sp["states"], sp["norm_states"], sp["num"], sp["den"]],
        out_specs=[sp["q"], sp["k"], sp["v"], sp["col"], sp["row"]],
        out_shape=[like(q), like(k), like(v), like(csc), like(csr)],
        scratch_shapes=[
            pltpu.VMEM((features, dv), _F32),           # dstate
            pltpu.VMEM((dim, dim), _F32),               # dnorm_state
            pltpu.VMEM((features, dv), cdt),            # low
            pltpu.VMEM((rows, dim), _F32),              # x32
            pltpu.VMEM((rows, dv), cdt),                # weighed
            pltpu.VMEM((rows, dv), _F32),               # dweighed
            pltpu.VMEM((span * dim, dv), cdt),          # dscaled
            pltpu.VMEM((rows, dim), _F32),              # dx
            pltpu.VMEM((rows, dim), _F32),              # dk_acc
            pltpu.VMEM((rows, dv), _F32),               # dv_acc
            pltpu.VMEM((rows, 1), _F32),                # dcol
            pltpu.VMEM((1, rows), _F32)] + [            # drow
            pltpu.VMEM((tile, span * dim), dtype)       # phi, dphi, turned
            for _ in range(rows // tile) for dtype in (cdt, _F32, cdt)],
        compiler_params=_compiler_params(), interpret=interpret,
    )(q, k, v, csc, csr, num, states, norm_states, dnum, dden)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scan(dims, interpret, q, k, v, csc, csr):
    return tuple(_forward_call(dims, interpret, False, q, k, v, csc, csr))


def _scan_fwd(dims, interpret, *operands):
    num, den, states, norm_states = _forward_call(dims, interpret, True,
                                                  *operands)
    return (num, den), operands + (num, states, norm_states)


def _scan_bwd(dims, interpret, kept, cotangents):
    return tuple(_backward_call(dims, interpret, *kept, *cotangents))


_scan.defvjp(_scan_fwd, _scan_bwd)


# ---------------------------------------------------------------------------
# registration
# ---------------------------------------------------------------------------
def _retention_supports(q, k, v, cs, chunk_size=1024, eps=1e-6):
    rows = int(chunk_size)
    if q.ndim != 4 or k.ndim != 4 or v.shape[:3] != k.shape[:3] \
            or q.shape[:2] != k.shape[:2] or q.shape[2] % k.shape[2] \
            or q.shape[3] != k.shape[3] or q.shape[1] % rows \
            or cs.shape != (q.shape[0], k.shape[2], q.shape[1] // rows, rows):
        return (f"shape:q{q.shape}_k{k.shape}_v{v.shape}_cs{cs.shape}"
                f"_chunk{rows}")
    if q.dtype not in (jnp.bfloat16, jnp.float32) or k.dtype != q.dtype \
            or v.dtype != q.dtype:
        return f"dtype:{q.dtype}_{k.dtype}_{v.dtype}"
    if cs.dtype != _F32:
        return f"dtype:cs_{cs.dtype}"
    if q.size == 0:
        return "empty"
    # whole tiles of the chip's registers: a head's coordinates and a
    # value's are one lane tile (an offset is a rotation of the lanes), the
    # rows of a chunk whole tiles of the a[t, s] form
    if q.shape[3] != _LANES or v.shape[3] != _LANES or rows % _LANES:
        return f"tile:d{q.shape[3]}_dv{v.shape[3]}_chunk{rows}"
    return None


def _retention_example():
    rng = np.random.RandomState(35)

    def case(length, heads, groups, rows):
        def unit(n):
            t = rng.randn(1, length, n, _LANES)
            return jnp.asarray(t / np.sqrt(np.mean(t * t, -1, keepdims=True)),
                               _F32)

        q, k = unit(heads), unit(groups)
        v = jnp.asarray(rng.randn(1, length, groups, _LANES), _F32)
        # gates that remember a chunk or so: the state carries
        log_g = -rng.uniform(0.25, 4.0, (1, groups, length // rows, rows)) \
            / rows
        cs = jnp.asarray(np.cumsum(log_g, axis=-1), _F32)
        return (q, k, v, cs), {"chunk_size": rows}

    # whole register tiles, as the chip's compiler wants them (chip_smoke.py
    # compiles these): two chunks of two key/value heads with two query
    # heads each; three chunks of one key/value head with three
    return [case(256, 4, 2, 128), case(384, 3, 1, 128)]


@register_kernel(
    "power_retention", xla_reference=retention_reference, tolerance=1e-4,
    backends=("tpu",), supports=_retention_supports,
    example=_retention_example,
    doc="Chunked power retention of degree 2 (ops/retention.py) as one pass "
        "over a head's chunks with its state (8320 x 128 float32 a "
        "key/value head) and the normaliser's in fast memory: the expanded "
        "rows of q and k are formed there a group of offsets at a time and "
        "never written to HBM, the a[t, s] form inside a chunk skips the "
        "tiles above the diagonal. Backward: a second kernel over the "
        "chunks in reverse, from the float32 states the forward wrote, the "
        "cotangent of an expanded row folded back onto q or k in fast "
        "memory. The reference is the jax.numpy scan (lax.map over the "
        "key/value heads, lax.scan over the chunks): PERF.md sec. 6, PR 35 "
        "has both on a v5e.")
# jitted like the calls inside it: autodiff then works on one cached jaxpr a
# shape, not on the custom_vjp of every layer anew
@functools.partial(jax.jit, static_argnames=("interpret", "chunk_size",
                                             "eps"))
def _retention_pallas(q, k, v, cs, interpret=False, chunk_size=1024,
                      eps=1e-6):
    bsz, length, heads, dim = q.shape
    groups, dv = k.shape[2], v.shape[3]
    rows = chunk_size
    dims = (rows, heads // groups, dim, dv)
    num, den = _scan(dims, bool(interpret),
                     q.reshape(bsz, length, heads * dim),
                     k.reshape(bsz, length, groups * dim),
                     v.reshape(bsz, length, groups * dv),
                     cs.reshape(bsz, groups, length, 1),
                     cs.reshape(bsz, groups, length // rows, 1, rows))
    # (B, G, L, R) -> (B, L, H, 1)
    den = jnp.transpose(den, (0, 2, 1, 3)).reshape(bsz, length, heads, 1)
    return (num.reshape(bsz, length, heads, dv) / (den + eps)).astype(q.dtype)
