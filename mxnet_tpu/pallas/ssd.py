"""``mamba2_ssd`` — the chunked Mamba-2 scan as one pass over the chunks.

The op (``ops/ssm.py``) computes, chunk by chunk of ``q`` positions,

    y[l] = sum_{s<=l} (C_l . B_s) exp(cs_l - cs_s) dt_s x_s        (inside)
         + (C_l . state^T) exp(cs_l)                       (entering state)
         + D x_l
    state <- exp(cs_end) state + sum_s exp(cs_end - cs_s) dt_s x_s B_s^T

with ``cs`` the cumulative log-decay inside the chunk. As ``jax.numpy``
(:func:`ssd_reference`: what runs off the TPU and what the kernel is held
to) XLA writes each term of ``y`` and every chunk's state to HBM in
float32 and carries the states through a ``lax.scan``. The kernel walks
the chunks on the last, sequential axis of its grid with the state of a
block of heads in fast memory: HBM sees ``x``, ``B``, ``C`` once and ``y``
once, in the compute dtype.

Layout: heads side by side on the lanes, ``x`` as ``(B, L, H * P)``, so
that the products that run over all heads of a block (``C state``, the
state's update and, backward, ``dB``, ``dC`` and the state's cotangent)
are one matrix product each. A head's own (l, s) work takes the 128-lane
tile its ``P`` lanes lie in (two heads of 64 share one): the product is
taken over the whole tile, which costs the matrix unit the same, and the
head's lanes are selected from the result.

Precisions are the reference's, term for term: ``dt``, ``cs``, the
exponentials, the state and every accumulation in float32; the operands of
every product in the compute dtype.

Backward (``jax.custom_vjp``): the forward writes the state every chunk
starts from, once, in the compute dtype; the backward kernel walks the
chunks in reverse with the state's cotangent in fast memory. With
``dS = dy x^T`` everything is local to a chunk (docs/granite_hybrid.md has
the equations). ``cs`` enters the kernel in both orientations (down the
sublanes for ``l``, along the lanes for ``s``) and as ``exp(cs)``,
``exp(cs_end - cs) dt`` and ``exp(cs_end)``, all ``L x H`` float32 made
outside in ``jax.numpy``; the kernel returns a cotangent for each and
autodiff adds them up.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import register_kernel

_F32 = jnp.float32
_LANES = 128
# lanes of x a grid step holds (a block of heads): 512 keep the per-step
# products wide and the state of a block (N x 512 float32) at 256 KB
_BLOCK_LANES = 512

_NN = (((1,), (0,)), ((), ()))      # (m, k) x (k, n)
_NT = (((1,), (1,)), ((), ()))      # (m, k) x (n, k)
_TN = (((0,), (0,)), ((), ()))      # (k, m) x (k, n)


# ---------------------------------------------------------------------------
# the reference: today's jax.numpy scan
# ---------------------------------------------------------------------------
def ssd_reference(x, dt, cs, b, c, d, chunk_size=256):
    """The scan from its float32 step sizes on. ``x`` (B, L, H, P) with L a
    multiple of ``chunk_size``; ``dt`` (B, L, H) float32, after its bias and
    softplus; ``cs`` (B, L, H) float32, the cumulative sum of ``dt * A``
    inside each chunk; ``b``, ``c`` (B, L, G, N); ``d`` (H,). Returns ``y``
    (B, L, H, P) in x's dtype. Inside a chunk masked products, between
    chunks a recurrence over the chunk states (``lax.scan``)."""
    bsz, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r = heads // groups                         # heads that share a B and C
    q = int(chunk_size)
    nc = length // q
    cdt = x.dtype

    def dot(spec, *operands):
        return jnp.einsum(spec, *(o.astype(cdt) for o in operands),
                          preferred_element_type=_F32)

    xc = x.reshape(bsz, nc, q, groups, r, p)
    bc = b.reshape(bsz, nc, q, groups, n)
    cc = c.reshape(bsz, nc, q, groups, n)
    dtc = dt.reshape(bsz, nc, q, groups, r)
    cs = cs.reshape(bsz, nc, q, groups, r)

    # inside a chunk: y[l] += sum_{s<=l} (C_l . B_s) exp(cs_l - cs_s) dt_s x_s
    # (the (l, s) matrices head-major, so that their minor axes are whole
    # tiles of the chip's registers)
    cs_h = jnp.moveaxis(cs, 2, -1)                      # (B, nc, G, R, Q)
    seg = cs_h[..., :, None] - cs_h[..., None, :]       # (B, nc, G, R, l, s)
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)), seg,
                              -jnp.inf))
    cb = dot("bclgn,bcsgn->bcgls", cc, bc)
    scores = cb[:, :, :, None] * decay \
        * jnp.moveaxis(dtc, 2, -1)[..., None, :]
    y = dot("bcgrls,bcsgrp->bclgrp", scores, xc)

    # the state each chunk adds: sum_s exp(cs_end - cs_s) dt_s x_s B_s^T
    to_end = jnp.exp(cs[:, :, -1:] - cs) * dtc
    added = dot("bcsgn,bcsgrp->bcgrpn", bc, xc * to_end[..., None])

    # between chunks: S_c = exp(cs_end) S_{c-1} + added_c; each chunk reads
    # the state it starts from
    def carry_state(state, chunk):
        keep, new = chunk
        return keep[..., None, None] * state + new, state

    _, entering = lax.scan(
        carry_state, jnp.zeros((bsz, groups, r, p, n), _F32),
        (jnp.moveaxis(jnp.exp(cs[:, :, -1]), 1, 0), jnp.moveaxis(added, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)             # (B, nc, G, R, P, N)
    y = y + dot("bclgn,bcgrpn->bclgrp", cc, entering) * jnp.exp(cs)[..., None]

    y = y.reshape(bsz, length, heads, p)
    skip = d.astype(_F32)[:, None] * x.astype(_F32)
    return (y + skip).astype(cdt)


# ---------------------------------------------------------------------------
# tiles, from the shapes
# ---------------------------------------------------------------------------
def ssd_tiles(heads_per_group, p):
    """``(hb, t)``: the heads a grid step holds and the heads that share a
    lane tile. A block lies inside one group (its heads share B and C) and
    takes up to 512 lanes; a tile is 128 lanes or one head, whichever is
    wider. Nemotron (8 groups of 8 heads of 64): the 8 heads of a group, in
    tiles of 2; Granite (one group of 64 heads of 64): 8 of them."""
    t = max(1, _LANES // p)
    while heads_per_group % t:          # fewer heads than fill a tile
        t -= 1
    hb = t
    while heads_per_group % (hb * 2) == 0 and hb * 2 * p <= _BLOCK_LANES:
        hb *= 2
    return hb, t


def _dot(a, b, dims, cdt):
    """One product on the matrix unit: operands in the compute dtype,
    accumulated in float32. Two-byte operands say DEFAULT themselves (one
    pass of bf16 x bf16 into float32 is exact, and the chip's compiler
    refuses the package's process-wide HIGHEST inside a kernel); float32
    operands take HIGHEST, as the reference's do."""
    precision = (lax.Precision.HIGHEST if cdt == jnp.float32
                 else lax.Precision.DEFAULT)
    return lax.dot_general(a.astype(cdt), b.astype(cdt), dims,
                           precision=precision, preferred_element_type=_F32)


def _spread(cols, p, lane):
    """The ``t`` columns of ``cols`` (q, t), each over the ``p`` lanes of its
    head in a tile: (q, t * p), or (q, 1) to broadcast where a tile is one
    head."""
    t = cols.shape[1]
    out = cols[:, t - 1:t]
    for j in range(t - 2, -1, -1):
        out = jnp.where(lane < (j + 1) * p, cols[:, j:j + 1], out)
    return out


def _own_lanes(value, j, p, lane):
    """``value`` (q, t * p) on the lanes of the tile's head ``j``, zero on
    the others."""
    if value.shape[1] == p:
        return value
    return jnp.where((lane >= j * p) & (lane < (j + 1) * p), value, 0.0)


def _scores(cb, csc, csr, dtr, j, tri):
    """``(decay, cb * decay, S)`` of a tile's head ``j``: ``exp(cs_l -
    cs_s)`` under the causal mask, and ``S = (C B^T) * decay * dt_s``,
    float32 (q, q)."""
    seg = csc[:, j:j + 1] - csr[j:j + 1, :]
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    cbl = cb * decay
    return decay, cbl, cbl * dtr[j:j + 1, :]


# A tile's work is one jitted function: a kernel body is traced anew for
# every pallas_call (three a layer, and again where the tier's staged
# branches are transposed), and tracing the heads unrolled cost a warm
# start of the Nemotron cell 11 s (PERF.md sec. 6, PR 33); a jitted function
# is traced once for the shapes of a tile and found again after.
@functools.partial(jax.jit, static_argnames=("p",))
def _tile_forward(cb, csc, csr, dtr, ec, wc, xt, from_state, dexp, *, p):
    """The ``t`` heads of one lane tile, forward. ``csc``, ``ec``, ``wc``
    (q, t): the heads' ``cs``, ``exp(cs)`` and ``exp(cs_end - cs) dt`` down
    the sublanes; ``csr``, ``dtr`` (t, q): ``cs`` and ``dt`` along the
    lanes; ``xt`` (q, t * p); ``from_state`` the tile of ``C state``.
    Returns the tile of ``y`` (float32) and of ``x exp(cs_end - cs) dt``
    (compute dtype), which the state's update takes."""
    cdt = xt.dtype
    q, width = xt.shape
    tri = lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lane = lax.broadcasted_iota(jnp.int32, (q, width), 1)
    inside = None
    for j in range(csr.shape[0]):
        mine = _dot(_scores(cb, csc, csr, dtr, j, tri)[2], xt, _NN, cdt)
        inside = mine if inside is None else jnp.where(lane >= j * p, mine,
                                                       inside)
    x32 = xt.astype(_F32)
    y = inside + from_state * _spread(ec, p, lane) + dexp * x32
    return y, (x32 * _spread(wc, p, lane)).astype(cdt)


@functools.partial(jax.jit, static_argnames=("p",))
def _tile_backward(cb, csc, csr, dtr, ec, wc, xt, gt, from_state, d_weighed,
                   dexp, first, head, *, p):
    """The ``t`` heads of one lane tile, backward; operands as
    :func:`_tile_forward`, with ``gt`` the tile of ``dy``, ``d_weighed`` of
    ``B dstate``, ``first`` the tile's first head in the block and ``head``
    the (q, hb) iota the columns are placed by. Returns the tile of ``dx``
    (float32), the tile's part of ``d(C B^T)``, of the three (q, hb) column
    cotangents (``cs``, ``exp(cs)``, ``w``; zero outside the tile's heads)
    and, a head each, the rows ``d dt_s`` and ``d cs_s``; then the tiles of
    ``x w`` and ``dy exp(cs)`` (compute dtype) and of ``dD``."""
    cdt = xt.dtype
    q, width = xt.shape
    tri = lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lane = lax.broadcasted_iota(jnp.int32, (q, width), 1)
    x32, g32 = xt.astype(_F32), gt.astype(_F32)
    from_e = g32 * from_state               # summed over p: d exp(cs)
    from_w = d_weighed * x32                # summed over p: d w
    d_x = None
    d_cb = jnp.zeros((q, q), _F32)
    d_csc, d_ec, d_wc = (jnp.zeros(head.shape, _F32) for _ in range(3))
    d_dtr, d_csr = [], []
    for j in range(csr.shape[0]):
        decay, cbl, s = _scores(cb, csc, csr, dtr, j, tri)
        # dS[l, s] = sum_p dy[l, p] x[s, p] over this head's lanes
        d_s = _dot(_own_lanes(g32, j, p, lane), xt, _NT, cdt)
        d_cb = d_cb + d_s * decay * dtr[j:j + 1, :]
        moved = d_s * cbl                   # dS * S / dt_s
        here = head == first + j
        d_csc = jnp.where(here, jnp.sum(moved * dtr[j:j + 1, :], axis=1,
                                        keepdims=True), d_csc)
        down = jnp.sum(moved, axis=0, keepdims=True)            # (1, s)
        d_dtr.append(down)
        d_csr.append(-down * dtr[j:j + 1, :])
        mine = _dot(s, gt, _TN, cdt)        # S^T dy
        d_x = mine if d_x is None else jnp.where(lane >= j * p, mine, d_x)
        d_ec = jnp.where(here, jnp.sum(_own_lanes(from_e, j, p, lane),
                                       axis=1, keepdims=True), d_ec)
        d_wc = jnp.where(here, jnp.sum(_own_lanes(from_w, j, p, lane),
                                       axis=1, keepdims=True), d_wc)
    w = _spread(wc, p, lane)
    d_x = d_x + d_weighed * w + dexp * g32
    return (d_x, d_cb, d_csc, d_ec, d_wc, d_dtr, d_csr,
            (x32 * w).astype(cdt), (g32 * _spread(ec, p, lane)).astype(cdt),
            jnp.sum(g32 * x32, axis=0, keepdims=True))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _forward_kernel(x_ref, b_ref, c_ref, csc_ref, csr_ref, dtr_ref, ec_ref,
                    wc_ref, keep_ref, dexp_ref, y_ref, *rest, hb, t, p,
                    save_states):
    from jax.experimental import pallas as pl
    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    cdt = x_ref.dtype
    width = t * p
    x, bm, cm = x_ref[0], b_ref[0], c_ref[0]
    csc, ec, wc = csc_ref[0, 0], ec_ref[0, 0], wc_ref[0, 0]
    csr, dtr = csr_ref[0, 0, 0], dtr_ref[0, 0, 0]

    entering = state[...]                               # (N, hb * P) float32
    low = entering.astype(cdt)
    if save_states:
        rest[0][0, 0] = low
    cb = _dot(cm, bm, _NT, cdt)                         # (l, s)
    from_state = _dot(cm, low, _NN, cdt)                # (q, hb * P)
    weighed = []
    for k in range(hb // t):
        at, of = slice(k * width, (k + 1) * width), slice(k * t, (k + 1) * t)
        y, xw = _tile_forward(cb, csc[:, of], csr[of], dtr[of], ec[:, of],
                              wc[:, of], x[:, at], from_state[:, at],
                              dexp_ref[:, at], p=p)
        y_ref[0, :, at] = y.astype(y_ref.dtype)
        weighed.append(xw)
    weighed = jnp.concatenate(weighed, axis=1)
    state[...] = keep_ref[0, 0] * entering + _dot(bm, weighed, _TN, cdt)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _backward_kernel(x_ref, b_ref, c_ref, csc_ref, csr_ref, dtr_ref, ec_ref,
                     wc_ref, keep_ref, dexp_ref, states_ref, dy_ref,
                     dx_ref, db_ref, dc_ref, dcsc_ref, dcsr_ref, ddtr_ref,
                     dec_ref, dwc_ref, dkeep_ref, ddexp_ref, dstate,
                     *, hb, t, p):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        ddexp_ref[...] = jnp.zeros_like(ddexp_ref)

    cdt = x_ref.dtype
    q, width = x_ref.shape[1], t * p
    x, bm, cm, dy = x_ref[0], b_ref[0], c_ref[0], dy_ref[0]
    csc, ec, wc = csc_ref[0, 0], ec_ref[0, 0], wc_ref[0, 0]
    csr, dtr = csr_ref[0, 0, 0], dtr_ref[0, 0, 0]
    low = states_ref[0, 0]                              # (N, hb * P), cdt
    head = lax.broadcasted_iota(jnp.int32, (q, hb), 1)

    leaving = dstate[...]       # cotangent of the state this chunk leaves
    leaving_low = leaving.astype(cdt)
    cb = _dot(cm, bm, _NT, cdt)
    from_state = _dot(cm, low, _NN, cdt)                # (q, hb * P)
    d_weighed = _dot(bm, leaving_low, _NN, cdt)         # (q, hb * P)

    d_cb = jnp.zeros((q, q), _F32)
    d_csc, d_ec, d_wc = (jnp.zeros((q, hb), _F32) for _ in range(3))
    weighed, scaled, d_dexp = [], [], []
    for k in range(hb // t):
        at, of = slice(k * width, (k + 1) * width), slice(k * t, (k + 1) * t)
        (d_x, cb_k, csc_k, ec_k, wc_k, d_dtr, d_csr, xw, ge,
         dd) = _tile_backward(
             cb, csc[:, of], csr[of], dtr[of], ec[:, of], wc[:, of],
             x[:, at], dy[:, at], from_state[:, at], d_weighed[:, at],
             dexp_ref[:, at], jnp.int32(k * t), head, p=p)
        dx_ref[0, :, at] = d_x.astype(dx_ref.dtype)
        d_cb, d_csc, d_ec, d_wc = (d_cb + cb_k, d_csc + csc_k, d_ec + ec_k,
                                   d_wc + wc_k)
        for j in range(t):
            ddtr_ref[0, 0, 0, k * t + j:k * t + j + 1, :] = d_dtr[j]
            dcsr_ref[0, 0, 0, k * t + j:k * t + j + 1, :] = d_csr[j]
        weighed.append(xw)
        scaled.append(ge)
        d_dexp.append(dd)
    weighed = jnp.concatenate(weighed, axis=1)          # x exp(..) dt
    scaled = jnp.concatenate(scaled, axis=1)            # dy exp(cs)
    dcsc_ref[0, 0] = d_csc
    dec_ref[0, 0] = d_ec
    dwc_ref[0, 0] = d_wc
    ddexp_ref[0] += jnp.concatenate(d_dexp, axis=1)
    dc_ref[0] = (_dot(d_cb, bm, _NN, cdt)
                 + _dot(scaled, low, _NT, cdt)).astype(dc_ref.dtype)
    db_ref[0] = (_dot(d_cb, cm, _TN, cdt)
                 + _dot(weighed, leaving_low, _NT, cdt)).astype(db_ref.dtype)
    dkeep_ref[0, 0] = jnp.sum(leaving * low.astype(_F32), axis=0,
                              keepdims=True)
    dstate[...] = keep_ref[0, 0] * leaving + _dot(cm, scaled, _TN, cdt)


# ---------------------------------------------------------------------------
# the two calls
# ---------------------------------------------------------------------------
def _specs(q, p, n, hb, blocks_per_group, chunk_of):
    """Block specs of the operands both kernels share, by name; the chunk a
    grid step works on is ``chunk_of(ci)`` (forward: itself; backward: from
    the last one down)."""
    from jax.experimental import pallas as pl
    lanes = hb * p
    return {
        "x": pl.BlockSpec((1, q, lanes),
                          lambda bi, hi, ci: (bi, chunk_of(ci), hi)),
        "bc": pl.BlockSpec((1, q, n), lambda bi, hi, ci: (
            bi, chunk_of(ci), lax.div(hi, blocks_per_group))),
        "partial": pl.BlockSpec((1, q, n),
                                lambda bi, hi, ci: (bi, chunk_of(ci), hi)),
        "col": pl.BlockSpec((1, 1, q, hb),
                            lambda bi, hi, ci: (bi, hi, chunk_of(ci), 0)),
        "row": pl.BlockSpec((1, 1, 1, hb, q),
                            lambda bi, hi, ci: (bi, hi, chunk_of(ci), 0, 0)),
        "keep": pl.BlockSpec((1, 1, 1, lanes),
                             lambda bi, hi, ci: (bi, chunk_of(ci), 0, hi)),
        "dexp": pl.BlockSpec((1, lanes), lambda bi, hi, ci: (0, hi)),
        "ddexp": pl.BlockSpec((1, 1, lanes), lambda bi, hi, ci: (bi, 0, hi)),
        "states": pl.BlockSpec((1, 1, n, lanes),
                               lambda bi, hi, ci: (bi, chunk_of(ci), 0, hi)),
    }


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 * 2 ** 20)


# The calls are jitted for the same reason as the tiles: every layer of a
# model has the same shapes, and the tier's staged branches are transposed
# twice, so one trace and one lowering of a kernel serve them all.
@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _forward_call(dims, interpret, save_states, x, b, c, csc, csr, dtr, ec,
                  wc, keep, dexp):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    q, heads, p, n, hb, t, bpg = dims
    bsz, length, _ = x.shape
    nc = length // q
    sp = _specs(q, p, n, hb, bpg, lambda ci: ci)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [sp["x"]]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((bsz, nc, n, heads * p),
                                              x.dtype))
        out_specs.append(sp["states"])
    out = pl.pallas_call(
        functools.partial(_forward_kernel, hb=hb, t=t, p=p,
                          save_states=save_states),
        grid=(bsz, heads // hb, nc),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["col"], sp["row"],
                  sp["row"], sp["col"], sp["col"], sp["keep"], sp["dexp"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, hb * p), _F32)],
        compiler_params=_compiler_params(), interpret=interpret,
    )(x, b, c, csc, csr, dtr, ec, wc, keep, dexp)
    return out if save_states else out[0]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _backward_call(dims, interpret, x, b, c, csc, csr, dtr, ec, wc, keep,
                   dexp, states, dy):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    q, heads, p, n, hb, t, bpg = dims
    bsz, length, _ = x.shape
    nc, nhb = length // q, heads // hb
    sp = _specs(q, p, n, hb, bpg, lambda ci: nc - 1 - ci)
    # a block that holds its whole group writes the group's dB and dC; else
    # the blocks' float32 partials are added outside
    partial = jax.ShapeDtypeStruct((bsz, length, nhb * n),
                                   x.dtype if bpg == 1 else _F32)

    def like(a):
        return jax.ShapeDtypeStruct(a.shape, _F32)

    return pl.pallas_call(
        functools.partial(_backward_kernel, hb=hb, t=t, p=p),
        grid=(bsz, nhb, nc),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["col"], sp["row"],
                  sp["row"], sp["col"], sp["col"], sp["keep"], sp["dexp"],
                  sp["states"], sp["x"]],
        out_specs=[sp["x"], sp["partial"], sp["partial"], sp["col"],
                   sp["row"], sp["row"], sp["col"], sp["col"], sp["keep"],
                   sp["ddexp"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), partial, partial,
                   like(csc), like(csr), like(dtr), like(ec), like(wc),
                   like(keep),
                   jax.ShapeDtypeStruct((bsz, 1, heads * p), _F32)],
        scratch_shapes=[pltpu.VMEM((n, hb * p), _F32)],
        compiler_params=_compiler_params(), interpret=interpret,
    )(x, b, c, csc, csr, dtr, ec, wc, keep, dexp, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scan(dims, interpret, x, b, c, csc, csr, dtr, ec, wc, keep, dexp):
    return _forward_call(dims, interpret, False, x, b, c, csc, csr, dtr, ec,
                         wc, keep, dexp)


def _scan_fwd(dims, interpret, *operands):
    y, states = _forward_call(dims, interpret, True, *operands)
    return y, operands + (states,)


def _scan_bwd(dims, interpret, kept, dy):
    q, heads, p, n, hb, t, bpg = dims
    x, b = kept[0], kept[1]
    dx, db, dc, dcsc, dcsr, ddtr, dec, dwc, dkeep, ddexp = _backward_call(
        dims, interpret, *kept, dy)
    bsz, length, _ = x.shape

    def group_sum(part):
        part = part.reshape(bsz, length, heads // hb // bpg, bpg, n)
        return jnp.sum(part, axis=3).reshape(b.shape).astype(b.dtype)

    return (dx, group_sum(db), group_sum(dc), dcsc, dcsr, ddtr, dec, dwc,
            dkeep, jnp.sum(ddexp, axis=0))


_scan.defvjp(_scan_fwd, _scan_bwd)


# ---------------------------------------------------------------------------
# registration
# ---------------------------------------------------------------------------
def _ssd_supports(x, dt, cs, b, c, d, chunk_size=256):
    q = int(chunk_size)
    if x.ndim != 4 or b.ndim != 4 or c.shape != b.shape \
            or dt.shape != x.shape[:3] or cs.shape != dt.shape \
            or x.shape[2] % b.shape[2] or x.shape[1] % q:
        return (f"shape:x{x.shape}_dt{dt.shape}_cs{cs.shape}_b{b.shape}"
                f"_c{c.shape}_chunk{q}")
    if x.dtype not in (jnp.bfloat16, jnp.float32) or b.dtype != x.dtype \
            or c.dtype != x.dtype:
        return f"dtype:{x.dtype}_{b.dtype}_{c.dtype}"
    if dt.dtype != _F32 or cs.dtype != _F32:
        return f"dtype:dt_{dt.dtype}_cs_{cs.dtype}"
    if x.size == 0:
        return "empty"
    heads, p = x.shape[2:]
    groups, n = b.shape[2:]
    hb, t = ssd_tiles(heads // groups, p)
    # whole tiles of the chip's registers: the lanes of a tile of heads, of
    # B and C, and of the (l, s) matrices
    if (t * p) % _LANES or n % _LANES or q % _LANES:
        return f"tile:p{p}_heads_per_group{heads // groups}_n{n}_chunk{q}"
    return None


def _ssd_example():
    rng = np.random.RandomState(6)

    def case(length, heads, p, groups, n, q):
        x = jnp.asarray(rng.randn(1, length, heads, p), _F32)
        dt = jnp.asarray(np.log1p(np.exp(rng.randn(1, length, heads) - 2.0)),
                         _F32)
        a = -jnp.asarray(rng.uniform(1, 16, heads), _F32)
        cs = jnp.cumsum((dt * a).reshape(1, length // q, q, heads),
                        axis=2).reshape(dt.shape)
        b, c = (jnp.asarray(rng.randn(1, length, groups, n) * 0.3, _F32)
                for _ in range(2))
        d = jnp.asarray(rng.randn(heads), _F32)
        return (x, dt, cs, b, c, d), {"chunk_size": q}

    # whole register tiles, as the chip's compiler wants them (chip_smoke.py
    # compiles these): three chunks at one group of four heads of 64 (a
    # block of two lane tiles); two chunks at two groups of two
    return [case(384, 4, 64, 1, 128, 128), case(256, 4, 64, 2, 128, 128)]


@register_kernel(
    "mamba2_ssd", xla_reference=ssd_reference, tolerance=1e-4,
    backends=("tpu",), supports=_ssd_supports, example=_ssd_example,
    doc="The chunked Mamba-2 scan (ops/ssm.py) as one pass over the chunks "
        "with the running state of a block of heads in fast memory: HBM "
        "sees x, B, C once and y once, in the compute dtype, and neither a "
        "float32 term of y nor a stack of float32 chunk states. Backward: "
        "a second kernel over the chunks in reverse, from the states the "
        "forward wrote in the compute dtype. Blocks from the shapes "
        "(ssd_tiles). The reference is the jax.numpy scan (lax.scan over "
        "the chunk states): PERF.md sec. 6, PR 33 has both on a v5e.")
# jitted like the calls inside it: autodiff then works on one cached jaxpr a
# shape, not on the re-layouts and the custom_vjp of every layer anew
@functools.partial(jax.jit, static_argnames=("interpret", "chunk_size"))
def _ssd_pallas(x, dt, cs, b, c, d, interpret=False, chunk_size=256):
    bsz, length, heads, p = x.shape
    groups, n = b.shape[2:]
    q = chunk_size
    nc = length // q
    hb, t = ssd_tiles(heads // groups, p)
    nhb = heads // hb
    dims = (q, heads, p, n, hb, t, heads // groups // hb)

    def by_block(a):                    # (B, L, H) -> (B, nc, q, nhb, hb)
        return a.reshape(bsz, nc, q, nhb, hb)

    def col(a):                         # positions down the sublanes
        return a.transpose(0, 3, 1, 2, 4).reshape(bsz, nhb, length, hb)

    def row(a):                         # positions along the lanes
        return a.transpose(0, 3, 1, 4, 2)

    cs_b, dt_b = by_block(cs), by_block(dt)
    end = cs_b[:, :, -1:]
    keep = jnp.repeat(jnp.exp(end).reshape(bsz, nc, 1, heads), p, axis=-1)
    y = _scan(dims, bool(interpret),
              x.reshape(bsz, length, heads * p),
              b.reshape(bsz, length, groups * n),
              c.reshape(bsz, length, groups * n),
              col(cs_b), row(cs_b), row(dt_b), col(jnp.exp(cs_b)),
              col(jnp.exp(end - cs_b) * dt_b), keep,
              jnp.repeat(d.astype(_F32), p).reshape(1, heads * p))
    return y.reshape(x.shape)
