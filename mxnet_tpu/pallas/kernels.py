"""Seed kernels of the guarded Pallas tier (docs/pallas.md).

Four kernels, each dispatched by a benchmark cell:

- ``matmul_epilogue`` — the BERT lever (~56% MFU inside XLA's matmul
  fusions, dropout-mask traffic measured 24% of a step pre-rbg): bias +
  activation + inverted dropout applied in one pass over the matmul
  output, wired behind the Gluon Dense/PositionwiseFFN path. Dropout
  keys follow the PR-1 ``(layer, tick, shard)`` fold discipline via
  :func:`dropout_bits`; mask semantics are bit-identical to
  ``ops/nn.py``'s Dropout (one uint8 per element, keep = bits >= ⌈p·256⌉).
- ``blockwise_attention`` — the existing long-context online-softmax
  kernel (parallel/ring_attention.py), routed through the same registry
  so every custom kernel shares one kill-switch / parity / journal story.
- ``grouped_matmul`` — rows ordered by group, one weight matrix a group:
  the routed-expert layer's two products (``ops/moe.py``), on the
  library's megablox kernels with ``lax.ragged_dot`` as the reference.
- ``mamba2_ssd`` — the chunked Mamba-2 scan (``ops/ssm.py``) as one pass
  over the chunks with the running state in fast memory, forward and
  backward; kernel and reference live in :mod:`.ssd`.
- ``power_retention`` — chunked power retention (``ops/retention.py``) as
  one pass over a head's chunks with its state in fast memory, forward and
  backward; kernel and reference live in :mod:`.retention`.

(Another, ``conv_epilogue``, was the ResNet lever until it lost on the
chip — a 2-D view of a tiled NCHW activation is a physical re-layout,
5.5x the step; PERF.md §6, PR 26 — and was deleted in PR 29.)

Every kernel registers with its XLA reference and tolerance; gradients of
the epilogue's Pallas paths are ``custom_vjp`` with the reference's VJP as
the backward (rematerialized — the backward is mathematically the
reference's, so the parity gate bounds the full training step, not just the
forward); the grouped product and the scan have backward kernels of their
own, held to autodiff of their references by tests/test_pallas.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from . import retention as _retention  # noqa: F401  (power_retention)
from . import ssd as _ssd      # noqa: F401  (registers mamba2_ssd)
from .registry import (block_ok, default_block, dispatch,
                       register_kernel)

__all__ = ["fused_matmul_epilogue", "dropout_bits", "keep_threshold",
           "EPILOGUE_ACTS"]


def _block_pair(r, c, block):
    """Resolve the (block_r, block_c) tiling for an (r, c) view: an
    explicit/tuned ``block`` where the chip's compiler takes it
    (registry.block_ok), else the default. The grid is ``pl.cdiv`` — edge
    blocks are padded on read and masked on write — and the epilogues are
    elementwise, so every tiling gives bit-identical results."""
    if block is not None:
        try:
            br, bc = int(block[0]), int(block[1])
        except (TypeError, ValueError, IndexError):
            br = bc = 0
        if block_ok(r, c, br, bc):
            return br, bc
    return default_block(r, c)


def _erf(x):
    """erf for float32 from primitives the TPU kernel compiler lowers
    (it has no ``erf``/``erfc``): the clamped rational approximation
    x·P(x²)/Q(x²) that Eigen and XLA use for float32. Its error against
    the exact function stays under 1e-6, so GELU built on it is inside
    the epilogues' registered 1e-5 tolerance (tests/test_pallas.py)."""
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p = jnp.float32(-2.72614225801306e-10)
    for a in (2.77068142495902e-08, -2.10102402082508e-06,
              -5.69250639462346e-05, -7.34990630326855e-04,
              -2.95459980854025e-03, -1.60960333262415e-02):
        p = p * x2 + jnp.float32(a)
    q = jnp.float32(-1.45660718464996e-05)
    for b in (-2.13374055278905e-04, -1.68282697438203e-03,
              -7.37332916720468e-03, -1.42647390514189e-02):
        q = q * x2 + jnp.float32(b)
    return x * p / q


def _act_fn(act_type, in_kernel=False):
    """Activation by MXNet name. MXNet's ``gelu`` is the erf form; inside
    a kernel it is built on :func:`_erf`, in the reference on XLA's own."""
    fns = {
        None: lambda x: x,
        "identity": lambda x: x,
        "relu": lambda x: jnp.maximum(x, 0.0),
        "gelu": ((lambda x: 0.5 * x * (1.0 + _erf(x * np.float32(0.5 ** 0.5))))
                 if in_kernel else
                 (lambda x: jax.nn.gelu(x, approximate=False))),
        "tanh": jnp.tanh,
        "sigmoid": jax.nn.sigmoid,
    }
    try:
        return fns[act_type]
    except KeyError:
        raise MXNetError(f"pallas epilogue: unknown act_type {act_type!r}; "
                         f"one of {sorted(k for k in fns if k)}") from None


EPILOGUE_ACTS = ("identity", "relu", "gelu", "tanh", "sigmoid")


def _out_struct(y, args):
    """Output type of an elementwise epilogue over ``args``: y's shape and
    dtype, varying over every manual mesh axis any operand varies over
    (inside a ``shard_map`` the call has to say so; outside there are none)."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in args))
    return jax.ShapeDtypeStruct(y.shape, y.dtype, vma=vma)


def _vec_spec(shape, br, bc):
    """BlockSpec for a (1, C) column-broadcast or (R, 1) row-broadcast
    vector riding next to (br, bc) data blocks."""
    from jax.experimental import pallas as pl
    if shape[1] == 1:       # (R, 1); a (1, C) vector has C >= 8 (supports)
        return pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    return pl.BlockSpec((1, bc), lambda i, j: (0, j))


def _check_vec(name, v, y):
    if v.shape not in ((1, y.shape[1]), (y.shape[0], 1)):
        return (f"shape:{name}{v.shape}_vs_y{y.shape} (want (1, C) or "
                f"(R, 1))")
    return _check_dtype(name, v)


def _check_dtype(name, x):
    """float32 and bfloat16 are the float types the chip's kernel compiler
    loads as vectors; it refuses float16 ("Invalid vector type")."""
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype:{name}_{x.dtype}"
    return None


def _epilogue_tune_key(y, *rest, **params):
    """Shape class of an epilogue dispatch ("RxC") — the tuned-table key
    under which a committed block shape applies to this call."""
    if getattr(y, "ndim", 0) != 2:
        return None
    return f"{y.shape[0]}x{y.shape[1]}"


# ---------------------------------------------------------------------------
# matmul epilogue: dropout(act(y + bias)) in one pass over the matmul output
# ---------------------------------------------------------------------------
def keep_threshold(p):
    """uint8 keep threshold, bit-identical to ops/nn.py Dropout: one
    random byte per element, keep where bits >= threshold."""
    return min(255, int(round(float(p) * 256)))


def dropout_bits(key, shape, layer=0, tick=0, shard=0):
    """Per-call dropout bytes under the PR-1 fold discipline: the
    (layer, tick, shard) identity folds into the key so every layer,
    microbatch/scan tick, and shard draws an independent mask from one
    threaded key (the correlated-mask class fixed in PR 1)."""
    for v in (layer, tick, shard):
        key = jax.random.fold_in(key, v)
    return jax.random.bits(key, tuple(shape), dtype=jnp.uint8)


def _matmul_epilogue_ref(y, bias, bits=None, act_type="gelu", p=0.0,
                         block=None):
    out = _act_fn(act_type)(y.astype(jnp.float32)
                            + bias.astype(jnp.float32))
    if bits is not None and p > 0:
        keep = bits >= jnp.uint8(keep_threshold(p))
        out = jnp.where(keep, out / (1.0 - p), 0.0)
    return out.astype(y.dtype)


def _matmul_epilogue_call(y, bias, bits, act_type, p, interpret, block):
    from jax.experimental import pallas as pl
    r, c = y.shape
    br, bc = _block_pair(r, c, block)
    act = _act_fn(act_type, in_kernel=True)
    data = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    thresh = keep_threshold(p)
    inv = 1.0 / (1.0 - p) if p < 1.0 else 0.0

    def kernel(y_ref, b_ref, *rest):
        o_ref = rest[-1]
        out = act(y_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32))
        if len(rest) == 2:
            # the chip has no uint8 vector compare: widen the bytes first
            keep = rest[0][...].astype(jnp.int32) >= thresh
            out = jnp.where(keep, out * inv, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)

    in_specs = [data, _vec_spec(bias.shape, br, bc)]
    args = [y, bias]
    if bits is not None and p > 0:
        in_specs.append(data)
        args.append(bits)
    return pl.pallas_call(
        kernel, grid=(pl.cdiv(r, br), pl.cdiv(c, bc)), in_specs=in_specs,
        out_specs=data,
        out_shape=_out_struct(y, args),
        interpret=interpret)(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _me_drop(act_type, p, interpret, block, y, bias, bits):
    return _matmul_epilogue_call(y, bias, bits, act_type, p, interpret,
                                 block)


def _me_drop_fwd(act_type, p, interpret, block, y, bias, bits):
    return (_me_drop(act_type, p, interpret, block, y, bias, bits),
            (y, bias, bits))


def _me_drop_bwd(act_type, p, interpret, block, saved, g):
    y, bias, bits = saved
    _, vjp = jax.vjp(
        lambda a, b: _matmul_epilogue_ref(a, b, bits, act_type=act_type,
                                          p=p), y, bias)
    dy, dbias = vjp(g)
    # integer primal: cotangent must be float0, not None
    return dy, dbias, np.zeros(bits.shape, dtype=jax.dtypes.float0)


_me_drop.defvjp(_me_drop_fwd, _me_drop_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _me_nodrop(act_type, interpret, block, y, bias):
    return _matmul_epilogue_call(y, bias, None, act_type, 0.0, interpret,
                                 block)


def _me_nodrop_fwd(act_type, interpret, block, y, bias):
    return _me_nodrop(act_type, interpret, block, y, bias), (y, bias)


def _me_nodrop_bwd(act_type, interpret, block, saved, g):
    y, bias = saved
    _, vjp = jax.vjp(
        lambda a, b: _matmul_epilogue_ref(a, b, act_type=act_type), y, bias)
    return vjp(g)


_me_nodrop.defvjp(_me_nodrop_fwd, _me_nodrop_bwd)


def _matmul_epilogue_supports(y, bias, bits=None, act_type="gelu", p=0.0,
                              block=None):
    if y.ndim != 2:
        return f"not_2d:{y.shape}"
    if y.size == 0:
        return "empty"
    if y.shape[1] < 8:
        return f"minor_dim_tiny:{y.shape[1]}"
    bad = _check_dtype("y", y) or _check_vec("bias", bias, y)
    if bad:
        return bad
    if bits is not None:
        if bits.shape != y.shape:
            return f"shape:bits{bits.shape}_vs_y{y.shape}"
        if bits.dtype != jnp.uint8:
            return f"dtype:bits_{bits.dtype}"
    if act_type not in (None,) + EPILOGUE_ACTS:
        return f"act:{act_type}"
    if not 0.0 <= float(p) < 1.0:
        return f"p:{p}"
    return None


def _matmul_epilogue_example():
    rng = np.random.RandomState(1)
    y = jnp.asarray(rng.randn(32, 128), jnp.float32)
    b = jnp.asarray(rng.randn(1, 128) * 0.1, jnp.float32)
    bits = dropout_bits(  # graftlint: disable=G2 deterministic parity-gate fixture
        jax.random.key(7), (32, 128), layer=1, tick=2)
    return [
        ((y, b, None), {"act_type": "gelu", "p": 0.0}),
        ((y, b, bits), {"act_type": "gelu", "p": 0.3}),
        ((y, b, bits), {"act_type": "identity", "p": 0.5}),
    ]


@register_kernel(
    "matmul_epilogue", xla_reference=_matmul_epilogue_ref, tolerance=1e-5,
    backends=("tpu",), supports=_matmul_epilogue_supports,
    example=_matmul_epilogue_example,
    doc="dropout(act(y + bias)) in one pass over a matmul output — the "
        "BERT MFU lever (docs/perf_notes.md: dropout-in-epilogue, "
        "ROADMAP.md S3/S4). Mask semantics bit-identical to "
        "ops/nn.py Dropout; bits come from dropout_bits() under the "
        "PR-1 (layer, tick, shard) fold discipline. block=(br, bc) "
        "overrides the default tiling (tuned tables; a block the chip's "
        "compiler would refuse clamps to the default).",
    tune_key=_epilogue_tune_key)
def _matmul_epilogue_pallas(y, bias, bits=None, interpret=False,
                            act_type="gelu", p=0.0, block=None):
    block = None if block is None else (int(block[0]), int(block[1]))
    if bits is None or p <= 0:
        return _me_nodrop(act_type, bool(interpret), block, y, bias)
    return _me_drop(act_type, float(p), bool(interpret), block, y, bias,
                    bits)


# ---------------------------------------------------------------------------
# blockwise attention: the existing online-softmax kernel, same guard story
# ---------------------------------------------------------------------------
def _blockwise_ref(q, k, v, block_size=512, causal=False, scale=None,
                   _chunk=2048):
    """Dense-attention reference with the query axis chunked: the same
    math as attention_reference (each chunk sees its exact key prefix,
    so bottom-right causal alignment is preserved), but the score-matrix
    footprint is bounded at chunk×S — the kill switch must not turn a
    long-context run's O(S·block) memory into an O(S²) OOM."""
    from ..parallel.ring_attention import attention_reference
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    s_q, s_kv = q.shape[-2], k.shape[-2]
    if s_q <= _chunk:
        return attention_reference(q, k, v, causal=causal, scale=scale)
    outs = []
    for i in range(0, s_q, _chunk):
        qc = q[..., i:i + _chunk, :]
        length = qc.shape[-2]
        if not causal:
            outs.append(attention_reference(qc, k, v, causal=False,
                                            scale=scale))
            continue
        # bottom-right alignment: global row i+r attends keys
        # j <= i + r + (s_kv - s_q). Slicing keys to that chunk's max
        # makes the reference's own (kmax - length) offset land exactly
        # there; a non-positive kmax means every row's set is empty.
        kmax = i + length + s_kv - s_q
        if kmax <= 0:
            outs.append(jnp.zeros(qc.shape[:-1] + v.shape[-1:], q.dtype))
            continue
        outs.append(attention_reference(
            qc, k[..., :kmax, :], v[..., :kmax, :], causal=True,
            scale=scale))
    return jnp.concatenate(outs, axis=-2)


def _blockwise_supports(q, k, v, block_size=512, causal=False, scale=None):
    if q.shape[-1] != k.shape[-1] or k.shape[:-1] != v.shape[:-1]:
        return f"shape:q{q.shape}_k{k.shape}_v{v.shape}"
    if q.size == 0:
        return "empty"
    return None


def _blockwise_example():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(2, 2, 64, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, 64, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, 64, 16), jnp.float32)
    return [
        ((q, k, v), {"block_size": 16, "causal": False}),
        ((q, k, v), {"block_size": 16, "causal": True}),
    ]


@register_kernel(
    "blockwise_attention", xla_reference=_blockwise_ref, tolerance=2e-4,
    backends=("tpu", "cpu", "gpu"), supports=_blockwise_supports,
    example=_blockwise_example,
    doc="Memory-efficient online-softmax attention over KV blocks "
        "(parallel/ring_attention.py) — registered so the long-context "
        "kernel shares the tier's kill-switch, parity gate, and journal "
        "story. Portable (lax.scan), so every backend is a first-class "
        "target; the reference materializes the full score matrix.")
def _blockwise_pallas(q, k, v, interpret=False, block_size=512, causal=False,
                      scale=None):
    from ..parallel.ring_attention import _blockwise_impl
    return _blockwise_impl(q, k, v, block_size=block_size, causal=causal,
                           scale=scale)


# ---------------------------------------------------------------------------
# grouped matmul: rows ordered by group, one weight matrix a group
# ---------------------------------------------------------------------------
def _grouped_ref(lhs, rhs, group_sizes):
    """``out[r] = lhs[r] @ rhs[g]`` for the rows ``r`` of group ``g`` (the
    first ``group_sizes[0]`` rows are group 0's, and so on); rows past the
    groups' end are zero. Accumulated in float32, returned in lhs's dtype.
    Two-byte operands say DEFAULT precision themselves, so that the products
    autodiff derives say it too: one pass of bf16 x bf16 into float32 is
    exact, and the chip's compiler refuses the package's process-wide
    HIGHEST on them ("Bad lhs type")."""
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes,
        precision=None if lhs.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32).astype(lhs.dtype)


def _dividing_tile(n, cap):
    """The whole of ``n`` where it is under ``cap``, else the largest
    multiple of 128 up to ``cap`` that divides it, else ``cap`` (the
    library kernels mask a last partial tile)."""
    if n <= cap:
        return n
    return next((t for t in range(cap // 128 * 128, 0, -128) if n % t == 0),
                cap)


def _row_tile(m, cap):
    return next(t for t in (512, 256, 128, 64, 32, 16, 8)
                if t <= cap and m % t == 0)


def grouped_tiles(m, k, n, itemsize=2):
    """Row, contraction and column tiles of the library's ``gmm`` for an
    (m, k) x (G, k, n) product, from the shapes. The sweep on a v5e
    (PERF.md sec. 6, PR 32; 12288 rows of which 16 groups own 6144, bf16):
    rows of 256 beat 512 (a group of 384 rows fills whole tiles of 512 at
    best by half) and 128; a contraction or column dimension taken whole is
    best where it fits (1856), else its largest divisor that is a multiple
    of 128 (2688: 896); one weight tile of up to 4 MB, two in flight, stays
    inside the kernel's 16 MB of fast memory with the row tiles and the
    float32 accumulator. Float32 operands take half the elements."""
    scale = max(1, itemsize // 2)
    whole, cap, budget = 2048 // scale, 1024 // scale, 2 ** 21 // scale
    tk = _dividing_tile(k, whole if k <= whole else cap)
    tn = _dividing_tile(n, whole if n <= whole else cap)
    if tk * tn > budget:
        tn = _dividing_tile(n, cap)
    if tk * tn > budget:
        tk = _dividing_tile(k, cap)
    return _row_tile(m, 256), tk, tn


def grouped_weight_tiles(m, k, n, itemsize=2):
    """The tiles of the library's ``tgmm`` (the weights' gradient: (k, m) x
    (m, n) -> (G, k, n)): its float32 accumulator is a whole (tk, tn) output
    tile, so both stay under 1024 (512 for float32 operands), and the rows
    it contracts over go 512 at a time."""
    cap = 1024 // max(1, itemsize // 2)
    return _row_tile(m, 512), _dividing_tile(k, cap), _dividing_tile(n, cap)


def _megablox():
    # the package's own ``gmm`` attribute is a function that hides the
    # module of the same name
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _grouped_precision(operand):
    """The package asks for HIGHEST precision on every float32 matmul,
    process-wide, and the compiler refuses that on a kernel's bfloat16
    product ("Bad lhs type"): one pass of bf16 x bf16 into float32 is
    already exact, so the default changes no result there. Held over the
    backward's trace too, which runs outside any scope the forward
    opened."""
    import contextlib
    if operand.dtype == jnp.float32:
        return contextlib.nullcontext()
    return jax.default_matmul_precision("default")


def _past_the_end(out, group_sizes):
    """Zero the rows no group owns: the library kernel skips their tiles
    and leaves what was in memory."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
    return jnp.where(rows < jnp.sum(group_sizes), out, jnp.zeros_like(out))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_library(lhs, rhs, group_sizes, interpret):
    megablox = _megablox()
    m, k = lhs.shape
    with _grouped_precision(lhs):
        out = megablox.gmm(lhs, rhs, group_sizes, lhs.dtype,
                           grouped_tiles(m, k, rhs.shape[2],
                                         lhs.dtype.itemsize),
                           interpret=interpret)
    return _past_the_end(out, group_sizes)


def _grouped_library_fwd(lhs, rhs, group_sizes, interpret):
    return (_grouped_library(lhs, rhs, group_sizes, interpret),
            (lhs, rhs, group_sizes))


def _grouped_library_bwd(interpret, kept, g):
    megablox = _megablox()
    lhs, rhs, group_sizes = kept
    m, k = lhs.shape
    n, size = rhs.shape[2], lhs.dtype.itemsize
    with _grouped_precision(lhs):
        d_lhs = megablox.gmm(g, rhs, group_sizes, lhs.dtype,
                             grouped_tiles(m, n, k, size), transpose_rhs=True,
                             interpret=interpret)
        d_rhs = megablox.tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                              grouped_weight_tiles(m, k, n, size),
                              num_actual_groups=rhs.shape[0],
                              interpret=interpret)
    return _past_the_end(d_lhs, group_sizes), d_rhs, None


_grouped_library.defvjp(_grouped_library_fwd, _grouped_library_bwd)


def _grouped_supports(lhs, rhs, group_sizes):
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1] \
            or group_sizes.shape != (rhs.shape[0],):
        return f"shape:lhs{lhs.shape}_rhs{rhs.shape}_sizes{group_sizes.shape}"
    if lhs.dtype != rhs.dtype or lhs.dtype not in (jnp.bfloat16,
                                                    jnp.float32):
        return f"dtype:{lhs.dtype}_{rhs.dtype}"
    if lhs.shape[0] % 8 or lhs.shape[0] == 0:
        return f"rows:{lhs.shape[0]}"
    return None


def _grouped_example():
    rng = np.random.RandomState(4)
    lhs = jnp.asarray(rng.randn(64, 128), jnp.float32)
    rhs = jnp.asarray(rng.randn(4, 128, 256) * 0.1, jnp.float32)
    return [
        # an empty group, and rows past the groups' end
        ((lhs, rhs, jnp.asarray([20, 0, 17, 11], jnp.int32)), {}),
        ((lhs, rhs, jnp.asarray([16, 16, 16, 16], jnp.int32)), {}),
    ]


@register_kernel(
    "grouped_matmul", xla_reference=_grouped_ref, tolerance=1e-4,
    backends=("tpu",), supports=_grouped_supports, example=_grouped_example,
    doc="Rows ordered by group times one (k, n) matrix a group: the two "
        "products of a routed-expert layer (ops/moe.py). The library's "
        "megablox kernels (custom calls named gmm and tgmm) with tiles "
        "from the shapes; they visit only the row tiles a group owns, so "
        "rows past the groups' end cost nothing. lax.ragged_dot is the "
        "reference: 3.3x the kernel's time forward and backward at 16 "
        "groups of (2688, 1856) on a v5e (PERF.md sec. 6, PR 32).")
def _grouped_pallas(lhs, rhs, group_sizes, interpret=False):
    return _grouped_library(lhs, rhs, group_sizes, bool(interpret))


# ---------------------------------------------------------------------------
# N-D wrapper — the surface ops/ and gluon/ wire against
# ---------------------------------------------------------------------------
def fused_matmul_epilogue(y, bias, act_type=None, p=0.0, rng=None,
                          training=False, layer=0, tick=0, shard=0,
                          interpret=False):
    """N-D entry for the matmul epilogue: dropout(act(y + bias)) with
    ``bias`` along the minor axis. Dropout engages only in training with
    ``p > 0`` and an rng key; bits derive via :func:`dropout_bits` under
    the (layer, tick, shard) fold discipline."""
    shape = y.shape
    c = shape[-1]
    y2 = y.reshape(-1, c)
    b2 = (jnp.zeros((1, c), y.dtype) if bias is None
          else bias.reshape(1, c))
    bits = None
    p = float(p)
    if training and p > 0 and rng is not None:
        bits = dropout_bits(rng, y2.shape, layer=layer, tick=tick,
                            shard=shard)
    out = dispatch("matmul_epilogue", y2, b2, bits, act_type=act_type,
                   p=p if bits is not None else 0.0, interpret=interpret)
    return out.reshape(shape)
