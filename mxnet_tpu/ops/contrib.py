"""Contrib ops — detection kernels and misc.

TPU-native equivalent of ``src/operator/contrib/`` (MultiBoxPrior, box_nms,
ROIAlign, BilinearResize2D, ...). The reference hand-writes CUDA for these;
here they are static-shape jnp/lax formulations (greedy NMS as a fori_loop,
ROIAlign as vectorized bilinear gathers) which XLA compiles for the VPU; a
Pallas fast path can slot in later where profiling justifies it.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from ..pallas.kernels import EPILOGUE_ACTS
from .nn import _epilogue_act
from .registry import OpParam, register


def _box_iou_corner(a, b):
    """IoU between (..., N, 4) and (..., M, 4) corner boxes -> (..., N, M)."""
    tl = jnp.maximum(a[..., :, None, :2], b[..., None, :, :2])
    br = jnp.minimum(a[..., :, None, 2:], b[..., None, :, 2:])
    wh = jnp.maximum(br - tl, 0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = jnp.maximum(a[..., 2] - a[..., 0], 0) * jnp.maximum(a[..., 3] - a[..., 1], 0)
    area_b = jnp.maximum(b[..., 2] - b[..., 0], 0) * jnp.maximum(b[..., 3] - b[..., 1], 0)
    union = area_a[..., :, None] + area_b[..., None, :] - inter
    return jnp.where(union > 0, inter / union, jnp.zeros_like(inter))


@register("_contrib_box_iou", aliases=["box_iou"], num_inputs=2,
          params=[OpParam("format", str, "corner")],
          differentiable=False,
          doc="Pairwise IoU (ref: src/operator/contrib/bounding_box.cc box_iou)")
def _box_iou(lhs, rhs, format="corner"):
    if format == "center":
        def c2c(b):
            xy = b[..., :2]
            wh = b[..., 2:] / 2
            return jnp.concatenate([xy - wh, xy + wh], axis=-1)
        lhs, rhs = c2c(lhs), c2c(rhs)
    return _box_iou_corner(lhs, rhs)


@register("_contrib_box_nms", aliases=["box_nms"],
          params=[OpParam("overlap_thresh", float, 0.5),
                  OpParam("valid_thresh", float, 0.0),
                  OpParam("topk", int, -1),
                  OpParam("coord_start", int, 2),
                  OpParam("score_index", int, 1),
                  OpParam("id_index", int, -1),
                  OpParam("background_id", int, -1),
                  OpParam("force_suppress", bool, False),
                  OpParam("in_format", str, "corner"),
                  OpParam("out_format", str, "corner")],
          differentiable=False,
          doc="Greedy non-max suppression, static shapes: suppressed entries "
              "are filled with -1 like the reference "
              "(ref: src/operator/contrib/bounding_box.cc box_nms)")
def _box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1,
             coord_start=2, score_index=1, id_index=-1, background_id=-1,
             force_suppress=False, in_format="corner", out_format="corner"):
    batched = data.ndim == 3
    if not batched:
        data = data[None]

    def nms_one(rows):
        scores = rows[:, score_index]
        boxes = lax.dynamic_slice_in_dim(rows, coord_start, 4, axis=1)
        if in_format == "center":
            xy, wh = boxes[:, :2], boxes[:, 2:] / 2
            boxes = jnp.concatenate([xy - wh, xy + wh], axis=-1)
        valid = scores > valid_thresh
        if id_index >= 0 and background_id >= 0:
            valid &= rows[:, id_index] != background_id
        order = jnp.argsort(-jnp.where(valid, scores, -jnp.inf))
        n = rows.shape[0]
        k = n if topk <= 0 else min(topk, n)
        iou = _box_iou_corner(boxes[order], boxes[order])
        if id_index >= 0 and not force_suppress:
            ids = rows[order, id_index]
            iou = jnp.where(ids[:, None] == ids[None, :], iou, 0.0)
        valid_sorted = valid[order]

        # Greedy NMS as a fixed-point iteration instead of a sequential
        # O(topk) loop: keep_i = valid_i AND no kept higher-ranked j with
        # IoU > t. Each sweep is one n x n matmul (MXU work), and the
        # iteration reaches the greedy fixpoint in suppression-chain-depth
        # sweeps (typically < 10) rather than topk sequential steps —
        # the survey's planned TPU formulation (SURVEY §7: "Pallas for
        # ... NMS"; measured speedup in benchmarks/nms_bench.py).
        ranks = jnp.arange(n)
        adj = (iou > overlap_thresh) & (ranks[None, :] < ranks[:, None]) \
            & (ranks[None, :] < k)          # j can suppress i: j<i, j<topk
        adjf = adj.astype(jnp.float32)

        def fp_cond(state):
            _, changed, it = state
            return changed & (it < n)

        def fp_body(state):
            keep, _, it = state
            suppressed = (adjf @ keep.astype(jnp.float32)) > 0
            new = valid_sorted & ~suppressed
            return new, jnp.any(new != keep), it + 1

        keep, _, _ = lax.while_loop(
            fp_cond, fp_body, (valid_sorted, jnp.bool_(True),
                               jnp.int32(0)))
        keep &= jnp.arange(n) < k
        # compact kept rows to the top (stable), suppressed slots become -1
        perm = jnp.argsort(~keep, stable=True)
        compacted = jnp.where(jnp.sort(~keep, stable=True)[:, None],
                              -jnp.ones_like(rows), rows[order][perm])
        return compacted

    out = jax.vmap(nms_one)(data)
    return out if batched else out[0]


@register("_contrib_BilinearResize2D", aliases=["BilinearResize2D"],
          params=[OpParam("height", int, 0), OpParam("width", int, 0),
                  OpParam("scale_height", float, None),
                  OpParam("scale_width", float, None),
                  OpParam("mode", str, "size"),
                  OpParam("align_corners", bool, True)],
          doc="ref: src/operator/contrib/bilinear_resize.cc")
def _bilinear_resize(x, height=0, width=0, scale_height=None, scale_width=None,
                     mode="size", align_corners=True):
    n, c, h, w = x.shape
    if scale_height is not None:
        height = int(h * scale_height)
        width = int(w * scale_width)
    if align_corners and height > 1 and width > 1:
        ys = jnp.linspace(0.0, h - 1.0, height)
        xs = jnp.linspace(0.0, w - 1.0, width)
        y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, h - 1)
        x0 = jnp.clip(jnp.floor(xs).astype(jnp.int32), 0, w - 1)
        y1 = jnp.clip(y0 + 1, 0, h - 1)
        x1 = jnp.clip(x0 + 1, 0, w - 1)
        wy = (ys - y0).reshape(1, 1, -1, 1)
        wx = (xs - x0).reshape(1, 1, 1, -1)
        g = lambda yy, xx: x[:, :, yy][:, :, :, xx]
        out = (g(y0, x0) * (1 - wy) * (1 - wx) + g(y1, x0) * wy * (1 - wx)
               + g(y0, x1) * (1 - wy) * wx + g(y1, x1) * wy * wx)
        return out.astype(x.dtype)
    return jax.image.resize(x, (n, c, height, width), method="bilinear").astype(x.dtype)


@register("_contrib_AdaptiveAvgPooling2D", aliases=["AdaptiveAvgPooling2D"],
          params=[OpParam("output_size", tuple, None)],
          doc="ref: src/operator/contrib/adaptive_avg_pooling.cc")
def _adaptive_avg_pool(x, output_size=None):
    n, c, h, w = x.shape
    if not output_size:
        oh = ow = 1
    elif len(output_size) == 1:
        oh = ow = int(output_size[0])
    else:
        oh, ow = int(output_size[0]), int(output_size[1])
    if h % oh == 0 and w % ow == 0:
        x = x.reshape(n, c, oh, h // oh, ow, w // ow)
        return x.mean(axis=(3, 5))
    # general case: average over adaptive windows via interpolation-free loop
    out = jnp.zeros((n, c, oh, ow), dtype=x.dtype)
    rows = [(int(jnp.floor(i * h / oh)), int(-(-((i + 1) * h) // oh))) for i in range(oh)]
    cols = [(int(jnp.floor(j * w / ow)), int(-(-((j + 1) * w) // ow))) for j in range(ow)]
    parts = []
    for (r0, r1) in rows:
        row = [x[:, :, r0:r1, c0:c1].mean(axis=(2, 3)) for (c0, c1) in cols]
        parts.append(jnp.stack(row, axis=-1))
    return jnp.stack(parts, axis=-2)


@register("_contrib_ROIAlign", aliases=["ROIAlign"], num_inputs=2,
          params=[OpParam("pooled_size", tuple, None, required=True),
                  OpParam("spatial_scale", float, 1.0),
                  OpParam("sample_ratio", int, -1),
                  OpParam("position_sensitive", bool, False),
                  OpParam("aligned", bool, False)],
          doc="ROI Align via vectorized bilinear gathers "
              "(ref: src/operator/contrib/roi_align.cc)")
def _roi_align(features, rois, pooled_size=None, spatial_scale=1.0,
               sample_ratio=-1, position_sensitive=False, aligned=False):
    ph, pw = int(pooled_size[0]), int(pooled_size[1])
    n, c, h, w = features.shape
    sr = sample_ratio if sample_ratio > 0 else 2
    offset = 0.5 if aligned else 0.0

    def one_roi(roi):
        batch_idx = roi[0].astype(jnp.int32)
        x1, y1, x2, y2 = (roi[1] * spatial_scale - offset,
                          roi[2] * spatial_scale - offset,
                          roi[3] * spatial_scale - offset,
                          roi[4] * spatial_scale - offset)
        rw = jnp.maximum(x2 - x1, 1.0 if not aligned else 1e-6)
        rh = jnp.maximum(y2 - y1, 1.0 if not aligned else 1e-6)
        bin_h, bin_w = rh / ph, rw / pw
        # sample grid: (ph*sr, pw*sr)
        ys = y1 + (jnp.arange(ph * sr) + 0.5) * bin_h / sr
        xs = x1 + (jnp.arange(pw * sr) + 0.5) * bin_w / sr
        img = lax.dynamic_index_in_dim(features, batch_idx, axis=0, keepdims=False)

        def bilinear(yy, xx):
            y0 = jnp.clip(jnp.floor(yy).astype(jnp.int32), 0, h - 1)
            x0 = jnp.clip(jnp.floor(xx).astype(jnp.int32), 0, w - 1)
            y1i = jnp.clip(y0 + 1, 0, h - 1)
            x1i = jnp.clip(x0 + 1, 0, w - 1)
            wy = jnp.clip(yy - y0, 0, 1).reshape(1, -1, 1)
            wx = jnp.clip(xx - x0, 0, 1).reshape(1, 1, -1)
            g = lambda a, b: img[:, a][:, :, b]
            return (g(y0, x0) * (1 - wy) * (1 - wx) + g(y1i, x0) * wy * (1 - wx)
                    + g(y0, x1i) * (1 - wy) * wx + g(y1i, x1i) * wy * wx)

        samples = bilinear(ys, xs)                       # (c, ph*sr, pw*sr)
        samples = samples.reshape(c, ph, sr, pw, sr)
        return samples.mean(axis=(2, 4))

    return jax.vmap(one_roi)(rois)


@register("_contrib_MultiBoxPrior", aliases=["MultiBoxPrior"],
          params=[OpParam("sizes", tuple, (1.0,)),
                  OpParam("ratios", tuple, (1.0,)),
                  OpParam("clip", bool, False),
                  OpParam("steps", tuple, (-1.0, -1.0)),
                  OpParam("offsets", tuple, (0.5, 0.5))],
          differentiable=False,
          doc="SSD anchor generation (ref: src/operator/contrib/multibox_prior.cc)")
def _multibox_prior(data, sizes=(1.0,), ratios=(1.0,), clip=False,
                    steps=(-1.0, -1.0), offsets=(0.5, 0.5)):
    h, w = data.shape[2], data.shape[3]
    step_y = steps[0] if steps[0] > 0 else 1.0 / h
    step_x = steps[1] if steps[1] > 0 else 1.0 / w
    cy = (jnp.arange(h) + offsets[0]) * step_y
    cx = (jnp.arange(w) + offsets[1]) * step_x
    cy, cx = jnp.meshgrid(cy, cx, indexing="ij")
    centers = jnp.stack([cx.ravel(), cy.ravel()], axis=-1)      # (h*w, 2)
    # reference: num_anchors = len(sizes) + len(ratios) - 1
    whs = []
    for s in sizes:
        whs.append((s * jnp.sqrt(ratios[0]), s / jnp.sqrt(ratios[0])))
    for r in ratios[1:]:
        whs.append((sizes[0] * jnp.sqrt(r), sizes[0] / jnp.sqrt(r)))
    whs = jnp.asarray(whs)                                       # (A, 2)
    half = whs / 2
    boxes = jnp.concatenate([
        centers[:, None, :] - half[None, :, :],
        centers[:, None, :] + half[None, :, :]], axis=-1)        # (h*w, A, 4)
    boxes = boxes.reshape(1, -1, 4)
    if clip:
        boxes = jnp.clip(boxes, 0.0, 1.0)
    return boxes


@register("arange_like", num_inputs=1,
          params=[OpParam("start", float, 0.0), OpParam("step", float, 1.0),
                  OpParam("repeat", int, 1), OpParam("axis", int, None)],
          differentiable=False,
          doc="ref: src/operator/contrib/arange_like op")
def _arange_like(x, start=0.0, step=1.0, repeat=1, axis=None):
    if axis is None:
        n = x.size
        return (start + step * jnp.arange(n)).reshape(x.shape).astype(x.dtype)
    n = x.shape[axis]
    return (start + step * jnp.arange(n)).astype(x.dtype)


@register("_contrib_div_sqrt_dim", aliases=["div_sqrt_dim"],
          doc="x / sqrt(last_dim) — attention scaling helper "
              "(ref: src/operator/contrib/transformer.cc)")
def _div_sqrt_dim(x):
    return x / jnp.sqrt(float(x.shape[-1]))


@register("_contrib_interleaved_matmul_selfatt_qk", num_inputs=1,
          params=[OpParam("heads", int, None, required=True)],
          doc="Transformer fused self-attention QK^T "
              "(ref: src/operator/contrib/transformer.cc). Input (T, N, 3*E) "
              "interleaved qkv projections.")
def _interleaved_qk(qkv, heads=None):
    t, n, e3 = qkv.shape
    e = e3 // 3
    hd = e // heads
    qkv = qkv.reshape(t, n, heads, 3, hd)
    q = qkv[:, :, :, 0]                                  # (T, N, H, D)
    k = qkv[:, :, :, 1]
    q = q.transpose(1, 2, 0, 3).reshape(n * heads, t, hd)
    k = k.transpose(1, 2, 0, 3).reshape(n * heads, t, hd)
    return jnp.matmul(q, k.transpose(0, 2, 1)) / jnp.sqrt(float(hd))


@register("_contrib_interleaved_matmul_selfatt_valatt", num_inputs=2,
          params=[OpParam("heads", int, None, required=True)],
          doc="Transformer fused attention AV (ref: contrib/transformer.cc)")
def _interleaved_valatt(qkv, att, heads=None):
    t, n, e3 = qkv.shape
    e = e3 // 3
    hd = e // heads
    v = qkv.reshape(t, n, heads, 3, hd)[:, :, :, 2]
    v = v.transpose(1, 2, 0, 3).reshape(n * heads, t, hd)
    out = jnp.matmul(att, v)                             # (N*H, T, D)
    out = out.reshape(n, heads, t, hd).transpose(2, 0, 1, 3)
    return out.reshape(t, n, e)


FLASH_COUNT_METRIC = "mxnet_tpu_flash_attention_traced_total"


def _count_traced_attention(branch, block_q="", block_k="", qk=0, v=0,
                            padded=""):
    """One attention call traced into a program, by the branch it took
    (``dense``, ``tpu_kernel``, ``portable``), that branch's query and key
    tiles, the query/key and value head sizes, and what was padded with
    zeros to reach the kernel (``v``: the value head to the query's; empty:
    nothing): trace-time only, so a compiled step never counts."""
    from ..observability.metrics import default_registry
    default_registry().counter(
        FLASH_COUNT_METRIC, "flash-attention calls traced into a program",
        ("branch", "block_q", "block_k", "qk", "v", "padded")).labels(
            branch=branch, block_q=str(block_q), block_k=str(block_k),
            qk=str(qk), v=str(v), padded=padded).inc()


def _tpu_flash_attention(q, k, v, causal, scale):
    """JAX's library flash-attention kernel for TPU on [B, H, S, D] (or
    [B, S, D], which rides as H=1 — e.g. FuseAttention pattern-1
    rewrites)."""
    if q.ndim == 3:
        return _library_flash(q[:, None], k[:, None], v[:, None],
                              causal, scale)[:, 0]
    return _library_flash(q, k, v, causal, scale)


def _flash_precision(q):
    """Matmul precision for the library kernel's trace. The package sets
    HIGHEST process-wide so that fp32 means fp32; for bf16 operands that
    asks the kernel compiler for a multi-pass product it refuses ("Bad
    lhs type"). One pass of bf16 x bf16 into fp32 is already exact, so
    DEFAULT changes no result there; fp32 operands keep the package's."""
    if q.dtype == jnp.float32:
        return contextlib.nullcontext()
    return jax.default_matmul_precision("default")


def _largest_tile(length, cap, step=128):
    """The largest multiple of ``step`` up to ``cap`` that divides
    ``length`` (itself a multiple of ``step``, so ``step`` always does)."""
    return next(t for t in range(min(cap, length) // step * step, 0, -step)
                if length % t == 0)


def _flash_tiles(s_q, s_kv, d, dtype):
    """The library kernel's eleven tiles from what the call can see. Its own
    default is 128 for all of them whatever the shape, and a grid step on a
    v5e costs about a third of a microsecond: at S=4096 the kernel then
    waits for its grid, not for its products. The caps are the sweep's on
    the chip (PERF.md sec. 6, PR 28). Minor tiles, one product's: 512.
    Major tiles, a grid step's: 1024 for two-byte heads of up to 128, else
    512 (float32's multi-pass products on the score tile run out of fast
    memory at 1024, and 512 is faster there anyway); a minor of 512 under
    a major of 1024 costs nothing measurable and keeps the score tile well
    inside fast memory. The dq kernel's key tiles: the minor, because it
    is handed each query's float32 row sum broadcast to its major key
    tile. A minor tile is the largest multiple of 128 under its cap that
    divides the sequence length, a major the largest multiple of its
    minor that does."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes
    cap = 1024 if jnp.dtype(dtype).itemsize == 2 and d <= 128 else 512
    q_minor, k_minor = _largest_tile(s_q, 512), _largest_tile(s_kv, 512)
    q = _largest_tile(s_q, cap, q_minor)
    k = _largest_tile(s_kv, cap, k_minor)
    return BlockSizes(
        block_q=q, block_k_major=k, block_k=k_minor, block_b=1,
        block_q_major_dkv=q, block_q_dkv=q_minor,
        block_k_major_dkv=k, block_k_dkv=k_minor,
        block_q_dq=q, block_k_major_dq=k_minor, block_k_dq=k_minor)


def _library_flash_call(q, k, v, causal, scale):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention)
    tiles = _flash_tiles(q.shape[-2], k.shape[-2], q.shape[-1], q.dtype)
    return flash_attention(q, k, v, causal=causal, sm_scale=scale,
                           block_sizes=tiles)


# custom_vjp only to hold the precision scope over the backward's trace
# too: the library's own backward kernels are traced when the cotangent
# arrives, outside any scope the forward opened.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _library_flash(q, k, v, causal, scale):
    with _flash_precision(q):
        return _library_flash_call(q, k, v, causal, scale)


def _library_flash_fwd(q, k, v, causal, scale):
    with _flash_precision(q):
        return jax.vjp(
            lambda q, k, v: _library_flash_call(q, k, v, causal, scale),
            q, k, v)


def _library_flash_bwd(causal, scale, vjp, g):
    with _flash_precision(g):
        return vjp(g)


_library_flash.defvjp(_library_flash_fwd, _library_flash_bwd)


def _kernel_head(d):
    """The head size the library kernel is called with for heads of ``d``:
    ``d`` up to 128, past it the next multiple of 128 (the kernel refuses
    others)."""
    return d if d <= 128 else -(-d // 128) * 128


def attention_branch(q, k, v):
    """The branch ``_contrib_flash_attention`` takes for these operands
    (arrays or tracers of [B, H, S, D]): ``dense`` up to 1024 keys, else
    ``tpu_kernel`` where they run on a TPU with the kernel tier on and the
    library kernel takes their shapes (a value head no wider than the
    query's; a head past 128 is padded to a multiple of 128), else
    ``portable``."""
    if k.shape[-2] <= 1024:
        return "dense"
    from ..pallas import mode as _pallas_mode
    from ..pallas.registry import runs_on
    if runs_on((q, k, v))[0] == "tpu" and _pallas_mode() != "off" and \
            q.shape[-2] % 128 == 0 and k.shape[-2] % 128 == 0 and \
            q.shape[-1] >= 64 and v.shape[-1] <= q.shape[-1] and \
            q.dtype in (jnp.bfloat16, jnp.float32):
        return "tpu_kernel"
    return "portable"


@register("_contrib_flash_attention", num_inputs=3,
          params=[OpParam("block_size", int, 512),
                  OpParam("causal", bool, False),
                  OpParam("sm_scale", float, None)],
          doc="Blockwise online-softmax attention on [B, H, S, D] inputs — "
              "memory-efficient long-context attention (net-new TPU "
              "capability, SURVEY §5.7; no reference analog — MXNet 1.x "
              "used full attention). v may have a head size of its own "
              "(the output's). Sequence-parallel variant: "
              "mxnet_tpu.parallel.ring_attention.")
def _flash_attention(q, k, v, block_size=512, causal=False, sm_scale=None):
    from ..parallel.ring_attention import blockwise_attention
    scale = float(q.shape[-1]) ** -0.5 if sm_scale is None else sm_scale
    sizes = dict(qk=q.shape[-1], v=v.shape[-1])
    branch = attention_branch(q, k, v)
    if branch == "dense":
        # short KV: one fused softmax(QK^T)V straight on the MXU via the
        # shared dense-attention definition (attention_reference — one
        # mask convention, fp32-accumulated row sums). The s_q x s_kv
        # score tensor is small here, and a single batched matmul pair
        # beats any streaming kernel (measured: the Pallas kernels cost
        # ~20x at S=128, at the library's default tiles — see
        # docs/perf_notes.md).
        from ..parallel.ring_attention import attention_reference
        _count_traced_attention("dense", **sizes)
        return attention_reference(q, k, v, causal=causal, scale=scale)
    # on TPU hardware route to the library's hand-tiled Pallas kernel
    # (MXU-tiled blocks, VMEM-resident online softmax), called with the
    # tiles _flash_tiles reads from (S_q, S_kv, D, dtype) and not the
    # library's 128 everywhere; the jnp blockwise kernel is the portable
    # path and the CPU-test oracle, and ``block_size`` is its key/value
    # block and nothing else's. What the library kernel raises is raised:
    # a quiet second path would hide a slower run on the chip
    # (tests/test_chip_compile.py compiles this call for a v5e). Inside
    # jit the platform is only known at lowering (pallas.runs_on), so
    # there both paths are staged and the lowering keeps one.
    from ..pallas.registry import runs_on

    def portable(q, k, v):
        return blockwise_attention(q, k, v, block_size=block_size,
                                   causal=causal, scale=scale)

    if branch == "tpu_kernel":
        head = _kernel_head(q.shape[-1])
        width = v.shape[-1]

        def on_tpu(q, k, v):
            if head == q.shape[-1] == width:
                return _tpu_flash_attention(q, k, v, causal, scale)
            # the library kernel takes one head size for q, k and v, and
            # past 128 only whole multiples of 128: the heads ride in zeros
            # to that size (zeros add nothing to a score; the scale is
            # given), and the columns the value's zeros give are cut off
            def padded(t):
                return jnp.pad(t, [(0, 0)] * (t.ndim - 1)
                               + [(0, head - t.shape[-1])])
            return _tpu_flash_attention(padded(q), padded(k), padded(v),
                                        causal, scale)[..., :width]
        tiles = _flash_tiles(q.shape[-2], k.shape[-2], head, q.dtype)
        _count_traced_attention(
            "tpu_kernel", tiles.block_q, tiles.block_k_major,
            padded=("qkv" if head > q.shape[-1] else "v"
                    if width < head else ""), **sizes)
        if runs_on((q, k, v))[1]:
            return lax.platform_dependent(q, k, v, tpu=on_tpu,
                                          default=portable)
        return on_tpu(q, k, v)
    _count_traced_attention("portable", block_k=block_size, **sizes)
    return portable(q, k, v)


@register("_contrib_conv_epilogue", num_inputs=2,
          params=[OpParam("act_type", str, "relu")],
          doc="Residual epilogue act(x + res): the add and the activation "
              "(identity, relu, gelu, tanh, sigmoid) in float32, cast back "
              "to x's dtype once. Plain jax.numpy on the arrays as they "
              "are; XLA fuses it into one elementwise pass.")
def _conv_epilogue_contrib(x, res, act_type="relu"):
    return _epilogue_act(x.astype(jnp.float32) + res.astype(jnp.float32),
                         act_type, x.dtype)


# the activations Gluon folds into ``_contrib_matmul_epilogue`` (Dense,
# PositionwiseFFN): the kernel tier's set; identity is what a bare bias is
MATMUL_EPILOGUE_ACTS = tuple(a for a in EPILOGUE_ACTS if a != "identity")


@register("_contrib_matmul_epilogue", num_inputs=2, needs_rng=True,
          needs_mode=True,
          params=[OpParam("act_type", str, None),
                  OpParam("p", float, 0.0,
                          doc="inverted-dropout rate folded into the "
                              "epilogue (training only); mask semantics "
                              "bit-identical to Dropout"),
                  OpParam("layer", int, 0),
                  OpParam("tick", int, 0)],
          doc="Fused matmul epilogue dropout(act(y + bias)) in one VMEM "
              "pass over the matmul output — the BERT MFU lever "
              "(docs/pallas.md, ROADMAP.md S3/S4). Dropout keys "
              "follow the PR-1 (layer, tick, shard) fold discipline. "
              "Dispatches the mxnet_tpu.pallas matmul_epilogue kernel on "
              "TPU with a parity-gated XLA fallback elsewhere.")
def _matmul_epilogue_contrib(y, bias, rng=None, act_type=None, p=0.0,
                             layer=0, tick=0, training=False):
    from ..pallas import fused_matmul_epilogue
    return fused_matmul_epilogue(y, bias, act_type=act_type, p=p, rng=rng,
                                 training=training, layer=layer, tick=tick)


@register("_contrib_ring_attention", num_inputs=3,
          params=[OpParam("axis_name", str, "seq"),
                  OpParam("causal", bool, False),
                  OpParam("batch_axis", str, "data"),
                  OpParam("head_axis", str, None)],
          doc="Sequence-parallel ring attention over the current mesh's "
              "ICI ring (lax.ppermute of K/V shards + online softmax). "
              "Net-new TPU capability (SURVEY §5.7); composes under jit "
              "via shard_map.")
def _ring_attention_op(q, k, v, axis_name="seq", causal=False,
                       batch_axis="data", head_axis=None):
    import jax
    from ..parallel.ring_attention import blockwise_attention, ring_attention
    from ..parallel.mesh import current_mesh
    if not isinstance(q, jax.core.Tracer):
        # eager execution (shape resolution, debugging): same math on one
        # device via the blockwise kernel; the ring engages under jit
        return blockwise_attention(q, k, v, block_size=q.shape[-2],
                                   causal=causal)
    return ring_attention(q, k, v, mesh=current_mesh(),
                          axis_name=axis_name, causal=causal,
                          batch_axis=batch_axis, head_axis=head_axis)


@register("_contrib_MultiBoxTarget", aliases=["MultiBoxTarget"],
          num_inputs=3, num_outputs=3,
          params=[OpParam("overlap_threshold", float, 0.5),
                  OpParam("ignore_label", float, -1.0),
                  OpParam("negative_mining_ratio", float, -1.0),
                  OpParam("negative_mining_thresh", float, 0.5),
                  OpParam("minimum_negative_samples", int, 0),
                  OpParam("variances", tuple, (0.1, 0.1, 0.2, 0.2))],
          differentiable=False,
          doc="SSD training target assignment: anchors x gt labels → "
              "(loc_target, loc_mask, cls_target). Static shapes, vmapped "
              "over the batch (ref: src/operator/contrib/"
              "multibox_target.cc). gt label rows are [cls, x0, y0, x1, "
              "y1], padded with cls=-1. TPU extension over the reference: "
              "anchors may be (N, A, 4) — one anchor set PER IMAGE (the "
              "Faster R-CNN proposal↔gt matching case, ref: "
              "src/operator/contrib/proposal_target.cc) — vmapped over "
              "both, so the whole assignment stays in-graph.")
def _multibox_target(anchors, labels, cls_preds, overlap_threshold=0.5,
                     ignore_label=-1.0, negative_mining_ratio=-1.0,
                     negative_mining_thresh=0.5, minimum_negative_samples=0,
                     variances=(0.1, 0.1, 0.2, 0.2)):
    def one(anc, label, cls_pred):
        anc = anc.reshape(-1, 4)                      # (A, 4) corner
        acx = (anc[:, 0] + anc[:, 2]) / 2
        acy = (anc[:, 1] + anc[:, 3]) / 2
        aw = jnp.maximum(anc[:, 2] - anc[:, 0], 1e-12)
        ah = jnp.maximum(anc[:, 3] - anc[:, 1], 1e-12)
        A = anc.shape[0]
        gt_cls = label[:, 0]
        gt_box = label[:, 1:5]
        valid = gt_cls >= 0                           # (M,)
        iou = _box_iou_corner(anc, gt_box)            # (A, M)
        iou = jnp.where(valid[None, :], iou, -1.0)
        best_gt = jnp.argmax(iou, axis=1)             # (A,)
        best_iou = jnp.max(iou, axis=1)
        # every gt's best anchor is forced positive (reference bipartite
        # matching stage)
        best_anchor = jnp.argmax(iou, axis=0)         # (M,)
        forced = jnp.zeros(A, bool).at[best_anchor].set(valid)
        forced_gt = jnp.zeros(A, jnp.int32).at[best_anchor].set(
            jnp.arange(gt_box.shape[0], dtype=jnp.int32))
        pos = forced | (best_iou >= overlap_threshold)
        gt_idx = jnp.where(forced, forced_gt, best_gt)
        # classification target: 0 = background, cls+1 for positives
        cls_t = jnp.where(pos, gt_cls[gt_idx] + 1.0, 0.0)
        # optional hard-negative mining: keep top-k negatives by max
        # class prob, others → ignore_label
        if negative_mining_ratio > 0:
            prob = jax.nn.softmax(cls_pred, axis=-1)
            neg_score = 1.0 - prob[:, 0]              # objectness-like
            num_pos = jnp.sum(pos)
            max_neg = jnp.maximum(
                (num_pos * negative_mining_ratio).astype(jnp.int32),
                minimum_negative_samples)
            neg_rank = jnp.argsort(jnp.argsort(
                -jnp.where(pos, -jnp.inf, neg_score)))
            keep_neg = (~pos) & (neg_rank < max_neg)
            cls_t = jnp.where(pos | keep_neg, cls_t, ignore_label)
        # localization target: encoded offsets with variances
        g = gt_box[gt_idx]
        gcx = (g[:, 0] + g[:, 2]) / 2
        gcy = (g[:, 1] + g[:, 3]) / 2
        gw = jnp.maximum(g[:, 2] - g[:, 0], 1e-12)
        gh = jnp.maximum(g[:, 3] - g[:, 1], 1e-12)
        loc_t = jnp.stack([
            (gcx - acx) / aw / variances[0],
            (gcy - acy) / ah / variances[1],
            jnp.log(gw / aw) / variances[2],
            jnp.log(gh / ah) / variances[3]], axis=-1)
        loc_t = jnp.where(pos[:, None], loc_t, 0.0)
        loc_m = jnp.broadcast_to(pos[:, None], loc_t.shape).astype(
            loc_t.dtype)
        return (loc_t.reshape(-1), loc_m.reshape(-1), cls_t)

    if anchors.ndim == 3 and anchors.shape[0] == labels.shape[0] \
            and anchors.shape[0] > 1:
        # per-image anchor sets (proposals): vmap over anchors too
        loc_t, loc_m, cls_t = jax.vmap(one)(anchors, labels, cls_preds)
    else:
        anc0 = anchors.reshape(-1, 4)
        loc_t, loc_m, cls_t = jax.vmap(
            lambda lb, cp: one(anc0, lb, cp))(labels, cls_preds)
    return loc_t, loc_m, cls_t


@register("_contrib_MultiBoxDetection", aliases=["MultiBoxDetection"],
          num_inputs=3,
          params=[OpParam("clip", bool, True),
                  OpParam("threshold", float, 0.01),
                  OpParam("background_id", int, 0),
                  OpParam("nms_threshold", float, 0.5),
                  OpParam("force_suppress", bool, False),
                  OpParam("variances", tuple, (0.1, 0.1, 0.2, 0.2)),
                  OpParam("nms_topk", int, -1)],
          differentiable=False,
          doc="SSD inference: decode anchors+offsets, per-class NMS; "
              "output rows [cls_id, score, x0, y0, x1, y1], suppressed "
              "rows -1 (static shape, ref: src/operator/contrib/"
              "multibox_detection.cc)")
def _multibox_detection(cls_prob, loc_pred, anchors, clip=True,
                        threshold=0.01, background_id=0, nms_threshold=0.5,
                        force_suppress=False,
                        variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1):
    anc = anchors.reshape(-1, 4)
    acx = (anc[:, 0] + anc[:, 2]) / 2
    acy = (anc[:, 1] + anc[:, 3]) / 2
    aw = anc[:, 2] - anc[:, 0]
    ah = anc[:, 3] - anc[:, 1]

    def one(probs, loc):
        # probs: (C, A); loc: (A*4,)
        loc = loc.reshape(-1, 4)
        cx = loc[:, 0] * variances[0] * aw + acx
        cy = loc[:, 1] * variances[1] * ah + acy
        w = jnp.exp(loc[:, 2] * variances[2]) * aw
        h = jnp.exp(loc[:, 3] * variances[3]) * ah
        boxes = jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2,
                           cy + h / 2], axis=-1)
        if clip:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        # best foreground class per anchor (reference picks argmax)
        fg = jnp.where(jnp.arange(probs.shape[0])[:, None] == background_id,
                       -jnp.inf, probs)
        cls_id = jnp.argmax(fg, axis=0).astype(boxes.dtype)
        score = jnp.max(fg, axis=0)
        keep = score > threshold
        cls_id = jnp.where(keep, cls_id - (background_id == 0), -1.0)
        score = jnp.where(keep, score, -1.0)
        rows = jnp.concatenate([cls_id[:, None], score[:, None], boxes],
                               axis=-1)
        return rows

    rows = jax.vmap(one)(cls_prob, loc_pred)
    return _box_nms(rows, overlap_thresh=nms_threshold, valid_thresh=0.0,
                    topk=nms_topk, coord_start=2, score_index=1,
                    id_index=0, background_id=-1,
                    force_suppress=force_suppress)


# ---------------------------------------------------------------------------
# Binary-network ops — the BMXNet fork delta (SURVEY §2 #23: yanghaojin is
# the BMXNet author; upstream BMXNet adds QConvolution/QFullyConnected/
# QActivation and det_sign with gradient cancellation, smd_hpi/src/).
# TPU design: binarization is sign() with a straight-through estimator;
# the "XNOR-popcount GEMM" becomes a ±1 matmul in bf16 on the MXU — the
# MXU at bf16 rate IS the fast binary GEMM on this hardware (no integer
# popcount unit to beat it).
# ---------------------------------------------------------------------------
def _ste_sign(x, grad_cancel=1.0):
    @jax.custom_vjp
    def core(v):
        return jnp.where(v >= 0, 1.0, -1.0).astype(v.dtype)

    def fwd(v):
        return core(v), v

    def bwd(v, g):
        # straight-through with cancellation: pass grad only where |x|<=t
        return (jnp.where(jnp.abs(v) <= grad_cancel, g,
                          jnp.zeros_like(g)),)

    core.defvjp(fwd, bwd)
    return core(x)


@register("det_sign", params=[OpParam("grad_cancel", float, 1.0)],
          doc="Deterministic sign with straight-through gradient, zeroed "
              "where |x| > grad_cancel (BMXNet det_sign / grad cancellation)")
def _det_sign(x, grad_cancel=1.0):
    return _ste_sign(x, grad_cancel)


@register("approx_sign", params=[],
          doc="ApproxSign (Bi-Real Net): sign forward, piecewise-parabolic "
              "backward (2-2|x| for |x|<=1) — BMXNet approx_sign")
def _approx_sign(x):
    @jax.custom_vjp
    def core(v):
        return jnp.where(v >= 0, 1.0, -1.0).astype(v.dtype)

    def fwd(v):
        return core(v), v

    def bwd(v, g):
        slope = jnp.where(jnp.abs(v) <= 1.0, 2.0 - 2.0 * jnp.abs(v), 0.0)
        return (g * slope,)

    core.defvjp(fwd, bwd)
    return core(x)


@register("QFullyConnected", num_inputs=-1,
          params=[OpParam("num_hidden", int, None, required=True),
                  OpParam("no_bias", bool, False),
                  OpParam("binarize_input", bool, True),
                  OpParam("scaling", bool, True)],
          doc="Binary fully-connected (BMXNet QFullyConnected): ±1 weights "
              "(and optionally inputs), XNOR-Net alpha scaling = mean|W|")
def _q_fully_connected(x, weight, *bias, num_hidden=None, no_bias=False,
                       binarize_input=True, scaling=True):
    xb = _ste_sign(x) if binarize_input else x
    wb = _ste_sign(weight)
    y = jnp.matmul(xb.reshape(xb.shape[0], -1), wb.T)
    if scaling:
        alpha = jnp.mean(jnp.abs(weight))
        y = y * alpha
    if not no_bias and bias:
        y = y + bias[0]
    return y


@register("QConvolution", num_inputs=-1,
          params=[OpParam("kernel", tuple, None, required=True),
                  OpParam("num_filter", int, None, required=True),
                  OpParam("stride", tuple, (1, 1)),
                  OpParam("pad", tuple, (0, 0)),
                  OpParam("dilate", tuple, (1, 1)),
                  OpParam("num_group", int, 1),
                  OpParam("no_bias", bool, True),
                  OpParam("binarize_input", bool, True),
                  OpParam("scaling", bool, True)],
          doc="Binary convolution (BMXNet QConvolution): ±1 weights/input, "
              "per-filter alpha scaling; lowers to a bf16 MXU conv")
def _q_convolution(x, weight, *bias, kernel=None, num_filter=None,
                   stride=(1, 1), pad=(0, 0), dilate=(1, 1), num_group=1,
                   no_bias=True, binarize_input=True, scaling=True):
    xb = _ste_sign(x) if binarize_input else x
    wb = _ste_sign(weight)
    nd_spatial = len(kernel)
    dn = lax.conv_dimension_numbers(
        xb.shape, wb.shape,
        ("NCHW", "OIHW", "NCHW") if nd_spatial == 2 else
        ("NCW", "OIW", "NCW"))
    y = lax.conv_general_dilated(
        xb, wb, window_strides=tuple(stride), padding=[(p, p) for p in pad],
        rhs_dilation=tuple(dilate), dimension_numbers=dn,
        feature_group_count=num_group)
    if scaling:
        alpha = jnp.mean(jnp.abs(weight), axis=tuple(
            range(1, weight.ndim)))                     # per output filter
        y = y * alpha.reshape((1, -1) + (1,) * nd_spatial)
    if not no_bias and bias:
        y = y + bias[0].reshape((1, -1) + (1,) * nd_spatial)
    return y


@register("QActivation", params=[OpParam("act_bit", int, 1),
                                OpParam("backward_only", bool, False)],
          doc="Quantized activation (BMXNet QActivation): 1 bit = STE sign "
              "of clipped input; k bit = uniform quantization of clip(x,0,1)")
def _q_activation(x, act_bit=1, backward_only=False):
    if act_bit == 1:
        return _ste_sign(jnp.clip(x, -1.0, 1.0))
    levels = (1 << act_bit) - 1

    @jax.custom_vjp
    def core(v):
        c = jnp.clip(v, 0.0, 1.0)
        return jnp.round(c * levels) / levels

    def fwd(v):
        return core(v), v

    def bwd(v, g):
        return (jnp.where((v >= 0) & (v <= 1), g, jnp.zeros_like(g)),)

    core.defvjp(fwd, bwd)
    return core(x)


@register("_contrib_ulysses_attention", num_inputs=3,
          params=[OpParam("axis_name", str, "seq"),
                  OpParam("causal", bool, False),
                  OpParam("batch_axis", str, "data")],
          doc="Ulysses all-to-all sequence-parallel attention over the "
              "current mesh (head-scatter alternative to ring attention; "
              "SURVEY §5.7). Eager execution falls back to the blockwise "
              "kernel like _contrib_ring_attention.")
def _ulysses_attention_op(q, k, v, axis_name="seq", causal=False,
                          batch_axis="data"):
    import jax
    from ..parallel.ring_attention import (blockwise_attention,
                                           ulysses_attention)
    from ..parallel.mesh import current_mesh
    if not isinstance(q, jax.core.Tracer):
        return blockwise_attention(q, k, v, block_size=q.shape[-2],
                                   causal=causal)
    return ulysses_attention(q, k, v, mesh=current_mesh(),
                             axis_name=axis_name, causal=causal,
                             batch_axis=batch_axis)


def _proposal_outputs(params):
    return 2 if params.get("output_score") else 1


# shared by Proposal and MultiProposal — MultiProposal forwards **kwargs
# into _proposal, so the two registrations must stay in lockstep
_PROPOSAL_PARAMS = [OpParam("rpn_pre_nms_top_n", int, 6000),
                    OpParam("rpn_post_nms_top_n", int, 300),
                    OpParam("threshold", float, 0.7),
                    OpParam("rpn_min_size", int, 16),
                    OpParam("scales", tuple, (4.0, 8.0, 16.0, 32.0)),
                    OpParam("ratios", tuple, (0.5, 1.0, 2.0)),
                    OpParam("feature_stride", int, 16),
                    OpParam("output_score", bool, False),
                    OpParam("iou_loss", bool, False)]


@register("_contrib_Proposal", aliases=["Proposal"], num_inputs=3,
          num_outputs=_proposal_outputs,
          params=list(_PROPOSAL_PARAMS),
          differentiable=False,
          doc="RPN proposal generation (ref: src/operator/contrib/"
              "proposal.cc): anchors + bbox deltas -> decode, clip, filter "
              "small, NMS, fixed top-N rows [batch_idx, x0, y0, x1, y1] "
              "(padded with -1) — static shapes throughout, vmapped over "
              "the batch.")
def _proposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000,
              rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
              scales=(4.0, 8.0, 16.0, 32.0), ratios=(0.5, 1.0, 2.0),
              feature_stride=16, output_score=False, iou_loss=False):
    # cls_prob: (N, 2A, H, W) bg/fg per anchor; bbox_pred: (N, 4A, H, W)
    n, c, h, w = cls_prob.shape
    a = len(scales) * len(ratios)
    if c != 2 * a or bbox_pred.shape[1] != 4 * a:
        raise MXNetError(
            f"Proposal: cls_prob needs 2*A={2 * a} channels and bbox_pred "
            f"4*A={4 * a} for {len(scales)} scales x {len(ratios)} ratios; "
            f"got {c} and {bbox_pred.shape[1]}")
    # base anchors centered on each stride cell (reference GenerateAnchors)
    base = []
    cx = cy = (feature_stride - 1) / 2.0
    base_size = float(feature_stride)
    for r in ratios:
        size = base_size * base_size / r
        ws = jnp.sqrt(size)
        hs = ws * r
        for s in scales:
            bw, bh = ws * s, hs * s
            base.append([cx - (bw - 1) / 2, cy - (bh - 1) / 2,
                         cx + (bw - 1) / 2, cy + (bh - 1) / 2])
    base = jnp.asarray(base)                                  # (A, 4)
    sx = jnp.arange(w) * feature_stride
    sy = jnp.arange(h) * feature_stride
    sx, sy = jnp.meshgrid(sx, sy, indexing="xy")
    shifts = jnp.stack([sx.ravel(), sy.ravel(),
                        sx.ravel(), sy.ravel()], axis=1)      # (H*W, 4)
    anchors = (base[None, :, :] + shifts[:, None, :]).reshape(-1, 4)

    def one(scores_map, deltas_map, info):
        im_h, im_w, im_scale = info[0], info[1], info[2]
        scores = scores_map[a:].transpose(1, 2, 0).reshape(-1)  # fg probs
        deltas = deltas_map.transpose(1, 2, 0).reshape(-1, 4)
        if iou_loss:
            # corner-delta decode (reference IoUTransformInv)
            boxes = anchors + deltas
        else:
            # center-offset decode (reference NonLinearTransformInv)
            aw = anchors[:, 2] - anchors[:, 0] + 1.0
            ah = anchors[:, 3] - anchors[:, 1] + 1.0
            acx = anchors[:, 0] + 0.5 * (aw - 1)
            acy = anchors[:, 1] + 0.5 * (ah - 1)
            cx2 = deltas[:, 0] * aw + acx
            cy2 = deltas[:, 1] * ah + acy
            w2 = jnp.exp(jnp.clip(deltas[:, 2], -10, 10)) * aw
            h2 = jnp.exp(jnp.clip(deltas[:, 3], -10, 10)) * ah
            boxes = jnp.stack(
                [cx2 - 0.5 * (w2 - 1), cy2 - 0.5 * (h2 - 1),
                 cx2 + 0.5 * (w2 - 1), cy2 + 0.5 * (h2 - 1)], axis=1)
        boxes = jnp.stack([jnp.clip(boxes[:, 0], 0, im_w - 1),
                           jnp.clip(boxes[:, 1], 0, im_h - 1),
                           jnp.clip(boxes[:, 2], 0, im_w - 1),
                           jnp.clip(boxes[:, 3], 0, im_h - 1)], axis=1)
        # min-size filter in SCALED image pixels (reference: min_size *
        # im_info[2])
        min_sz = rpn_min_size * im_scale
        keep = ((boxes[:, 2] - boxes[:, 0] + 1 >= min_sz)
                & (boxes[:, 3] - boxes[:, 1] + 1 >= min_sz))
        scores = jnp.where(keep, scores, -1.0)
        pre_n = min(rpn_pre_nms_top_n, scores.shape[0])
        top_scores, order = jax.lax.top_k(scores, pre_n)
        rows = jnp.concatenate([top_scores[:, None], boxes[order]], axis=1)
        # NMS over ALL pre_nms candidates, then take the first post_n
        # SURVIVORS (compacted to the top) — the reference keeps scanning
        # past rank post_n until post_n survivors are collected
        nmsed = _box_nms(rows, overlap_thresh=threshold, valid_thresh=0.0,
                         topk=-1, coord_start=1, score_index=0,
                         id_index=-1)
        out_n = rpn_post_nms_top_n
        padded = jnp.full((out_n, 5), -1.0, rows.dtype)
        take = min(out_n, nmsed.shape[0])
        padded = padded.at[:take].set(nmsed[:take])
        return padded

    per_img = jax.vmap(one)(cls_prob, bbox_pred, im_info)   # (N, topN, 5)
    batch_idx = jnp.repeat(jnp.arange(n, dtype=per_img.dtype),
                           rpn_post_nms_top_n).reshape(n, -1, 1)
    valid = per_img[:, :, 0:1] >= 0
    rois = jnp.concatenate(
        [jnp.where(valid, batch_idx, -1.0), per_img[:, :, 1:5]], axis=-1)
    rois = rois.reshape(-1, 5)
    if output_score:
        return rois, per_img[:, :, 0].reshape(-1, 1)
    return rois


@register("_contrib_PSROIPooling", aliases=["PSROIPooling"], num_inputs=2,
          params=[OpParam("spatial_scale", float, None, required=True),
                  OpParam("output_dim", int, None, required=True),
                  OpParam("pooled_size", int, None, required=True),
                  OpParam("group_size", int, 0)],
          doc="Position-sensitive ROI pooling (ref: src/operator/contrib/"
              "psroi_pooling.cc, R-FCN): output channel d, bin (i,j) "
              "average-pools input channel (d*gs+g_i)*gs+g_j over the "
              "bin's integer extent. Formulated as separable row/col bin "
              "masks + ONE einsum per ROI so XLA maps it onto the MXU "
              "instead of the reference's per-bin CUDA loops.")
def _psroi_pooling(data, rois, spatial_scale=None, output_dim=None,
                   pooled_size=None, group_size=0):
    ph = pw = int(pooled_size)
    gs = int(group_size) or ph
    n, c, h, w = data.shape
    if c != output_dim * gs * gs:
        raise MXNetError(
            f"PSROIPooling: data needs output_dim*group_size^2 = "
            f"{output_dim}*{gs}^2 = {output_dim * gs * gs} channels, "
            f"got {c}")
    hs_idx = jnp.arange(h, dtype=jnp.float32)
    ws_idx = jnp.arange(w, dtype=jnp.float32)
    ii = jnp.arange(ph, dtype=jnp.float32)
    jj = jnp.arange(pw, dtype=jnp.float32)
    # bin (i,j) -> position-sensitive channel group (reference: gh =
    # floor(i*gs/ph), identity when gs == pooled_size)
    gh = jnp.clip(jnp.floor(ii * gs / ph), 0, gs - 1).astype(jnp.int32)
    gw = jnp.clip(jnp.floor(jj * gs / pw), 0, gs - 1).astype(jnp.int32)
    cidx = ((jnp.arange(int(output_dim))[:, None, None] * gs
             + gh[None, :, None]) * gs + gw[None, None, :])   # (od, ph, pw)

    def c_round(v):
        # C round(): half AWAY from zero — jnp.round is half-to-even,
        # which shifts bins for .5 coordinates (common after 0.5x scales)
        return jnp.sign(v) * jnp.floor(jnp.abs(v) + 0.5)

    def one_roi(roi):
        batch_idx = roi[0].astype(jnp.int32)
        # reference rounds ROI corners to pixels BEFORE scaling and adds 1
        # to the far edge
        x1 = c_round(roi[1]) * spatial_scale
        y1 = c_round(roi[2]) * spatial_scale
        x2 = c_round(roi[3] + 1.0) * spatial_scale
        y2 = c_round(roi[4] + 1.0) * spatial_scale
        bin_h = jnp.maximum(y2 - y1, 0.1) / ph
        bin_w = jnp.maximum(x2 - x1, 0.1) / pw
        hstart = jnp.clip(jnp.floor(y1 + ii * bin_h), 0, h)
        hend = jnp.clip(jnp.ceil(y1 + (ii + 1) * bin_h), 0, h)
        wstart = jnp.clip(jnp.floor(x1 + jj * bin_w), 0, w)
        wend = jnp.clip(jnp.ceil(x1 + (jj + 1) * bin_w), 0, w)
        row = ((hs_idx[None, :] >= hstart[:, None])
               & (hs_idx[None, :] < hend[:, None]))           # (ph, H)
        col = ((ws_idx[None, :] >= wstart[:, None])
               & (ws_idx[None, :] < wend[:, None]))           # (pw, W)
        img = lax.dynamic_index_in_dim(data, batch_idx, axis=0,
                                       keepdims=False)
        sums = jnp.einsum("ih,chw,jw->cij",
                          row.astype(jnp.float32),
                          img.astype(jnp.float32),
                          col.astype(jnp.float32))
        counts = (row.sum(-1).astype(jnp.float32)[:, None]
                  * col.sum(-1).astype(jnp.float32)[None, :])  # (ph, pw)
        avg = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), 0.0)
        out = avg[cidx,
                  jnp.arange(ph)[None, :, None],
                  jnp.arange(pw)[None, None, :]]               # (od, ph, pw)
        return out.astype(data.dtype)

    return jax.vmap(one_roi)(rois)


@register("_contrib_MultiProposal", aliases=["MultiProposal"], num_inputs=3,
          num_outputs=_proposal_outputs,
          params=list(_PROPOSAL_PARAMS),
          differentiable=False,
          doc="Batched RPN proposals (ref: src/operator/contrib/"
              "multi_proposal.cc — upstream Proposal asserts batch 1 and "
              "MultiProposal re-implements it per image; this Proposal is "
              "vmapped over the batch already, so MultiProposal IS "
              "Proposal here).")
def _multi_proposal(cls_prob, bbox_pred, im_info, **kwargs):
    return _proposal(cls_prob, bbox_pred, im_info, **kwargs)


# ---------------------------------------------------------------------------
# Deformable convolution (ref: src/operator/contrib/deformable_convolution.cc
# + ../modulated_deformable_convolution.cc — hand-CUDA deformable_im2col
# there; here a fully vectorized bilinear-gather that XLA fuses, followed by
# one grouped einsum on the MXU. Differentiable in data/offset/mask/weight
# via autodiff (the reference hand-writes all three backward kernels).
# ---------------------------------------------------------------------------
def _deformable_sample(data, offset, mask, kernel, stride, dilate, pad,
                       num_deformable_group):
    """Bilinear-sample data at kernel-tap positions displaced by offset.

    data (N,C,H,W); offset (N, dg*2*kh*kw, oh, ow) with per-dg-block
    channel layout [2*t]=dy, [2*t+1]=dx of tap t (reference
    deformable_im2col channel order); mask (N, dg*kh*kw, oh, ow) or None.
    Returns columns (N, C, kh*kw, oh, ow).
    """
    n, c, h, w = data.shape
    kh, kw = kernel
    dg = num_deformable_group
    oh = (h + 2 * pad[0] - (dilate[0] * (kh - 1) + 1)) // stride[0] + 1
    ow = (w + 2 * pad[1] - (dilate[1] * (kw - 1) + 1)) // stride[1] + 1
    k = kh * kw
    off = offset.reshape(n, dg, k, 2, oh, ow)
    base_y = (jnp.arange(oh) * stride[0] - pad[0])[None, None, None, :,
                                                   None]
    base_x = (jnp.arange(ow) * stride[1] - pad[1])[None, None, None, None,
                                                   :]
    tap_y = jnp.repeat(jnp.arange(kh) * dilate[0],
                       kw).reshape(1, 1, k, 1, 1)
    tap_x = jnp.tile(jnp.arange(kw) * dilate[1],
                     kh).reshape(1, 1, k, 1, 1)
    py = base_y + tap_y + off[:, :, :, 0]           # (N, dg, K, oh, ow)
    px = base_x + tap_x + off[:, :, :, 1]

    y0 = jnp.floor(py)
    x0 = jnp.floor(px)
    wy1 = (py - y0).astype(data.dtype)
    wx1 = (px - x0).astype(data.dtype)
    dataf = data.reshape(n, dg, c // dg, h * w)

    def corner(yi, xi, wgt):
        # reference dmcn_im2col_bilinear: zero contribution outside
        valid = (yi >= 0) & (yi < h) & (xi >= 0) & (xi < w)
        yc = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
        xc = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
        idx = (yc * w + xc).reshape(n, dg, -1)
        gathered = jnp.take_along_axis(
            dataf, jnp.broadcast_to(idx[:, :, None, :],
                                    (n, dg, c // dg, idx.shape[-1])),
            axis=3).reshape(n, dg, c // dg, k, oh, ow)
        wgt = jnp.where(valid, wgt, 0.0).astype(data.dtype)
        return gathered * wgt[:, :, None]

    cols = (corner(y0, x0, (1 - wy1) * (1 - wx1))
            + corner(y0, x0 + 1, (1 - wy1) * wx1)
            + corner(y0 + 1, x0, wy1 * (1 - wx1))
            + corner(y0 + 1, x0 + 1, wy1 * wx1))
    if mask is not None:
        m = mask.reshape(n, dg, 1, k, oh, ow).astype(data.dtype)
        cols = cols * m
    return cols.reshape(n, c, k, oh, ow)


def _deformable_conv_impl(data, offset, mask, weight, bias, kernel, stride,
                          dilate, pad, num_filter, num_group,
                          num_deformable_group):
    n, c, _, _ = data.shape
    kh, kw = kernel
    cols = _deformable_sample(data, offset, mask, kernel, stride, dilate,
                              pad, num_deformable_group)
    _, _, _, oh, ow = cols.shape
    g = num_group
    colsr = cols.reshape(n, g, c // g, kh * kw, oh, ow)
    wr = weight.reshape(g, num_filter // g, c // g, kh * kw)
    out = jnp.einsum("ngckyx,gock->ngoyx", colsr, wr)
    out = out.reshape(n, num_filter, oh, ow)
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


def _pairify(v, n=2):
    v = (v,) * n if isinstance(v, int) else tuple(v)
    return v * n if len(v) == 1 else v


@register("_contrib_DeformableConvolution",
          aliases=["DeformableConvolution"], num_inputs=-1,
          params=[OpParam("kernel", tuple, None, required=True),
                  OpParam("stride", tuple, None),
                  OpParam("dilate", tuple, None),
                  OpParam("pad", tuple, None),
                  OpParam("num_filter", int, None, required=True),
                  OpParam("num_group", int, 1),
                  OpParam("num_deformable_group", int, 1),
                  OpParam("no_bias", bool, False),
                  OpParam("layout", str, None),
                  OpParam("workspace", int, 1024)],
          doc="Deformable convolution v1 (ref: src/operator/contrib/"
              "deformable_convolution.cc). Inputs: data, offset "
              "(N, dg*2*kh*kw, oh, ow), weight, [bias]. Completes the "
              "Faster-RCNN/DCN op family.")
def _deformable_convolution(data, offset, weight, *bias, kernel=None,
                            stride=None, dilate=None, pad=None,
                            num_filter=None, num_group=1,
                            num_deformable_group=1, no_bias=False,
                            layout=None, workspace=1024):
    stride = _pairify(stride or 1)
    dilate = _pairify(dilate or 1)
    pad = _pairify(pad or 0)
    return _deformable_conv_impl(
        data, offset, None, weight,
        None if no_bias or not bias else bias[0], tuple(kernel), stride,
        dilate, pad, num_filter, num_group, num_deformable_group)


@register("_contrib_ModulatedDeformableConvolution",
          aliases=["ModulatedDeformableConvolution"], num_inputs=-1,
          params=[OpParam("kernel", tuple, None, required=True),
                  OpParam("stride", tuple, None),
                  OpParam("dilate", tuple, None),
                  OpParam("pad", tuple, None),
                  OpParam("num_filter", int, None, required=True),
                  OpParam("num_group", int, 1),
                  OpParam("num_deformable_group", int, 1),
                  OpParam("no_bias", bool, False),
                  OpParam("layout", str, None),
                  OpParam("workspace", int, 1024)],
          doc="DCNv2: adds a per-tap modulation mask input (ref: "
              "src/operator/contrib/modulated_deformable_convolution.cc). "
              "Inputs: data, offset, mask (N, dg*kh*kw, oh, ow), weight, "
              "[bias].")
def _modulated_deformable_convolution(data, offset, mask, weight, *bias,
                                      kernel=None, stride=None,
                                      dilate=None, pad=None,
                                      num_filter=None, num_group=1,
                                      num_deformable_group=1,
                                      no_bias=False, layout=None,
                                      workspace=1024):
    stride = _pairify(stride or 1)
    dilate = _pairify(dilate or 1)
    pad = _pairify(pad or 0)
    return _deformable_conv_impl(
        data, offset, mask, weight,
        None if no_bias or not bias else bias[0], tuple(kernel), stride,
        dilate, pad, num_filter, num_group, num_deformable_group)


@register("_contrib_count_sketch", aliases=["count_sketch"], num_inputs=3,
          params=[OpParam("out_dim", int, None, required=True),
                  OpParam("processing_batch_size", int, 32)],
          doc="Count sketch projection (ref: src/operator/contrib/"
              "count_sketch.cc, compact bilinear pooling): out[n, h[i]] "
              "+= s[i] * data[n, i]. Linear, so autodiff provides the "
              "reference's hand-written backward.")
def _count_sketch(data, h, s, out_dim=None, processing_batch_size=32):
    n, in_dim = data.shape
    hh = h.reshape(-1).astype(jnp.int32)
    ss = s.reshape(-1).astype(data.dtype)
    out = jnp.zeros((n, out_dim), data.dtype)
    return out.at[:, hh].add(data * ss[None, :])


# ---------------------------------------------------------------------------
# XNOR-popcount packed binary inference (the BMXNet fork's signature
# capability, SURVEY §2 #23: smd_hpi/src xnor GEMM with int32 bit packing).
# Weights/activations store ONE BIT per value (32x memory compression);
# the ±1 dot product is  K - 2*popcount(xor(a, b))  over packed words,
# computed with lax.population_count on the VPU. On TPU the bf16 MXU
# matmul of ±1 values is usually FASTER (docs/divergences.md) — the packed
# path's win is memory/bandwidth (deployment), exactly like the
# reference's mobile targets.
# ---------------------------------------------------------------------------
def _pack_bits_lastdim(x):
    """Sign-bit pack the last dim into uint32 words (bit i of word j =
    sign(x[..., 32j+i]) >= 0). Pad tail bits with +1 (consistent packing
    of both operands makes pads xor to 0 and drop out of the popcount)."""
    k = x.shape[-1]
    words = -(-k // 32)
    pad = words * 32 - k
    bits = (x >= 0)
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.ones(x.shape[:-1] + (pad,), bool)], axis=-1)
    bits = bits.reshape(x.shape[:-1] + (words, 32))
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(bits.astype(jnp.uint32) * weights, axis=-1,
                   dtype=jnp.uint32)


@register("_contrib_binary_pack", aliases=["binary_pack"],
          differentiable=False,
          doc="Pack sign bits of the last dim into uint32 words "
              "(BMXNet binary_word packing, 32x weight compression)")
def _binary_pack(x):
    return _pack_bits_lastdim(x)


@register("_contrib_xnor_fully_connected", num_inputs=-1,
          params=[OpParam("in_dim", int, None, required=True)],
          differentiable=False,
          doc="Packed-binary GEMM: y = in_dim - 2*popcount(xor) over "
              "uint32-packed ±1 rows (BMXNet xnor_gemm). Inputs: x_packed "
              "[N, W32], w_packed [num_hidden, W32], (alpha [num_hidden] "
              "fp32 scale), (bias).")
def _xnor_fully_connected(xp, wp, *rest, in_dim=None):
    pc = jnp.sum(lax.population_count(
        jnp.bitwise_xor(xp[:, None, :], wp[None, :, :])).astype(jnp.int32),
        axis=-1)
    y = (in_dim - 2 * pc).astype(jnp.float32)
    if rest:
        y = y * rest[0]      # alpha: scalar or [num_hidden], broadcasts
    if len(rest) > 1:
        y = y + rest[1]
    return y


@register("_contrib_xnor_convolution", num_inputs=-1,
          params=[OpParam("kernel", tuple, None, required=True),
                  OpParam("num_filter", int, None, required=True),
                  OpParam("stride", tuple, (1, 1)),
                  OpParam("pad", tuple, (0, 0))],
          differentiable=False,
          doc="Packed-binary convolution: im2col patches packed to uint32, "
              "then the xnor-popcount GEMM (BMXNet binary conv inference). "
              "Inputs: x fp (binarized+packed internally), w_packed "
              "[num_filter, W32] packed over (C*kh*kw), (alpha), (bias). "
              "Padding uses +1 bits (BMXNet pads with +1, not 0).")
def _xnor_convolution(x, wp, *rest, kernel=None, num_filter=None,
                      stride=(1, 1), pad=(0, 0)):
    kh, kw = kernel
    n = x.shape[0]
    # im2col: [N, C*kh*kw, OH, OW] patches; pad value +1 keeps the ±1
    # algebra exact (sign bit of +1 is 1)
    xpad = jnp.pad(x, ((0, 0), (0, 0), (pad[0], pad[0]),
                       (pad[1], pad[1])), constant_values=1.0)
    patches = lax.conv_general_dilated_patches(
        xpad, filter_shape=(kh, kw), window_strides=tuple(stride),
        padding=[(0, 0), (0, 0)])
    _, ckk, oh, ow = patches.shape
    cols = patches.transpose(0, 2, 3, 1).reshape(n * oh * ow, ckk)
    xp = _pack_bits_lastdim(cols)
    pc = jnp.sum(lax.population_count(
        jnp.bitwise_xor(xp[:, None, :], wp[None, :, :])).astype(jnp.int32),
        axis=-1)
    y = (ckk - 2 * pc).astype(jnp.float32)
    if rest:
        y = y * rest[0]      # alpha: scalar or [num_filter], broadcasts
    if len(rest) > 1:
        y = y + rest[1]
    return y.reshape(n, oh, ow, num_filter).transpose(0, 3, 1, 2)


@register("_contrib_fused_self_attention", num_inputs=1,
          params=[OpParam("heads", int, None, required=True),
                  OpParam("causal", bool, False),
                  OpParam("block_size", int, 512)],
          doc="Self-attention straight off the fused QKV projection "
              "(B, S, 3C), q-major column blocks. Short sequences compute "
              "softmax(QK^T)V with einsums over the (B, S, H, D) layout — "
              "no data-movement transposes, XLA folds the head split into "
              "the matmuls (measured: the (3,B,H,S,D) permute chain cost "
              "~6 GB/step of layout copies in BERT, docs/perf_notes.md). "
              "Long sequences route to the streaming flash path.")
def _fused_self_attention(qkv, heads=None, causal=False, block_size=512):
    b, s, c3 = qkv.shape
    c = c3 // 3
    d = c // heads
    q = qkv[:, :, :c].reshape(b, s, heads, d)
    k = qkv[:, :, c:2 * c].reshape(b, s, heads, d)
    v = qkv[:, :, 2 * c:].reshape(b, s, heads, d)
    if s <= 1024:
        from .tensor import shifted_expsum
        scale = float(d) ** -0.5
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            qi = jnp.arange(s)[:, None]
            ki = jnp.arange(s)[None, :]
            scores = jnp.where(qi >= ki, scores,
                               jnp.finfo(scores.dtype).min)
        _, shifted, se32 = shifted_expsum(scores, axis=-1)
        att = (jnp.exp(shifted).astype(jnp.float32)
               / se32).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", att, v)
        return out.reshape(b, s, c)
    # long-sequence streaming path wants [B, H, S, D]; the downstream
    # kernels clamp block_size to a divisor of S themselves
    # (blockwise_attention), so callers stay shape-free — required for
    # symbolic export of attention blocks
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out = _flash_attention(qh, kh, vh, block_size=block_size,
                           causal=causal)
    return out.transpose(0, 2, 1, 3).reshape(b, s, c)


@register("_contrib_fused_cross_attention", num_inputs=2,
          params=[OpParam("heads", int, None, required=True),
                  OpParam("block_size", int, 512)],
          doc="Cross-attention off fused projections: q (B, Sq, C) "
              "attends over kv (B, Sk, 2C) — the decoder→encoder shape "
              "of the NMT transformer. Same (B, S, H, D) einsum layout "
              "and fp32-accumulated softmax as "
              "_contrib_fused_self_attention; shape-free for callers so "
              "decoder blocks export symbolically.")
def _fused_cross_attention(q_in, kv, heads=None, block_size=512):
    b, sq, c = q_in.shape
    sk = kv.shape[1]
    d = c // heads
    q = q_in.reshape(b, sq, heads, d)
    k = kv[:, :, :c].reshape(b, sk, heads, d)
    v = kv[:, :, c:].reshape(b, sk, heads, d)
    if sk <= 1024:
        from .tensor import shifted_expsum
        scale = float(d) ** -0.5
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        _, shifted, se32 = shifted_expsum(scores, axis=-1)
        att = (jnp.exp(shifted).astype(jnp.float32)
               / se32).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", att, v)
        return out.reshape(b, sq, c)
    out = _flash_attention(q.transpose(0, 2, 1, 3),
                           k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), block_size=block_size)
    return out.transpose(0, 2, 1, 3).reshape(b, sq, c)


# ---------------------------------------------------------------------------
# FFT (ref: src/operator/contrib/fft.cc, ifft.cc)
# ---------------------------------------------------------------------------

@register("_contrib_fft", aliases=["fft"],
          params=[OpParam("compute_size", int, 128)],
          doc="1-D FFT over the last axis; real input (..., d) -> "
              "interleaved real/imag output (..., 2*d), matching the "
              "reference's cuFFT wire format "
              "(ref: src/operator/contrib/fft.cc). compute_size (the "
              "reference's batching knob for cuFFT plans) is accepted "
              "and ignored — XLA plans the whole batch at once.")
def _fft(x, compute_size=128):
    spec = jnp.fft.fft(x.astype(jnp.float32), axis=-1)
    out = jnp.stack([spec.real, spec.imag], axis=-1)
    return out.reshape(x.shape[:-1] + (2 * x.shape[-1],)).astype(jnp.float32)


@register("_contrib_ifft", aliases=["ifft"],
          params=[OpParam("compute_size", int, 128)],
          doc="Inverse of _contrib_fft: interleaved (..., 2*d) -> real "
              "(..., d). Like the reference (cuFFT CUFFT_INVERSE), the "
              "output is UNNORMALIZED: ifft(fft(x)) == d * x "
              "(ref: src/operator/contrib/ifft.cc).")
def _ifft(x, compute_size=128):
    d = x.shape[-1] // 2
    pairs = x.reshape(x.shape[:-1] + (d, 2)).astype(jnp.float32)
    spec = lax.complex(pairs[..., 0], pairs[..., 1])
    # unnormalized inverse = conj(fft(conj(spec))); jnp.fft.ifft divides
    # by d, so scale back up to match the reference wire format
    return (jnp.fft.ifft(spec, axis=-1).real * d).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Spatial sampling trio (ref: src/operator/{grid_generator,
# bilinear_sampler, spatial_transformer}.cc). All three share one
# bilinear-gather core, the same machinery ROIAlign/DeformableConv use,
# but with the reference's zero-padding boundary (outside samples read 0)
# instead of border clamping.
# ---------------------------------------------------------------------------

def _bilinear_sample_zero_pad(img, xf, yf):
    """Sample img (C, H, W) at float pixel coords xf/yf (...,) with
    bilinear interpolation and zero padding outside; differentiable in
    img and coords. Vectorized: one advanced-indexing gather per corner,
    which XLA lowers to a single gather + FMA chain per corner (VPU
    work), the TPU-native shape of the reference's per-pixel CUDA loop."""
    h, w = img.shape[1], img.shape[2]
    x0 = jnp.floor(xf)
    y0 = jnp.floor(yf)
    wx = xf - x0
    wy = yf - y0

    def corner(yi, xi, wgt):
        inb = (yi >= 0) & (yi < h) & (xi >= 0) & (xi < w)
        yc = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
        xc = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
        vals = img[:, yc, xc]                     # (C, ...)
        return jnp.where(inb[None], vals * wgt[None], 0.0)

    return (corner(y0, x0, (1 - wy) * (1 - wx))
            + corner(y0 + 1, x0, wy * (1 - wx))
            + corner(y0, x0 + 1, (1 - wy) * wx)
            + corner(y0 + 1, x0 + 1, wy * wx))


@register("BilinearSampler", num_inputs=2,
          params=[OpParam("cudnn_off", bool, False)],
          doc="Sample data (B, C, H, W) at grid (B, 2, Ho, Wo) of "
              "normalized [-1, 1] (x, y) coords; zero padding outside "
              "(ref: src/operator/bilinear_sampler.cc). x maps to "
              "(x+1)*(W-1)/2 like the reference.")
def _bilinear_sampler(data, grid, cudnn_off=False):
    h, w = data.shape[2], data.shape[3]

    def one(img, g):
        xf = (g[0] + 1.0) * (w - 1.0) / 2.0
        yf = (g[1] + 1.0) * (h - 1.0) / 2.0
        return _bilinear_sample_zero_pad(img, xf, yf)

    return jax.vmap(one)(data, grid)


@register("GridGenerator", num_inputs=1,
          params=[OpParam("transform_type", str, "affine", required=True),
                  OpParam("target_shape", tuple, (0, 0))],
          doc="Generate BilinearSampler grids "
              "(ref: src/operator/grid_generator.cc). 'affine': data "
              "(B, 6) 2x3 matrices over a normalized [-1, 1] target "
              "grid -> (B, 2, H, W). 'warp': data = pixel flow "
              "(B, 2, H, W) added to the identity grid, normalized.")
def _grid_generator(data, transform_type="affine", target_shape=(0, 0)):
    if transform_type == "affine":
        hh, ww = int(target_shape[0]), int(target_shape[1])
        b = data.shape[0]
        ys = jnp.linspace(-1.0, 1.0, hh) if hh > 1 else jnp.zeros((1,))
        xs = jnp.linspace(-1.0, 1.0, ww) if ww > 1 else jnp.zeros((1,))
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        src = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()])  # (3, H*W)
        theta = data.reshape(b, 2, 3).astype(jnp.float32)
        grid = jnp.einsum("bij,jk->bik", theta, src)             # (B, 2, H*W)
        return grid.reshape(b, 2, hh, ww).astype(data.dtype)
    if transform_type == "warp":
        b, _, hh, ww = data.shape
        base_x, base_y = jnp.meshgrid(jnp.arange(ww, dtype=jnp.float32),
                                      jnp.arange(hh, dtype=jnp.float32),
                                      indexing="xy")
        x = data[:, 0] + base_x
        y = data[:, 1] + base_y
        xn = x * (2.0 / max(ww - 1, 1)) - 1.0
        yn = y * (2.0 / max(hh - 1, 1)) - 1.0
        return jnp.stack([xn, yn], axis=1).astype(data.dtype)
    raise MXNetError(f"GridGenerator: unknown transform_type {transform_type!r}")


@register("SpatialTransformer", num_inputs=2,
          params=[OpParam("transform_type", str, "affine", required=True),
                  OpParam("sampler_type", str, "bilinear", required=True),
                  OpParam("target_shape", tuple, (0, 0)),
                  OpParam("cudnn_off", bool, False)],
          doc="Affine spatial transformer = GridGenerator('affine') + "
              "BilinearSampler, fused in one traced graph so XLA shares "
              "the grid across channels "
              "(ref: src/operator/spatial_transformer.cc).")
def _spatial_transformer(data, loc, transform_type="affine",
                         sampler_type="bilinear", target_shape=(0, 0),
                         cudnn_off=False):
    if transform_type != "affine" or sampler_type != "bilinear":
        raise MXNetError("SpatialTransformer supports transform_type='affine'"
                         " sampler_type='bilinear' (like the reference)")
    hh, ww = int(target_shape[0]), int(target_shape[1])
    if hh <= 0 or ww <= 0:
        hh, ww = data.shape[2], data.shape[3]
    grid = _grid_generator(loc, transform_type="affine",
                           target_shape=(hh, ww))
    return _bilinear_sampler(data, grid)


# ---------------------------------------------------------------------------
# round-5 contrib stragglers: the small parity ops reference scripts touch
# ---------------------------------------------------------------------------

@register("_contrib_quadratic", aliases=["quadratic"],
          params=[OpParam("a", float, 0.0), OpParam("b", float, 0.0),
                  OpParam("c", float, 0.0)],
          doc="a*x^2 + b*x + c — the reference's custom-op tutorial op "
              "(ref: src/operator/contrib/quadratic_op.cc)")
def _quadratic(x, a=0.0, b=0.0, c=0.0):
    return a * x * x + b * x + c


@register("_contrib_allclose", aliases=["allclose"], num_inputs=2,
          params=[OpParam("rtol", float, 1e-5), OpParam("atol", float, 1e-8),
                  OpParam("equal_nan", bool, False)],
          differentiable=False,
          doc="Elementwise closeness reduced to one scalar (ref: "
              "src/operator/contrib/allclose_op.cc)")
def _allclose(a, b, rtol=1e-5, atol=1e-8, equal_nan=False):
    return jnp.allclose(a, b, rtol=rtol, atol=atol,
                        equal_nan=equal_nan).astype(jnp.float32)


@register("_contrib_index_copy", aliases=["index_copy"], num_inputs=3,
          doc="Copy rows of new_tensor into old_tensor at index (ref: "
              "src/operator/contrib/index_copy.cc); functional on TPU — "
              "returns the updated array instead of mutating")
def _index_copy(old, index, new):
    if not isinstance(index, jax.core.Tracer):
        idx = jnp.asarray(index)
        n = old.shape[0]
        if idx.size and (int(idx.min()) < 0 or int(idx.max()) >= n):
            raise MXNetError(
                f"index_copy: index out of range for dim-0 size {n} "
                f"(got min {int(idx.min())}, max {int(idx.max())}) — the "
                "reference validates bounds; a silent scatter-drop would "
                "leave rows un-copied")
    return old.at[index.astype(jnp.int32)].set(new)


@register("_contrib_boolean_mask", aliases=["boolean_mask"], num_inputs=2,
          params=[OpParam("axis", int, 0)], differentiable=False,
          doc="Select rows where mask != 0 (ref: src/operator/contrib/"
              "boolean_mask.cc). DATA-DEPENDENT output shape: eager-only "
              "(a jit trace would need static shapes — use `where` with a "
              "neutral fill, or SequenceMask, inside compiled code)")
def _boolean_mask(data, mask, axis=0):
    if isinstance(data, jax.core.Tracer) or isinstance(mask,
                                                       jax.core.Tracer):
        raise MXNetError(
            "boolean_mask has a data-dependent output shape and cannot "
            "run inside jit/hybridize; use where/SequenceMask there")
    import numpy as _onp
    mask_np = _onp.asarray(mask)
    if mask_np.ndim != 1:
        raise MXNetError(
            f"boolean_mask: mask must be 1-D, got shape {mask_np.shape} "
            "(a 2-D mask would index one row per nonzero ELEMENT)")
    if mask_np.shape[0] != data.shape[axis]:
        raise MXNetError(
            f"boolean_mask: mask length {mask_np.shape[0]} != data axis "
            f"{axis} size {data.shape[axis]}")
    keep = _onp.nonzero(mask_np != 0)[0]
    return jnp.take(data, jnp.asarray(keep, jnp.int32), axis=axis)


@register("_contrib_BatchNormWithReLU", aliases=["BatchNormWithReLU"],
          num_inputs=5, num_outputs=3, needs_mode=True,
          params=[OpParam("eps", float, 1e-3),
                  OpParam("momentum", float, 0.9),
                  OpParam("fix_gamma", bool, True),
                  OpParam("use_global_stats", bool, False),
                  OpParam("output_mean_var", bool, False),
                  OpParam("axis", int, 1),
                  OpParam("cudnn_off", bool, False)],
          doc="BatchNorm with fused ReLU epilogue (ref: src/operator/nn/"
              "batch_norm_relu.cc); XLA fuses the max into the normalize")
def _batch_norm_with_relu(x, gamma, beta, moving_mean, moving_var, **kw):
    from .nn import _batch_norm
    out, mean, var = _batch_norm(x, gamma, beta, moving_mean, moving_var,
                                 **kw)
    return jnp.maximum(out, 0.0).astype(out.dtype), mean, var
