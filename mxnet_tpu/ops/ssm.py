"""State-space mixer ops (Mamba-2) and the gated elementwise ops of the
decoder layers built on them. No reference analog: MXNet 1.x has no scan
op; these are new TPU-side capability (ROADMAP R6).

- ``_contrib_causal_conv1d``: the depthwise causal convolution in front of
  the scan (kernel ``K``, left-padded by ``K - 1``), with its activation;
- ``_contrib_mamba2_ssd``: the selective state-space recurrence
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``
  computed in chunks (Dao & Gu, "Transformers are SSMs", arXiv:2405.21060,
  sec. 6): inside a chunk as masked products, between chunks as a
  recurrence over the chunk states;
- ``_contrib_gated_rms_norm``: ``RMSNorm(y * silu(z))`` behind the scan, over
  all channels or over each of ``groups`` runs of them;
- ``_contrib_swiglu``: ``silu(g) * u`` over the two halves of the last axis.

All four are plain ``jax.numpy`` / ``lax`` that XLA compiles, and their
backward passes are autodiff's. ``dt``, ``A``, the cumulative sums, the
exponentials and every accumulation are float32 whatever the compute dtype;
the operands of the four products are in the compute dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import OpParam, register

SCAN_COUNT_METRIC = "mxnet_tpu_ssd_scans_traced_total"

_F32 = jnp.float32


def _count_traced_scan(chunk, length):
    """One chunked scan traced into a program, by chunk size and (padded)
    sequence length: trace-time only, so a compiled step never counts."""
    from ..observability.metrics import default_registry
    default_registry().counter(
        SCAN_COUNT_METRIC, "chunked state-space scans traced into a program",
        ("chunk", "length")).labels(chunk=str(chunk),
                                    length=str(length)).inc()


def _act(x, act_type):
    if act_type == "silu":
        return jax.nn.silu(x)
    if act_type in (None, "identity"):
        return x
    raise MXNetError(f"unknown act_type {act_type!r}; one of silu, identity")


@register("_contrib_causal_conv1d", num_inputs=3,
          params=[OpParam("act_type", str, "silu")],
          doc="Depthwise causal 1-D convolution over (B, L, C) with weight "
              "(C, K) and bias (C,): out[t] = act(bias + sum_j w[:, j] * "
              "x[t + j - (K - 1)]), zeros before the sequence. Accumulated "
              "in float32, returned in x's dtype.")
def _causal_conv1d(x, weight, bias, act_type="silu"):
    if x.ndim != 3 or weight.ndim != 2 or weight.shape[0] != x.shape[-1]:
        raise MXNetError(f"causal_conv1d: x (B, L, C) and weight (C, K) "
                         f"expected, got {x.shape} and {weight.shape}")
    taps, length = weight.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    w = weight.astype(_F32)
    acc = bias.astype(_F32)
    for j in range(taps):
        acc = acc + padded[:, j:j + length].astype(_F32) * w[:, j]
    return _act(acc, act_type).astype(x.dtype)


@register("_contrib_mamba2_ssd", num_inputs=7,
          params=[OpParam("chunk_size", int, 256)],
          doc="Mamba-2 selective state-space scan, chunked. Inputs: x (B, L, "
              "H, P), dt (B, L, H) before its bias and softplus, A_log (H,), "
              "B and C (B, L, G, N) with H a multiple of G, D (H,), dt_bias "
              "(H,). Returns y (B, L, H, P) in x's dtype. A length that is "
              "no multiple of chunk_size is padded at the end (the scan is "
              "causal, so the padding changes no output that is kept).")
def _mamba2_ssd(x, dt, a_log, b, c, d, dt_bias, chunk_size=256):
    if x.ndim != 4 or b.ndim != 4 or x.shape[2] % b.shape[2]:
        raise MXNetError(f"mamba2_ssd: x (B, L, H, P) and B, C (B, L, G, N) "
                         f"with H a multiple of G expected, got {x.shape} "
                         f"and {b.shape}")
    bsz, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r = heads // groups                         # heads that share a B and C
    q = int(chunk_size)
    pad = -length % q
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (length + pad) // q
    if isinstance(x, jax.core.Tracer):
        _count_traced_scan(q, length + pad)
    cdt = x.dtype

    def dot(spec, *operands):
        return jnp.einsum(spec, *(o.astype(cdt) for o in operands),
                          preferred_element_type=_F32)

    dt = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    a = -jnp.exp(a_log.astype(_F32))
    xc = x.reshape(bsz, nc, q, groups, r, p)
    bc = b.reshape(bsz, nc, q, groups, n)
    cc = c.reshape(bsz, nc, q, groups, n)
    dtc = dt.reshape(bsz, nc, q, groups, r)
    # log of the decay from the chunk's start to each position, inclusive
    cs = jnp.cumsum(dtc * a.reshape(groups, r), axis=2)

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

    y = y.reshape(bsz, nc * q, heads, p)[:, :length]
    skip = d.astype(_F32)[:, None] * x[:, :length].astype(_F32)
    return (y + skip).astype(cdt)


@register("_contrib_gated_rms_norm", num_inputs=3,
          params=[OpParam("eps", float, 1e-5), OpParam("groups", int, 1)],
          doc="RMSNorm(y * silu(z)) * gamma over the last axis (gate before "
              "norm), the mean square taken over each of `groups` equal "
              "runs of consecutive channels apart (1: over all of them), "
              "computed in float32, returned in y's dtype.")
def _gated_rms_norm(y, z, gamma, eps=1e-5, groups=1):
    g = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    if groups == 1:
        ms = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
        normed = g * lax.rsqrt(ms + eps)
    else:
        if g.shape[-1] % groups:
            raise MXNetError(f"gated_rms_norm: {g.shape[-1]} channels are no "
                             f"multiple of groups {groups}")
        by_group = g.reshape(g.shape[:-1] + (groups, -1))
        ms = jnp.mean(jnp.square(by_group), axis=-1, keepdims=True)
        normed = (by_group * lax.rsqrt(ms + eps)).reshape(g.shape)
    return (normed * gamma.astype(_F32)).astype(y.dtype)


@register("_contrib_swiglu", num_inputs=1,
          doc="silu(g) * u with [g, u] the two halves of the last axis, "
              "computed in float32, returned in the input's dtype.")
def _swiglu(gu):
    if gu.shape[-1] % 2:
        raise MXNetError(f"swiglu: last axis must be even, got {gu.shape}")
    g, u = jnp.split(gu.astype(_F32), 2, axis=-1)
    return (jax.nn.silu(g) * u).astype(gu.dtype)
