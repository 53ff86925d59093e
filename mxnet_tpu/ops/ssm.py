"""State-space mixer ops (Mamba-2) and the gated elementwise ops of the
decoder layers built on them. No reference analog: MXNet 1.x has no scan
op; these are new TPU-side capability (ROADMAP R6).

- ``_contrib_causal_conv1d``: the depthwise causal convolution in front of
  the scan (kernel ``K``, left-padded by ``K - 1``), with its activation;
- ``_contrib_mamba2_ssd``: the selective state-space recurrence
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``
  computed in chunks (Dao & Gu, "Transformers are SSMs", arXiv:2405.21060,
  sec. 6): inside a chunk as masked products, between chunks as a
  recurrence over the chunk states. The step sizes and the cumulative
  log-decay are made here; the scan itself is the kernel tier's
  ``mamba2_ssd`` (``pallas/ssd.py``): one fused pass over the chunks on a
  TPU, the ``jax.numpy`` scan elsewhere;
- ``_contrib_gated_rms_norm``: ``RMSNorm(y * silu(z))`` behind the scan, over
  all channels or over each of ``groups`` runs of them (those as products
  with the 0/1 group matrix, so that nothing is viewed by group);
- ``_contrib_swiglu``: ``silu(g) * u`` over the two halves of the last axis.

But for that kernel all four are plain ``jax.numpy`` / ``lax`` that XLA
compiles, and their backward passes are autodiff's. ``dt``, ``A``, the
cumulative sums, the exponentials and every accumulation are float32
whatever the compute dtype; the operands of the scan's products are in the
compute dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import OpParam, register

SCAN_COUNT_METRIC = "mxnet_tpu_ssd_scans_traced_total"
NORM_COUNT_METRIC = "mxnet_tpu_gated_norms_traced_total"

_F32 = jnp.float32


def _count_traced_scan(chunk, length, path):
    """One chunked scan traced into a program, by chunk size, (padded)
    sequence length and the path the kernel tier chose (``kernel``: the
    fused scan where the program is lowered for a TPU; ``xla``: the
    ``jax.numpy`` scan): trace-time only, so a compiled step never counts."""
    from ..observability.metrics import default_registry
    default_registry().counter(
        SCAN_COUNT_METRIC, "chunked state-space scans traced into a program",
        ("chunk", "length", "path")).labels(
            chunk=str(chunk), length=str(length), path=path).inc()


def _count_traced_norm(groups, channels):
    """One gated norm traced into a program, by groups, channels and form
    (``plain``: one group; ``grouped``: the product form): trace-time only,
    as the scans are counted."""
    from ..observability.metrics import default_registry
    default_registry().counter(
        NORM_COUNT_METRIC, "gated RMS norms traced into a program",
        ("groups", "channels", "form")).labels(
            groups=str(groups), channels=str(channels),
            form="plain" if groups == 1 else "grouped").inc()


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
    length, heads = x.shape[1], x.shape[2]
    q = int(chunk_size)
    pad = -length % q
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    dt = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    a = -jnp.exp(a_log.astype(_F32))
    # log of the decay from the chunk's start to each position, inclusive:
    # the cumulative sum of dt * A inside a chunk, as a float32 product with
    # the lower triangle of ones (exact terms, float32 sums). jnp.cumsum
    # over (B, nc, q, G, R) is a reduce-window whose minor axes are 8 x 8 at
    # Nemotron's shape: 1.96 ms for these 2 MB on a v5e, twice a layer a
    # step, against 0.20 for the product (PERF.md sec. 6, PR 33)
    cs = jnp.einsum("ls,bcsh->bclh", jnp.tril(jnp.ones((q, q), _F32)),
                    (dt * a).reshape(x.shape[0], -1, q, heads),
                    precision=lax.Precision.HIGHEST).reshape(dt.shape)
    from ..pallas import dispatch, tier_provenance
    before = tier_provenance().get("mamba2_ssd", {}).get("pallas", 0)
    y = dispatch("mamba2_ssd", x, dt, cs, b, c, d, chunk_size=q)
    if isinstance(x, jax.core.Tracer):
        took_kernel = tier_provenance().get("mamba2_ssd", {}).get(
            "pallas", 0) > before
        _count_traced_scan(q, length + pad, "kernel" if took_kernel else "xla")
    return y[:, :length]


@register("_contrib_gated_rms_norm", num_inputs=3,
          params=[OpParam("eps", float, 1e-5), OpParam("groups", int, 1)],
          doc="RMSNorm(y * silu(z)) * gamma over the last axis (gate before "
              "norm), the mean square taken over each of `groups` equal "
              "runs of consecutive channels apart (1: over all of them), "
              "computed in float32, returned in y's dtype.")
def _gated_rms_norm(y, z, gamma, eps=1e-5, groups=1):
    g = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    channels = g.shape[-1]
    if isinstance(g, jax.core.Tracer):
        _count_traced_norm(groups, channels)
    if groups == 1:
        ms = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
        normed = g * lax.rsqrt(ms + eps)
    else:
        if channels % groups:
            raise MXNetError(f"gated_rms_norm: {channels} channels are no "
                             f"multiple of groups {groups}")
        # group sums and per-channel scale as float32 products with the
        # (C, groups) 0/1 matrix of "channel c is in group j", so that g
        # keeps its (..., C) layout: a (..., groups, C / groups) view puts
        # the groups on a TPU's sublanes and costs a broadcast and a
        # reshape of the whole array (PERF.md sec. 5.1, PR 37). HIGHEST:
        # the default precision rounds g^2 to bf16.
        member = jnp.repeat(jnp.eye(groups, dtype=_F32), channels // groups,
                            axis=0)
        ms = jnp.matmul(jnp.square(g), member,
                        precision=lax.Precision.HIGHEST) / (channels // groups)
        normed = g * jnp.matmul(lax.rsqrt(ms + eps), member.T,
                                precision=lax.Precision.HIGHEST)
    return (normed * gamma.astype(_F32)).astype(y.dtype)


@register("_contrib_swiglu", num_inputs=1,
          doc="silu(g) * u with [g, u] the two halves of the last axis, "
              "computed in float32, returned in the input's dtype.")
def _swiglu(gu):
    if gu.shape[-1] % 2:
        raise MXNetError(f"swiglu: last axis must be even, got {gu.shape}")
    g, u = jnp.split(gu.astype(_F32), 2, axis=-1)
    return (jax.nn.silu(g) * u).astype(gu.dtype)
