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

``phi`` is taken over the unordered pairs of coordinates
(``pallas/retention.py:pair_features``): ``(u . w)^2 = sum_r w_r sum_a (u_a
u_{a+r}) (w_a w_{a+r})`` over the offsets ``r = 0 .. d // 2``, indices modulo
``d``, ``w_r`` = 1 where ``r`` and ``d - r`` are the same offset and 2
elsewhere. At ``d`` = 128 that is 65 x 128 = 8320 products, each a rotation
of the coordinates times the coordinates, against 16384 for the full outer
product and 8256 for the distinct pairs; the state is (8320, d) in float32
for one key/value head.

This module keeps the operator's checks, its padding, the float32 cumulative
log-decay and the counter; the retention from there on is the kernel tier's
``power_retention`` (``pallas/retention.py``, ``pallas.dispatch``):

- on a TPU, where a head and a value are one lane tile (128) and the chunk a
  multiple of 128 rows, one fused pass over a head's chunks with both states
  in fast memory, forward and backward (a kernel each): the expanded rows
  are formed there and never written to HBM;
- everywhere else (the CPU, a program the compiler partitions, a shape the
  kernel declines: counted and journaled by the tier) the ``jax.numpy`` scan
  the kernel is held to: ``lax.map`` over the key/value heads and
  ``lax.scan`` over the chunks, one step forming the expanded rows of one
  chunk of one key/value head's query heads (5 x 1024 x 8320 bf16 = 85 MB at
  the published sizes), its chunk body ``jax.checkpoint``ed and its backward
  autodiff's.

What is never held on either path: the expanded queries of a sequence (8192
x 40 x 8320 bf16 = 5.5 GB) and more than one chunk's ``C x C`` scores. What
autodiff keeps on either path: the operator's inputs, the numerators and
normalisers (the division is outside the loops and outside the kernel) and
the float32 states at the chunk boundaries (8 x 8 x 4.3 MB a layer at 8192
rows and chunk 1024); the rest is computed again chunk by chunk in the
backward pass.

Decays, cumulative sums, exponentials, states and every accumulation are
float32 whatever the compute dtype; the operands of the products are in the
compute dtype (as ``_contrib_mamba2_ssd``), on both paths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import OpParam, register

RETENTION_COUNT_METRIC = "mxnet_tpu_power_retentions_traced_total"

_F32 = jnp.float32


def _count_traced_retention(chunk, length, path):
    """One chunked retention traced into a program, by chunk size, (padded)
    sequence length and the path the kernel tier chose (``kernel``: the
    fused pass where the program is lowered for a TPU; ``xla``: the
    ``jax.numpy`` scan): trace-time only, so a compiled step never counts."""
    from ..observability.metrics import default_registry
    default_registry().counter(
        RETENTION_COUNT_METRIC,
        "chunked power retentions traced into a program",
        ("chunk", "length", "path")).labels(
            chunk=str(chunk), length=str(length), path=path).inc()


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
    batch, length = q.shape[:2]
    groups = k.shape[2]
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
    from ..pallas import dispatch, tier_provenance
    before = tier_provenance().get("power_retention", {}).get("pallas", 0)
    y = dispatch("power_retention", q, k, v, cs, chunk_size=rows,
                 eps=float(eps))
    if isinstance(q, jax.core.Tracer):
        took_kernel = tier_provenance().get("power_retention", {}).get(
            "pallas", 0) > before
        _count_traced_retention(rows, length + pad,
                                "kernel" if took_kernel else "xla")
    return y[:, :length]
