"""mxnet_tpu.pallas — the guarded custom-kernel tier (docs/pallas.md).

One registry (``registry.py``) maps op names to (pallas_impl,
xla_reference, tolerance) triples; ``dispatch`` auto-selects the custom
path only where it is verified to run and falls back — journaled, never
silent — to the XLA reference everywhere else (non-TPU backends,
unsupported shapes, ``MXNET_TPU_PALLAS=off``). Every registered kernel is
parity-gated against its reference at test time (tests/test_pallas.py),
so the tier can never silently change numerics, and CI's G10 lint rule
keeps raw ``pl.pallas_call`` out of library code so no kernel can bypass
the guard.

Importing this package registers the seed kernels (``kernels.py``); it
never dials a backend (G1 contract — backend checks happen at dispatch
time).
"""
from __future__ import annotations

from . import kernels as _kernels          # noqa: F401  (registration)
from .kernels import (EPILOGUE_ACTS, dropout_bits, fused_matmul_epilogue,
                      keep_threshold)
from .registry import (MODES, KernelSpec, dispatch, get_kernel, kernels,
                       mode, register_kernel, reset_provenance, set_mode,
                       tier_provenance)

__all__ = ["KernelSpec", "MODES", "EPILOGUE_ACTS", "dispatch",
           "dropout_bits", "fused_matmul_epilogue", "get_kernel",
           "keep_threshold", "kernels", "mode", "register_kernel",
           "reset_provenance", "set_mode", "tier_provenance"]
