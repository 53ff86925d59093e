"""Base utilities for mxnet_tpu.

TPU-native re-design of the reference's base layer. The reference routes
everything through a C ABI (ref: include/mxnet/base.h, include/mxnet/c_api.h);
here the "runtime" is JAX/XLA, so the base layer is dtype/string plumbing,
error types, and the environment-variable knobs the reference exposes as
``MXNET_*`` (ref: docs env_var.md catalog, read via dmlc::GetEnv).
"""
from __future__ import annotations

import os

import numpy as _np

__all__ = [
    "MXNetError",
    "string_types",
    "numeric_types",
    "integer_types",
    "mx_real_t",
    "_as_np_dtype",
    "_dtype_name",
    "getenv",
]


class MXNetError(RuntimeError):
    """Error raised by the framework (ref: MXGetLastError carries C++ errors
    across the C ABI; here plain Python exceptions)."""


string_types = (str,)
numeric_types = (float, int, _np.generic)
integer_types = (int, _np.integer)

# Default real dtype (ref: mshadow::default_real_t = float32).
mx_real_t = _np.float32

_DTYPE_ALIASES = {
    "float": _np.float32,
    "double": _np.float64,
    "half": _np.float16,
    "bfloat16": None,  # resolved lazily via ml_dtypes below
}


def _as_np_dtype(dtype):
    """Normalize a user dtype (string/np.dtype/type) to a numpy dtype object.

    Supports 'bfloat16' through ml_dtypes (what JAX uses on TPU).
    """
    if dtype is None:
        return _np.dtype(mx_real_t)
    if isinstance(dtype, str):
        if dtype in ("bfloat16", "bf16"):
            import ml_dtypes

            return _np.dtype(ml_dtypes.bfloat16)
        if dtype in _DTYPE_ALIASES and _DTYPE_ALIASES[dtype] is not None:
            return _np.dtype(_DTYPE_ALIASES[dtype])
    try:
        return _np.dtype(dtype)
    except TypeError:
        import ml_dtypes

        if dtype in (ml_dtypes.bfloat16,):
            return _np.dtype(dtype)
        raise


def _dtype_name(dtype) -> str:
    """Canonical string name for a dtype ('float32', 'bfloat16', ...)."""
    return _as_np_dtype(dtype).name


def getenv(name: str, default=None, typ=str):
    """Read an ``MXNET_*`` env knob (ref: dmlc::GetEnv use sites)."""
    val = os.environ.get(name)
    if val is None:
        return default
    if typ is bool:
        return val not in ("0", "false", "False", "")
    return typ(val)


# A value named so with ``jax.ad_checkpoint.checkpoint_name`` is kept for the
# backward pass by ``HybridBlock.recompute()`` and not computed again there.
# A router's choice is the case: the compiler may round a recomputed layer
# elsewhere than it rounded the forward's, and a score at the cut then sends
# a token to another expert in the backward pass than in the forward pass.
RECOMPUTE_KEEP = "mxnet_tpu.keep"
