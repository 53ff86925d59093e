"""Test utilities — port of the reference's test methodology
(ref: python/mxnet/test_utils.py): dtype-aware ``assert_almost_equal``,
central-finite-difference ``check_numeric_gradient``, and
``check_consistency`` across contexts (the reference's CPU-vs-GPU trick,
here CPU-jax vs TPU-jax / eager vs jit).
"""
from __future__ import annotations

import numpy as np

from . import _rng
from .base import _as_np_dtype
from .context import Context, cpu, current_context
from .ndarray import NDArray, array

__all__ = ["default_context", "set_default_context", "assert_almost_equal",
           "almost_equal", "same", "rand_ndarray", "rand_shape_2d",
           "rand_shape_3d", "rand_shape_nd", "check_numeric_gradient",
           "check_consistency", "default_dtype", "list_contexts"]

_default_ctx = [None]

# dtype-aware default tolerances (ref: test_utils.py assert_almost_equal)
_RTOL = {np.dtype(np.float16): 1e-2, np.dtype(np.float32): 1e-4,
         np.dtype(np.float64): 1e-6}
_ATOL = {np.dtype(np.float16): 1e-3, np.dtype(np.float32): 1e-5,
         np.dtype(np.float64): 1e-7}


def default_context() -> Context:
    return _default_ctx[0] or current_context()


def set_default_context(ctx: Context):
    _default_ctx[0] = ctx


def default_dtype():
    return np.float32


def list_contexts():
    from .context import num_tpus, tpu
    return [cpu()] + ([tpu()] if num_tpus() else [])


def _as_np(a):
    if isinstance(a, NDArray):
        return a.asnumpy()
    return np.asarray(a)


def same(a, b) -> bool:
    return np.array_equal(_as_np(a), _as_np(b))


def almost_equal(a, b, rtol=None, atol=None) -> bool:
    a, b = _as_np(a), _as_np(b)
    rtol = rtol if rtol is not None else _RTOL.get(a.dtype, 1e-4)
    atol = atol if atol is not None else _ATOL.get(a.dtype, 1e-5)
    return np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=True)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b")):
    a_np, b_np = _as_np(a).astype(np.float64), _as_np(b).astype(np.float64)
    rtol = rtol if rtol is not None else _RTOL.get(_as_np(a).dtype, 1e-4)
    atol = atol if atol is not None else _ATOL.get(_as_np(a).dtype, 1e-5)
    np.testing.assert_allclose(a_np, b_np, rtol=rtol, atol=atol,
                               err_msg=f"{names[0]} vs {names[1]}")


def rand_ndarray(shape, stype="default", density=None, dtype=None, ctx=None,
                 scale=1.0) -> NDArray:
    if stype != "default":
        raise NotImplementedError("sparse rand_ndarray not supported yet")
    arr = np.random.uniform(-scale, scale, size=shape)
    return array(arr.astype(_as_np_dtype(dtype or np.float32)), ctx=ctx)


def rand_shape_2d(dim0=10, dim1=10):
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1),
            np.random.randint(1, dim2 + 1))


def rand_shape_nd(ndim, dim=10):
    return tuple(np.random.randint(1, dim + 1, size=ndim))


def numeric_grad(executor_fn, inputs, eps=1e-4):
    """Central finite differences d(sum(f))/d(inputs)
    (ref: test_utils.py numeric_grad)."""
    grads = []
    for i, x in enumerate(inputs):
        x_np = x.asnumpy().astype(np.float64)
        g = np.zeros_like(x_np)
        flat = x_np.ravel()
        gflat = g.ravel()
        for j in range(flat.size):
            orig = flat[j]
            flat[j] = orig + eps
            plus = float(np.sum(_as_np(executor_fn(
                [array(x_np.astype(np.float32)) if k == i else inputs[k]
                 for k in range(len(inputs))]))))
            flat[j] = orig - eps
            minus = float(np.sum(_as_np(executor_fn(
                [array(x_np.astype(np.float32)) if k == i else inputs[k]
                 for k in range(len(inputs))]))))
            flat[j] = orig
            gflat[j] = (plus - minus) / (2 * eps)
        grads.append(g)
    return grads


def check_numeric_gradient(fn, inputs, rtol=1e-2, atol=1e-3, eps=1e-3):
    """Compare autograd gradients of ``sum(fn(*inputs))`` against central
    finite differences (ref: mx.test_utils.check_numeric_gradient — the
    reference's primary per-op gradient test method, SURVEY §4)."""
    from . import autograd
    inputs = [x if isinstance(x, NDArray) else array(x) for x in inputs]
    for x in inputs:
        x.attach_grad()
    with autograd.record():
        out = fn(*inputs)
        loss = out.sum() if isinstance(out, NDArray) else sum(o.sum() for o in out)
    loss.backward()
    analytic = [x.grad.asnumpy() for x in inputs]

    def run(xs):
        # numeric pass must evaluate in the SAME mode the analytic pass
        # recorded under (train): pause() alone flips mode-dependent ops
        # (training BatchNorm) to inference and the comparison is then
        # between two different functions
        with autograd.pause(train_mode=True):
            out2 = fn(*xs)
        return out2 if isinstance(out2, NDArray) else out2[0] + sum(out2[1:], 0 * out2[0])

    numeric = numeric_grad(lambda xs: run(xs), inputs, eps=eps)
    for i, (a, n) in enumerate(zip(analytic, numeric)):
        np.testing.assert_allclose(a, n, rtol=rtol, atol=atol,
                                   err_msg=f"gradient mismatch on input {i}")


def check_consistency(fn, inputs, ctx_list=None, rtol=1e-4, atol=1e-5):
    """Run ``fn`` on each context and check outputs agree — the reference's
    CPU-vs-GPU consistency harness (ref: tests/python/gpu/test_operator_gpu.py
    check_consistency), retargeted to CPU-jax vs accelerator-jax."""
    ctx_list = ctx_list or list_contexts()
    baseline = None
    for ctx in ctx_list:
        moved = [x.as_in_context(ctx) for x in inputs]
        out = fn(*moved)
        outs = out if isinstance(out, (list, tuple)) else [out]
        if baseline is None:
            baseline = [o.asnumpy() for o in outs]
        else:
            for b, o in zip(baseline, outs):
                np.testing.assert_allclose(b, o.asnumpy(), rtol=rtol, atol=atol,
                                           err_msg=f"inconsistent on {ctx}")
    return baseline
