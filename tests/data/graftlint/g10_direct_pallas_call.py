# graftlint: scope=library
"""G10 fixture: direct pl.pallas_call outside mxnet_tpu/pallas/ — a raw
kernel that bypasses the registry's parity gate and journaled fallback
(docs/pallas.md). Parsed only, never executed."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import pallas_call as direct_call


def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def unguarded_kernel(x):
    return pl.pallas_call(  # expect: G10
        _kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x)


def unguarded_kernel_via_from_import(x):
    return direct_call(  # expect: G10
        _kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x)


def disabled_twin(x):
    # interop shim pinned to a prebuilt upstream kernel, parity-tested
    # in its own suite
    return pl.pallas_call(  # graftlint: disable=G10 vetted interop shim
        _kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x)


def registry_path_is_clean(x):
    # the sanctioned route: registered kernel + guarded dispatch
    from mxnet_tpu.pallas import dispatch
    return dispatch("matmul_epilogue", x, jnp.zeros((1, x.shape[1])),
                    None, act_type="relu")
