"""Compile the main path's kernels for a described (not attached) TPU v5e.

The rule this file holds: nothing a registered kernel's ``supports``
accepts may be refused by the chip's compiler. Interpret-mode parity
(tests/test_pallas.py) cannot show that — block alignment, missing
lowerings and unsupported vector types only surface in a real compile.
libtpu compiles for a ``v5e:2x2`` topology without a chip, about a second
per kernel; nothing here runs, so results and times are the chip's to give
(``chip_smoke.py``).

This is the only file that describes a chip. The topology is described
inside a module-scoped fixture — never at import, in a ``skipif`` or in
``parametrize`` arguments — because only one process may load libtpu and
every xdist worker imports every test file; and the compiles run in the
test's own process, because a child could not load the library either.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu import pallas

RN50_BATCH = 256
# (N*C, H*W) row-broadcast views of ResNet-50's NCHW activations at batch
# 256: the stem and the output of each of the four stages
RN50_STAGE_SHAPES = [(RN50_BATCH * c, hw * hw) for c, hw in
                     ((64, 112), (256, 56), (512, 28), (1024, 14),
                      (2048, 7))]
# BERT-base at batch 128 x seq 128: attention/FFN output, FFN hidden,
# vocabulary projection
BERT_SHAPES = [(16384, 768), (16384, 3072), (16384, 30522)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around these
    tests so they stay silent whatever the environment sets."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *structs):
    """Raises what the chip's compiler would raise."""
    return jax.jit(fn).lower(*structs).compile()


def _accepted(spec, args, params):
    reason = spec.supports(*args, **params)
    assert reason is None, f"supports rejected a main-path shape: {reason}"


@pytest.mark.parametrize("with_res", [False, True],
                         ids=["nores", "residual"])
@pytest.mark.parametrize("shape", RN50_STAGE_SHAPES,
                         ids=lambda s: f"{s[0]}x{s[1]}")
def test_conv_epilogue_compiles_at_resnet50_shapes(one_chip, shape,
                                                   with_res):
    spec = pallas.get_kernel("conv_epilogue")
    r, c = shape
    y = jax.ShapeDtypeStruct((r, c), jnp.bfloat16, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((r, 1), jnp.bfloat16, sharding=one_chip)
    args = (y, vec, vec) + ((y,) if with_res else (None,))
    _accepted(spec, args, {"act_type": "relu"})
    live = [a for a in args if a is not None]
    compiled = _compile(
        lambda y, s, b, *res: spec.pallas_impl(
            y, s, b, res[0] if res else None, act_type="relu"), *live)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("case", ["relu", "gelu", "gelu_dropout"])
@pytest.mark.parametrize("shape", BERT_SHAPES,
                         ids=lambda s: f"{s[0]}x{s[1]}")
def test_matmul_epilogue_compiles_at_bert_shapes(one_chip, shape, case):
    """``gelu`` is the GELU that ships: MXNet's erf form, built in the
    kernel from primitives the chip's compiler lowers."""
    spec = pallas.get_kernel("matmul_epilogue")
    r, c = shape
    y = jax.ShapeDtypeStruct((r, c), jnp.bfloat16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((1, c), jnp.bfloat16, sharding=one_chip)
    bits = jax.ShapeDtypeStruct((r, c), jnp.uint8, sharding=one_chip)
    act = "relu" if case == "relu" else "gelu"
    if case == "gelu_dropout":
        params, live = {"act_type": act, "p": 0.1}, (y, bias, bits)
        _accepted(spec, live, params)
    else:
        params, live = {"act_type": act, "p": 0.0}, (y, bias)
        _accepted(spec, (y, bias, None), params)
    compiled = _compile(
        lambda y, b, *bits: spec.pallas_impl(
            y, b, bits[0] if bits else None, **params), *live)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("act", pallas.EPILOGUE_ACTS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_every_epilogue_activation_compiles(one_chip, act, dtype):
    """Each activation ``supports`` accepts, in both dtypes it accepts, at
    a shape that needs padded edge blocks on both axes (1000 = 1.95x512,
    300 = 1.17x256)."""
    spec = pallas.get_kernel("conv_epilogue")
    y = jax.ShapeDtypeStruct((1000, 300), dtype, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((1, 300), dtype, sharding=one_chip)
    _accepted(spec, (y, vec, vec, y), {"act_type": act})
    _compile(lambda y, s, b, res: spec.pallas_impl(y, s, b, res,
                                                   act_type=act),
             y, vec, vec, y)


def test_training_step_through_a_kernel_compiles(one_chip):
    """The backward of a kernel is its reference's VJP: forward kernel and
    backward in one program, as a trainer compiles them."""
    spec = pallas.get_kernel("matmul_epilogue")
    y = jax.ShapeDtypeStruct((16384, 3072), jnp.bfloat16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((1, 3072), jnp.bfloat16, sharding=one_chip)
    bits = jax.ShapeDtypeStruct((16384, 3072), jnp.uint8, sharding=one_chip)

    def loss(y, b, bits):
        out = spec.pallas_impl(y, b, bits, act_type="gelu", p=0.1)
        return out.astype(jnp.float32).sum()

    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1)), y, bias,
                        bits)
    assert "tpu_custom_call" in compiled.as_text()


# (B, H, S, D), dtype: BERT-base heads at S=2048; Granite's attention layer
# (32 heads of 64, one 4096-token sequence); a length that only 128 and 384
# divide; float32, whose products take several passes over the score tile
# (at Granite's shape the compiler refused its dkv kernel at tiles of 1024)
FLASH_SHAPES = {
    "bert_s2048_d64": ((1, 12, 2048, 64), jnp.bfloat16),
    "bert_s2048_d128": ((1, 12, 2048, 128), jnp.bfloat16),
    "granite_s4096_d64": ((1, 32, 4096, 64), jnp.bfloat16),
    "s1152_d64": ((1, 12, 1152, 64), jnp.bfloat16),
    "f32_s2048_d128": ((1, 12, 2048, 128), jnp.float32),
    "f32_s4096_d64": ((1, 32, 4096, 64), jnp.float32),
}


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("case", list(FLASH_SHAPES))
def test_flash_attention_call_compiles_forward_and_backward(one_chip, case,
                                                            causal):
    """The library flash-attention call ``_contrib_flash_attention`` takes
    on a TPU for long sequences, at the tiles ``_flash_tiles`` reads from
    the shapes, under the package's process-wide HIGHEST matmul precision:
    forward and ``jax.grad``, each through the kernel."""
    from mxnet_tpu.ops.contrib import _tpu_flash_attention
    shape, dtype = FLASH_SHAPES[case]
    qkv = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    scale = shape[-1] ** -0.5

    def fwd(q, k, v):
        return _tpu_flash_attention(q, k, v, causal, scale)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    assert "tpu_custom_call" in _compile(fwd, qkv, qkv, qkv).as_text()
    grad = _compile(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert grad.as_text().count("tpu_custom_call") >= 3


def test_tuned_blocks_the_compiler_refuses_are_refused_first(one_chip):
    """``block_ok`` is the compiler's rule: what it accepts compiles, and
    the divisor-of-the-dim blocks the kernels used to pick (224 of 3136,
    196 of 784) are what the compiler refuses."""
    from mxnet_tpu.pallas.registry import block_ok
    spec = pallas.get_kernel("conv_epilogue")
    r, c = 16384, 3136
    y = jax.ShapeDtypeStruct((r, c), jnp.bfloat16, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((r, 1), jnp.bfloat16, sharding=one_chip)

    def build(block):
        return lambda y, s, b: spec.pallas_impl(y, s, b, None,
                                                act_type="relu",
                                                block=block)

    for block in ((8, 128), (256, 3136), (504, 256)):
        assert block_ok(r, c, *block)
        _compile(build(block), y, vec, vec)
    assert not block_ok(r, c, 512, 224)
    assert not block_ok(131072, 784, 512, 196)
    # and a refused block clamps to the default, so it still compiles
    _compile(build((512, 224)), y, vec, vec)


def test_supports_rejects_with_a_named_reason():
    """Every shape ``supports`` turns away is turned away in the open,
    with the reason that ``tier_provenance()`` will carry."""
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8

    def sds(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype)

    conv = pallas.get_kernel("conv_epilogue").supports
    mm = pallas.get_kernel("matmul_epilogue").supports
    y = sds((64, 256))
    col, row = sds((1, 256)), sds((64, 1))
    cases = [
        (conv(sds((4, 8, 256)), col, col), "not_2d"),
        (conv(sds((0, 256)), col, col), "empty"),
        (conv(sds((64, 256), i32), col, col), "dtype"),
        (conv(sds((64, 256), jnp.float16), col, col), "dtype"),
        (conv(sds((64, 4)), sds((1, 4)), sds((1, 4))), "minor_dim_tiny"),
        (conv(y, sds((1, 128)), col), "shape:scale"),
        (conv(y, col, row), "shape:scale"),
        (conv(y, col, col, sds((64, 128))), "shape:res"),
        (conv(y, col, col, act_type="softrelu"), "act:"),
        (mm(y, row, act_type="bogus"), "act:"),
        (mm(y, sds((1, 128))), "shape:bias"),
        (mm(y, col, sds((64, 128), u8), p=0.1), "shape:bits"),
        (mm(y, col, sds((64, 256), i32), p=0.1), "dtype:bits"),
        (mm(y, col, p=1.0), "p:"),
    ]
    for got, want in cases:
        assert got is not None and got.startswith(want), (got, want)
