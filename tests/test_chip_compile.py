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
    spec = pallas.get_kernel("matmul_epilogue")
    y = jax.ShapeDtypeStruct((1000, 300), dtype, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((1, 300), dtype, sharding=one_chip)
    _accepted(spec, (y, bias, None), {"act_type": act})
    _compile(lambda y, b: spec.pallas_impl(y, b, None, act_type=act),
             y, bias)


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
    the divisor-of-the-dim blocks a search would try first at BERT-base's
    widths (192 of the FFN's 3072, 96 of the model's 768) are what the
    compiler refuses."""
    from mxnet_tpu.pallas.registry import block_ok
    spec = pallas.get_kernel("matmul_epilogue")
    r, c = 16384, 3072
    y = jax.ShapeDtypeStruct((r, c), jnp.bfloat16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((1, c), jnp.bfloat16, sharding=one_chip)

    def build(block):
        return lambda y, b: spec.pallas_impl(y, b, None, act_type="relu",
                                             block=block)

    for block in ((8, 128), (256, 3072), (504, 256)):
        assert block_ok(r, c, *block)
        _compile(build(block), y, bias)
    assert not block_ok(r, c, 512, 192)
    assert not block_ok(r, 768, 512, 96)
    # and a refused block clamps to the default, so it still compiles
    _compile(build((512, 192)), y, bias)


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


_Y, _COL, _ROW = _sds((64, 256)), _sds((1, 256)), _sds((64, 1))


@pytest.mark.parametrize("args,params,want", [
    ((_sds((4, 8, 256)), _COL), {}, "not_2d"),
    ((_sds((0, 256)), _COL), {}, "empty"),
    ((_sds((64, 256), jnp.float16), _COL), {}, "dtype:y"),
    ((_sds((64, 4)), _sds((1, 4))), {}, "minor_dim_tiny"),
    ((_Y, _ROW), {"act_type": "bogus"}, "act:"),
    ((_Y, _sds((1, 128))), {}, "shape:bias"),
    ((_Y, _COL, _sds((64, 128), jnp.uint8)), {"p": 0.1}, "shape:bits"),
    ((_Y, _COL, _sds((64, 256), jnp.int32)), {"p": 0.1}, "dtype:bits"),
    ((_Y, _COL), {"p": 1.0}, "p:"),
], ids=["not_2d", "empty", "y_dtype", "minor_dim_tiny", "act", "bias_shape",
        "bits_shape", "bits_dtype", "p"])
def test_supports_rejects_with_a_named_reason(args, params, want):
    """Every shape ``supports`` turns away is turned away in the open,
    with the reason that ``tier_provenance()`` will carry."""
    got = pallas.get_kernel("matmul_epilogue").supports(*args, **params)
    assert got is not None and got.startswith(want), (got, want)


# the routed-expert layer of the nemotron_3_nano_30b_a3b cell: 8192 tokens,
# 6 experts a token, 16 experts held of 128, 2688 -> 1856 -> 2688, bf16
MOE_SHAPES = {"tokens": 8192, "k": 6, "held": 16, "units": 2688,
              "hidden": 1856}


def test_routed_experts_compile_forward_and_backward(one_chip, monkeypatch):
    """``_contrib_moe_experts`` at the cell's shapes, ``jax.grad`` of it: both
    sizes of the gather buffer under ``lax.cond``, each with its two grouped
    products on the library's kernels at the tiles ``grouped_tiles`` reads
    from the shapes (gmm forward, its transposed form and tgmm backward)."""
    from mxnet_tpu.ops import moe
    from mxnet_tpu.pallas import registry
    m = MOE_SHAPES
    # the process's backend is the CPU, and the tier asks it where abstract
    # operands run: say what the chip run will say
    monkeypatch.setattr(registry, "runs_on", lambda args: ("tpu", True))

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    structs = (sds((m["tokens"], m["units"])),
               sds((m["tokens"], m["k"]), jnp.float32),
               sds((m["tokens"], m["k"]), jnp.int32),
               sds((m["held"], m["units"], m["hidden"])),
               sds((m["held"], m["hidden"], m["units"])))

    def loss(x, w, ids, w1, w2):
        return moe._moe_experts(x, w, ids, w1, w2, num_experts=128)[
            0].astype(jnp.float32).sum()

    spec = pallas.get_kernel("grouped_matmul")
    small, full = moe.buffer_rows(m["tokens"] * m["k"])
    assert (small, full) == (12288, 49152)
    _accepted(spec, (sds((small, m["units"])), structs[3],
                     sds((m["held"],), jnp.int32)), {})
    before = pallas.tier_provenance().get("grouped_matmul", {}).get(
        "pallas", 0)
    grad = _compile(jax.grad(loss, argnums=(0, 1, 3, 4)), *structs)
    # in each of the two branches: two products forward, and for each of
    # them two kernels backward
    assert grad.as_text().count("tpu_custom_call") >= 12
    assert pallas.tier_provenance()["grouped_matmul"]["pallas"] - before == 4


# the deepseek_v2 cell's attention core: 8 heads held, one 8192-token
# sequence, query/key heads of 128 + 64, value heads of 128, bf16
MLA_SHAPES = {"heads": 8, "seq": 8192, "qk": 192, "v": 128,
              "scale": 192 ** -0.5 * 1.2607986 ** 2}


def test_latent_attention_core_compiles_at_the_cells_shape(one_chip,
                                                           monkeypatch):
    """``_contrib_flash_attention`` with a value head smaller than the
    query's, as the latent attention block calls it at the cell's shape:
    the library kernel with every head padded to 256 (it refuses 192),
    forward and ``jax.grad`` through it."""
    from mxnet_tpu.ops import contrib
    from mxnet_tpu.pallas import registry
    m = MLA_SHAPES
    # the process's backend is the CPU: say what the chip run will say
    monkeypatch.setattr(registry, "runs_on", lambda args: ("tpu", False))
    qk = jax.ShapeDtypeStruct((1, m["heads"], m["seq"], m["qk"]),
                              jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, m["heads"], m["seq"], m["v"]),
                             jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return contrib._flash_attention(q, k, v, causal=True,
                                        sm_scale=m["scale"])

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    assert contrib.attention_branch(qk, qk, v) == "tpu_kernel"
    assert jax.eval_shape(fwd, qk, qk, v).shape[-1] == m["v"]
    assert "tpu_custom_call" in _compile(fwd, qk, qk, v).as_text()
    grad = _compile(jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v)
    assert grad.as_text().count("tpu_custom_call") >= 3


# grouped-query attention as the cells call it: (batch, query heads,
# key/value heads, tokens, query/key head, value head, dtype), and the
# branch it takes; DeepSeek-V2's latent core has equal heads and stays on
# the library kernel; float32 grouped heads of 128 take the splash kernel
GROUPED_SHAPES = {
    "lfm2_24b_a2b": ((2, 32, 8, 8192, 64, 64, jnp.bfloat16), "tpu_splash"),
    "granite_4_0_h_micro": ((1, 32, 8, 4096, 64, 64, jnp.bfloat16),
                            "tpu_splash"),
    "nemotron_3_nano_30b_a3b": ((1, 32, 2, 8192, 128, 128, jnp.bfloat16),
                                "tpu_splash"),
    "deepseek_v2": ((1, 8, 8, 8192, 192, 128, jnp.bfloat16), "tpu_kernel"),
    "f32_s4096_d128": ((1, 32, 8, 4096, 128, 128, jnp.float32),
                       "tpu_splash"),
}


@pytest.mark.parametrize("cell", list(GROUPED_SHAPES))
def test_grouped_attention_compiles_on_its_branch(one_chip, monkeypatch,
                                                  cell):
    """``_contrib_flash_attention`` at each cell's attention shape, causal,
    forward and ``jax.grad``: grouped heads on the splash kernel (its
    forward and its fused backward), equal heads on the library kernel."""
    from mxnet_tpu.ops import contrib
    from mxnet_tpu.pallas import registry
    (b, hq, hkv, s, qk, vd, dtype), branch = GROUPED_SHAPES[cell]
    monkeypatch.setattr(registry, "runs_on", lambda args: ("tpu", False))

    def struct(heads, d):
        return jax.ShapeDtypeStruct((b, heads, s, d), dtype,
                                    sharding=one_chip)

    q, k, v = struct(hq, qk), struct(hkv, qk), struct(hkv, vd)

    def fwd(q, k, v):
        return contrib._flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    assert contrib.attention_branch(q, k, v, causal=True) == branch
    assert jax.eval_shape(fwd, q, k, v).shape == (b, hq, s, vd)
    forward = _compile(fwd, q, k, v).as_text()
    backward = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, v).as_text()
    assert "tpu_custom_call" in forward
    assert backward.count("tpu_custom_call") >= (
        3 if branch == "tpu_kernel" else 2)
    assert ("splash_mha" in forward + backward) == (branch == "tpu_splash")


def test_gated_routed_experts_compile_forward_and_backward(one_chip,
                                                           monkeypatch):
    """``_contrib_moe_experts`` with gated experts at the deepseek_v2 cell's
    shapes (8192 tokens, 6 experts a token, 10 held of 160, 5120 -> 2 x 1536
    -> 5120), ``jax.grad`` of it: the two grouped products of both buffer
    sizes on the library's kernels."""
    from mxnet_tpu.ops import moe
    from mxnet_tpu.pallas import registry
    monkeypatch.setattr(registry, "runs_on", lambda args: ("tpu", True))
    tokens, k, held, units, hidden = 8192, 6, 10, 5120, 1536

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    structs = (sds((tokens, units)), sds((tokens, k), jnp.float32),
               sds((tokens, k), jnp.int32), sds((held, units, 2 * hidden)),
               sds((held, hidden, units)))

    def loss(x, w, ids, w1, w2):
        return moe._moe_experts(x, w, ids, w1, w2, num_experts=160,
                                gated=True)[0].astype(jnp.float32).sum()

    before = pallas.tier_provenance().get("grouped_matmul", {}).get(
        "pallas", 0)
    grad = _compile(jax.grad(loss, argnums=(0, 1, 3, 4)), *structs)
    assert grad.as_text().count("tpu_custom_call") >= 12
    assert pallas.tier_provenance()["grouped_matmul"]["pallas"] - before == 4


# one layer's scan of the two hybrid cells: (L, H, P, G, N, chunk), bf16
SSD_SHAPES = {
    "granite_4_0_h_micro": (4096, 64, 64, 1, 128, 256),
    "nemotron_3_nano_30b_a3b": (8192, 64, 64, 8, 128, 128),
}


@pytest.mark.parametrize("what", ["forward", "backward"])
@pytest.mark.parametrize("cell", list(SSD_SHAPES))
def test_scan_kernel_compiles_at_the_cells_shapes(one_chip, monkeypatch, cell,
                                                  what):
    """``_contrib_mamba2_ssd`` as a cell traces it (the step sizes and the
    cumulative sum in ``jax.numpy``, the scan through the tier's dispatch),
    forward and ``jax.grad`` over all seven operands: ``supports`` takes
    both shapes, the forward is one kernel and the backward two (the
    forward again, writing the chunk states, and the pass in reverse), and
    no ``while`` is left of the ``lax.scan`` over the chunk states."""
    from mxnet_tpu.ops import ssm
    from mxnet_tpu.pallas import registry
    length, h, p, g, n, chunk = SSD_SHAPES[cell]
    # the process's backend is the CPU, and the tier asks it where abstract
    # operands run: say what the chip run will say
    monkeypatch.setattr(registry, "runs_on", lambda args: ("tpu", True))

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    structs = (sds((1, length, h, p)), sds((1, length, h)),
               sds((h,), jnp.float32), sds((1, length, g, n)),
               sds((1, length, g, n)), sds((h,), jnp.float32),
               sds((h,), jnp.float32))

    def fwd(*a):
        return ssm._mamba2_ssd(*a, chunk_size=chunk)

    def loss(*a):
        return fwd(*a).astype(jnp.float32).sum()

    before = pallas.tier_provenance().get("mamba2_ssd", {}).get("pallas", 0)
    if what == "forward":
        text, kernels = _compile(fwd, *structs).as_text(), 1
    else:
        text = _compile(jax.grad(loss, argnums=tuple(range(7))),
                        *structs).as_text()
        kernels = 2
    assert text.count("tpu_custom_call") == kernels
    assert " while(" not in text
    assert pallas.tier_provenance()["mamba2_ssd"]["pallas"] - before == 1


# the retention operator as the Brumby cell traces it, as its comparison's
# state check calls it with every chunk a sequence of its own, and at the
# kernel's float32 example: (B, L, H, G, chunk, dtype), heads of 128
RETENTION_SHAPES = {
    "brumby_14b_base": (1, 8192, 40, 8, 1024, jnp.bfloat16),
    "state_check_one_chunk": (8, 1024, 10, 2, 1024, jnp.bfloat16),
    "example_float32": (1, 256, 4, 2, 128, jnp.float32),
}


@pytest.mark.parametrize("what", ["forward", "backward"])
@pytest.mark.parametrize("case", list(RETENTION_SHAPES))
def test_retention_kernel_compiles_at_the_cells_shapes(one_chip, monkeypatch,
                                                       case, what):
    """``_contrib_power_retention`` as a cell traces it (the cumulative sum
    and the tier's dispatch), forward and ``jax.grad`` over q, k, v and the
    log-gates: ``supports`` takes the shapes, the forward is one kernel and
    the backward two (the forward again, writing the boundary states, and
    the pass in reverse), and no ``while`` is left of the scans over the
    key/value heads and the chunks. 64 MiB of fast memory are asked for:
    the states, their cotangents and a group of expanded rows."""
    from mxnet_tpu.ops import retention
    from mxnet_tpu.pallas import registry
    bsz, length, h, g, chunk, dtype = RETENTION_SHAPES[case]
    monkeypatch.setattr(registry, "runs_on", lambda args: ("tpu", True))

    def sds(shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    structs = (sds((bsz, length, h, 128)), sds((bsz, length, g, 128)),
               sds((bsz, length, g, 128)),
               sds((bsz, length, g), jnp.float32))

    def fwd(*a):
        return retention._power_retention(*a, chunk_size=chunk)

    def loss(*a):
        return fwd(*a).astype(jnp.float32).sum()

    before = pallas.tier_provenance().get("power_retention", {}).get(
        "pallas", 0)
    if what == "forward":
        text, kernels = _compile(fwd, *structs).as_text(), 1
    else:
        text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3)),
                        *structs).as_text()
        kernels = 2
    assert text.count("tpu_custom_call") == kernels
    assert " while(" not in text
    assert pallas.tier_provenance()["power_retention"]["pallas"] - before == 1


def _relayouts(text, elements):
    """The entry computation's ``copy``, ``reshape``, ``broadcast`` and
    ``transpose`` instructions whose result has ``elements`` or more."""
    import re
    entry = text[text.index("\nENTRY"):]
    found = []
    for dims, kind in re.findall(
            r"= \w+\[([\d,]*)\]\S* (copy|reshape|broadcast|transpose)\(",
            entry):
        size = 1
        for d in filter(None, dims.split(",")):
            size *= int(d)
        if size >= elements:
            found.append(kind)
    return found


@pytest.mark.parametrize("form", ["products", "reshaped"])
def test_grouped_norm_at_nemotrons_shape_relays_out_nothing(one_chip, form):
    """``_contrib_gated_rms_norm(groups=8)`` at one Nemotron layer's
    ``(1, 8192, 4096)``, forward and backward: the product form leaves no
    re-layout of an ``L x C`` array in the program; the reshape form it
    replaced (PR 37) leaves several, which is what this test would see
    again."""
    from jax import lax
    from mxnet_tpu.ops import ssm
    length, channels, groups = 8192, 4096, 8

    def reshaped(y, z, gamma):
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        by_group = g.reshape(g.shape[:-1] + (groups, -1))
        ms = jnp.mean(jnp.square(by_group), axis=-1, keepdims=True)
        return ((by_group * lax.rsqrt(ms + 1e-5)).reshape(g.shape)
                * gamma).astype(y.dtype)

    norm = reshaped if form == "reshaped" else (
        lambda *a: ssm._gated_rms_norm(*a, groups=groups))

    def forward_backward(y, z, gamma, cotangent):
        return jax.vjp(norm, y, z, gamma)[1](cotangent)

    row = jax.ShapeDtypeStruct((1, length, channels), jnp.bfloat16,
                               sharding=one_chip)
    gamma = jax.ShapeDtypeStruct((channels,), jnp.float32, sharding=one_chip)
    found = _relayouts(_compile(forward_backward, row, row, gamma,
                                row).as_text(), length * channels)
    if form == "products":
        assert found == []
    else:
        assert {"copy", "reshape", "broadcast"} <= set(found)


# the cells' embedding tables (rows, units, tokens), bf16: the two 5120-wide
# decoders, Nemotron's, Granite's (tied to its head), BERT's words and its
# two token types
EMBEDDING_TABLES = {
    "deepseek_v2": (12800, 5120, 8192),
    "brumby_14b_base": (18992, 5120, 8192),
    "nemotron_3_nano_30b_a3b": (16384, 2688, 8192),
    "granite_4_0_h_micro": (25088, 2048, 4096),
    "bert_words": (30522, 768, 16384),
    "bert_token_types": (2, 768, 16384),
}


@pytest.mark.parametrize("cell", list(EMBEDDING_TABLES))
def test_embedding_grad_compiles_at_the_cells_tables(one_chip, monkeypatch,
                                                     cell):
    """``Embedding`` as a cell traces it, forward and ``jax.grad`` over the
    table: the lookup is XLA's gather, the table's gradient the tier's
    ``embedding_grad`` (one ``tgmm`` custom call), and ``supports`` takes
    the shapes."""
    from mxnet_tpu.ops import nn
    from mxnet_tpu.pallas import registry
    rows, units, tokens = EMBEDDING_TABLES[cell]
    monkeypatch.setattr(registry, "runs_on", lambda args: ("tpu", True))
    table = jax.ShapeDtypeStruct((rows, units), jnp.bfloat16,
                                 sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, tokens), jnp.int32, sharding=one_chip)
    _accepted(pallas.get_kernel("embedding_grad"),
              (ids, jax.ShapeDtypeStruct((1, tokens, units), jnp.bfloat16)),
              {"rows": rows})

    def loss(w, ids):
        return nn._embedding(ids, w).astype(jnp.float32).sum()

    before = pallas.tier_provenance().get("embedding_grad", {}).get(
        "pallas", 0)
    text = _compile(jax.grad(loss), table, ids).as_text()
    assert text.count("tpu_custom_call") == 1
    assert pallas.tier_provenance()["embedding_grad"]["pallas"] - before == 1


def test_lfm2_training_step_compiles_at_the_cells_shapes(one_chip,
                                                         monkeypatch):
    """One training step of the lfm2_24b_a2b cell as the chip runs it: the
    nine layers at published widths with every layer recomputed, two
    8192-token sequences, the loss's gradient and an Adam update of the bf16
    weights and moments (donated, as the trainer's step donates them). The
    compiler's memory analysis says what the step needs besides the state:
    the program must fit the chip's 16 GB, and PERF.md records both numbers
    (arguments and temporaries) before the cell's first chip run."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.block import functional_apply
    from mxnet_tpu.gluon.model_zoo import lfm2_moe
    from mxnet_tpu.pallas import registry
    from chipbench import manifest
    from chipbench.models import lfm2_24b_a2b as lm
    monkeypatch.setattr(registry, "runs_on", lambda args: ("tpu", True))
    args = manifest.load_config(manifest.load_manifest(),
                                "lfm2_24b_a2b")["args"]
    held, width = lm.experts_held(args)
    net = lfm2_moe.lfm2_moe(
        experts_held=held, return_routes=True, recompute=True,
        num_experts=width, expert_bias_update_rate=1e-3,
        **{key: args[key] for key in lm.MODEL_KEYS})
    trainable, aux = net._param_split()
    # nothing is allocated: the parameters are only told where they would
    # live, and the step binds abstract arrays to them
    for p in trainable + aux:
        p._ctx_list = [mx.cpu()]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    weights = [sds(p.shape) for p in trainable]
    buffers = [sds(p.shape, jnp.dtype(p.dtype)) for p in aux]
    tokens = sds((2, 8192), jnp.int32)

    def step(weights, moments, buffers, tokens, labels):
        def loss(weights):
            outs, _, new = functional_apply(net, jax.random.key(0), weights,
                                            buffers, [tokens], training=True)
            logp = jax.nn.log_softmax(outs[0].astype(jnp.float32), -1)
            return -jnp.mean(jnp.take_along_axis(logp, labels[..., None],
                                                 -1)), new

        with autograd.pause(train_mode=True):
            (value, new), grads = jax.value_and_grad(loss, has_aux=True)(
                weights)
        out_w, out_m = [], []
        for w, (m, v), g in zip(weights, moments, grads):
            g = g.astype(jnp.float32)
            m = 0.9 * m.astype(jnp.float32) + 0.1 * g
            v = 0.95 * v.astype(jnp.float32) + 0.05 * g * g
            w = w.astype(jnp.float32) * (1 - 2e-5) - 2e-4 * m / (
                jnp.sqrt(v) + 1e-8)
            out_w.append(w.astype(jnp.bfloat16))
            out_m.append((m.astype(jnp.bfloat16), v.astype(jnp.bfloat16)))
        return value, out_w, out_m, new

    compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        weights, [(w, w) for w in weights], buffers, tokens, tokens).compile()
    memory = compiled.memory_analysis()
    arguments = memory.argument_size_in_bytes
    temporaries = memory.temp_size_in_bytes
    print(f"lfm2_24b_a2b step for a v5e: arguments {arguments} B, "
          f"temporaries {temporaries} B")
    # the state: 832,651,520 bf16 weights and two bf16 moments, 5.0 GB
    assert arguments == pytest.approx(3 * 2 * 832_651_520, rel=0.01)
    assert arguments + temporaries < 15.5e9
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 8       # the grouped products
