"""Grouped-query attention through ``_contrib_flash_attention``: k and v
with fewer heads than q, query head ``h`` reading key/value head
``h // (Hq // Hkv)``.

On the CPU the ``dense`` and ``portable`` branches repeat k and v inside
the op, so a grouped call is the repeat-then-call program; on a TPU,
grouped causal self-attention takes the splash kernel (``tpu_splash``),
driven here in the library's interpret mode through the op's own helper.
The compile for a described v5e is in tests/test_chip_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import observability
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import contrib
from mxnet_tpu.parallel.ring_attention import attention_reference

HEADS = 16


def _operands(groups, seq, d=16, dtype=jnp.float32, batch=1, heads=HEADS):
    rng = np.random.default_rng(groups * 1000 + seq)
    q = jnp.asarray(rng.standard_normal((batch, heads, seq, d)), dtype)
    k, v = (jnp.asarray(rng.standard_normal((batch, heads // groups, seq, d)),
                        dtype) for _ in range(2))
    ct = jnp.asarray(rng.standard_normal((batch, heads, seq, d)), jnp.float32)
    return q, k, v, ct


def _repeated(k, v, groups):
    return jnp.repeat(k, groups, axis=1), jnp.repeat(v, groups, axis=1)


def _loss(fn, ct):
    return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * ct)


def _largest_share(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("groups", [1, 4, 16])
@pytest.mark.parametrize("seq, branch", [(64, "dense"), (1152, "portable")])
def test_a_grouped_call_is_the_repeat_then_call(groups, seq, branch):
    """Forward and the gradients of q, k and v: the grouped call against
    the op called on k and v repeated to the query's heads (whose k and v
    gradients autodiff sums over each group), and both against the plain
    definition."""
    flash = jax.jit(lambda q, k, v: contrib._flash_attention(
        q, k, v, causal=True))
    q, k, v, ct = _operands(groups, seq)
    assert contrib.attention_branch(q, k, v, causal=True) == branch

    def repeated(q, k, v):
        return flash(q, *_repeated(k, v, groups))

    def reference(q, k, v):
        return attention_reference(q, *_repeated(k, v, groups), causal=True)

    got = flash(q, k, v)
    np.testing.assert_allclose(got, repeated(q, k, v), rtol=0, atol=1e-6)
    np.testing.assert_allclose(got, reference(q, k, v), rtol=0, atol=1e-5)
    grads = jax.grad(_loss(flash, ct), argnums=(0, 1, 2))(q, k, v)
    for fn, tol in ((repeated, 1e-6), (reference, 1e-5)):
        want = jax.grad(_loss(fn, ct), argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(grads, want):
            assert g.shape == w.shape
            assert _largest_share(g, w) < tol


# the splash kernel's tiles: the committed caps (one block at 512 tokens),
# and small tiles that give the block map blocks above the diagonal to skip,
# whole blocks under it and partial ones on it, with one product or two a
# key tile
SPLASH_TILES = {
    "committed": {},
    "blocks_of_128_256": dict(SPLASH_Q_CAP=128, SPLASH_KV_CAP=256,
                              SPLASH_KV_COMPUTE_CAP=256),
    "blocks_of_128_256_in_products_of_128": dict(
        SPLASH_Q_CAP=128, SPLASH_KV_CAP=256, SPLASH_KV_COMPUTE_CAP=128),
}


@pytest.mark.parametrize("tiles", list(SPLASH_TILES))
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_splash_path_matches_the_reference(tiles, d, dtype, tol,
                                               monkeypatch):
    """The ``tpu_splash`` branch's helper in the library's interpret mode:
    two sequences, 8 query heads over 2 key/value heads, 512 tokens,
    against ``attention_reference`` in float32 on the same operands, the
    output and the gradients of q, k and v (bf16: to its rounding)."""
    for name, value in SPLASH_TILES[tiles].items():
        monkeypatch.setattr(contrib, name, value)
    q, k, v, ct = _operands(4, 512, d=d, dtype=dtype, batch=2, heads=8)
    scale = d ** -0.5

    def splash(q, k, v):
        return contrib._splash_flash(q, k, v, scale, True)

    def reference(q, k, v):
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        return attention_reference(q, *_repeated(k, v, 4), causal=True,
                                   scale=scale)

    got = splash(q, k, v)
    assert got.shape == q.shape and got.dtype == dtype
    assert _largest_share(got, reference(q, k, v)) < tol
    grads = jax.grad(_loss(splash, ct), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(_loss(reference, ct), argnums=(0, 1, 2))(q, k, v)
    for g, w, t in zip(grads, want, (q, k, v)):
        assert g.shape == t.shape and g.dtype == dtype
        assert _largest_share(g, w) < tol


def _struct(heads, seq, d=64, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((1, heads, seq, d), dtype)


# (query heads, key/value heads, query length, key length, causal, where,
# head, dtype)
BRANCHES = {
    "grouped_causal_self": ((32, 8, 2048, 2048, True, "tpu", 64,
                             jnp.bfloat16), "tpu_splash"),
    "one_key_value_head": ((32, 1, 2048, 2048, True, "tpu", 64,
                            jnp.bfloat16), "tpu_splash"),
    "float32_heads_of_128": ((32, 8, 2048, 2048, True, "tpu", 128,
                              jnp.float32), "tpu_splash"),
    "float32_heads_of_256": ((32, 8, 2048, 2048, True, "tpu", 256,
                              jnp.float32), "tpu_kernel"),
    "equal_heads": ((32, 32, 2048, 2048, True, "tpu", 64, jnp.bfloat16),
                    "tpu_kernel"),
    "grouped_not_causal": ((32, 8, 2048, 2048, False, "tpu", 64,
                            jnp.bfloat16), "tpu_kernel"),
    "grouped_cross": ((32, 8, 1024, 2048, True, "tpu", 64, jnp.bfloat16),
                      "tpu_kernel"),
    "grouped_short_keys": ((32, 8, 1024, 1024, True, "tpu", 64,
                            jnp.bfloat16), "dense"),
    "grouped_on_the_cpu": ((32, 8, 2048, 2048, True, "cpu", 64,
                            jnp.bfloat16), "portable"),
}


@pytest.mark.parametrize("case", list(BRANCHES))
def test_the_branch_and_the_counters_labels(case, monkeypatch):
    """``tpu_splash`` for grouped causal self-attention on a TPU and for
    nothing else; the traced call counts its branch, tiles and groups."""
    from mxnet_tpu.pallas import registry
    (hq, hkv, s_q, s_kv, causal, where, d, dtype), branch = BRANCHES[case]
    monkeypatch.setattr(registry, "runs_on",
                        lambda args: (where, where == "tpu"))
    q, kv = _struct(hq, s_q, d, dtype), _struct(hkv, s_kv, d, dtype)
    assert contrib.attention_branch(q, kv, kv, causal=causal) == branch
    tiles = {"tpu_splash": lambda: contrib._splash_tiles(s_q),
             "tpu_kernel": lambda: contrib._flash_tiles(s_q, s_kv, d,
                                                        dtype)}
    block_q = block_k = ""
    if branch in tiles:
        t = tiles[branch]()
        block_q = t.block_q
        block_k = t.block_kv if branch == "tpu_splash" else t.block_k_major
    elif branch == "portable":
        block_k = 512
    key = (f"branch={branch},block_q={block_q},block_k={block_k},qk={d},"
           f"v={d},padded=,groups={hq // hkv}")

    def count():
        return observability.snapshot()["metrics"].get(
            contrib.FLASH_COUNT_METRIC, {}).get("values", {}).get(key, 0)

    before = count()
    out = jax.eval_shape(lambda q, k, v: contrib._flash_attention(
        q, k, v, causal=causal), q, kv, kv)
    assert out.shape == q.shape and count() == before + 1


@pytest.mark.parametrize("heads, kv_heads", [(6, 4), (8, 3)])
def test_heads_that_do_not_divide_are_refused(heads, kv_heads):
    rng = np.random.default_rng(0)

    def arr(h):
        return mx.nd.array(rng.standard_normal((1, h, 32, 16)))

    with pytest.raises(MXNetError, match="no multiple"):
        mx.nd.contrib.flash_attention(arr(heads), arr(kv_heads),
                                      arr(kv_heads), causal=True)


# the splash kernel's custom call as the TPU compiler prints it: its
# frontend attribute ``kernel_metadata`` breaks the instruction over three
# lines, the second and third with no indent
SPLASH_HLO = '''HloModule jit_step, entry_computation_layout={()->f32[]}

%fused (p: bf16[8,64]) -> bf16[8,64] {
  %p = bf16[8,64]{1,0} parameter(0)
  ROOT %m.1 = bf16[8,64]{1,0} multiply(%p, %p), metadata={op_name="mxnet_tpu.mlp/mul"}
}

ENTRY %main (x: bf16[8,64]) -> bf16[8,64] {
  %x = bf16[8,64]{1,0} parameter(0)
  %splash.1 = (bf16[8,64]{1,0}) custom-call(%x), frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 1024}"
}}, metadata={op_name="jvp(mxnet_tpu.attention)/splash_mha_fwd/pallas_call"}
  %gte.1 = bf16[8,64]{1,0} get-tuple-element(%splash.1), index=0, frontend_attributes={
kernel_metadata={"xprof_metadata":"{}"}
}, metadata={op_name="jvp(mxnet_tpu.attention)/pallas_call"}
  ROOT %fusion.3 = bf16[8,64]{1,0} fusion(%gte.1), kind=kLoop, calls=%fused
}
'''


@pytest.mark.parametrize("instruction, scope", [
    ("splash.1", "attention"),
    ("gte.1", "attention"),
    ("fusion.3", "mlp"),
])
def test_a_kernel_printed_over_several_lines_keeps_the_scopes(instruction,
                                                               scope):
    """The scope of every instruction of a program that holds the splash
    kernel: the custom call's own, and those after it (a fusion takes its
    root's)."""
    from mxnet_tpu.observability import scopes
    record = scopes.scopes_of_program(SPLASH_HLO)
    assert record["module"] == "jit_step"
    assert record["scopes"][instruction] == scope
