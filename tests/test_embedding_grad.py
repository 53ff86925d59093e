"""The embedding's table gradient as the kernel tier's grouped product
(``embedding_grad``, mxnet_tpu/pallas/kernels.py; docs/pallas.md): the
kernel in interpret mode against autodiff's scatter-add, its reference, and
against a float64 ``np.add.at``; what ``supports`` declines; the lookup's
choice of form and its counter; and ``jax.grad`` through ``nn.Embedding``
inside a jitted step with the kernel as the backward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import gluon, nd, observability, pallas
from mxnet_tpu.gluon.block import functional_apply
from mxnet_tpu.ops import nn as nn_ops
from mxnet_tpu.pallas import registry
from mxnet_tpu.pallas.kernels import EMBEDDING_BLOCK_ROWS as BLOCK


def exact(ids, cotangent, rows):
    """float64 truth: ``np.add.at`` with the lookup's reading of the ids."""
    ids = np.asarray(ids).reshape(-1)
    g = np.asarray(jnp.asarray(cotangent, jnp.float32),
                   np.float64).reshape(ids.size, -1)
    ids = np.where(ids < 0, ids + rows, ids)
    keep = (ids >= 0) & (ids < rows)
    out = np.zeros((rows, g.shape[1]))
    np.add.at(out, ids[keep], g[keep])
    return out


def ids_of(case, rows, tokens, rng):
    if case == "uniform":
        return rng.integers(0, rows, tokens)
    if case == "one_row":
        return np.full(tokens, rows // 3)
    if case == "block_edges":     # the first and last row of blocks
        edges = [b * BLOCK + e for b in range(rows // BLOCK) for e in
                 (0, BLOCK - 1)] + [rows - 1]
        return rng.choice(edges, tokens)
    if case == "out_of_range":    # from the end, and past both ends
        return rng.choice([-1, -rows, rows, rows + 7, -rows - 1, 5, 300],
                          tokens)
    raise ValueError(case)


def grad_of(ids, cotangent, rows, interpret=True):
    spec = pallas.get_kernel("embedding_grad")
    ids = jnp.asarray(ids, jnp.int32)
    if interpret:
        return spec.pallas_impl(ids, cotangent, interpret=True, rows=rows)
    return spec.xla_reference(ids, cotangent, rows=rows)


# rows that are no multiple of the block (600) and one that is (512)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["uniform", "block_edges", "out_of_range"])
@pytest.mark.parametrize("rows", [600, 512])
def test_the_kernel_is_the_scatter_add(case, rows, dtype):
    rng = np.random.default_rng([rows, len(case), len(dtype)])
    ids = ids_of(case, rows, 96, rng)
    cotangent = jnp.asarray(rng.standard_normal((96, 256)), dtype)
    got = grad_of(ids, cotangent, rows)
    want = grad_of(ids, cotangent, rows, interpret=False)
    truth = exact(ids, cotangent, rows)
    assert got.shape == want.shape == (rows, 256)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    got64, want64 = (np.asarray(jnp.asarray(a, jnp.float32), np.float64)
                     for a in (got, want))
    if dtype == "float32":
        np.testing.assert_allclose(got64, truth, rtol=0, atol=1e-5)
        np.testing.assert_allclose(got64, want64, rtol=0, atol=1e-5)
    else:
        # float32 sums rounded once to bf16: one spacing of the truth
        assert (np.abs(got64 - truth) <= np.abs(truth) * 2.0 ** -8).all()
    # rows no id reaches are zero, exactly
    hit = np.zeros(rows, bool)
    flat = np.where(ids < 0, ids + rows, ids)
    hit[flat[(flat >= 0) & (flat < rows)]] = True
    assert not got64[~hit].any()
    if case == "out_of_range":
        assert hit.sum() == 4      # -1, -rows, 5 and 300 land; the rest drop


def test_one_row_hit_by_every_token_sums_in_float32():
    """Every id the same: the kernel adds 128 bf16 rows in float32 and rounds
    once, autodiff's scatter adds them in bf16; the kernel is at least as
    close to the float64 sum."""
    rng = np.random.default_rng(7)
    ids = ids_of("one_row", 600, 128, rng)
    cotangent = jnp.asarray(rng.standard_normal((128, 256)) + 0.25,
                            jnp.bfloat16)
    truth = exact(ids, cotangent, 600)
    got, want = (np.asarray(jnp.asarray(a, jnp.float32), np.float64)
                 for a in (grad_of(ids, cotangent, 600),
                           grad_of(ids, cotangent, 600, interpret=False)))
    row = 600 // 3
    kernel_err = np.abs(got[row] - truth[row]).max()
    assert kernel_err <= np.abs(want[row] - truth[row]).max()
    assert kernel_err <= np.abs(truth[row]).max() * 2.0 ** -8
    assert not np.delete(got, row, axis=0).any()


def test_ids_of_any_shape_are_the_flat_ids():
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 300, (4, 16))
    cotangent = jnp.asarray(rng.standard_normal((4, 16, 128)), jnp.float32)
    got = grad_of(ids, cotangent, 300)
    np.testing.assert_allclose(np.asarray(got), exact(ids, cotangent, 300),
                               rtol=0, atol=1e-5)


def test_supports_declines_with_a_named_reason():
    spec = pallas.get_kernel("embedding_grad")
    sds = jax.ShapeDtypeStruct
    ids = sds((64,), jnp.int32)
    assert spec.supports(ids, sds((64, 5120), jnp.bfloat16), rows=12800) \
        is None
    assert spec.supports(ids, sds((64, 128), jnp.float32), rows=10) is None
    for args, want in (
            # a table of three dimensions: its cotangent has two more
            ((ids, sds((64, 16, 320), jnp.bfloat16)), "shape:"),
            ((sds((64,), jnp.float32), sds((64, 512), jnp.bfloat16)),
             "dtype:ids"),
            ((ids, sds((64, 512), jnp.float16)), "dtype:cotangent"),
            ((sds((60,), jnp.int32), sds((60, 512), jnp.bfloat16)),
             "tokens:")):
        got = spec.supports(*args, rows=100)
        assert got is not None and got.startswith(want), (got, want)


def counted():
    return dict(observability.snapshot()["metrics"].get(
        nn_ops.EMBEDDING_GRAD_COUNT_METRIC, {}).get("values", {}))


@pytest.fixture
def clean_tier(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_PALLAS", raising=False)
    pallas.set_mode(None)
    pallas.reset_provenance()
    yield
    pallas.set_mode(None)
    pallas.reset_provenance()


def lookup_jaxpr(width, rows=300, tokens=64, dtype=jnp.bfloat16):
    op = nn_ops._embedding

    def loss(w, ids):
        return jnp.sum(jnp.square(op(ids, w).astype(jnp.float32)))

    return str(jax.make_jaxpr(jax.grad(loss))(
        jax.ShapeDtypeStruct((rows, width), dtype),
        jax.ShapeDtypeStruct((tokens,), jnp.int32)))


@pytest.mark.parametrize("width", [5120, 768])
def test_the_lookup_stages_the_kernel_only_where_the_tier_takes_it(
        clean_tier, monkeypatch, width):
    """On the CPU the lookup is the plain ``jnp.take`` and counts
    ``scatter``; traced for a TPU the table's gradient is the kernel, staged
    beside its reference, and counts ``grouped``, narrow table or wide;
    switched off, or under a mesh the compiler partitions, the lookup
    traces the CPU's program."""
    from mxnet_tpu import parallel
    before = counted()
    plain = lookup_jaxpr(width)
    assert "pallas_call" not in plain and "scatter-add" in plain
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    text = lookup_jaxpr(width)
    assert "pallas_call" in text and "platform_index" in text
    with parallel.use_mesh(parallel.make_mesh({"data": 4},
                                              devices=jax.devices()[:4])):
        assert lookup_jaxpr(width) == plain
    pallas.set_mode("off")
    assert lookup_jaxpr(width) == plain
    after = counted()

    def grew(path):
        key = f"rows=300,units={width},tokens=64,path={path}"
        return after.get(key, 0) - before.get(key, 0)

    assert (grew("grouped"), grew("scatter")) == (1, 3)
    # what fell back never reached the tier: it was asked, not dispatched
    assert pallas.tier_provenance()["embedding_grad"] == {
        "pallas": 1, "xla": 0, "fallback_reasons": {}}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("width", [640, 256])
def test_grad_through_embedding_in_a_jitted_step(clean_tier, monkeypatch,
                                                 width, dtype):
    """``jax.grad`` of a loss through ``nn.Embedding`` in a jitted step, the
    program traced as for a TPU and the kernel in interpret mode: the
    table's gradient is the kernel's, the float64 sum of the cotangent's
    rows."""
    monkeypatch.setattr(registry, "runs_on", lambda args: ("tpu", True))
    monkeypatch.setattr(pallas, "dispatch", lambda name, *args, **params:
                        registry.dispatch(name, *args, interpret=True,
                                          **params))
    rows, rng = 300, np.random.default_rng(width)
    layer = gluon.nn.Embedding(rows, width, dtype=dtype)
    layer.initialize()
    ids = rng.integers(0, rows, (4, 16)).astype(np.int32)
    layer(nd.array(ids))
    tr, aux = layer._param_split()
    scale = jnp.asarray(rng.standard_normal((4, 16, width)), jnp.float32)

    @jax.jit
    def step(tr_data, ids):
        def loss(tr_data):
            outs, _, _ = functional_apply(layer, jax.random.key(0), tr_data,
                                          [], [ids], training=True)
            return jnp.sum(outs[0].astype(jnp.float32) * scale)
        return jax.grad(loss)(tr_data)[0]

    got = step([p.data()._data for p in tr], jnp.asarray(ids))
    assert not aux
    assert got.dtype == jnp.dtype(dtype)
    assert pallas.tier_provenance()["embedding_grad"]["pallas"] == 1
    truth = exact(ids, scale.astype(dtype), rows)
    got64 = np.asarray(jnp.asarray(got, jnp.float32), np.float64)
    if dtype == "float32":
        np.testing.assert_allclose(got64, truth, rtol=0, atol=1e-5)
    else:
        assert (np.abs(got64 - truth) <= np.abs(truth) * 2.0 ** -7).all()
