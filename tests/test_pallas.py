"""The guarded custom-kernel tier (mxnet_tpu/pallas/, docs/pallas.md):
interpret-mode parity for EVERY registered kernel vs its XLA reference
(the registration-time numerics gate), fallback selection (non-TPU
backend, unsupported shape, env kill-switch — each journaled with a
reason), gradient parity through the custom_vjp paths, dropout-key
independence under the PR-1 (layer, tick, shard) fold discipline, the
gluon/ops wiring (Dense epilogue, blockwise-attention routing, bench A/B
flag), and what is deliberately NOT wired: BatchNorm act_type and the
resnet residual epilogue are plain jax.numpy equal to the float32 fold
the deleted conv_epilogue kernel had as its reference, with no reshape and
no kernel in ResNet-50's step."""
import json
import os

import numpy as np
import pytest

from mxnet_tpu import nd, pallas
from mxnet_tpu.base import MXNetError


@pytest.fixture
def clean_tier(monkeypatch):
    """Pristine tier state: auto mode, empty provenance."""
    monkeypatch.delenv("MXNET_TPU_PALLAS", raising=False)
    pallas.set_mode(None)
    pallas.reset_provenance()
    yield
    pallas.set_mode(None)
    pallas.reset_provenance()


# -- the registration-time parity gate ---------------------------------------

def _cases():
    out = []
    for name, spec in pallas.kernels().items():
        assert spec.example is not None, \
            f"kernel {name!r} registered without example() — the parity " \
            f"gate cannot cover it"
        for i, (args, params) in enumerate(spec.example()):
            out.append(pytest.param(name, i, id=f"{name}-{i}"))
    return out


@pytest.mark.parametrize("name,case", _cases())
def test_parity_gate_smoke(name, case, clean_tier):
    """EVERY registered kernel passes its CPU interpret-mode parity gate
    vs the XLA reference within the registered tolerance — the contract
    that lets the tier claim it can never silently change numerics."""
    spec = pallas.get_kernel(name)
    args, params = spec.example()[case]
    got = np.asarray(spec.pallas_impl(*args, interpret=True, **params),
                     np.float32)
    want = np.asarray(spec.xla_reference(*args, **params), np.float32)
    err = float(np.abs(got - want).max())
    assert err <= spec.tolerance, \
        f"{name} case {case}: max err {err} > tolerance {spec.tolerance}"


def test_parity_gate_covers_shape_and_dtype(clean_tier):
    import jax.numpy as jnp
    for name, spec in pallas.kernels().items():
        for args, params in spec.example():
            got = spec.pallas_impl(*args, interpret=True, **params)
            want = spec.xla_reference(*args, **params)
            assert got.shape == want.shape
            assert jnp.result_type(got) == jnp.result_type(want)


def test_grads_match_reference_smoke(clean_tier):
    """The custom_vjp paths (pallas forward, reference VJP backward)
    agree with differentiating the reference end-to-end — the bias
    vector included, so Dense's bias gradient is covered."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    y = jnp.asarray(rng.randn(16, 128), jnp.float32)
    b = jnp.asarray(rng.randn(1, 128) * 0.1, jnp.float32)
    mspec = pallas.get_kernel("matmul_epilogue")

    def loss_p(y, b):
        return (mspec.pallas_impl(y, b, None, interpret=True,
                                  act_type="relu") ** 2).sum()

    def loss_r(y, b):
        return (mspec.xla_reference(y, b, None, act_type="relu") ** 2).sum()

    gp = jax.grad(loss_p, argnums=(0, 1))(y, b)
    gr = jax.grad(loss_r, argnums=(0, 1))(y, b)
    for a, bb in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-4)
    # and with dropout folded in
    bits = pallas.dropout_bits(jax.random.key(5), (16, 128))
    gp = jax.grad(lambda v: (mspec.pallas_impl(
        v, b, bits, interpret=True, act_type="gelu", p=0.3) ** 2).sum())(y)
    gr = jax.grad(lambda v: (mspec.xla_reference(
        v, b, bits, act_type="gelu", p=0.3) ** 2).sum())(y)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=1e-4, atol=1e-4)


# -- fallback selection (the guard half of the tier) -------------------------

def _journal_records(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def test_fallback_non_tpu_backend_is_journaled_smoke(clean_tier, tmp_path):
    """The default CPU path never executes the unverified kernel: the
    dispatch falls back to the reference and journals why."""
    import jax.numpy as jnp
    from mxnet_tpu.diagnostics import reset_journal
    jpath = str(tmp_path / "journal.jsonl")
    reset_journal(jpath)
    try:
        y, b = jnp.ones((16, 128)), jnp.zeros((1, 128))
        out = pallas.dispatch("matmul_epilogue", y, b, None,
                              act_type="relu")
        assert out.shape == (16, 128)
    finally:
        reset_journal(None)
    prov = pallas.tier_provenance()["matmul_epilogue"]
    assert prov["pallas"] == 0 and prov["xla"] == 1
    assert prov["fallback_reasons"] == {"backend:cpu": 1}
    recs = [r for r in _journal_records(jpath)
            if r["kind"] == "pallas_fallback"]
    assert len(recs) == 1
    assert recs[0]["kernel"] == "matmul_epilogue"
    assert recs[0]["reason"] == "backend:cpu"
    # dedupe: a second identical fallback journals nothing new but counts
    pallas.dispatch("matmul_epilogue", y, b, None, act_type="relu")
    assert pallas.tier_provenance()["matmul_epilogue"]["xla"] == 2


def _epilogue_args():
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    return (jnp.asarray(rng.randn(16, 128), jnp.float32),
            jnp.asarray(rng.randn(1, 128) * 0.1, jnp.float32))


def test_concrete_operands_decide_by_where_they_live(clean_tier,
                                                     monkeypatch):
    """On a TPU host the default backend says "tpu" while an array made on
    mx.cpu() — the reference's default context — is computed on the host:
    the gate reads the operands, not the process."""
    from mxnet_tpu.pallas import registry
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    y, b = _epilogue_args()
    assert registry.runs_on((y, b, None)) == ("cpu", False)
    out = pallas.dispatch("matmul_epilogue", y, b, None, act_type="relu")
    ref = pallas.get_kernel("matmul_epilogue").xla_reference(
        y, b, None, act_type="relu")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    prov = pallas.tier_provenance()["matmul_epilogue"]
    assert prov["fallback_reasons"] == {"backend:cpu": 1}


def test_traced_dispatch_stages_kernel_and_reference(clean_tier,
                                                     monkeypatch):
    """Inside jit the platform is only known at lowering: with a TPU as the
    default backend the kernel is staged beside its reference, and the same
    program lowered for the host CPU holds the reference — it runs, where
    a kernel chosen in Python would die in the CPU lowering."""
    import jax
    from mxnet_tpu.pallas import registry
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    y, b = _epilogue_args()

    def f(y, b):
        return pallas.dispatch("matmul_epilogue", y, b, None,
                               act_type="relu")

    text = str(jax.make_jaxpr(f)(y, b))
    assert "pallas_call" in text and "platform_index" in text
    out = jax.jit(f)(y, b)                  # lowered for the CPU here
    ref = pallas.get_kernel("matmul_epilogue").xla_reference(
        y, b, None, act_type="relu")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    g = jax.jit(jax.grad(lambda y: f(y, b).sum()))(y)
    assert g.shape == y.shape and np.isfinite(np.asarray(g)).all()
    prov = pallas.tier_provenance()["matmul_epilogue"]
    assert prov["pallas"] >= 2 and prov["xla"] == 0


def test_kernel_is_not_staged_into_a_program_the_compiler_partitions(
        clean_tier, monkeypatch):
    """The compiler refuses to partition a Mosaic kernel: under a GSPMD
    mesh of several devices the reference runs, named in the open; inside
    a shard_map the shards are cut by hand and the kernel stays."""
    import jax
    from jax import shard_map
    from mxnet_tpu import parallel
    from mxnet_tpu.pallas import registry
    from mxnet_tpu.parallel import PartitionSpec as P
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    y, b = _epilogue_args()
    mesh = parallel.make_mesh({"data": 4}, devices=jax.devices()[:4])

    def fresh():        # a new function each time: traces are cached
        return lambda y, b: pallas.dispatch(
            "matmul_epilogue", y, b, None, act_type="relu")

    with parallel.use_mesh(mesh):
        assert "pallas_call" not in str(jax.make_jaxpr(fresh())(y, b))
    prov = pallas.tier_provenance()["matmul_epilogue"]
    assert prov["fallback_reasons"] == {"auto_partition:4dev": 1}

    by_hand = shard_map(fresh(), mesh=mesh, in_specs=(P("data"), P()),
                        out_specs=P("data"))
    with parallel.use_mesh(mesh):
        assert "pallas_call" in str(jax.make_jaxpr(by_hand)(y, b))
    # and on the mesh of one device a one-chip trainer uses, it stays too
    with parallel.use_mesh(parallel.make_mesh({"data": 1},
                                              devices=jax.devices()[:1])):
        assert "pallas_call" in str(jax.make_jaxpr(fresh())(y, b))


def test_fallback_unsupported_shape(clean_tier):
    """supports() rejection falls back with the concrete reason — even
    when interpret would otherwise force the custom path."""
    import jax.numpy as jnp
    y = jnp.ones((4, 2))          # minor dim below the tier's floor
    b = jnp.zeros((1, 2))
    out = pallas.dispatch("matmul_epilogue", y, b, None,
                          act_type="relu", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.ones((4, 2)))
    reasons = pallas.tier_provenance()["matmul_epilogue"]["fallback_reasons"]
    assert any(r.startswith("minor_dim_tiny") for r in reasons)
    # int input: dtype gate
    pallas.dispatch("matmul_epilogue", jnp.ones((16, 128), jnp.int32),
                    jnp.zeros((1, 128), jnp.int32), None, act_type="relu",
                    interpret=True)
    reasons = pallas.tier_provenance()["matmul_epilogue"]["fallback_reasons"]
    assert any(r.startswith("dtype") for r in reasons)


def test_kill_switch_env_beats_interpret(clean_tier, monkeypatch):
    """MXNET_TPU_PALLAS=off is absolute: even a forced interpret dispatch
    gets the reference."""
    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_TPU_PALLAS", "off")
    y, b = jnp.ones((16, 128)), jnp.zeros((1, 128))
    pallas.dispatch("matmul_epilogue", y, b, None, act_type="relu",
                    interpret=True)
    prov = pallas.tier_provenance()["matmul_epilogue"]
    assert prov["pallas"] == 0
    assert prov["fallback_reasons"] == {"mode_off": 1}


def test_malformed_mode_degrades_to_auto(clean_tier, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PALLAS", "bogus")
    assert pallas.mode() == "auto"
    with pytest.raises(MXNetError):
        pallas.set_mode("bogus")


def test_mode_on_makes_fallback_loud(clean_tier):
    import jax.numpy as jnp
    pallas.set_mode("on")
    y, b = jnp.ones((16, 128)), jnp.zeros((1, 128))
    with pytest.warns(RuntimeWarning, match="fell back"):
        pallas.dispatch("matmul_epilogue", y, b, None, act_type="relu")


def test_duplicate_registration_rejected(clean_tier):
    spec = pallas.get_kernel("matmul_epilogue")
    with pytest.raises(MXNetError, match="duplicate"):
        pallas.register_kernel(
            "matmul_epilogue", xla_reference=spec.xla_reference,
            tolerance=1.0)(spec.pallas_impl)


# -- dropout-key independence (PR-1 fold discipline) -------------------------

def test_dropout_key_independence_smoke(clean_tier):
    """(layer, tick, shard) fold into the key: any identity change gives
    an independent mask; the same identity is deterministic."""
    import jax
    key = jax.random.key(11)
    base = np.asarray(pallas.dropout_bits(key, (64, 128)))
    same = np.asarray(pallas.dropout_bits(key, (64, 128)))
    np.testing.assert_array_equal(base, same)
    varied = [np.asarray(pallas.dropout_bits(key, (64, 128), **kw))
              for kw in ({"layer": 1}, {"tick": 1}, {"shard": 1},
                         {"layer": 1, "tick": 2, "shard": 3})]
    for v in varied:
        frac = float((v != base).mean())
        assert frac > 0.9          # independent uint8 draws differ a.s.
    # and through the fused epilogue: different ticks -> different masks
    import jax.numpy as jnp
    y = jnp.ones((64, 128))
    b = jnp.zeros((1, 128))
    outs = [np.asarray(pallas.fused_matmul_epilogue(
        y, b, act_type="identity", p=0.5, rng=key, training=True,
        tick=t, interpret=True)) for t in (0, 1)]
    assert (outs[0] != outs[1]).mean() > 0.3
    kept = outs[0] != 0
    np.testing.assert_allclose(outs[0][kept], 2.0)   # inverted scaling


# -- wiring: gluon / ops / model-zoo surfaces --------------------------------

def test_dense_fused_epilogue_matches_unfused(clean_tier):
    from mxnet_tpu import gluon
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(4, 32).astype(np.float32))
    fused = gluon.nn.Dense(16, activation="relu", in_units=32)
    fused.initialize()
    y = fused(x).asnumpy()
    w = fused.weight.data().asnumpy()
    b = fused.bias.data().asnumpy()
    want = np.maximum(x.asnumpy() @ w.T + b, 0.0)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    # gelu is epilogue-only (plain Activation has no gelu mode) — new
    # capability unlocked by the tier
    import jax
    g = gluon.nn.Dense(16, activation="gelu", in_units=32)
    g.initialize()
    yg = g(x).asnumpy()
    wantg = np.asarray(jax.nn.gelu(
        x.asnumpy() @ g.weight.data().asnumpy().T
        + g.bias.data().asnumpy(), approximate=False))
    np.testing.assert_allclose(yg, wantg, rtol=1e-5, atol=1e-6)


def test_dense_epilogue_dropout_train_eval(clean_tier):
    from mxnet_tpu import autograd, gluon
    rng = np.random.RandomState(1)
    x = nd.array(rng.randn(256, 32).astype(np.float32))
    net = gluon.nn.Dense(512, activation="relu", in_units=32,
                         epilogue_dropout=0.5)
    net.initialize()
    y_eval = net(x).asnumpy()           # inference: dropout is a no-op
    w = net.weight.data().asnumpy()
    b = net.bias.data().asnumpy()
    np.testing.assert_allclose(
        y_eval, np.maximum(x.asnumpy() @ w.T + b, 0.0),
        rtol=1e-5, atol=1e-6)
    with autograd.record():
        y_tr = net(x).asnumpy()
    kept = y_tr != 0
    # inverted dropout: kept activations are scaled by 1/(1-p)
    np.testing.assert_allclose(y_tr[kept], (y_eval * 2.0)[kept],
                               rtol=1e-5, atol=1e-6)
    # relu keeps about half and dropout half of those: 0.25 expected, and
    # 256x512 samples put the bounds many standard errors away
    assert 0.2 < float(kept.mean()) < 0.3


def test_batchnorm_activation_fused_parity(clean_tier):
    """BatchNorm(activation=...) == BatchNorm() + Activation, train and
    eval, NCHW (row-broadcast path) and channel-last (col-broadcast)."""
    from mxnet_tpu import autograd, gluon
    rng = np.random.RandomState(2)
    for axis, shape in ((1, (4, 8, 6, 6)), (-1, (4, 6, 8))):
        x = nd.array(rng.randn(*shape).astype(np.float32))
        fused = gluon.nn.BatchNorm(axis=axis, activation="relu")
        plain = gluon.nn.BatchNorm(axis=axis)
        fused.initialize()
        plain.initialize()
        for train in (True, False):
            if train:
                with autograd.record():
                    a = fused(x).asnumpy()
                with autograd.record():
                    b = nd.relu(plain(x)).asnumpy()
            else:
                a = fused(x).asnumpy()
                b = nd.relu(plain(x)).asnumpy()
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_contrib_conv_epilogue_matches_add_relu(clean_tier):
    rng = np.random.RandomState(4)
    x = nd.array(rng.randn(2, 8, 4, 4).astype(np.float32))
    r = nd.array(rng.randn(2, 8, 4, 4).astype(np.float32))
    got = nd.contrib.conv_epilogue(x, r).asnumpy()
    want = np.maximum(x.asnumpy() + r.asnumpy(), 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _bf16_ulps(a, b):
    """Distance between two bfloat16 arrays in units in the last place."""
    def ordered(v):
        bits = np.asarray(v).view(np.uint16).astype(np.int32)
        mag = bits & 0x7FFF
        return np.where(bits & 0x8000, -mag, mag)
    return np.abs(ordered(a) - ordered(b))


def _conv_epilogue_ref(y, scale, bias, res=None, act_type="relu"):
    """The float32 fold BatchNorm ``act_type=`` and ``contrib.conv_epilogue``
    are held to: act(scale * y + bias [+ res]) accumulated in float32 and
    cast back to y's dtype. It was the XLA reference of the ``conv_epilogue``
    kernel (deleted in PR 29); the plain jax.numpy ops still have to equal
    it."""
    import jax
    import jax.numpy as jnp
    act = {"relu": lambda x: jnp.maximum(x, 0.0),
           "gelu": lambda x: jax.nn.gelu(x, approximate=False)}[act_type]
    out = (y.astype(jnp.float32) * scale.astype(jnp.float32)
           + bias.astype(jnp.float32))
    if res is not None:
        out = out + res.astype(jnp.float32)
    return act(out).astype(y.dtype)


def _rows_view(layout, x, vecs):
    """The 2D view the conv_epilogue kernel was fed: NCHW as (N*C, H*W)
    rows with (R, 1) vectors, channel-last as (rows, C) with (1, C)."""
    import jax.numpy as jnp
    if layout == "NCHW":
        n, c = x.shape[:2]
        return (lambda a: a.reshape(n * c, -1),
                [jnp.tile(v, n).reshape(n * c, 1) for v in vecs])
    c = x.shape[-1]
    return lambda a: a.reshape(-1, c), [v.reshape(1, c) for v in vecs]


@pytest.mark.parametrize("act", ["relu", "gelu"])
@pytest.mark.parametrize("layout,shape,axis", [("NCHW", (4, 8, 6, 6), 1),
                                               ("NHWC", (4, 6, 6, 8), -1)])
def test_batchnorm_act_bf16_is_the_reference_fold(clean_tier, layout, shape,
                                                  axis, act):
    """BatchNorm(act_type=) on the N-D array, in bf16, is
    ``_conv_epilogue_ref`` on the 2D view the kernel used to get: the same
    fp32 fold and one cast back, forward and gradients, to one bf16 ulp.
    The parity is with the reference fed the float32 scale and offset, as
    ISSUE 26 wrote the formula, not with what the tree before PR 26
    computed: that rounded both vectors to bf16 before the fold and sits a
    few ulps away where ``x*scale`` and ``offset`` cancel."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import _batch_norm
    rng = np.random.RandomState(11)
    c, eps = shape[axis], 1e-5
    x = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    cot = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    gamma = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(c) * 0.3, jnp.float32)
    mean = jnp.asarray(rng.randn(c) * 0.2, jnp.float32)
    var = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)

    def op(x, gamma, beta):
        return _batch_norm(x, gamma, beta, mean, var, eps=eps,
                           fix_gamma=False, axis=axis, act_type=act)[0]

    def ref(x, gamma, beta):
        scale = jax.lax.rsqrt(var + eps) * gamma
        view, (s2, b2) = _rows_view(layout, x,
                                    [scale, beta - mean * scale])
        return _conv_epilogue_ref(view(x), s2, b2,
                                  act_type=act).reshape(x.shape)

    got, want = op(x, gamma, beta), ref(x, gamma, beta)
    assert got.dtype == jnp.bfloat16
    assert _bf16_ulps(got, want).max() <= 1
    grads = [jax.grad(lambda *a: (f(*a).astype(jnp.float32)
                                  * cot.astype(jnp.float32)).sum(),
                      argnums=(0, 1, 2))(x, gamma, beta) for f in (op, ref)]
    assert _bf16_ulps(grads[0][0], grads[1][0]).max() <= 1
    for g, w in zip(grads[0][1:], grads[1][1:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4)
    # and with batch statistics: the fold of the mean and var it reports
    out, bmean, bvar = _batch_norm(x, gamma, beta, mean, var, eps=eps,
                                   fix_gamma=False, axis=axis, act_type=act,
                                   training=True)
    scale = jax.lax.rsqrt(bvar + eps) * gamma
    view, (s2, b2) = _rows_view(layout, x, [scale, beta - bmean * scale])
    want = _conv_epilogue_ref(view(x), s2, b2, act_type=act)
    assert _bf16_ulps(out, want.reshape(x.shape)).max() <= 1


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("act", ["relu", "gelu"])
@pytest.mark.parametrize("layout,shape,axis", [("NCHW", (4, 8, 6, 6), 1),
                                               ("NHWC", (4, 6, 6, 8), -1)])
def test_batchnorm_act_train_mode_gradients(clean_tier, layout, shape, axis,
                                            act, dtype):
    """With batch statistics (``training=True``) the gradients of
    BatchNorm(act_type=) for x, gamma and beta, the paths through the mean
    and the variance included, are those of the plain formula: two-pass
    float32 statistics folded by ``_conv_epilogue_ref`` on the 2D view.
    In bf16 the x-gradient agrees to one ulp; again the reference is fed
    float32 vectors (the tree before PR 26 rounded them to bf16)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import _batch_norm
    rng = np.random.RandomState(13)
    c, eps, f32 = shape[axis], 1e-5, jnp.float32
    axes = tuple(i for i in range(len(shape)) if i != axis % len(shape))
    x = jnp.asarray(rng.randn(*shape) * 1.7 + 0.4, dtype)
    cot = jnp.asarray(rng.randn(*shape), dtype)
    gamma = jnp.asarray(rng.rand(c) + 0.5, f32)
    beta = jnp.asarray(rng.randn(c) * 0.3, f32)
    moving_mean = jnp.asarray(rng.randn(c) * 0.2, f32)
    moving_var = jnp.asarray(rng.rand(c) + 0.5, f32)

    def op(x, gamma, beta):
        return _batch_norm(x, gamma, beta, moving_mean, moving_var, eps=eps,
                           fix_gamma=False, axis=axis, act_type=act,
                           training=True)[0]

    def ref(x, gamma, beta):
        xf = x.astype(f32)
        mean = xf.mean(axes)
        var = jnp.square(xf - jax.lax.expand_dims(mean, axes)).mean(axes)
        scale = jax.lax.rsqrt(var + eps) * gamma
        view, (s2, b2) = _rows_view(layout, x, [scale, beta - mean * scale])
        return _conv_epilogue_ref(view(x), s2, b2,
                                  act_type=act).reshape(x.shape)

    got, want = (jax.grad(lambda *a: (f(*a).astype(f32)
                                      * cot.astype(f32)).sum(),
                          argnums=(0, 1, 2))(x, gamma, beta)
                 for f in (op, ref))
    assert got[0].dtype == x.dtype
    if dtype == "bfloat16":
        assert _bf16_ulps(got[0], want[0]).max() <= 1
        got, want = got[1:], want[1:]
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert np.abs(g - w).max() <= 1e-5 * np.abs(w).max()


@pytest.mark.parametrize("layout,shape", [("NCHW", (4, 8, 6, 6)),
                                          ("NHWC", (4, 6, 6, 8))])
def test_contrib_conv_epilogue_bf16_is_the_reference_fold(clean_tier,
                                                          layout, shape):
    """contrib.conv_epilogue on the N-D arrays, in bf16, is
    ``_conv_epilogue_ref`` with unit scale and a residual on the 2D view,
    forward and both gradients, to one bf16 ulp."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.contrib import _conv_epilogue_contrib
    rng = np.random.RandomState(12)
    x, res, cot = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                   for _ in range(3))
    c = shape[1] if layout == "NCHW" else shape[-1]

    def ref(x, res):
        view, (s2, b2) = _rows_view(layout, x, [jnp.ones(c), jnp.zeros(c)])
        return _conv_epilogue_ref(view(x), s2, b2, view(res),
                                  act_type="relu").reshape(x.shape)

    got = _conv_epilogue_contrib(x, res)
    assert got.dtype == jnp.bfloat16
    assert _bf16_ulps(got, ref(x, res)).max() <= 1
    grads = [jax.grad(lambda *a: (f(*a).astype(jnp.float32)
                                  * cot.astype(jnp.float32)).sum(),
                      argnums=(0, 1))(x, res)
             for f in (_conv_epilogue_contrib, ref)]
    for g, w in zip(*grads):
        assert _bf16_ulps(g, w).max() <= 1


@pytest.mark.parametrize("training", [False, True], ids=["eval", "train"])
def test_batchnorm_act_never_reshapes(clean_tier, training):
    """BatchNorm(act_type=) works on the array as it is: on the chip a 2D
    view of a tiled NCHW activation is a physical re-layout (PERF.md §6,
    PR 26), so its jaxpr holds no reshape at all. The train case is why
    the shifted-moment statistics broadcast with ``lax.expand_dims`` too;
    the ``act_type=None`` branch shares that helper and keeps the bf16
    arithmetic it had."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import _batch_norm
    x = jnp.ones((2, 8, 4, 4), jnp.bfloat16)
    vec = jnp.ones((8,), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda x, g, b, m, v: _batch_norm(x, g, b, m, v, act_type="relu",
                                          fix_gamma=False,
                                          training=training))(
        x, vec, vec, vec, vec))
    assert "reshape" not in text and "mul" in text


def test_resnet50_step_holds_no_custom_kernel(clean_tier, monkeypatch):
    """ResNet-50's forward and backward, traced under jit with a TPU as the
    default backend and lowered for one, dispatch nothing through the
    tier: the provenance stays empty, no Mosaic call in the text."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.gluon.block import functional_apply
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.pallas import registry
    net = vision.resnet50_v1(classes=10)
    net.initialize()
    x = np.random.RandomState(5).randn(2, 3, 32, 32).astype(np.float32)
    net(nd.array(x))                        # deferred shapes
    trainable, aux = net._param_split()
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    pallas.reset_provenance()

    def loss(tr, ax, x):
        out, _, new_aux = functional_apply(net, jax.random.PRNGKey(0), tr,
                                           ax, [x], training=True)
        return out[0].astype(jnp.float32).sum(), new_aux

    traced = jax.jit(jax.value_and_grad(loss, has_aux=True)).trace(
        [p.data()._data.astype(jnp.bfloat16) for p in trainable],
        [p.data()._data for p in aux], jnp.asarray(x, jnp.bfloat16))
    assert pallas.tier_provenance() == {}
    assert "pallas_call" not in str(traced.jaxpr)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "stablehlo.convolution" in text
    assert "tpu_custom_call" not in text


def test_positionwise_ffn_fused_parity_eval(clean_tier):
    """The fused FFN (bias+gelu epilogue on ffn_1, bias+dropout epilogue
    on ffn_2) equals the classic composition in eval mode."""
    import jax
    from mxnet_tpu.gluon.model_zoo.bert import PositionwiseFFN
    ffn = PositionwiseFFN(units=16, hidden_size=32, dropout=0.4)
    ffn.initialize()
    assert ffn.ffn_1._activation == "gelu"
    assert ffn.ffn_2._epilogue_dropout == pytest.approx(0.4)
    rng = np.random.RandomState(5)
    x = nd.array(rng.randn(2, 3, 16).astype(np.float32))
    got = ffn(x).asnumpy()
    w1 = ffn.ffn_1.weight.data().asnumpy()
    b1 = ffn.ffn_1.bias.data().asnumpy()
    w2 = ffn.ffn_2.weight.data().asnumpy()
    b2 = ffn.ffn_2.bias.data().asnumpy()
    h = np.asarray(jax.nn.gelu(x.asnumpy() @ w1.T + b1,
                               approximate=False))
    want = h @ w2.T + b2
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_blockwise_attention_routes_through_registry(clean_tier,
                                                     monkeypatch):
    """The long-context kernel shares the tier's guard story: auto mode
    runs the online-softmax kernel (a verified backend on CPU), the kill
    switch falls back to the dense reference."""
    from mxnet_tpu.parallel.ring_attention import (attention_reference,
                                                   blockwise_attention)
    import jax.numpy as jnp
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(2, 2, 32, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, 32, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, 32, 8), jnp.float32)
    out = blockwise_attention(q, k, v, block_size=8, causal=True)
    prov = pallas.tier_provenance()["blockwise_attention"]
    assert prov["pallas"] == 1          # cpu IS a verified backend here
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    monkeypatch.setenv("MXNET_TPU_PALLAS", "off")
    out2 = blockwise_attention(q, k, v, block_size=8, causal=True)
    prov = pallas.tier_provenance()["blockwise_attention"]
    assert prov["fallback_reasons"].get("mode_off") == 1
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_bench_pallas_flag(clean_tier, monkeypatch, capsys):
    """bench.py --pallas {on,off,auto}: valid modes export the env knob
    a deployment would set; an invalid mode is a structured one-line
    diagnostic, not a crash."""
    import importlib
    bench = importlib.import_module("bench")
    assert bench._parse_pallas_flag(["bench.py", "--pallas", "off"]) == "off"
    assert bench._parse_pallas_flag(["bench.py", "--pallas=on"]) == "on"
    assert bench._parse_pallas_flag(["bench.py"]) is None
    monkeypatch.setattr("sys.argv", ["bench.py", "--pallas", "sideways"])
    monkeypatch.delenv("MXNET_TPU_PALLAS", raising=False)
    rc = bench.main()
    assert rc == 2
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["error"] == "bad_flag"
    assert rec["metric"] == bench.METRIC
    # valid flag exports the knob before the body runs
    monkeypatch.setattr("sys.argv", ["bench.py", "--pallas", "off"])
    monkeypatch.setattr(bench, "_run_body", lambda: {"metric": bench.METRIC})
    try:
        assert bench.main() == 0
        assert os.environ["MXNET_TPU_PALLAS"] == "off"
    finally:
        # bench.main set the var itself; delenv on an absent var
        # registers no undo, so restore by hand or it leaks into
        # every later test in the process
        os.environ.pop("MXNET_TPU_PALLAS", None)


def test_blockwise_reference_chunking_is_exact(clean_tier):
    """The kill-switch fallback for attention chunks its query axis
    (bounded score-matrix memory) — same math as the unchunked dense
    reference, bottom-right causal alignment included, s_q != s_kv and
    empty-row edges covered."""
    import jax.numpy as jnp
    from mxnet_tpu.pallas.kernels import _blockwise_ref
    from mxnet_tpu.parallel.ring_attention import attention_reference
    rng = np.random.RandomState(7)
    cases = [(40, 40), (48, 32), (32, 48)]   # square, s_q>s_kv, s_q<s_kv
    for s_q, s_kv in cases:
        q = jnp.asarray(rng.randn(2, 2, s_q, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 2, s_kv, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 2, s_kv, 8), jnp.float32)
        for causal in (False, True):
            got = _blockwise_ref(q, k, v, causal=causal, _chunk=16)
            want = attention_reference(q, k, v, causal=causal)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5,
                err_msg=f"s_q={s_q} s_kv={s_kv} causal={causal}")


# -- the fused scan kernel (mamba2_ssd) ---------------------------------------

# (length, heads, head size, groups, state size, chunk): three chunks and more
# everywhere, so a state and its cotangent are carried from chunk to chunk
SSD_CASES = {
    # 16 heads of 64 in one group: blocks of 8 heads in lane tiles of 2,
    # two blocks a group, whose partial dB and dC are added outside
    "one_group_many_heads": (24, 16, 64, 1, 16, 8),
    "eight_groups_of_eight": (32, 64, 8, 8, 16, 8),
    "a_group_a_head": (24, 4, 8, 4, 16, 8),
    # a length that is no multiple of the chunk: padded at the end
    "padded": (20, 16, 64, 1, 16, 8),
}
# PR 27's bounds: float32 against float32, and a bfloat16 program against
# the same program in float32
SSD_RTOL = {"float32": 1e-4, "bfloat16": 2e-2}


def _ssd_op_inputs(case, dtype, seed=0):
    import jax.numpy as jnp
    length, h, p, g, n, _ = SSD_CASES[case]
    rng = np.random.default_rng(seed)
    shapes = [(1, length, h, p), (1, length, h), (h,), (1, length, g, n),
              (1, length, g, n), (h,), (h,)]
    args = [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in shapes]
    args[1] = args[1] - 2.0     # steps of a tenth or so, as dt_bias is drawn
    args[2] = jnp.log(jnp.asarray(rng.uniform(1, 16, h), jnp.float32))
    # x, B and C in the compute dtype; the parameters stay float32
    return [a.astype(dtype) if i in (0, 3, 4) else a
            for i, a in enumerate(args)]


@pytest.fixture
def scan_on_the_kernel(clean_tier, monkeypatch):
    """``_contrib_mamba2_ssd`` with its scan on the kernel in interpret
    mode. ``supports`` asks for whole tiles of the chip's registers, which
    a toy shape has not and the interpreter does not need: it is taken out
    here, and tests/test_chip_compile.py holds it to the chip's compiler."""
    from mxnet_tpu.pallas import registry
    monkeypatch.setattr(pallas.get_kernel("mamba2_ssd"), "supports", None)
    monkeypatch.setattr(pallas, "dispatch", lambda name, *args, **params:
                        registry.dispatch(name, *args, interpret=True,
                                          **params))


def _rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("dtype", list(SSD_RTOL))
@pytest.mark.parametrize("case", list(SSD_CASES))
def test_ssd_kernel_is_its_reference_and_the_recurrence(case, dtype,
                                                        scan_on_the_kernel):
    """The whole op, forward: the kernel against the ``jax.numpy`` scan it
    replaces on a TPU, and against the recurrence one position at a time."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import ssm
    from test_granite_hybrid import recurrence
    chunk = SSD_CASES[case][-1]
    args = _ssd_op_inputs(case, dtype)
    got = ssm._mamba2_ssd(*args, chunk_size=chunk)
    assert pallas.tier_provenance()["mamba2_ssd"]["pallas"] == 1
    assert got.shape == args[0].shape and got.dtype == jnp.dtype(dtype)
    exact = [a.astype(jnp.float32) for a in args]
    assert _rel(got, recurrence(*exact)) <= SSD_RTOL[dtype]
    pallas.set_mode("off")              # the same call on the reference
    want = ssm._mamba2_ssd(*args, chunk_size=chunk)
    assert pallas.tier_provenance()["mamba2_ssd"]["xla"] == 1
    assert _rel(got, want) <= SSD_RTOL[dtype]


@pytest.mark.parametrize("dtype", list(SSD_RTOL))
@pytest.mark.parametrize("case", list(SSD_CASES))
def test_ssd_kernel_has_every_gradient_of_the_op(case, dtype,
                                                 scan_on_the_kernel):
    """x, dt, A_log, B, C, D and dt_bias: the backward kernel (the chunks in
    reverse, the state's cotangent carried) with autodiff of the step sizes
    and the cumulative sum around it, against autodiff of the reference and,
    in float32, of the recurrence (the cosine of an output rounded to
    bfloat16 is another number: there the two scans are held to each
    other)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import ssm
    from test_granite_hybrid import recurrence
    chunk = SSD_CASES[case][-1]
    args = _ssd_op_inputs(case, dtype, seed=1)
    every = tuple(range(len(args)))

    def grads(fn, operands):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(
            fn(*a).astype(jnp.float32))), every)(*operands)

    def op(*a):
        return ssm._mamba2_ssd(*a, chunk_size=chunk)

    got = grads(op, args)
    assert pallas.tier_provenance()["mamba2_ssd"]["pallas"] == 1
    exact = grads(recurrence, [a.astype(jnp.float32) for a in args])
    pallas.set_mode("off")
    want = grads(op, args)
    for g, w, e, a in zip(got, want, exact, args):
        assert g.shape == a.shape and g.dtype == a.dtype
        assert _rel(g, w) <= SSD_RTOL[dtype]
        assert dtype != "float32" or _rel(g, e) <= SSD_RTOL[dtype]


def test_ssd_tiles_are_read_from_the_shapes():
    """A block of heads lies inside one group and takes up to 512 lanes; a
    lane tile holds the heads that fit 128 lanes."""
    from mxnet_tpu.pallas.ssd import ssd_tiles
    assert ssd_tiles(8, 64) == (8, 2)       # Nemotron: a group of 8
    assert ssd_tiles(64, 64) == (8, 2)      # Granite: 8 of a group's 64
    assert ssd_tiles(4, 128) == (4, 1)
    assert ssd_tiles(1, 64) == (1, 1)       # a head a group: half a tile
    spec = pallas.get_kernel("mamba2_ssd")
    import jax
    import jax.numpy as jnp

    def operands(h, p, g, n, length=256, dtype=jnp.bfloat16):
        sds = jax.ShapeDtypeStruct
        return (sds((1, length, h, p), dtype), sds((1, length, h),
                                                   jnp.float32),
                sds((1, length, h), jnp.float32), sds((1, length, g, n),
                                                      dtype),
                sds((1, length, g, n), dtype), sds((h,), jnp.float32))

    assert spec.supports(*operands(64, 64, 8, 128), chunk_size=128) is None
    assert spec.supports(*operands(64, 64, 1, 128), chunk_size=256) is None
    for bad, params in ((operands(4, 64, 4, 128), {"chunk_size": 128}),
                        (operands(8, 64, 1, 16), {"chunk_size": 128}),
                        (operands(8, 64, 1, 128), {"chunk_size": 64})):
        assert spec.supports(*bad, **params).startswith("tile:")
    assert spec.supports(*operands(8, 64, 1, 128, dtype=jnp.float16),
                         chunk_size=128).startswith("dtype:")
    assert spec.supports(*operands(8, 64, 1, 128, length=200),
                         chunk_size=128).startswith("shape:")


def test_traced_scan_counts_the_path_it_took(clean_tier, monkeypatch):
    """``mxnet_tpu_ssd_scans_traced_total{chunk,length,path}``: a scan traced
    where a TPU is the backend stages the kernel (beside its reference, for
    the lowering to choose) and counts ``kernel``; on the CPU, or at a shape
    the kernel declines, it counts ``xla``."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import observability
    from mxnet_tpu.ops import ssm
    from mxnet_tpu.pallas import registry

    def counted():
        return dict(observability.snapshot()["metrics"].get(
            ssm.SCAN_COUNT_METRIC, {}).get("values", {}))

    def trace(heads):
        shapes = [(1, 256, heads, 64), (1, 256, heads), (heads,),
                  (1, 256, 1, 128), (1, 256, 1, 128), (heads,), (heads,)]
        dtypes = [jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16,
                  jnp.bfloat16, jnp.float32, jnp.float32]
        return str(jax.make_jaxpr(
            lambda *a: ssm._mamba2_ssd(*a, chunk_size=128))(
                *[jax.ShapeDtypeStruct(s, d)
                  for s, d in zip(shapes, dtypes)]))

    before = counted()
    assert "pallas_call" not in trace(8)            # the CPU: the reference
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    text = trace(8)
    assert "pallas_call" in text and "platform_index" in text
    assert "pallas_call" not in trace(1)            # declined: half a tile
    after = counted()

    def grew(path):
        key = f"chunk=128,length=256,path={path}"
        return after.get(key, 0) - before.get(key, 0)

    assert (grew("kernel"), grew("xla")) == (1, 2)
    prov = pallas.tier_provenance()["mamba2_ssd"]
    assert prov["pallas"] == 1 and prov["fallback_reasons"] == {
        "backend:cpu": 1, "tile:p64_heads_per_group1_n128_chunk128": 1}


# -- the fused retention kernel (power_retention) -----------------------------

def _retention_op_inputs(length, heads, groups, dim, dtype="float32", seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)

    def unit(n):
        t = rng.standard_normal((1, length, n, dim))
        return t / np.sqrt(np.mean(t * t, -1, keepdims=True))

    q, k = unit(heads), unit(groups)
    v = rng.standard_normal((1, length, groups, dim))
    # gates that remember a chunk of 128 or so: the state carries
    log_g = -rng.uniform(0.25, 4.0, (1, length, groups)) / 128
    return [jnp.asarray(t, dtype) for t in (q, k, v)] \
        + [jnp.asarray(log_g, jnp.float32)]


@pytest.fixture
def retention_on_the_kernel(clean_tier, monkeypatch):
    """``_contrib_power_retention`` with its scan on the kernel in interpret
    mode; ``supports`` stays (the shapes here are whole register tiles)."""
    from mxnet_tpu.pallas import registry
    monkeypatch.setattr(pallas, "dispatch", lambda name, *args, **params:
                        registry.dispatch(name, *args, interpret=True,
                                          **params))


@pytest.mark.parametrize("length", [256, 200], ids=["whole_chunks", "padded"])
def test_retention_op_runs_on_the_kernel(length, retention_on_the_kernel):
    """The whole op through the tier: two key/value heads of two query heads
    each, two chunks of 128 (the second padded where the length is 200),
    against the same call on the ``jax.numpy`` scan."""
    from mxnet_tpu.ops import retention
    args = _retention_op_inputs(length, 4, 2, 128)
    got = retention._power_retention(*args, chunk_size=128)
    assert pallas.tier_provenance()["power_retention"] == {
        "pallas": 1, "xla": 0, "fallback_reasons": {}}
    assert got.shape == args[0].shape and got.dtype == args[0].dtype
    pallas.set_mode("off")
    want = retention._power_retention(*args, chunk_size=128)
    assert pallas.tier_provenance()["power_retention"]["xla"] == 1
    assert _rel(got, want) <= 1e-4


def test_retention_kernel_declines_what_is_no_whole_tile(
        retention_on_the_kernel, tmp_path):
    """A head of 8 coordinates and a chunk that is no multiple of 128 run
    the ``jax.numpy`` scan, counted and journaled with the reason; so does
    what the kernel has no arithmetic for."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.diagnostics import reset_journal
    from mxnet_tpu.ops import retention
    from mxnet_tpu.pallas.retention import pair_span, row_tile
    spec = pallas.get_kernel("power_retention")
    sds = jax.ShapeDtypeStruct

    def operands(length=2048, heads=40, groups=8, dim=128, chunk=1024,
                 dtype=jnp.bfloat16, cs=jnp.float32):
        return (sds((1, length, heads, dim), dtype),
                sds((1, length, groups, dim), dtype),
                sds((1, length, groups, dim), dtype),
                sds((1, groups, length // chunk, chunk), cs))

    assert spec.supports(*operands(), chunk_size=1024) is None
    assert spec.supports(*operands(length=1024), chunk_size=1024) is None
    assert spec.supports(*operands(dtype=jnp.float32, chunk=128),
                         chunk_size=128) is None
    assert spec.supports(*operands(dim=8), chunk_size=1024) \
        == "tile:d8_dv8_chunk1024"
    assert spec.supports(*operands(length=2000, chunk=100),
                         chunk_size=100).startswith("tile:")
    assert spec.supports(*operands(dtype=jnp.float16),
                         chunk_size=1024).startswith("dtype:")
    assert spec.supports(*operands(cs=jnp.bfloat16),
                         chunk_size=1024) == "dtype:cs_bfloat16"
    assert spec.supports(*operands(heads=12), chunk_size=1024).startswith(
        "shape:")
    assert spec.supports(*operands(), chunk_size=512).startswith("shape:")
    # 65 offsets in groups of 13; tiles of 256 rows, 128 where they must be
    assert (pair_span(128), row_tile(1024), row_tile(384)) == (13, 256, 128)

    jpath = str(tmp_path / "journal.jsonl")
    reset_journal(jpath)
    try:
        small = _retention_op_inputs(24, 4, 2, 8)
        retention._power_retention(*small, chunk_size=8)
        odd = _retention_op_inputs(200, 2, 1, 128)
        retention._power_retention(*odd, chunk_size=100)
    finally:
        reset_journal(None)
    prov = pallas.tier_provenance()["power_retention"]
    assert prov["pallas"] == 0 and prov["fallback_reasons"] == {
        "tile:d8_dv8_chunk8": 1, "tile:d128_dv128_chunk100": 1}
    reasons = [r["reason"] for r in _journal_records(jpath)
               if r.get("kind") == "pallas_fallback"
               and r.get("kernel") == "power_retention"]
    assert reasons == ["tile:d8_dv8_chunk8", "tile:d128_dv128_chunk100"]


def test_traced_retention_counts_the_path_it_took(clean_tier, monkeypatch):
    """``mxnet_tpu_power_retentions_traced_total{chunk,length,path}``: a
    retention traced where a TPU is the backend stages the kernel (beside its
    reference, for the lowering to choose) and counts ``kernel``; on the CPU,
    or at a shape the kernel declines, it counts ``xla``."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import observability
    from mxnet_tpu.ops import retention
    from mxnet_tpu.pallas import registry

    def counted():
        return dict(observability.snapshot()["metrics"].get(
            retention.RETENTION_COUNT_METRIC, {}).get("values", {}))

    def trace(dim):
        shapes = [(1, 256, 4, dim), (1, 256, 2, dim), (1, 256, 2, dim),
                  (1, 256, 2)]
        dtypes = [jnp.bfloat16] * 3 + [jnp.float32]
        return str(jax.make_jaxpr(
            lambda *a: retention._power_retention(*a, chunk_size=128))(
                *[jax.ShapeDtypeStruct(s, d)
                  for s, d in zip(shapes, dtypes)]))

    before = counted()
    assert "pallas_call" not in trace(128)          # the CPU: the reference
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    text = trace(128)
    assert "pallas_call" in text and "platform_index" in text
    assert "pallas_call" not in trace(64)           # declined: half a tile
    after = counted()

    def grew(path):
        key = f"chunk=128,length=256,path={path}"
        return after.get(key, 0) - before.get(key, 0)

    assert (grew("kernel"), grew("xla")) == (1, 2)
    prov = pallas.tier_provenance()["power_retention"]
    assert prov["pallas"] == 1 and prov["fallback_reasons"] == {
        "backend:cpu": 1, "tile:d64_dv64_chunk128": 1}
