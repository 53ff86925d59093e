"""The hybrid state-space / attention decoder (``gluon.model_zoo.
granite_hybrid``, ``ops/ssm.py``, ``HybridBlock.recompute``) at a small
size on the CPU: hidden 64, Mamba-2 + attention + Mamba-2, 4 Mamba heads of
16, state 16, chunk 8, 8 query / 2 key-value heads, vocabulary 128. The
system is held to the benchmark's plain reference
(``chipbench/models/granite_4_0_h_micro.py``: the recurrence step by step,
attention as a full masked softmax), which shares no function with
``mxnet_tpu/ops``.

RTOL: both sides compute in float32 on the CPU with exact products, so they
differ only by the order of summation: the system sums inside chunks and
carries chunk states, and takes decays as exp of differences of cumulative
sums where the reference multiplies step by step. Read here: 1e-7 to 6e-6
of the largest element. 1e-4 leaves that a factor of 15 for other seeds and
depths; one bfloat16 rounding is 2**-8 = 3.9e-3, so bf16 compute fails it
(``test_bf16_compute_...``) and passes the runner's 0.03.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, observability, parallel
from mxnet_tpu.gluon.model_zoo import granite_hybrid
from mxnet_tpu.ops import ssm

from chipbench import manifest
from chipbench.models import granite_4_0_h_micro as gm
from chipbench.runners import train

RTOL = 1e-4
SMALL = dict(
    manifest.load_config(manifest.load_manifest(),
                         "granite_4_0_h_micro")["args"],
    vocab_size=128, hidden_size=64, shared_intermediate_size=96,
    num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
    num_attention_heads=8, num_key_value_heads=2, mamba_n_heads=4,
    mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
    compute_dtype=None, master_dtype=None)


@pytest.fixture
def mesh():
    return parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])


def batch(seq, seed=3, n=2):
    return gm.make_batch(SMALL, {"seq": seq}, n, np.random.default_rng(seed))


def close(got, want, rtol=RTOL):
    scale = np.abs(want).max()
    assert scale > 0
    return np.abs(np.asarray(got) - want).max() <= rtol * scale


def system_loss_and_grads(net, x, y):
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(net(mx.nd.array(x)), mx.nd.array(y)).mean()
    loss.backward()
    return float(loss.asscalar()), gm.reference_params(
        net, read=lambda p: p.grad().asnumpy())


# -- (a) the system against the plain reference ------------------------------

@pytest.mark.parametrize("hybridize", [False, True],
                         ids=["eager", "hybridized"])
@pytest.mark.parametrize("seq", [24, 20], ids=["whole_chunks", "padded"])
def test_logits_loss_and_every_gradient_agree_with_the_reference(
        mesh, hybridize, seq):
    net, _ = gm.build(SMALL, mesh, 3)
    x, y = batch(seq)
    if hybridize:
        net.hybridize()
    assert close(net(mx.nd.array(x)).asnumpy(), gm.reference_logits(net, x))
    loss, grads = system_loss_and_grads(net, x, y)
    want_loss, want = gm.reference_loss_and_grads(net, x, y)
    assert loss == pytest.approx(want_loss, rel=RTOL)
    got_leaves, treedef = jax.tree_util.tree_flatten(grads)
    want_leaves, want_treedef = jax.tree_util.tree_flatten(want)
    assert treedef == want_treedef and len(want_leaves) == 34
    for path, g, w in zip(jax.tree_util.tree_leaves_with_path(want),
                          got_leaves, want_leaves):
        assert g.shape == w.shape
        assert close(g, w), jax.tree_util.keystr(path[0])


def test_every_parameter_of_the_net_is_in_the_reference(mesh):
    net, _ = gm.build(SMALL, mesh, 3)
    leaves = jax.tree_util.tree_leaves(gm.reference_params(net))
    # the tied embedding is one parameter: head and lookup share it
    assert len(leaves) == len(net.collect_params())
    assert sum(leaf.size for leaf in leaves) == sum(
        int(np.prod(p.shape)) for p in net.collect_params().values())


# -- (b) the chunked scan against the recurrence -----------------------------

def recurrence(x, dt, a_log, b, c, d, dt_bias):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t +
    D x_t``, one position at a time."""
    bsz, _, h, p = x.shape
    g, n = b.shape[2:]
    dt = jax.nn.softplus(dt + dt_bias)
    a = -jnp.exp(a_log)
    b, c = (jnp.repeat(t, h // g, axis=2) for t in (b, c))

    def position(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) \
            + d[:, None] * x_t

    _, y = lax.scan(position, jnp.zeros((bsz, h, p, n)),
                    tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def scan_inputs(length, seed=0, bsz=2, h=4, p=16, g=2, n=16):
    rng = np.random.default_rng(seed)
    shapes = [(bsz, length, h, p), (bsz, length, h), (h,),
              (bsz, length, g, n), (bsz, length, g, n), (h,), (h,)]
    args = [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in shapes]
    args[2] = jnp.log(jnp.asarray(rng.uniform(1, 16, h), jnp.float32))
    return args


@pytest.mark.parametrize("length", [8, 24, 20])
def test_chunked_scan_is_the_recurrence(length):
    args = scan_inputs(length)
    got = ssm._mamba2_ssd(*args, chunk_size=8)
    assert got.shape == args[0].shape
    assert close(got, np.asarray(recurrence(*args)))


@pytest.mark.parametrize("length", [8, 24, 20])
def test_chunked_scan_has_the_gradients_of_the_recurrence(length):
    args = scan_inputs(length, seed=1)
    every = tuple(range(len(args)))
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(
        ssm._mamba2_ssd(*a, chunk_size=8))), every)(*args)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(recurrence(*a))), every)(*args)
    for g, w in zip(got, want):
        assert close(g, np.asarray(w))


def test_scan_is_an_op_of_both_worlds():
    args = scan_inputs(16)
    want = np.asarray(ssm._mamba2_ssd(*args, chunk_size=8))
    got = mx.nd.contrib.mamba2_ssd(*[mx.nd.array(np.asarray(a))
                                     for a in args], chunk_size=8)
    assert np.array_equal(got.asnumpy(), want)
    sym = mx.sym.contrib.mamba2_ssd(*[mx.sym.var(f"a{i}") for i in range(7)],
                                    chunk_size=8)
    assert len(sym.list_arguments()) == 7
    with pytest.raises(mx.MXNetError, match="multiple of G"):
        ssm._mamba2_ssd(args[0], args[1], args[2], args[3][:, :, :1].repeat(
            3, axis=2), args[4], args[5], args[6])


def test_conv_gate_and_norm_ops():
    rng = np.random.default_rng(5)
    x = rng.standard_normal((2, 9, 6)).astype(np.float32)
    w = rng.standard_normal((6, 4)).astype(np.float32)
    bias = rng.standard_normal(6).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for j in range(4):
            if t + j - 3 >= 0:
                want[:, t] += w[:, j] * x[:, t + j - 3]
    want += bias
    got = ssm._causal_conv1d(x, w, bias, act_type="identity")
    assert close(got, want, 1e-6)
    silu = want / (1 + np.exp(-want))
    assert close(ssm._causal_conv1d(x, w, bias), silu, 1e-6)
    z = rng.standard_normal(x.shape).astype(np.float32)
    gated = x * z / (1 + np.exp(-z))
    normed = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    assert close(ssm._gated_rms_norm(x, z, bias, eps=1e-5), normed * bias,
                 1e-6)
    assert close(ssm._swiglu(x), x[..., :3] / (1 + np.exp(-x[..., :3]))
                 * x[..., 3:], 1e-6)
    # float32 inside whatever the dtype: a bf16 call is the float32 result
    # rounded once
    low = ssm._gated_rms_norm(jnp.asarray(x, jnp.bfloat16),
                              jnp.asarray(z, jnp.bfloat16), jnp.asarray(bias))
    assert low.dtype == jnp.bfloat16
    assert close(np.asarray(low, np.float32), normed * bias, 2e-2)


# -- (c) recomputation --------------------------------------------------------

def test_recomputed_layers_give_the_same_gradients(mesh):
    x, y = batch(24)
    grads = {}
    for recompute in (False, True):
        net, _ = gm.build(dict(SMALL, recompute=recompute), mesh, 3)
        net.hybridize()
        assert all(layer._recompute is recompute for layer in net.layers)
        grads[recompute] = system_loss_and_grads(net, x, y)
    assert grads[True][0] == pytest.approx(grads[False][0], rel=1e-6)
    for g, w in zip(*(jax.tree_util.tree_leaves(grads[r][1])
                      for r in (True, False))):
        assert close(g, w, 1e-5)


def test_recompute_is_in_the_training_program_and_not_in_predict_mode(mesh):
    x, _ = batch(16)

    def forward_jaxpr(net, training):
        tr = [p.data()._data for p in net._param_split()[0]]
        return str(jax.make_jaxpr(
            lambda tr, toks: gluon.block.functional_apply(
                net, jax.random.key(0), tr, [], [toks],
                training=training)[0][0])(tr, jnp.asarray(x)))

    net, _ = gm.build(SMALL, mesh, 3)
    # one jax.checkpoint for each decoder layer, in training mode only
    assert forward_jaxpr(net, True).count("remat2[") == 3
    assert "remat2[" not in forward_jaxpr(net, False)
    plain, _ = gm.build(dict(SMALL, recompute=False), mesh, 3)
    assert "remat2[" not in forward_jaxpr(plain, True)
    # and the eager tape takes the layers as they are
    _, grads = system_loss_and_grads(net, *batch(16))
    assert np.abs(grads["embed"]).max() > 0


def test_recompute_carries_auxiliary_state(mesh):
    """A block with BatchNorm under ``recompute()``: running statistics
    leave the recomputed region as outputs and move as without it."""
    x = np.random.default_rng(0).standard_normal((8, 6)).astype(np.float32)
    y = np.random.default_rng(1).integers(0, 3, (8,))
    seen = []
    for recompute in (False, True):
        mx.random.seed(7)
        net = gluon.nn.HybridSequential()
        body = gluon.nn.HybridSequential()
        body.add(gluon.nn.Dense(5, in_units=6), gluon.nn.BatchNorm(
            in_channels=5))
        net.add(body.recompute(recompute), gluon.nn.Dense(3, in_units=5))
        net.initialize()
        trainer = parallel.ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1}, mesh=mesh)
        losses = [float(trainer.step(x, y).asscalar()) for _ in range(3)]
        stats = [p.data().asnumpy() for name, p in
                 net.collect_params().items() if "running" in name]
        seen.append((losses, stats))
    assert seen[0][0] == pytest.approx(seen[1][0], rel=1e-6)
    for a, b in zip(seen[0][1], seen[1][1]):
        assert np.abs(a).max() > 0 and np.allclose(a, b, rtol=1e-6)


# -- (d) run_steps(k) is k steps ----------------------------------------------

def test_run_steps_is_k_times_step(mesh):
    x, y = batch(24)
    _, one = gm.build(SMALL, mesh, 3)
    net_k, many = gm.build(SMALL, mesh, 3)
    net_1 = one._block
    losses = [float(one.step(x, y).asscalar()) for _ in range(3)]
    last = float(many.run_steps(x, y, num_steps=3).asscalar())
    assert last == pytest.approx(losses[-1], rel=1e-5)
    assert losses[-1] < losses[0]
    for a, b in zip(net_1.collect_params().values(),
                    net_k.collect_params().values()):
        assert np.allclose(a.data().asnumpy(), b.data().asnumpy(),
                           rtol=1e-4, atol=1e-6)
    assert many.num_update == one.num_update == 3


# -- (e) the tolerance tells precisions apart --------------------------------

def test_bf16_compute_fails_the_tight_tolerance_and_passes_the_runners(mesh):
    config = {"reference": "chipbench.models.granite_4_0_h_micro."
                           "reference_logits", "reference_samples": 2}
    shares = {}
    for dtype in (None, "bfloat16"):
        args = dict(SMALL, compute_dtype=dtype, master_dtype=dtype)
        net, trainer = gm.build(args, mesh, 3)
        x, y = batch(24)
        net(mx.nd.array(x[:1]))
        reference = train.plain_reference(config, net, x)
        trainer.prepare(x[:1])
        check = train.forward_check(
            train.system_logits(trainer, args, x, y, len(reference)),
            reference)
        shares[dtype] = check["share"]
        assert check["ok"]                      # FORWARD_TOLERANCE, 0.03
    assert shares[None] <= RTOL < shares["bfloat16"] <= 0.03


# -- scopes and counters ------------------------------------------------------

def test_device_scopes_names_the_instructions_of_the_built_programs(mesh):
    net, trainer = gm.build(SMALL, mesh, 3)
    x, y = batch(16)
    before = observability.snapshot()["metrics"].get(
        ssm.SCAN_COUNT_METRIC, {}).get("values", {})
    trainer.run_steps(x, y, num_steps=2)
    after = observability.snapshot()["metrics"][ssm.SCAN_COUNT_METRIC][
        "values"]
    # two Mamba layers traced into one program, chunk 8, 16 positions, on
    # the jax.numpy scan (the CPU has no kernel)
    assert after["chunk=8,length=16,path=xla"] - before.get(
        "chunk=8,length=16,path=xla", 0) >= 2
    programs = {name: record for name, record
                in observability.device_scopes().items()
                if name.endswith("run_steps(2)")}
    assert len(programs) >= 1
    record = programs.popitem()[1]
    assert record["module"] == "jit_multi"
    found = set(record["scopes"].values())
    assert {"mamba2.in_proj", "mamba2.conv", "mamba2.ssd",
            "mamba2.gate_norm", "mamba2.out_proj", "attention", "mlp",
            "lm_head", "loss", "optimizer"} <= found
    # a program that was never run is not listed, and asking changes none
    assert not any(name.endswith("step") for name in programs)
    assert trainer.num_update == 2


def test_unknown_layer_type_is_refused():
    with pytest.raises(mx.MXNetError, match="unknown layer types"):
        granite_hybrid.granite_hybrid(**dict(
            granite_hybrid.GRANITE_4_0_H_MICRO, layer_types=["mamba", "moe"]))
    with pytest.raises(mx.MXNetError, match="no multiple"):
        granite_hybrid.GroupedQueryAttention(64, 8, 3)
