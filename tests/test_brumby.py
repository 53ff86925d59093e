"""The power-retention decoder (``gluon.model_zoo.brumby``,
``ops/retention.py``, ``_contrib_rotary_embedding``) at a small size on the
CPU: the operator against the ``a[t, s]`` form of the benchmark's plain
reference (``chipbench/models/brumby_14b_base.py``: no chunk, no state, no
function of ``mxnet_tpu/ops``) and against the recurrence written here step
by step over the full second tensor power; its gradients against autodiff of
the ``a[t, s]`` form; the planted fault (no state carried) caught; rotary
against complex numbers; the whole model's logits, loss and every gradient
against the reference; then the same net through ``ShardedTrainer``.

RTOL: both sides compute in float32 on the CPU with exact products, so they
differ only by the order of summation (the operator sums inside chunks,
carries states and takes the second power over pairs of coordinates). Read
here: 6e-8 to 7e-6 of the largest element. 1e-4 leaves that a factor of 15;
one bfloat16 rounding is 2**-8 = 3.9e-3.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, observability, parallel
from mxnet_tpu.gluon.model_zoo import brumby
from mxnet_tpu.ops import nn as nn_ops
from mxnet_tpu.ops import retention
from mxnet_tpu.pallas import retention as retention_kernel

from chipbench import manifest
from chipbench.models import brumby_14b_base as bm

RTOL = 1e-4
EPS = 1e-6      # the operator's default, and the configuration's
CONFIG = manifest.load_config(manifest.load_manifest(), "brumby_14b_base")
ARGS = CONFIG["args"]
SMALL = dict(
    ARGS, vocab_size=128, hidden_size=64, intermediate_size=96,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, chunk_size=8, init_sigma=0.1, compute_dtype=None,
    master_dtype=None)


@pytest.fixture
def mesh():
    return parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])


def batch(seq, seed=3, n=2):
    return bm.make_batch(SMALL, {"seq": seq}, n, np.random.default_rng(seed))


def close(got, want, rtol=RTOL):
    scale = np.abs(want).max()
    assert scale > 0
    return np.abs(np.asarray(got) - want).max() <= rtol * scale


# -- the operator --------------------------------------------------------------

def retention_inputs(length, heads, groups, dim, gates, seed=0, bsz=2):
    """q, k of unit mean square, v, and log-gates: ``slow`` remembers a few
    chunks of 8, ``fast`` is the log-sigmoid of a standard normal (what
    seeded weights give: it forgets within a few rows)."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    def unit(t):
        return t / np.sqrt(np.mean(t * t, -1, keepdims=True))

    log_g = -rng.uniform(0.03, 0.5, (bsz, length, groups)) / 8 \
        if gates == "slow" else np.asarray(
            jax.nn.log_sigmoid(normal(bsz, length, groups)))
    return tuple(jnp.asarray(t, jnp.float32) for t in (
        unit(normal(bsz, length, heads, dim)),
        unit(normal(bsz, length, groups, dim)),
        normal(bsz, length, groups, dim), log_g))


def recurrence(q, k, v, log_g, eps=EPS):
    """``S_t = g_t S_{t-1} + phi(k_t) v_t^T``, ``z_t = g_t z_{t-1} +
    phi(k_t)``, ``y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)``, one row at
    a time, ``phi(u)`` the full outer product ``u u^T / sqrt(d)`` (d^2
    entries), so that ``phi(u) . phi(w) = (u . w)^2 / d``."""
    bsz, _, heads, dim = q.shape
    share = heads // k.shape[2]
    k, v, log_g = (jnp.repeat(t, share, axis=2) for t in (k, v, log_g))

    def phi(u):
        return (u[..., :, None] * u[..., None, :]).reshape(
            u.shape[:-1] + (-1,)) / np.sqrt(dim)

    def row(carry, at):
        state, z = carry
        q_t, k_t, v_t, g_t = at
        gate = jnp.exp(g_t)
        state = gate[..., None, None] * state \
            + phi(k_t)[..., :, None] * v_t[..., None, :]
        z = gate[..., None] * z + phi(k_t)
        num = jnp.einsum("bhf,bhfe->bhe", phi(q_t), state)
        den = jnp.sum(phi(q_t) * z, -1)
        return (state, z), num / (den[..., None] + eps)

    start = (jnp.zeros((bsz, heads, dim * dim, v.shape[-1])),
             jnp.zeros((bsz, heads, dim * dim)))
    _, y = lax.scan(row, start, tuple(jnp.moveaxis(t, 1, 0)
                                      for t in (q, k, v, log_g)))
    return jnp.moveaxis(y, 0, 1)


CASES = [
    # length, query heads, key/value heads, head size, chunk, gates
    (24, 4, 2, 8, 8, "slow"),       # whole chunks, G < H
    (24, 4, 2, 8, 8, "fast"),
    (20, 4, 4, 8, 8, "slow"),       # padded, a gate a query head (G = H)
    (20, 6, 2, 16, 8, "fast"),      # three query heads a key/value head
    (16, 4, 2, 8, 64, "slow"),      # one chunk
    (37, 2, 1, 7, 4, "slow"),       # an odd head size, many chunks
]
IDS = ["whole_slow", "whole_fast", "padded_gate_a_head", "share_of_three",
       "one_chunk", "odd_head_size"]


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_operator_is_the_a_ts_form_and_the_recurrence(case):
    length, heads, groups, dim, chunk, gates = case
    args = retention_inputs(length, heads, groups, dim, gates)
    got = retention._power_retention(*args, chunk_size=chunk)
    assert got.shape == args[0].shape[:3] + args[2].shape[3:]
    want = np.asarray(bm.retention_reference(*args, eps=EPS, block=16))
    assert close(got, want)
    # the recurrence squares a product as a sum over the d^2 pairs, so where
    # a row's weights sum to next to nothing (a first row whose q . k nearly
    # cancels) float32 rounding of that sum shows in the quotient: it is
    # held to the form at an eps above that rounding, which also shows that
    # all three forms put eps in the same place
    assert close(recurrence(*args, eps=1e-2),
                 np.asarray(bm.retention_reference(*args, eps=1e-2)))
    assert close(retention._power_retention(*args, chunk_size=chunk,
                                            eps=1e-2),
                 np.asarray(recurrence(*args, eps=1e-2)))


@pytest.mark.parametrize("case", CASES[:4] + CASES[5:],
                         ids=IDS[:4] + IDS[5:])
def test_operator_has_the_gradients_of_the_a_ts_form(case):
    length, heads, groups, dim, chunk, gates = case
    args = retention_inputs(length, heads, groups, dim, gates, seed=1)
    every = tuple(range(4))
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(retention._power_retention(
        *a, chunk_size=chunk))), every)(*args)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(bm.retention_reference(
        *a, eps=EPS, block=16))), every)(*args)
    for name, g, w in zip(("q", "k", "v", "log_g"), got, want):
        assert g.shape == w.shape and close(g, np.asarray(w)), name


def test_the_result_does_not_depend_on_the_chunk_and_pads_change_nothing():
    args = retention_inputs(40, 4, 2, 8, "slow", seed=2)
    want = np.asarray(retention._power_retention(*args, chunk_size=8))
    for chunk in (5, 16, 40, 1024):
        assert close(retention._power_retention(*args, chunk_size=chunk),
                     want)
    # causal: the first 13 rows alone give the first 13 outputs
    first = retention._power_retention(*(t[:, :13] for t in args),
                                       chunk_size=8)
    assert close(first, want[:, :13])


def test_the_second_power_over_pairs_is_the_square_of_the_product():
    rng = np.random.default_rng(0)
    for dim in (8, 7, 128):
        u, w = (jnp.asarray(rng.standard_normal((dim, 5)), jnp.float32)
                for _ in range(2))
        pairs = retention_kernel.pair_features(u, weighted=True)
        assert pairs.shape == ((dim // 2 + 1) * dim, 5)
        assert close(jnp.sum(pairs * retention_kernel.pair_features(w), 0),
                     np.asarray(jnp.sum(u * w, 0) ** 2), 1e-5)
    # 65 x 128 products at the published head size, 64 of them twice
    assert retention_kernel.pair_features(
        jnp.ones((128, 1))).shape == (8320, 1)
    assert retention_kernel.pair_weights(128).sum() * 128 == 128 * 128


def test_a_state_that_is_not_carried_is_caught():
    """The planted fault of the benchmark's comparison: every chunk given to
    the operator as a sequence of its own. On gates that remember it is far
    from the ``a[t, s]`` form; the operator itself is within rounding."""
    carried, zeroed = bm.state_check(SMALL, 24, 5)
    assert carried < RTOL < bm.STATE_LIMIT < 0.1 < zeroed
    q, k, v, log_g = bm.state_check_inputs(SMALL, 24, 5)
    assert q.shape == (1, 24, 4, 8) and k.shape == v.shape == (1, 24, 2, 8)
    assert log_g.max() <= -0.25 / 8 and log_g.min() >= -4 / 8
    # a length of less than two chunks is checked at two
    assert bm.state_check_inputs(SMALL, 5, 5)[0].shape[1] == 16
    # seeded gates (sigmoid of a standard normal) forget within a few rows:
    # there the fault moves only the rows next to a boundary, which is why
    # the logits alone cannot hold the state path
    args = retention_inputs(24, 4, 2, 8, "fast", bsz=1)
    want = np.asarray(bm.retention_reference(*args, eps=EPS))
    alone = np.asarray(bm.operator_outputs(SMALL, *args, carry=False))
    rows = np.abs(alone - want).max((0, 2, 3)) / np.abs(want).max()
    assert rows[:8].max() < RTOL and rows[8:].max() > 10 * RTOL
    assert np.median(rows[8:]) < rows[8:].max() / 4


def test_operator_through_nd_and_sym_and_its_refusals():
    args = retention_inputs(12, 4, 2, 8, "slow")
    want = np.asarray(retention._power_retention(*args, chunk_size=8))
    got = nd.contrib.power_retention(*[nd.array(np.asarray(a)) for a in args],
                                     chunk_size=8)
    assert close(got.asnumpy(), want, 1e-6)
    sym = mx.sym.contrib.power_retention(
        *[mx.sym.var(f"a{i}") for i in range(4)], chunk_size=8)
    assert sym.list_arguments() == ["a0", "a1", "a2", "a3"]
    q, k, v, log_g = args
    with pytest.raises(mx.MXNetError, match="multiple of G"):
        retention._power_retention(q[:, :, :3], k, v, log_g)
    with pytest.raises(mx.MXNetError, match="multiple of G"):
        retention._power_retention(q, k, v, log_g[:, :, :1])
    with pytest.raises(mx.MXNetError, match="multiple of G"):
        retention._power_retention(q[..., :4], k, v, log_g)


def test_bfloat16_operands_accumulate_in_float32():
    args = retention_inputs(24, 4, 2, 8, "slow", seed=4)
    want = np.asarray(bm.retention_reference(*args, eps=EPS))
    low = [t.astype(jnp.bfloat16) for t in args[:3]] + [args[3]]
    got = retention._power_retention(*low, chunk_size=8)
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got.astype(jnp.float32)) - want).max() \
        / np.abs(want).max()
    assert RTOL < err < bm.STATE_LIMIT


# -- rotary --------------------------------------------------------------------

@pytest.mark.parametrize("theta", [1e6, 1e4])
def test_rotary_is_a_rotation_of_complex_pairs(theta):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 9, 3, 16)).astype(np.float32)
    # coordinates i and i + 8 are one complex number, turned by the angle
    # position * theta^(-i/8)
    z = x[..., :8].astype(np.complex128) + 1j * x[..., 8:]
    angles = np.arange(9)[:, None] * theta ** (-np.arange(8) / 8.0)
    turned = z * np.exp(1j * angles)[None, :, None, :]
    want = np.concatenate([turned.real, turned.imag], -1)
    assert close(nn_ops._rotary_embedding(x, theta=theta), want, 1e-6)
    assert close(bm.rotary_reference(jnp.asarray(x), theta), want, 1e-6)
    got = nd.contrib.rotary_embedding(nd.array(x), theta=theta)
    assert close(got.asnumpy(), want, 1e-6)
    # a rotation keeps each pair's length, and a product of two rotated rows
    # depends on their distance alone: rows 0 and 3 against rows 5 and 8
    pairs = np.tile(np.eye(8), (2, 1))
    assert close(got.asnumpy() ** 2 @ pairs, x ** 2 @ pairs, 1e-5)
    same = np.broadcast_to(x[:, :1], x.shape)
    turned = nn_ops._rotary_embedding(same, theta=theta)
    assert close(np.sum(turned[:, 0] * turned[:, 3], -1),
                 np.sum(turned[:, 5] * turned[:, 8], -1), 1e-5)
    with pytest.raises(mx.MXNetError, match="D even"):
        nn_ops._rotary_embedding(x[..., :15])


def test_rotary_keeps_bfloat16_and_computes_in_float32():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 300, 2, 8)),
                    jnp.bfloat16)
    got = nn_ops._rotary_embedding(x, theta=1e6)
    assert got.dtype == jnp.bfloat16
    want = bm.rotary_reference(x.astype(jnp.float32), 1e6)
    # one rounding of the result; positions past 256 are exact (float32)
    assert close(got.astype(jnp.float32), np.asarray(want), 2.0 ** -7)


# -- the whole model against the plain reference -------------------------------

def system_loss_and_grads(net, x, y):
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(net(nd.array(x))[0], nd.array(y)).mean()
    loss.backward()
    return float(loss.asscalar()), bm.reference_params(
        net, read=lambda p: p.grad().asnumpy())


@pytest.mark.parametrize("hybridize", [False, True],
                         ids=["eager", "hybridized"])
@pytest.mark.parametrize("seq", [24, 20], ids=["whole_chunks", "padded"])
def test_logits_loss_and_every_gradient_agree_with_the_reference(
        mesh, hybridize, seq):
    net, _ = bm.build(SMALL, mesh, 3)
    x, y = batch(seq)
    if hybridize:
        net.hybridize()
    # the benchmark's net puts the residual stream out after the logits
    logits, *hidden = net(nd.array(x))
    assert close(logits.asnumpy(), bm.reference_logits(net, x))
    outs, _ = bm.staged_reference(bm.reference_params(net), SMALL, x)
    assert len(hidden) == len(outs) == SMALL["num_hidden_layers"]
    for got, want in zip(hidden, outs):
        assert close(got.asnumpy(), want)
    loss, grads = system_loss_and_grads(net, x, y)
    want_loss, want = bm.reference_loss_and_grads(net, x, y)
    assert loss == pytest.approx(want_loss, rel=RTOL)
    got_leaves, treedef = jax.tree_util.tree_flatten(grads)
    want_leaves, want_treedef = jax.tree_util.tree_flatten(want)
    assert treedef == want_treedef and len(want_leaves) == 3 + 2 * 11
    for path, g, w in zip(jax.tree_util.tree_leaves_with_path(want),
                          got_leaves, want_leaves):
        assert g.shape == w.shape
        assert close(g, w), jax.tree_util.keystr(path[0])


def test_every_parameter_of_the_net_is_in_the_reference(mesh):
    net, _ = bm.build(SMALL, mesh, 3)
    leaves = jax.tree_util.tree_leaves(bm.reference_params(net))
    assert len(leaves) == len(net.collect_params())
    assert sum(leaf.size for leaf in leaves) == sum(
        int(np.prod(p.shape)) for p in net.collect_params().values())
    # no bias anywhere, the gate included: gamma = x W_g
    assert not [name for name in net.collect_params()
                if name.endswith("bias")]
    assert net.layers[0].mixer.g_proj.weight.shape == (2, 64)
    # the embedding and the head are two parameters: untied
    assert net.embed_weight is not net.head_weight
    assert net.embed_weight.shape == net.head_weight.shape == (128, 64)


def test_a_reference_with_another_rotary_or_head_norm_is_another_function(
        mesh):
    """The comparison can see both. A gain that is the same for every
    coordinate of q (or of k) cancels in the quotient, so the head norms'
    weights show only where they differ by coordinate."""
    net, _ = bm.build(SMALL, mesh, 3)
    x, _ = batch(24, n=1)
    params = bm.reference_params(net)
    want = np.asarray(bm._forward(params, SMALL, x))
    assert not close(bm._forward(params, dict(SMALL, rope_theta=1e4), x),
                     want, 1e-3)
    for name, gain, seen in (("q_norm", 1.5, False), ("k_norm", 1.5, False),
                             ("q_norm", [3, 3, 3, 3, 1, 1, 1, 1], True)):
        bent = jax.tree_util.tree_map(np.copy, params)
        for w in bent["layers"]:
            w[name] = w[name] * np.asarray(gain, np.float32)
        assert close(bm._forward(bent, SMALL, x), want, 1e-3) is not seen


# -- through the trainer -------------------------------------------------------

def test_trains_through_sharded_trainer_with_recomputation(mesh):
    args = dict(SMALL, optimizer_params=dict(ARGS["optimizer_params"],
                                             learning_rate=3e-3))
    net, trainer = bm.build(args, mesh, 5)
    assert all(layer._recompute for layer in net.layers)
    x, y = batch(24, seed=5)
    losses = [float(trainer.run_steps(x, y, num_steps=3).asscalar())
              for _ in range(3)]
    assert losses[-1] < losses[0] and trainer.num_update == 9
    trainer.evaluate(x, y)
    # the logits, then what each of the two layers put out
    assert [o.shape for o in trainer.last_outputs] \
        == [(2, 24, 128)] + 2 * [(2, 24, 64)]


def test_recomputed_layers_give_the_same_gradients(mesh):
    x, y = batch(24)
    grads = {}
    for recompute in (False, True):
        net, _ = bm.build(dict(SMALL, recompute=recompute), mesh, 3)
        net.hybridize()
        assert all(layer._recompute is recompute for layer in net.layers)
        _, grads[recompute] = system_loss_and_grads(net, x, y)
    for g, w in zip(*(jax.tree_util.tree_leaves(grads[r])
                      for r in (True, False))):
        assert close(g, w, 1e-5)


def test_bf16_compute_fails_rtol_by_far(mesh):
    from chipbench.runners import train
    args = dict(SMALL, compute_dtype="bfloat16", master_dtype="bfloat16")
    net, trainer = bm.build(args, mesh, 3)
    x, y = batch(24)
    net(nd.array(x[:1]))
    want = bm.reference_logits(net, x)
    trainer.prepare(x[:1])
    check = train.forward_check(
        train.system_logits(trainer, args, x, y, len(want)), want)
    # heads of 8 and a hidden size of 64 average little: read here 1.6e-2
    # to 7.2e-2 over three seeds and two widths of the initialisation. The
    # runner's 0.03 is judged at the published widths, on the chip
    assert 100 * RTOL < check["share"] < 0.15


def test_device_scopes_and_the_counter_name_the_retention(mesh):
    net, trainer = bm.build(SMALL, mesh, 3)
    x, y = batch(16)

    def traced():
        return dict(observability.snapshot()["metrics"].get(
            retention.RETENTION_COUNT_METRIC, {}).get("values", {}))

    before = traced()
    trainer.run_steps(x, y, num_steps=2)
    key = "chunk=8,length=16,path=xla"
    assert traced()[key] - before.get(key, 0) >= 2      # one a layer
    record = [record for name, record
              in observability.device_scopes().items()
              if name.endswith("run_steps(2)")][-1]
    found = set(record["scopes"].values())
    assert {"retention", "retention.scan", "mlp", "norm", "embed", "lm_head",
            "loss", "optimizer"} <= found


# -- the constructor -----------------------------------------------------------

def test_built_from_the_keys_of_a_config_json():
    published = CONFIG["published"]
    net = brumby.brumby(**published)        # shapes only: not allocated
    assert len(net.layers) == 40
    assert all(type(layer.mixer).__name__ == "PowerRetention"
               for layer in net.layers)
    assert net.head_weight.shape == net.embed_weight.shape == (151936, 5120)
    total = sum(int(np.prod(p.shape)) for p in net.collect_params().values())
    assert total == 40 * 330_352_896 + 2 * 151936 * 5120 + 5120
    assert round(total / 1e9, 2) == 14.77       # the row says 14B
    same = brumby.brumby_14b_base()
    assert {n.split("_", 1)[1]: p.shape for n, p in
            same.collect_params().items()} == {
        n.split("_", 1)[1]: p.shape for n, p in net.collect_params().items()}
    four = brumby.brumby_14b_base(num_hidden_layers=4, vocab_size=18992,
                                  chunk_size=256, retention_eps=1e-9)
    assert len(four.layers) == 4 and four.layers[0].mixer._chunk == 256


def test_hidden_states_are_put_out_only_where_asked():
    small = {k: SMALL[k] for k in bm.MODEL_KEYS}
    x, _ = batch(24)
    outs = []
    for asked in (False, True):
        mx.random.seed(5)
        net = brumby.brumby(output_hidden_states=asked, **small)
        net.initialize(mx.init.Normal(0.1))
        net.hybridize()
        outs.append(net(nd.array(x)))
    logits, (with_logits, *hidden) = outs
    assert (logits.asnumpy() == with_logits.asnumpy()).all()
    assert [h.shape for h in hidden] \
        == [(x.shape[0], 24, SMALL["hidden_size"])] \
        * SMALL["num_hidden_layers"]


def test_bad_configurations_are_refused():
    small = {k: SMALL[k] for k in bm.MODEL_KEYS}
    with pytest.raises(mx.MXNetError, match="multiple"):
        brumby.brumby(**dict(small, num_attention_heads=5))
    with pytest.raises(mx.MXNetError, match="silu"):
        brumby.brumby(hidden_act="gelu", **small)
    with pytest.raises(mx.MXNetError, match="untied"):
        brumby.brumby(tie_word_embeddings=True, **small)
    with pytest.raises(mx.MXNetError, match="rope scaling"):
        brumby.brumby(rope_scaling={"type": "yarn"}, **small)
    with pytest.raises(TypeError):
        brumby.brumby(rope_thetta=1e6, **small)


# -- the operator on the kernel tier (pallas/retention.py, interpret mode) ----

# the op with its scan on the tier's kernel in interpret mode (off the TPU the
# tier runs the jax.numpy scan): tests/test_pallas.py's fixtures
from test_pallas import clean_tier, retention_on_the_kernel  # noqa: E402,F401

# (length, query heads, key/value heads, chunk) at the published head size:
# a head is one lane tile, a chunk whole tiles of the a[t, s] form
KERNEL_SHAPES = {"one_chunk": (128, 2, 1, 128),
                 "three_chunks": (384, 3, 1, 128)}
# each of the shapes in both dtypes and under both kinds of gate (a case
# compiles the interpreted kernels anew: 10-20 s)
KERNEL_CASES = [("one_chunk", "slow", "float32"),
                ("one_chunk", "fast", "bfloat16"),
                ("three_chunks", "slow", "float32"),
                ("three_chunks", "slow", "bfloat16"),
                ("three_chunks", "fast", "float32"),
                ("three_chunks", "fast", "bfloat16")]
# float32: the order of summation alone (read: 2e-7 to 9e-6). bfloat16: held
# to the scan's own distance from the float32 form, times KERNEL_ROOM: the
# kernel rounds a cotangent to the compute dtype where it is the operand of a
# product, as every operand of its products; autodiff of the scan multiplies
# the float32 cotangent as it is (read: forward 1.0, gradients up to 1.7)
KERNEL_ROOM = 2.5


def kernel_inputs(shape, gates, dtype, seed=0):
    length, heads, groups, chunk = KERNEL_SHAPES[shape]
    rng = np.random.default_rng(seed)
    q, k, v, log_g = retention_inputs(length, heads, groups, 128, gates,
                                      seed=seed, bsz=1)
    if gates == "slow":     # a row still weighs a quarter three chunks on
        log_g = jnp.asarray(-rng.uniform(0.1, 1.0, log_g.shape) / chunk,
                            jnp.float32)
    return [t.astype(dtype) for t in (q, k, v)] + [log_g], chunk


def rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("shape,gates,dtype", KERNEL_CASES,
                         ids=["-".join(c) for c in KERNEL_CASES])
def test_kernel_is_the_a_ts_form_and_the_scan(shape, gates, dtype,
                                              retention_on_the_kernel):
    """Forward and every gradient (q, k, v, log_g) of the op on the fused
    kernel against the benchmark's plain ``a[t, s]`` form in float32 and
    against the ``jax.numpy`` scan the kernel replaces on a TPU."""
    from mxnet_tpu import pallas
    args, chunk = kernel_inputs(shape, gates, dtype)
    every = tuple(range(4))

    def both(fn, operands):
        def loss(*a):
            y = fn(*a)
            return jnp.sum(jnp.sin(y.astype(jnp.float32))), y
        grads, y = jax.grad(loss, every, has_aux=True)(*operands)
        return (y,) + grads

    def op(*a):
        return retention._power_retention(*a, chunk_size=chunk)

    got = both(op, args)
    assert pallas.tier_provenance()["power_retention"]["pallas"] == 1
    assert got[0].dtype == jnp.dtype(dtype)
    exact = both(lambda *a: bm.retention_reference(*a, eps=EPS, block=128),
                 [a.astype(jnp.float32) for a in args])
    pallas.set_mode("off")              # the same call on the jax.numpy scan
    scan = both(op, args)
    assert pallas.tier_provenance()["power_retention"]["xla"] == 1
    for name, g, s, e, a in zip(("y", "q", "k", "v", "log_g"), got, scan,
                                exact, [args[0]] + args):
        assert g.shape == e.shape and g.dtype == s.dtype, name
        if dtype == "float32":
            assert rel(g, e) <= RTOL and rel(g, s) <= RTOL, name
        else:
            assert rel(g, e) <= KERNEL_ROOM * rel(s, e) + RTOL, name
