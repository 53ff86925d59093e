"""The Nemotron-H decoder (``gluon.model_zoo.nemotron_h``) at a small size
on the CPU: the mixer's ops at this model's shapes (the grouped norm, the
scan at 8 groups and chunk 128), grouped-query attention at 2 key/value
heads of 128, and the whole model against the plain float32 reference of
``chipbench/models/nemotron_3_nano_30b_a3b.py`` at given routes: logits,
loss and every gradient; then the same net through ``ShardedTrainer``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, observability, parallel
from mxnet_tpu.gluon.model_zoo import granite_hybrid, nemotron_h
from mxnet_tpu.ops import moe, ssm

from chipbench import manifest
from chipbench.models import nemotron_3_nano_30b_a3b as nm

RTOL = 1e-4
ARGS = manifest.load_config(manifest.load_manifest(),
                            "nemotron_3_nano_30b_a3b")["args"]
SMALL = dict(
    ARGS, vocab_size=128, hidden_size=64, hybrid_override_pattern="ME*E",
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
    n_groups=2, chunk_size=8, n_routed_experts=4,
    first_expert=2,
    published_counts=dict(ARGS["published_counts"], n_routed_experts=16),
    num_experts_per_tok=3, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, init_sigma=0.1,
    compute_dtype=None, master_dtype=None)


@pytest.fixture
def mesh():
    return parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])


def batch(seq, seed=3, n=2):
    return nm.make_batch(SMALL, {"seq": seq}, n, np.random.default_rng(seed))


def close(got, want, rtol=RTOL):
    scale = np.abs(want).max()
    assert scale > 0
    return np.abs(np.asarray(got) - want).max() <= rtol * scale


# -- the mixer's ops at this model's shapes ------------------------------------

def test_grouped_norm_is_eight_separate_norms():
    rng = np.random.default_rng(0)
    y, z = (rng.standard_normal((2, 5, 64)).astype(np.float32)
            for _ in range(2))
    gamma = rng.standard_normal(64).astype(np.float32)
    gated = y * z / (1 + np.exp(-z))
    want = np.concatenate([
        part / np.sqrt((part ** 2).mean(-1, keepdims=True) + 1e-5)
        for part in np.split(gated, 8, axis=-1)], -1) * gamma
    assert close(ssm._gated_rms_norm(y, z, gamma, eps=1e-5, groups=8), want,
                 1e-6)
    got = nd.contrib.gated_rms_norm(nd.array(y), nd.array(z),
                                    nd.array(gamma), groups=8)
    assert close(got.asnumpy(), want, 1e-6)
    with pytest.raises(mx.MXNetError, match="no multiple"):
        ssm._gated_rms_norm(y, z, gamma, groups=7)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_group_is_to_the_bit_what_the_norm_gave_before(dtype):
    rng = np.random.default_rng(1)
    y, z = (jnp.asarray(rng.standard_normal((2, 5, 64)), dtype)
            for _ in range(2))
    gamma = jnp.asarray(rng.standard_normal(64), jnp.float32)
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    before = (g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                            + 1e-5) * gamma).astype(dtype)
    assert np.array_equal(np.asarray(ssm._gated_rms_norm(y, z, gamma)),
                          np.asarray(before))
    assert np.array_equal(np.asarray(ssm._gated_rms_norm(y, z, gamma,
                                                         groups=1)),
                          np.asarray(before))


def reshaped_norm(y, z, gamma, groups, eps=1e-5):
    """The grouped norm as it was until PR 37: ``g`` viewed ``(..., groups,
    C / groups)``, each group's mean square taken over its last axis."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    by_group = g.reshape(g.shape[:-1] + (groups, -1))
    ms = jnp.mean(jnp.square(by_group), axis=-1, keepdims=True)
    normed = (by_group * lax.rsqrt(ms + eps)).reshape(g.shape)
    return (normed * gamma.astype(jnp.float32)).astype(y.dtype)


def norm_inputs(width, dtype, seed=2):
    rng = np.random.default_rng(seed)
    y, z, cotangent = (jnp.asarray(rng.standard_normal((2, 6, width)), dtype)
                       for _ in range(3))
    gamma = jnp.asarray(rng.standard_normal(width), jnp.float32)
    return y, z, gamma, cotangent


def equations(jaxpr):
    """Every equation of a closed jaxpr, those of nested jaxprs too."""
    for eqn in jaxpr.jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            inners = param if isinstance(param, (list, tuple)) else [param]
            for inner in inners:
                if hasattr(inner, "jaxpr") and hasattr(inner.jaxpr, "eqns"):
                    yield from equations(inner)


@pytest.mark.parametrize("width", [64, 4096])
def test_grouped_products_are_the_reshaped_norm_in_float32(width):
    y, z, gamma, cotangent = norm_inputs(width, jnp.float32)
    assert close(ssm._gated_rms_norm(y, z, gamma, groups=8),
                 np.asarray(reshaped_norm(y, z, gamma, 8)), 1e-6)

    def weighed(norm):
        return lambda *a: jnp.sum(norm(*a) * cotangent)

    got = jax.grad(weighed(lambda *a: ssm._gated_rms_norm(*a, groups=8)),
                   (0, 1, 2))(y, z, gamma)
    want = jax.grad(weighed(lambda *a: reshaped_norm(*a, 8)),
                    (0, 1, 2))(y, z, gamma)
    for g, w in zip(got, want):
        assert close(g, np.asarray(w), 1e-6)


def test_grouped_products_in_bf16_are_within_a_spacing_of_the_reshaped_norm():
    y, z, gamma, _ = norm_inputs(4096, jnp.bfloat16)
    got = np.asarray(ssm._gated_rms_norm(y, z, gamma, groups=8), np.float32)
    want = np.asarray(reshaped_norm(y, z, gamma, 8), np.float32)
    # bf16 keeps 8 significant bits: the spacing of a value in [2^e, 2^e+1)
    spacing = 2.0 ** (np.floor(np.log2(np.abs(want) + 1e-30)) - 7)
    assert (np.abs(got - want) <= spacing).all()
    assert (got != want).mean() < 0.01


def test_grouped_products_view_nothing_by_group_and_run_at_highest():
    """No reshape of an ``L x C`` array, forward or backward, and every
    product at ``Precision.HIGHEST`` (the default rounds ``g^2`` to bf16 on
    a TPU)."""
    y, z, gamma, cotangent = norm_inputs(4096, jnp.bfloat16)
    program = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssm._gated_rms_norm(*a, groups=8) * cotangent),
        (0, 1, 2)))(y, z, gamma)
    found = list(equations(program))
    assert not [e for e in found if e.primitive.name == "reshape"
                and e.outvars[0].aval.size >= y.size]
    products = [e for e in found if e.primitive.name == "dot_general"]
    assert len(products) == 4       # two forward, their two transposes
    for e in products:
        assert all(p == lax.Precision.HIGHEST for p in e.params["precision"])
        assert all(v.aval.dtype == jnp.float32 for v in e.invars)


@pytest.mark.parametrize("groups", [3, 7])
def test_grouped_products_refuse_groups_that_do_not_divide_the_channels(
        groups):
    y, z, gamma, _ = norm_inputs(64, jnp.float32)
    with pytest.raises(mx.MXNetError, match="no multiple"):
        ssm._gated_rms_norm(y, z, gamma, groups=groups)


def test_traced_norm_counts_its_form():
    """``mxnet_tpu_gated_norms_traced_total{groups,channels,form}``: one
    traced call of each form, and nothing for an eager call."""
    def counted():
        return dict(observability.snapshot()["metrics"].get(
            ssm.NORM_COUNT_METRIC, {}).get("values", {}))

    y, z, gamma, _ = norm_inputs(64, jnp.bfloat16)
    before = counted()
    for groups in (1, 8):
        jax.make_jaxpr(lambda *a: ssm._gated_rms_norm(*a, groups=groups))(
            y, z, gamma)
    ssm._gated_rms_norm(y, z, gamma, groups=8)
    after = counted()
    moved = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    assert moved == {"groups=1,channels=64,form=plain": 1,
                     "groups=8,channels=64,form=grouped": 1}


def recurrence(x, dt, a_log, b, c, d, dt_bias):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t +
    D x_t``, one position at a time; a group serves consecutive heads."""
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


def scan_inputs(length, h=16, g=8, p=8, n=16, seed=0):
    rng = np.random.default_rng(seed)
    shapes = [(1, length, h, p), (1, length, h), (h,), (1, length, g, n),
              (1, length, g, n), (h,), (h,)]
    args = [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in shapes]
    args[1] = args[1] - 2.0     # steps of a tenth or so, as dt_bias is drawn
    args[2] = jnp.log(jnp.asarray(rng.uniform(1, 16, h), jnp.float32))
    return args


@pytest.mark.parametrize("length", [256, 300], ids=["whole", "padded"])
def test_scan_at_eight_groups_and_chunk_128_is_the_recurrence(length):
    args = scan_inputs(length)
    got = ssm._mamba2_ssd(*args, chunk_size=128)
    assert got.shape == args[0].shape
    assert close(got, np.asarray(recurrence(*args)))


def test_scan_at_eight_groups_has_the_gradients_of_the_recurrence():
    args = scan_inputs(140, seed=1)
    every = tuple(range(len(args)))
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(
        ssm._mamba2_ssd(*a, chunk_size=128))), every)(*args)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(recurrence(*a))), every)(*args)
    for g, w in zip(got, want):
        assert close(g, np.asarray(w))


def test_attention_at_two_key_value_heads_of_128():
    mx.random.seed(0)
    block = granite_hybrid.GroupedQueryAttention(64, 8, 2, head_dim=128)
    block.initialize(mx.init.Normal(0.1))
    assert block.q_proj.weight.shape == (1024, 64)
    assert block.k_proj.weight.shape == block.v_proj.weight.shape == (256, 64)
    assert block.o_proj.weight.shape == (64, 1024)
    x = np.random.default_rng(0).standard_normal((2, 12, 64)).astype(
        np.float32)
    w = {n: getattr(block, n + "_proj").weight.data().asnumpy() for n in "qkvo"}
    q = (x @ w["q"].T).reshape(2, 12, 8, 128)
    k = np.repeat((x @ w["k"].T).reshape(2, 12, 2, 128), 4, axis=2)
    v = np.repeat((x @ w["v"].T).reshape(2, 12, 2, 128), 4, axis=2)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(128)
    scores = np.where(np.tri(12, dtype=bool), scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", p, v).reshape(2, 12, -1) @ w["o"].T
    assert close(block(nd.array(x)).asnumpy(), want)


# -- the whole model against the plain reference -------------------------------

def system_outputs_loss_and_grads(net, x, y):
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        outs = net(nd.array(x))
        loss = loss_fn(outs[0], nd.array(y)).mean()
    loss.backward()
    return outs, float(loss.asscalar()), nm.reference_params(
        net, read=lambda p: p.grad().asnumpy())


@pytest.mark.parametrize("seq", [24, 20], ids=["whole_chunks", "padded"])
def test_logits_loss_and_every_gradient_agree_with_the_reference(mesh, seq):
    net, _ = nm.build(SMALL, mesh, 3)
    x, y = batch(seq)
    net.hybridize()
    outs, loss, grads = system_outputs_loss_and_grads(net, x, y)
    routes = [o.asnumpy() for o in outs[1:3]]
    assert len(outs) == 6 and routes[0].shape == (2, seq, 3)
    logits, own, scores = nm.forward_at(
        nm.reference_params(net), SMALL, x, routes)
    assert close(outs[0].asnumpy(), logits)
    # in float32 the system chooses what the reference chooses
    assert all((np.sort(r, -1) == np.sort(o, -1)).all()
               for r, o in zip(routes, own))
    # the router's scores are outputs too, and the reference's
    for got, want in zip(outs[3:5], scores):
        assert got.shape == (2, seq, 16) and close(got.asnumpy(), want)
    # the rows computed are the pairs that name experts 2..5
    held = [np.bincount(r.reshape(-1), minlength=16)[2:6] for r in routes]
    assert (outs[-1].asnumpy() == np.stack(held)).all()
    want_loss, want = nm.reference_loss_and_grads(net, x, y, routes)
    assert loss == pytest.approx(want_loss, rel=RTOL)
    got_leaves, treedef = jax.tree_util.tree_flatten(grads)
    want_leaves, want_treedef = jax.tree_util.tree_flatten(want)
    assert treedef == want_treedef and len(want_leaves) == 29
    worst = 0.0
    for path, g, w in zip(jax.tree_util.tree_leaves_with_path(want),
                          got_leaves, want_leaves):
        assert g.shape == w.shape
        assert close(g, w), jax.tree_util.keystr(path[0])
        worst = max(worst, np.abs(g - w).max() / np.abs(w).max())
    assert worst < RTOL


def test_the_reference_at_other_routes_is_another_function(mesh):
    net, _ = nm.build(SMALL, mesh, 3)
    x, _ = batch(8, n=1)
    params = nm.reference_params(net)
    logits, own, _ = nm.forward_at(params, SMALL, x)
    # the eager net is the reference at its own routes
    assert close(net(nd.array(x))[0].asnumpy(), logits)
    # send every token of the first expert layer to experts held here
    other = [np.broadcast_to(np.array([2, 3, 4]), own[0].shape), own[1]]
    assert not close(nm.forward_at(params, SMALL, x, other)[0], logits, 1e-3)


def test_every_float_parameter_of_the_net_is_in_the_reference(mesh):
    net, _ = nm.build(SMALL, mesh, 3)
    leaves = jax.tree_util.tree_leaves(nm.reference_params(net))
    params = net.collect_params()
    # the buffers are not the reference's: each expert layer's bias of the
    # choice (zero and frozen) and its two counters
    buffers = [name for name, p in params.items() if p.grad_req == "null"]
    assert len(buffers) == 3 * 2 and len(leaves) == len(params) - len(buffers)
    assert sum(leaf.size for leaf in leaves) == sum(
        int(np.prod(p.shape)) for name, p in params.items()
        if name not in buffers)


# -- through the trainer -------------------------------------------------------

def test_trains_through_sharded_trainer_with_recomputation(mesh):
    args = dict(SMALL, optimizer_params=dict(ARGS["optimizer_params"],
                                             learning_rate=3e-3))
    net, trainer = nm.build(args, mesh, 5)
    assert all(layer._recompute for layer in net.layers._children.values())
    x, y = batch(24, seed=5)
    losses = [float(trainer.run_steps(x, y, num_steps=3).asscalar())
              for _ in range(3)]
    assert losses[-1] < losses[0] and trainer.num_update == 9
    from mxnet_tpu.gluon.contrib import nn as cnn
    load = {layer: said for layer, said in cnn.expert_load().items()
            if layer.startswith(net.prefix)}
    assert len(load) == 2
    assert all(said["steps"] == 9 and said["first_expert"] == 2
               for said in load.values())
    # evaluate(): the outputs the comparison reads, counters left alone
    trainer.evaluate(x, y)
    shapes = [o.shape for o in trainer.last_outputs]
    assert shapes == [(2, 24, 128), (2, 24, 3), (2, 24, 3), (2, 24, 16),
                      (2, 24, 16), (2, 4)]
    assert {layer: said["steps"] for layer, said in cnn.expert_load().items()
            if layer.startswith(net.prefix)} == dict.fromkeys(load, 9)


def test_recomputed_layers_give_the_same_gradients(mesh):
    x, y = batch(24)
    grads = {}
    for recompute in (False, True):
        net, _ = nm.build(dict(SMALL, recompute=recompute), mesh, 3)
        net.hybridize()
        assert all(layer._recompute is recompute
                   for layer in net.layers._children.values())
        _, loss, grads[recompute] = system_outputs_loss_and_grads(net, x, y)
    for g, w in zip(*(jax.tree_util.tree_leaves(grads[r])
                      for r in (True, False))):
        assert close(g, w, 1e-5)


def test_the_routers_choice_is_kept_across_recomputation(mesh):
    """A layer recomputed in the backward pass must send each token where
    the forward pass sent it: the compiler may round the recomputed scores
    elsewhere than the forward's, and at the cut that is another expert (on
    the chip, bfloat16: a gradient of the experts' weights off by 0.66 of its
    largest entry until the choice was kept; PERF.md sec. 6, PR 32). So the
    choice is a named residual, and the backward pass makes no choice."""
    from mxnet_tpu.base import RECOMPUTE_KEEP
    x, y = batch(16)

    def choices_in_the_gradient(recompute):
        net, _ = nm.build(dict(SMALL, recompute=recompute), mesh, 3)
        net(nd.array(x))
        trainable, aux = net._param_split()
        tr = [p.data()._data for p in trainable]
        ax = [p.data()._data for p in aux]

        def loss(tr):
            outs = gluon.block.functional_apply(
                net, jax.random.key(0), tr, ax, [jnp.asarray(x)],
                training=True)[0]
            return jnp.sum(outs[0])
        text = str(jax.make_jaxpr(jax.grad(loss))(tr))
        return text.count("top_k["), text.count(f"name={RECOMPUTE_KEEP}")

    # two expert layers: one choice each, named, with or without recompute
    assert choices_in_the_gradient(False) == (2, 2)
    assert choices_in_the_gradient(True) == (2, 2)


def test_device_scopes_names_the_expert_layers_instructions(mesh):
    net, trainer = nm.build(SMALL, mesh, 3)
    x, y = batch(16)
    before = observability.snapshot()["metrics"].get(
        moe.MOE_COUNT_METRIC, {}).get("values", {})
    trainer.run_steps(x, y, num_steps=2)
    after = observability.snapshot()["metrics"][moe.MOE_COUNT_METRIC][
        "values"]
    key = ("experts=16,held=4,top_k=3,rows=512,grouped=ragged_dot,"
           "expert=relu2")
    assert after[key] - before.get(key, 0) >= 2
    record = [record for name, record
              in observability.device_scopes().items()
              if name.endswith("run_steps(2)")][-1]
    found = set(record["scopes"].values())
    assert {"moe.router", "moe.dispatch", "moe.experts", "moe.combine",
            "moe.shared", "mamba2.ssd", "mamba2.gate_norm", "attention",
            "embed", "lm_head", "loss", "optimizer"} <= found


# -- the constructor -----------------------------------------------------------

def test_built_from_the_keys_of_a_config_json():
    published = manifest.load_config(
        manifest.load_manifest(), "nemotron_3_nano_30b_a3b")["published"]
    net = nemotron_h.nemotron_h(**published)     # shapes only: not allocated
    assert len(net.layers) == 52
    kinds = [type(layer.mixer).__name__ for layer in net.layers._children.values()]
    assert kinds.count("Mamba2Mixer") == 23 and kinds.count(
        "RoutedExperts") == 23 and kinds.count("GroupedQueryAttention") == 6
    assert net.head_weight.shape == net.embed_weight.shape == (131072, 2688)
    total = sum(int(np.prod(p.shape)) for p in net.collect_params().values()
                if p.grad_req != "null")
    assert round(total / 1e9, 2) == 31.58       # the row says 31.6B
    same = nemotron_h.nemotron_3_nano_30b_a3b()
    assert {n.split("_", 1)[1]: p.shape for n, p in
            same.collect_params().items()} == {
        n.split("_", 1)[1]: p.shape for n, p in net.collect_params().items()}


def test_bad_configurations_are_refused():
    small = {k: SMALL[k] for k in nm.MODEL_KEYS}
    with pytest.raises(mx.MXNetError, match="hybrid_override_pattern"):
        nemotron_h.nemotron_h(n_routed_experts=16, **dict(
            small, hybrid_override_pattern="MXE"))
    # the format's plain feed-forward letter and any activation but relu^2:
    # no configuration built here has them, so neither is taken
    with pytest.raises(mx.MXNetError, match="hybrid_override_pattern"):
        nemotron_h.nemotron_h(n_routed_experts=16, **dict(
            small, hybrid_override_pattern="ME-"))
    with pytest.raises(mx.MXNetError, match="mlp_hidden_act"):
        nemotron_h.nemotron_h(n_routed_experts=16, **dict(
            small, mlp_hidden_act="silu"))
    with pytest.raises(mx.MXNetError, match="num_hidden_layers"):
        nemotron_h.nemotron_h(n_routed_experts=16, num_hidden_layers=7,
                              **small)
    with pytest.raises(TypeError):
        nemotron_h.nemotron_h(n_routed_experts=16, rope_thetta=1e4, **small)
    loss = nemotron_h.FirstOutputLoss(gluon.loss.SoftmaxCrossEntropyLoss())
    assert loss.amp_safe is True
