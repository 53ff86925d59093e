"""The LFM2-MoE decoder (``gluon.model_zoo.lfm2_moe``) at a small size on the
CPU, with seeded random weights: what it brought to the shared code (the
convolution without a bias, the route's epsilon, head norms and rotary in
grouped-query attention, the balancing bias that moves once a step), the gated
short convolution against its three-tap sum, the shares of an expert layer
against the uncut reference layer, and the whole model against the plain
float32 reference of ``chipbench/models/lfm2_24b_a2b.py``: logits, loss and
every gradient at the system's routes; then the same net through
``ShardedTrainer``. Last, the programs that the configurations already in the
benchmark trace through the code this model shares with them, held to what
they were before it."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, observability, ops, parallel
from mxnet_tpu.gluon.block import functional_apply
from mxnet_tpu.gluon.contrib.nn import (ROUTER_ROWS_METRIC, RoutedExperts,
                                        expert_load)
from mxnet_tpu.gluon.model_zoo import granite_hybrid, lfm2_moe
from mxnet_tpu.ops import moe

from chipbench import manifest
from chipbench.models import lfm2_24b_a2b as lm

RTOL = 1e-4
ARGS = manifest.load_config(manifest.load_manifest(), "lfm2_24b_a2b")["args"]
# every published width cut down: 5 layers (one dense conv layer, then attn
# conv conv conv with experts), 4 query and 2 key/value heads of 16, 8
# experts of 16 held from the fifth, 4 a token
SMALL = dict(
    ARGS, vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, layer_types=["conv", "full_attention", "conv",
                                           "conv", "conv"],
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    num_experts=8, first_expert=4, init_sigma=0.1, compute_dtype=None,
    master_dtype=None,
    published_counts=dict(ARGS["published_counts"], num_experts=16),
    optimizer_params=dict(ARGS["optimizer_params"], learning_rate=1e-3))


@pytest.fixture
def mesh():
    return parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])


def batch(seq, seed=3, n=2):
    return lm.make_batch(SMALL, {"seq": seq}, n, np.random.default_rng(seed))


def close(got, want, rtol=RTOL):
    scale = np.abs(want).max()
    assert scale > 0
    return np.abs(np.asarray(got) - want).max() <= rtol * scale


# -- the ops and blocks ----------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_convolution_without_a_bias_is_the_op_with_a_zero_bias(dtype):
    conv = ops.get("_contrib_causal_conv1d").fn
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 10, 6)), dtype)
    w = jnp.asarray(rng.standard_normal((6, 3)), dtype)
    zero = jnp.zeros(6, dtype)
    got = conv(x, w, no_bias=True, act_type="identity")
    want = conv(x, w, zero, act_type="identity")
    assert got.dtype == dtype and (np.asarray(got) == np.asarray(want)).all()

    def loss(*operands, **kw):
        return jnp.sum(jnp.square(conv(*operands, **kw).astype(jnp.float32)))

    for g, h in zip(jax.grad(loss, argnums=(0, 1))(x, w, no_bias=True),
                    jax.grad(loss, argnums=(0, 1))(x, w, zero)):
        assert (np.asarray(g) == np.asarray(h)).all()
    with pytest.raises(mx.MXNetError, match="bias inputs"):
        conv(x, w, zero, no_bias=True)
    # through the NDArray front end, as a block calls it
    out = nd.contrib.causal_conv1d(nd.array(np.asarray(x, np.float32)),
                                   nd.array(np.asarray(w, np.float32)),
                                   no_bias=True, act_type="identity")
    assert out.shape == (2, 10, 6)


def test_the_short_convolution_is_its_three_tap_sum():
    rng = np.random.default_rng(1)
    block = lfm2_moe.ShortConv(16, kernel=3)
    block.initialize(mx.init.Normal(0.2))
    x = rng.standard_normal((2, 9, 16)).astype(np.float32)
    w_in = block.in_proj.weight.data().asnumpy()
    w = block.conv_weight.data().asnumpy()
    w_out = block.out_proj.weight.data().asnumpy()
    assert np.abs(w).max() <= 3 ** -0.5        # torch's Conv1d default
    b, c, xs = np.split(x @ w_in.T, 3, axis=-1)
    u = b * xs
    v = np.zeros_like(u)
    for t in range(9):                  # v[t] = w0 u[t-2] + w1 u[t-1] + w2 u[t]
        for j in range(3):
            if t + j - 2 >= 0:
                v[:, t] += w[:, j] * u[:, t + j - 2]
    want = (c * v) @ w_out.T
    assert close(block(nd.array(x)).asnumpy(), want)
    assert close(np.asarray(lm.reference_short_conv(
        dict(SMALL, hidden_size=16), {"in": jnp.asarray(w_in),
                                      "weight": jnp.asarray(w),
                                      "out": jnp.asarray(w_out)},
        jnp.asarray(x))), want)


def test_the_routes_epsilon_is_the_normalisations():
    route = ops.get("_contrib_moe_route").fn
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    b = jnp.zeros(16, jnp.float32)
    weights, ids, scores = route(x, r, b, top_k=4, norm_eps=1e-6)
    picked = np.take_along_axis(np.asarray(scores), np.asarray(ids), -1)
    assert np.allclose(np.asarray(weights),
                       picked / (picked.sum(-1, keepdims=True) + 1e-6),
                       rtol=1e-6)
    default = route(x, r, b, top_k=4)[0]
    assert np.allclose(np.asarray(default),
                       picked / (picked.sum(-1, keepdims=True) + 1e-20),
                       rtol=1e-6)
    # a sum of four sigmoids is near 2: the epsilon moves a weight by 5e-7
    assert 0 < np.abs(np.asarray(default - weights)).max() < 1e-6


def test_attention_with_head_norms_and_rotary_is_the_references():
    rng = np.random.default_rng(3)
    block = granite_hybrid.GroupedQueryAttention(
        64, 4, 2, qk_norm=True, rope_theta=1e6)
    block.initialize(mx.init.Normal(0.2))
    block.q_norm.gamma.set_data(nd.array(rng.uniform(0.5, 1.5, 16)))
    block.k_norm.gamma.set_data(nd.array(rng.uniform(0.5, 1.5, 16)))
    x = rng.standard_normal((2, 12, 64)).astype(np.float32)
    w = {name: jnp.asarray(getattr(block, name + "_proj").weight.data()
                           .asnumpy()) for name in "qkvo"}
    w["q_norm"] = jnp.asarray(block.q_norm.gamma.data().asnumpy())
    w["k_norm"] = jnp.asarray(block.k_norm.gamma.data().asnumpy())
    want = np.asarray(lm.reference_attention(SMALL, w, jnp.asarray(x)))
    assert close(block(nd.array(x)).asnumpy(), want)
    # without the norms or the rotary it is another function
    plain = granite_hybrid.GroupedQueryAttention(64, 4, 2)
    plain.initialize()
    for name in "qkvo":
        getattr(plain, name + "_proj").weight.set_data(
            getattr(block, name + "_proj").weight.data())
    assert not close(plain(nd.array(x)).asnumpy(), want, 1e-2)


# -- the balancing bias ------------------------------------------------------------

class _Wrapped(gluon.HybridBlock):
    """An expert layer in a recomputed block of its own, as a decoder layer
    holds it."""

    def __init__(self, experts, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.experts = experts

    def hybrid_forward(self, F, x):
        return self.experts(x)


class _Plain(gluon.HybridBlock):
    def hybrid_forward(self, F, outputs, label):
        return F.mean(F.square(outputs), axis=(1, 2))


def planted(bias, tokens, top, rate, steps):
    """The bias after ``steps`` steps of the rule when every score is the
    same: each step every token chooses the ``top`` largest biases (the lower
    index first among equals), so their counts are ``tokens`` and the others'
    0. Returns the bias and the counts summed."""
    bias = np.asarray(bias, np.float32)
    rows = np.zeros(bias.shape, np.int64)
    for _ in range(steps):
        chosen = np.argsort(-bias, kind="stable")[:top]
        counts = np.zeros(bias.shape, np.int64)
        counts[chosen] = tokens
        rows += counts
        sign = np.sign(counts.mean() - counts).astype(np.float32)
        bias = bias + np.float32(rate) * sign
    return bias, rows


def test_the_bias_moves_by_the_rule_once_a_step_under_recompute(mesh):
    """A zero router scores every expert 0.5, so the choice is the bias's
    alone and each step's counts are planted by it; a learning rate of 0
    keeps the router at zero. Over six steps of two compiled programs, with
    the layer recomputed in the backward pass, the bias is the rule applied
    once a step, and the counter holds the counts."""
    start = np.array([0.030, 0.012, 0.029, -0.004, 0.011, 0.0, 0.031, 0.010],
                     np.float32)
    experts = RoutedExperts(8, 4, 8, k=2, first_expert=2, experts_held=4,
                            gated=True, norm_eps=1e-6, bias_update_rate=0.01,
                            prefix="planted_")
    net = _Wrapped(experts).recompute(True)
    net.initialize(mx.init.Normal(0.1))
    net(nd.zeros((1, 3, 8)))
    experts.router_weight.set_data(nd.zeros((8, 8)))
    experts.router_bias.set_data(nd.array(start))
    trainer = parallel.ShardedTrainer(net, _Plain(), "sgd",
                                      {"learning_rate": 0.0}, mesh=mesh)
    x = np.random.default_rng(4).standard_normal((2, 3, 8)).astype(np.float32)
    y = np.zeros((2, 3, 8), np.float32)
    trainer.run_steps(x, y, num_steps=4).asscalar()
    trainer.run_steps(x, y, num_steps=2).asscalar()
    want, rows = planted(start, 6, 2, 0.01, 6)
    assert np.array_equal(experts.router_bias.data().asnumpy(), want)
    said = expert_load()[experts.prefix.rstrip("_")]
    assert said["router_rows"] == rows.tolist() and said["steps"] == 6
    # twice a step would have moved it otherwise
    assert not np.array_equal(want, planted(start, 6, 2, 0.005, 12)[0])
    gauge = observability.snapshot()["metrics"][ROUTER_ROWS_METRIC]["values"]
    layer = experts.prefix.rstrip("_")
    assert gauge[f"layer={layer},expert=0"] == rows[0]
    # a frozen bias has no counter and stays where it was
    frozen = RoutedExperts(8, 4, 8, k=2, gated=True)
    assert frozen.router_rows is None
    assert not any("router_rows" in name for name in frozen.collect_params())


def test_the_counts_are_of_all_the_experts_and_the_step_is_the_rule():
    from mxnet_tpu.gluon.contrib.nn import balance_counts, moved_bias
    routes = jnp.asarray([[0, 5], [5, 7], [3 - 8, 5]], jnp.int32)
    counts = balance_counts(routes, 8)
    assert np.asarray(counts).tolist() == [1, 0, 0, 1, 0, 3, 0, 1]
    bias = moved_bias(jnp.zeros(8, jnp.float32), counts, 0.5)
    # the mean is 6 / 8: experts above it go down, the others up
    assert np.asarray(bias).tolist() == [-0.5, 0.5, 0.5, -0.5, 0.5, -0.5,
                                         0.5, -0.5]


# -- the shares of an expert layer add up to the uncut layer ----------------------

def test_eight_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of two experts each (the deployment's 8 x 8 of 64, in
    small), with a bias that changes the choice: summed, they give what the
    uncut reference layer gives."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((2, 12, 64)).astype(np.float32)
    make = dict(units=64, hidden_size=32, num_experts=16, k=4,
                norm_eps=1e-6, gated=True)
    uncut = RoutedExperts(**make)
    uncut.initialize(mx.init.Normal(0.1))
    router = uncut.router_weight.data().asnumpy()
    bias = rng.uniform(-0.2, 0.2, 16).astype(np.float32)
    w1, w2 = uncut.expert_w1.data().asnumpy(), uncut.expert_w2.data().asnumpy()
    parts = []
    for first in range(0, 16, 2):
        share = RoutedExperts(first_expert=first, experts_held=2, **make)
        share.initialize()
        share.router_weight.set_data(nd.array(router))
        share.router_bias.set_data(nd.array(bias))
        share.expert_w1.set_data(nd.array(w1[first:first + 2]))
        share.expert_w2.set_data(nd.array(w2[first:first + 2]))
        parts.append(share(nd.array(x)).asnumpy())
    uncut_cfg = dict(SMALL, first_expert=0, num_experts=16)
    w = {"router": jnp.asarray(router), "w1": jnp.asarray(w1),
         "w2": jnp.asarray(w2), "bias": jnp.asarray(bias)}
    want, _ = lm.reference_experts(uncut_cfg, w, x)
    assert close(sum(parts), np.asarray(want))
    # the bias changed some token's choice: without it the sum is another
    unbiased, _ = lm.reference_experts(uncut_cfg, dict(w, bias=0.0), x)
    assert not close(np.asarray(unbiased), np.asarray(want), 1e-3)


# -- the whole model against the plain reference -------------------------------

def system_outputs_loss_and_grads(net, x, y):
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        outs = net(nd.array(x))
        loss = loss_fn(outs[0], nd.array(y)).mean()
    loss.backward()
    return outs, float(loss.asscalar()), lm.reference_params(
        net, read=lambda p: p.grad().asnumpy())


def test_logits_loss_and_every_gradient_agree_with_the_reference(mesh):
    net, _ = lm.build(SMALL, mesh, 3)
    x, y = batch(20)
    net.hybridize()
    # a recorded forward trains: the step's choice is made with the bias as
    # it was, which moves after it
    before = lm.reference_params(net)
    outs, loss, grads = system_outputs_loss_and_grads(net, x, y)
    routes = [o.asnumpy() for o in outs[1:5]]
    assert len(outs) == 10 and routes[0].shape == (2, 20, 4)
    assert not all((a == b).all() for a, b in zip(
        lm.biases(before), lm.biases(lm.reference_params(net))))
    logits, own, scores = lm.forward_at(before, SMALL, x, routes)
    assert close(outs[0].asnumpy(), logits)
    # in float32 the system chooses what the reference chooses
    assert all((r == o).all() for r, o in zip(routes, own))
    for got, want in zip(outs[5:9], scores):
        assert got.shape == (2, 20, 16) and close(got.asnumpy(), want)
    # the rows computed are the pairs that name experts 4..11
    held = [np.bincount(r.reshape(-1), minlength=16)[4:12] for r in routes]
    assert (outs[-1].asnumpy() == np.stack(held)).all()
    want_loss, want = lm.reference_loss_and_grads(net, x, y, routes)
    assert loss == pytest.approx(want_loss, rel=RTOL)
    got_leaves, treedef = jax.tree_util.tree_flatten(grads)
    want_leaves, want_treedef = jax.tree_util.tree_flatten(want)
    # embedding, final norm; per layer two norms, the operator's weights
    # (conv 3, attention 6) and the MLP's (dense 2, experts 3)
    assert treedef == want_treedef \
        and len(want_leaves) == 2 + 5 * 2 + 4 * 3 + 6 + 2 + 4 * 3
    for path, g, w in zip(jax.tree_util.tree_leaves_with_path(want),
                          got_leaves, want_leaves):
        assert g.shape == w.shape
        assert close(g, w), jax.tree_util.keystr(path[0])


def test_the_trainers_loss_is_the_references(mesh):
    """The loss ``ShardedTrainer`` reads from its compiled step (the layers
    recomputed, the bias moving after the step) is the reference's at the
    routes the net chooses before it."""
    net, trainer = lm.build(SMALL, mesh, 3)
    x, y = batch(20)
    routes = [o.asnumpy() for o in net(nd.array(x))[1:5]]
    want = lm.reference_loss_and_grads(net, x, y, routes)[0]
    assert float(trainer.step(x, y).asscalar()) == pytest.approx(want,
                                                                 rel=RTOL)


def test_the_reference_follows_the_bias_and_the_routes(mesh):
    net, _ = lm.build(SMALL, mesh, 3)
    x, _ = batch(8, n=1)
    params = lm.reference_params(net)
    logits, own, scores = lm.forward_at(params, SMALL, x)
    assert close(net(nd.array(x))[0].asnumpy(), logits)
    other = [np.broadcast_to(np.array([4, 5, 6, 7]), own[0].shape)] + own[1:]
    assert not close(lm.forward_at(params, SMALL, x, other)[0], logits, 1e-3)
    # a bias that favours experts 0..3 moves every token of the first layer
    # there, in the reference and in the system alike
    block = list(net.layers._children.values())[1].mlp
    block.router_bias.set_data(nd.array(np.r_[np.ones(4), np.zeros(12)]))
    params = lm.reference_params(net)
    logits, own, _ = lm.forward_at(params, SMALL, x)
    assert (np.sort(own[0], -1) == np.arange(4)).all()
    outs = net(nd.array(x))
    assert (np.sort(outs[1].asnumpy(), -1) == np.arange(4)).all()
    assert close(outs[0].asnumpy(), logits)


def test_every_float_parameter_of_the_net_is_in_the_reference(mesh):
    net, _ = lm.build(SMALL, mesh, 3)
    leaves = jax.tree_util.tree_leaves(lm.reference_params(
        net, read=lambda p: p.data().asnumpy()))
    params = net.collect_params()
    # the buffers: each expert layer's bias and three counters
    buffers = [name for name, p in params.items() if p.grad_req == "null"]
    assert len(buffers) == 4 * 4 and len(leaves) == len(params) - len(buffers)
    assert sum(leaf.size for leaf in leaves) == sum(
        lm.parameter_counts(SMALL).values())


def test_the_model_refuses_what_it_does_not_implement():
    keys = {key: SMALL[key] for key in lm.MODEL_KEYS}
    for bad, match in (({"conv_bias": True}, "without bias"),
                       ({"rope_parameters": {"rope_theta": 1e6,
                                             "rope_type": "yarn"}},
                        "default rotary"),
                       ({"layer_types": ["conv", "sliding_attention"],
                         "num_hidden_layers": 2}, "kinds among"),
                       ({"num_hidden_layers": 4}, "length of layer_types")):
        with pytest.raises(mx.MXNetError, match=match):
            lfm2_moe.lfm2_moe(num_experts=16, **dict(
                keys, num_hidden_layers=5) | bad)
    # the published keys, all of them, build (shapes only: nothing is drawn)
    net = lfm2_moe.lfm2_24b_a2b(
        layer_types=lfm2_moe.LFM2_24B_A2B["layer_types"][1:4],
        num_dense_layers=1)
    assert len(net.layers) == 3
    assert [i for i, kind in enumerate(lfm2_moe.LFM2_24B_A2B["layer_types"])
            if kind == "full_attention"] == list(range(2, 40, 4))


# -- through the trainer -------------------------------------------------------

def test_trains_through_sharded_trainer_with_recomputation(mesh):
    net, trainer = lm.build(SMALL, mesh, 3)
    x, y = batch(16)
    losses = [float(trainer.run_steps(x, y, num_steps=4).asscalar())
              for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert trainer.skipped_steps == 0
    record = [record for name, record
              in observability.device_scopes().items()
              if name.endswith("run_steps(4)")][-1]
    found = set(record["scopes"].values())
    assert {"shortconv", "shortconv.conv", "attention", "mlp", "moe.router",
            "moe.experts", "lm_head"} <= found
    traced = observability.snapshot()["metrics"][moe.MOE_COUNT_METRIC][
        "values"]
    assert any(key.startswith("experts=16,held=8,top_k=4,")
               and key.endswith("expert=swiglu") for key in traced)
    # every expert layer counted its pairs over all 16 experts, 12 steps of
    # 32 tokens, and its bias moved by whole steps of the rate
    load = {layer: said for layer, said in expert_load().items()
            if layer.startswith(net.prefix)}
    assert len(load) == 4
    assert all(said["steps"] == 12
               and sum(said["router_rows"]) == 12 * 32 * 4
               for said in load.values())
    for block in (layer.mlp for layer in net.layers._children.values()
                  if hasattr(layer.mlp, "router_bias")):
        steps = block.router_bias.data().asnumpy() / 1e-3
        assert np.allclose(steps, np.round(steps), atol=1e-3)
        assert np.abs(steps).max() > 0


# -- the programs of the cells already in the benchmark ------------------------
#
# The jaxpr, forward and backward, of what the configurations measured before
# this model came trace through the code it shares with them: Nemotron's and
# DeepSeek-V2's expert ops and RoutedExperts blocks in training mode (the bias
# frozen: no counter, no update), Granite's convolution with its bias and its
# attention, Nemotron's attention, Brumby's rotary and the Mamba-2 mixer. The
# digests are those the code gave before the convolution's ``no_bias``, the
# route's ``norm_eps``, attention's ``qk_norm`` / ``rope_theta`` and the
# bias's update were added, so the programs of those cells are unchanged to
# the instruction. A change that alters one of these programs on purpose
# replaces its digest and says so.

BEFORE = {
    "moe_nemotron":
        "43c806a461f97e0f5ab0fe0581f6c9f15bb3e8c9996a4893b18c432c6de97831",
    "moe_deepseek":
        "50b10fb0ca6349b4f3e1012bfc206b2c21b66dca303b38d0d6a3e14213d72cab",
    "causal_conv1d":
        "b1d59a51478c8c77ecb41f157ea9f39b37b40ef04314eb47809deb7c175db4a8",
    "rotary":
        "a66680fe8c8748e1bad61b8102a9a6fcf4a47eccc82cfd45c7d0f3e4bda5122c",
    "gqa_granite":
        "2bbf1d20e70ecc75b15c74cc7d7c239ba315c700dad9db395422531f0185bebd",
    "gqa_nemotron":
        "217e1ba76a2c683a23bf0ce529c62b569addcc4c63b12b35fbd2f65f5a10b0a6",
    "routed_nemotron":
        "deb52608534ab0261e082aac41be90c2be731924cefac1601eb0aa1991a6c0ae",
    "routed_deepseek":
        "edb08f8e1e3c2713cfea72b0016cdad3be5f339c8245aa9226e48cfbb028e33b",
    "mamba2":
        "fa137638efdcf4ad835dac48c12a065769a98ba931e42aec90b07190aa07c33b",
}


def digest(fn, *args):
    """The jaxpr's text without the addresses of Python objects."""
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()


def sq(t):
    return jnp.sum(jnp.square(t.astype(jnp.float32)))


def op_programs():
    rng = np.random.default_rng(0)

    def a(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    route_op = ops.get("_contrib_moe_route").fn
    experts = ops.get("_contrib_moe_experts").fn
    conv = ops.get("_contrib_causal_conv1d").fn
    rot = ops.get("_contrib_rotary_embedding").fn
    x, r, b = a((24, 32)), a((16, 32)), jnp.zeros(16, jnp.float32)

    def nemotron(x, r, w1, w2):
        w, ids, s = route_op(x, r, b, top_k=3, norm_topk_prob=True,
                             scaling_factor=2.5)
        y, _ = experts(x, w, ids, w1, w2, first_expert=4, num_experts=16)
        return sq(y) + jnp.sum(s) + jnp.sum(w)

    yield "moe_nemotron", jax.grad(nemotron, argnums=(0, 1, 2, 3)), \
        (x, r, a((8, 32, 24)), a((8, 24, 32)))

    def deepseek(x, r, w1, w2):
        w, ids, s = route_op(x, r, b, top_k=3, norm_topk_prob=False,
                             scaling_factor=16.0, scoring="softmax",
                             n_group=4, topk_group=2, capacity_factor=1.0,
                             first_expert=4, experts_held=8)
        y, _ = experts(x, w, ids, w1, w2, first_expert=4, num_experts=16,
                       gated=True, capacity=36)
        return sq(y) + jnp.sum(s) + jnp.sum(w)

    yield "moe_deepseek", jax.grad(deepseek, argnums=(0, 1, 2, 3)), \
        (x, r, a((8, 32, 48)), a((8, 24, 32)))

    def granite_conv(t, w, bias):
        return sq(conv(t, w, bias, act_type="silu"))

    yield "causal_conv1d", jax.grad(granite_conv, argnums=(0, 1, 2)), \
        (a((2, 12, 16)), a((16, 4)), a((16,)))
    yield "rotary", jax.grad(lambda t: sq(rot(t, theta=1e6))), \
        (a((1, 16, 4, 8)),)


def block_programs():
    x = np.random.default_rng(1).standard_normal((2, 16, 32)).astype(
        np.float32)
    yield "gqa_granite", lambda: granite_hybrid.GroupedQueryAttention(
        32, 4, 2, sm_scale=0.25), x
    yield "gqa_nemotron", lambda: granite_hybrid.GroupedQueryAttention(
        32, 4, 2, head_dim=16), x
    yield "routed_nemotron", lambda: RoutedExperts(
        32, 16, 16, k=3, first_expert=4, experts_held=8,
        scaling_factor=2.5, shared_hidden_size=24), x
    yield "routed_deepseek", lambda: RoutedExperts(
        32, 16, 16, k=3, first_expert=4, experts_held=8,
        norm_topk_prob=False, scaling_factor=16.0, shared_hidden_size=32,
        scoring="softmax", n_group=4, topk_group=2, gated=True,
        capacity_factor=1.0), x
    yield "mamba2", lambda: granite_hybrid.Mamba2Mixer(
        32, 4, 8, 8, n_groups=2, conv_kernel=4, chunk_size=8), x


def block_digest(block, x):
    """The jaxpr of a training-mode forward and its gradient through the
    trainer's functional form, the new auxiliary state included."""
    mx.random.seed(0)
    block.initialize(mx.init.Normal(0.1))
    block(mx.nd.array(x))
    tr, aux = block._param_split()
    tr_data = [p.data()._data for p in tr]
    aux_data = [p.data()._data for p in aux]
    key = jax.random.key(0)

    def loss(tr_data, x):
        outs, _, aux_new = functional_apply(block, key, tr_data, aux_data,
                                            [x], training=True)
        return sq(outs[0]) + sum(jnp.sum(a.astype(jnp.float32))
                                 for a in aux_new)
    return digest(jax.grad(loss, argnums=(0, 1)), tr_data,
                  jnp.asarray(x, jnp.bfloat16))


@pytest.mark.parametrize("name", ["moe_nemotron", "moe_deepseek",
                                  "causal_conv1d", "rotary"])
def test_the_shared_ops_trace_what_they_traced_before(name):
    fn, args = {n: (f, a) for n, f, a in op_programs()}[name]
    assert digest(fn, *args) == BEFORE[name]


@pytest.mark.parametrize("name", ["gqa_granite", "gqa_nemotron",
                                  "routed_nemotron", "routed_deepseek",
                                  "mamba2"])
def test_the_shared_blocks_trace_what_they_traced_before(name):
    make, x = {n: (m, x) for n, m, x in block_programs()}[name]
    assert block_digest(make(), x) == BEFORE[name]
