"""The routed-expert layer that holds a share of the experts
(``ops/moe.py``, ``gluon.contrib.nn.RoutedExperts``) against a loop over the
experts in numpy: forward and gradients, the shares adding up to the uncut
layer, nothing dropped, the counters it carries through a compiled step, and
the kernel tier's grouped product behind it."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, observability, pallas, parallel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.contrib import nn as cnn
from mxnet_tpu.ops import moe

# the package's ``kernels`` attribute is the registry's function
tier_kernels = importlib.import_module("mxnet_tpu.pallas.kernels")

U, F, E, K, SHARED = 32, 24, 16, 3, 40


def make(first=0, held=None, seed=0, sigma=0.3, **kwargs):
    mx.random.seed(seed)
    block = cnn.RoutedExperts(U, F, E, k=K, first_expert=first,
                              experts_held=held, scaling_factor=2.5,
                              shared_hidden_size=SHARED, **kwargs)
    block.initialize(mx.init.Normal(sigma))
    return block


def tokens(seed=1, shape=(2, 20, U)):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


def weights_of(block):
    return {name: getattr(block, name).data().asnumpy().astype(np.float64)
            for name in ("router_weight", "router_bias", "expert_w1",
                         "expert_w2")} | {
        "shared_in": block.shared.w_in.weight.data().asnumpy().astype(
            np.float64),
        "shared_out": block.shared.w_out.weight.data().asnumpy().astype(
            np.float64)}


def loop_over_experts(w, x, first, held, routes=None, shared=True):
    """The layer in numpy, one expert after another; ``routes`` given or
    chosen here. Returns ``(y, routes)``."""
    flat = x.reshape(-1, x.shape[-1]).astype(np.float64)
    scores = 1.0 / (1.0 + np.exp(-(flat @ w["router_weight"].T)))
    if routes is None:
        routes = np.argsort(-(scores + w["router_bias"]), axis=-1,
                            kind="stable")[:, :K]
    routes = routes.reshape(-1, K)
    picked = np.take_along_axis(scores, routes, -1)
    gates = 2.5 * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    y = np.zeros_like(flat)
    for e in range(first, first + held):
        for t, j in zip(*np.nonzero(routes == e)):
            hidden = np.maximum(flat[t] @ w["expert_w1"][e - first], 0) ** 2
            y[t] += gates[t, j] * (hidden @ w["expert_w2"][e - first])
    if shared:
        y += (np.maximum(flat @ w["shared_in"].T, 0) ** 2) \
            @ w["shared_out"].T
    return y.reshape(x.shape), routes.reshape(x.shape[:-1] + (K,))


def close(got, want, rtol):
    scale = np.abs(want).max()
    assert scale > 0
    return np.abs(np.asarray(got, np.float64) - want).max() <= rtol * scale


# -- against the loop ---------------------------------------------------------

@pytest.mark.parametrize("first, held", [(0, 16), (0, 4), (6, 5)],
                         ids=["all", "first_four", "middle_five"])
def test_forward_is_the_loop_over_the_experts_held(first, held):
    block = make(first, held, return_routes=True)
    x = tokens()
    y, routes, rows, _ = block(nd.array(x))
    want, own = loop_over_experts(weights_of(block), x, first, held)
    assert (routes.asnumpy() == own).all() and routes.dtype == np.int32
    assert close(y.asnumpy(), want, 1e-5)
    local = own.reshape(-1) - first
    assert (rows.asnumpy() == np.bincount(
        local[(local >= 0) & (local < held)], minlength=held)).all()


def layer_as_function(block, x, routes):
    """``f(params) -> y`` of the two ops at given routes, for jax.grad, and
    the parameters it takes."""
    from mxnet_tpu.ops import registry
    first, _ = block.experts_held
    experts = registry.get("_contrib_moe_experts").fn
    names = ("router_weight", "expert_w1", "expert_w2")
    params = {n: getattr(block, n).data()._data for n in names}

    def f(params, x, dtype=jnp.float32):
        x = x.astype(dtype)
        scores = jax.nn.sigmoid(x.astype(jnp.float32)
                                @ params["router_weight"].T)
        picked = jnp.take_along_axis(scores, routes, -1)
        gates = 2.5 * picked / (picked.sum(-1, keepdims=True) + 1e-20)
        return experts(x, gates, routes, params["expert_w1"].astype(dtype),
                       params["expert_w2"].astype(dtype),
                       first_expert=first)[0]
    return f, params


def loop_as_function(first, held, routes):
    def f(params, x):
        scores = jax.nn.sigmoid(x @ params["router_weight"].T)
        picked = jnp.take_along_axis(scores, routes, -1)
        gates = 2.5 * picked / (picked.sum(-1, keepdims=True) + 1e-20)
        y = jnp.zeros_like(x)
        for e in range(first, first + held):
            gate = jnp.sum(jnp.where(routes == e, gates, 0.0), -1)
            hidden = jnp.square(jax.nn.relu(
                x @ params["expert_w1"][e - first]))
            y = y + gate[..., None] * (hidden @ params["expert_w2"][e - first])
        return y
    return f


@pytest.mark.parametrize("dtype, rtol", [(jnp.float32, 1e-5),
                                         (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_gradients_at_given_routes_are_the_loops(dtype, rtol):
    block = make(4, 8)
    x = jnp.asarray(tokens(shape=(40, U)))
    routes = jnp.asarray(np.random.default_rng(5).permuted(
        np.tile(np.arange(E), (40, 1)), axis=1)[:, :K].astype(np.int32))
    f, params = layer_as_function(block, x, routes)
    seed = jnp.asarray(tokens(7, (40, U)))

    def loss(fn, *more):
        return lambda p, x: jnp.sum(
            fn(p, x, *more).astype(jnp.float32) * seed)
    got_y = f(params, x, dtype)
    want_y = loop_as_function(4, 8, routes)(params, x)
    assert close(got_y.astype(jnp.float32), np.asarray(want_y, np.float64),
                 rtol)
    got = jax.grad(loss(f, dtype), argnums=(0, 1))(params, x)
    want = jax.grad(loss(loop_as_function(4, 8, routes)),
                    argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape
        assert close(g, np.asarray(w, np.float64), rtol)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    whole = make(0, 16)
    x = tokens()
    w = weights_of(whole)
    want, _ = loop_over_experts(w, x, 0, 16)
    parts = np.zeros_like(want)
    for share in range(8):
        part = make(2 * share, 2)
        for name in ("router_weight", "router_bias"):
            getattr(part, name).set_data(getattr(whole, name).data())
        for name in ("expert_w1", "expert_w2"):
            getattr(part, name).set_data(
                getattr(whole, name).data()[2 * share:2 * share + 2])
        part.shared.w_in.weight.set_data(whole.shared.w_in.weight.data())
        part.shared.w_out.weight.set_data(whole.shared.w_out.weight.data())
        parts += part(nd.array(x)).asnumpy()
    # what every chip computes alike, the shared expert, counted once
    shared = (np.maximum(x.reshape(-1, U) @ w["shared_in"].T, 0) ** 2) \
        @ w["shared_out"].T
    assert close(parts - 7 * shared.reshape(x.shape), want, 1e-5)
    assert close(whole(nd.array(x)).asnumpy(), want, 1e-5)


def test_nothing_is_dropped_when_every_token_chooses_the_same_experts():
    block = make(0, 4, return_routes=True)
    # a router whose first three rows beat all others for every token: the
    # worst case, every pair of every token lands here and fills the buffer
    router = np.full((E, U), -1.0, np.float32)
    router[:K] = 1.0
    block.router_weight.set_data(nd.array(router))
    x = np.abs(tokens(shape=(2, 300, U)))
    y, routes, rows, _ = block(nd.array(x))
    assert (np.sort(routes.asnumpy(), -1) == np.arange(K)).all()
    assert rows.asnumpy().tolist() == [600, 600, 600, 0]
    small, full = moe.buffer_rows(600 * K)
    assert small < 600 * K <= full      # the small buffer cannot hold them
    want, _ = loop_over_experts(weights_of(block), x, 0, 4)
    assert close(y.asnumpy(), want, 1e-5)


def test_a_token_with_no_held_expert_gets_the_shared_expert_alone():
    block = make(12, 4, return_routes=True)
    router = np.zeros((E, U), np.float32)
    router[:K] = 1.0                    # every token chooses experts 0, 1, 2
    block.router_weight.set_data(nd.array(router))
    x = np.abs(tokens())
    y, routes, rows, _ = block(nd.array(x))
    assert routes.asnumpy().max() < 12 and rows.asnumpy().sum() == 0
    assert close(y.asnumpy(), block.shared(nd.array(x)).asnumpy(), 1e-6)


def test_both_sizes_of_the_buffer_give_the_same_result():
    block = make(0, 4)
    x = jnp.asarray(tokens(shape=(200, U)))
    w = weights_of(block)
    _, routes = loop_over_experts(w, np.asarray(x), 0, 4)
    routes = jnp.asarray(routes.astype(np.int32))
    key = jnp.where(routes.reshape(-1) < 4, routes.reshape(-1), 4)
    sizes = jnp.bincount(key, length=5)[:4].astype(jnp.int32)
    gates = jnp.ones(routes.shape, jnp.float32)
    w1, w2 = block.expert_w1.data()._data, block.expert_w2.data()._data
    small, full = moe.buffer_rows(200 * K)
    assert int(sizes.sum()) <= small < full
    order, at = moe.pair_order(key)
    assert (np.asarray(key)[np.asarray(order)] == np.sort(key)).all()
    assert (np.asarray(order)[np.asarray(at)] == np.arange(600)).all()
    outs = [moe._held_experts(x, gates, order, at, sizes, w1, w2, rows=rows)
            for rows in (small, full)]
    assert np.allclose(outs[0][0], outs[1][0], rtol=1e-6, atol=1e-6)
    assert (np.asarray(outs[0][1]) == np.asarray(sizes)).all()
    # a buffer too small would show in the rows computed
    short = moe._held_experts(x, gates, order, at, sizes, w1, w2, rows=8)[1]
    assert int(short.sum()) == 8 < int(sizes.sum())


def test_the_bias_chooses_and_does_not_weigh():
    block = make(0, 16, return_routes=True)
    x = tokens()
    _, before, _, _ = block(nd.array(x))
    bias = np.zeros(E, np.float32)
    bias[5] = 10.0
    block.router_bias.set_data(nd.array(bias))
    y, after, _, scores = block(nd.array(x))
    assert (after.asnumpy() == 5).any(-1).all()
    assert not (before.asnumpy() == 5).any(-1).all()
    want, own = loop_over_experts(weights_of(block), x, 0, 16)
    assert (after.asnumpy() == own).all() and close(y.asnumpy(), want, 1e-5)
    # the scores the block returns are without the bias
    assert scores.shape == (2, 20, E) and scores.asnumpy().max() < 1.0
    assert block.router_bias.grad_req == "null"


@pytest.mark.parametrize("norm, scaling, shared",
                         [(False, 1.0, SHARED), (True, 2.5, 0),
                          (False, 0.5, 0)],
                         ids=["unnormalised", "no_shared", "neither"])
def test_weights_as_they_are_and_no_shared_expert(norm, scaling, shared):
    mx.random.seed(0)
    block = cnn.RoutedExperts(U, F, 8, k=2, norm_topk_prob=norm,
                              scaling_factor=scaling,
                              shared_hidden_size=shared)
    block.initialize(mx.init.Normal(0.3))
    x = tokens()
    flat = jnp.asarray(x.reshape(-1, U))
    scores = jax.nn.sigmoid(flat @ block.router_weight.data()._data.T)
    gates, routes = jax.lax.top_k(scores, 2)
    if norm:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    w1, w2 = block.expert_w1.data()._data, block.expert_w2.data()._data

    def ffn(x, a, b):
        return jnp.square(jax.nn.relu(x @ a)) @ b
    want = ffn(flat, block.shared.w_in.weight.data()._data.T,
               block.shared.w_out.weight.data()._data.T) if shared else 0.0
    for e in range(8):
        gate = scaling * jnp.sum(jnp.where(routes == e, gates, 0.0), -1)
        want = want + gate[:, None] * ffn(flat, w1[e], w2[e])
    assert (block.shared is None) == (shared == 0)
    assert close(block(nd.array(x)).asnumpy().reshape(-1, U),
                 np.asarray(want, np.float64), 1e-5)


def test_arguments_are_checked():
    with pytest.raises(MXNetError, match="not among"):
        cnn.RoutedExperts(U, F, E, first_expert=12, experts_held=8)
    with pytest.raises(MXNetError, match="k 20"):
        cnn.RoutedExperts(U, F, E, k=20)
    block = make(0, 4)
    with pytest.raises(MXNetError, match="moe_route"):
        nd.contrib.moe_route(nd.array(tokens()), block.expert_w2.data(),
                             block.router_bias.data())
    # the forms of expert and score are named by ``gated`` and ``scoring``
    # (a configuration uses each); other spellings are refused, not ignored
    for gone in ({"activation": "silu"}, {"score_func": "softmax"}):
        with pytest.raises(TypeError):
            cnn.RoutedExperts(U, F, E, **gone)
    with pytest.raises(MXNetError, match="moe_experts"):
        nd.contrib.moe_experts(
            nd.array(tokens()), nd.ones((2, 20, K)),
            nd.zeros((2, 20, K), dtype="int32"), block.expert_w2.data(),
            block.expert_w2.data())


# -- the counters and what observability exports ------------------------------

class Summed(gluon.loss.Loss):
    def __init__(self):
        super().__init__(None, 0)

    def hybrid_forward(self, F, pred, label):
        return F.mean(F.square(pred - label), axis=(1, 2))


def test_rows_are_counted_in_the_compiled_step_and_not_in_predict_mode():
    block = make(4, 8)
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    trainer = parallel.ShardedTrainer(block, Summed(), "sgd",
                                      {"learning_rate": 1e-3}, mesh=mesh)
    x = tokens()
    trainer.run_steps(x, np.zeros_like(x), num_steps=3).asscalar()
    trainer.step(x, np.zeros_like(x)).asscalar()
    load = cnn.expert_load()[block.prefix.rstrip("_")]
    assert load["steps"] == 4 and load["first_expert"] == 4
    assert block.expert_rows.data().dtype == np.int32
    _, routes = loop_over_experts(weights_of(block), x, 4, 8)
    # lr 1e-3 for four steps moves no route of this batch: four times a step
    local = routes.reshape(-1) - 4
    one_step = np.bincount(local[(local >= 0) & (local < 8)], minlength=8)
    assert abs(sum(load["rows"]) - 4 * one_step.sum()) <= 8
    trainer.evaluate(x, np.zeros_like(x))
    block(nd.array(x))
    assert cnn.expert_load()[block.prefix.rstrip("_")] == load
    # the training program holds no host callback
    text = "\n".join(trainer.program_texts().values())
    assert "callback" not in text.lower()

    snap = observability.snapshot()["metrics"]
    layer = block.prefix.rstrip("_")
    rows = snap[cnn.EXPERT_ROWS_METRIC]["values"]
    assert [rows[f"layer={layer},expert={e}"] for e in range(4, 12)] \
        == load["rows"]
    assert snap[cnn.EXPERT_STEPS_METRIC]["values"][f"layer={layer}"] == 4
    assert cnn.EXPERT_ROWS_METRIC in observability.prometheus_text()
    traced = snap[moe.MOE_COUNT_METRIC]["values"]
    assert any(key.startswith("experts=16,held=8,top_k=3,rows=512,grouped=")
               for key in traced)


def test_eager_training_counts_too():
    block = make(0, 16)
    with autograd.record():
        block(nd.array(tokens())).sum().backward()
    load = cnn.expert_load()[block.prefix.rstrip("_")]
    assert load["steps"] == 1 and sum(load["rows"]) == 40 * K


# -- the grouped product of the kernel tier -----------------------------------

def test_the_grouped_kernel_zeroes_rows_past_the_end_and_their_gradients():
    spec = pallas.get_kernel("grouped_matmul")
    (lhs, rhs, sizes), _ = spec.example()[0]
    assert int(sizes.sum()) < lhs.shape[0]
    got = spec.pallas_impl(lhs, rhs, sizes, interpret=True)
    assert (np.asarray(got[int(sizes.sum()):]) == 0).all()

    def loss(fn, **kw):
        return lambda lhs, rhs: jnp.sum(jnp.sin(fn(lhs, rhs, sizes, **kw)))
    got = jax.grad(loss(spec.pallas_impl, interpret=True), (0, 1))(lhs, rhs)
    want = jax.grad(loss(spec.xla_reference), (0, 1))(lhs, rhs)
    assert (np.asarray(got[0][int(sizes.sum()):]) == 0).all()
    for g, w in zip(got, want):
        assert np.allclose(g, w, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m, k, n, itemsize, want, weight", [
    (12288, 2688, 1856, 2, (256, 896, 1856), (512, 896, 1024)),
    (12288, 1856, 2688, 2, (256, 1856, 896), (512, 1024, 896)),
    (49152, 2688, 1856, 2, (256, 896, 1856), (512, 896, 1024)),
    (12288, 2688, 1856, 4, (256, 384, 512), (512, 384, 512)),
    (12288, 2048, 2048, 2, (256, 2048, 1024), (512, 1024, 1024)),
    (64, 128, 256, 4, (64, 128, 256), (64, 128, 256)),
    (24, 4096, 640, 2, (8, 1024, 640), (8, 1024, 640)),
])
def test_grouped_tiles_come_from_the_shapes(m, k, n, itemsize, want, weight):
    assert tier_kernels.grouped_tiles(m, k, n, itemsize) == want
    assert tier_kernels.grouped_weight_tiles(m, k, n, itemsize) == weight
    for (tm, tk, tn), rows in ((want, 256), (weight, 512)):
        assert m % tm == 0 and tm <= rows
        assert (tk == k or tk % 128 == 0) and (tn == n or tn % 128 == 0)
        # one weight tile, two in flight, beside the rest in 16 MB
        assert tk * tn * itemsize <= 4 * 2 ** 20


def test_unsupported_operands_fall_back_with_a_reason():
    spec = pallas.get_kernel("grouped_matmul")
    (lhs, rhs, sizes), _ = spec.example()[0]
    assert spec.supports(lhs, rhs, sizes) is None
    assert spec.supports(lhs[:63], rhs, sizes).startswith("rows:")
    assert spec.supports(lhs.astype(jnp.float16), rhs.astype(jnp.float16),
                         sizes).startswith("dtype:")
    assert spec.supports(lhs, rhs[:, :64], sizes).startswith("shape:")
    pallas.reset_provenance()
    out = pallas.dispatch("grouped_matmul", lhs, rhs, sizes)
    assert np.allclose(out, spec.xla_reference(lhs, rhs, sizes))
    reasons = pallas.tier_provenance()["grouped_matmul"]["fallback_reasons"]
    assert list(reasons) == ["backend:cpu"]
