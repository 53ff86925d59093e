"""The DeepSeek-V2 decoder (``gluon.model_zoo.deepseek_v2``) at a small size
on the CPU, with seeded random weights: the ops it brought (YaRN rotary,
attention with a value head smaller than the query's, the softmax router's
group-limited choice, gated routed experts, the device budget, the balance
losses), the shares of a layer against the uncut reference layer, and the whole
model against the plain float32 reference of
``chipbench/models/deepseek_v2.py``: logits, loss and every gradient at the
system's routes; then the same net through ``ShardedTrainer``. Last, the
programs that the configurations already in the benchmark trace through the
ops this model shares with them, held to what they were before it."""
import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, observability, ops, parallel
from mxnet_tpu.gluon.block import functional_apply
from mxnet_tpu.gluon.contrib.nn import RoutedExperts
from mxnet_tpu.gluon.model_zoo import (brumby, deepseek_v2, granite_hybrid,
                                       nemotron_h)
from mxnet_tpu.ops import moe
from mxnet_tpu.ops.nn import yarn_inverse_frequencies, yarn_mscale
from mxnet_tpu.parallel.ring_attention import attention_reference

from chipbench import manifest
from chipbench.models import deepseek_v2 as dm

RTOL = 1e-4
ARGS = manifest.load_config(manifest.load_manifest(), "deepseek_v2")["args"]
# every published width cut down, 3 layers (one dense, two with experts),
# 2 heads of 4 held from the second, 8 experts of 16 held from the fifth, 4
# groups of which 2 are kept; YaRN active at these lengths
SMALL = dict(
    ARGS, vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=3, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
    num_attention_heads=2, first_head=1, n_routed_experts=8, first_expert=4,
    n_group=4, topk_group=2, num_experts_per_tok=3, init_sigma=0.1,
    compute_dtype=None, master_dtype=None,
    published_counts=dict(ARGS["published_counts"], n_routed_experts=16,
                          num_attention_heads=4),
    rope_scaling=dict(ARGS["rope_scaling"],
                      original_max_position_embeddings=8, factor=4),
    optimizer_params=dict(ARGS["optimizer_params"], learning_rate=1e-3),
    lr_warmup_steps=0)


@pytest.fixture
def mesh():
    return parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])


def batch(seq, seed=3, n=2):
    return dm.make_batch(SMALL, {"seq": seq}, n, np.random.default_rng(seed))


def close(got, want, rtol=RTOL):
    scale = np.abs(want).max()
    assert scale > 0
    return np.abs(np.asarray(got) - want).max() <= rtol * scale


# -- YaRN ---------------------------------------------------------------------

def test_yarn_frequencies_follow_the_formula_at_the_published_keys():
    scaling = ARGS["rope_scaling"]
    d, base = ARGS["qk_rope_head_dim"], ARGS["rope_theta"]

    def turns(n):
        return d * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(base))

    lo, hi = math.floor(turns(32)), math.ceil(turns(1))
    assert (lo, hi) == (10, 23)
    i = np.arange(32)
    theta = base ** (-2.0 * i / 64)
    ramp = np.clip((i - lo) / (hi - lo), 0, 1)
    want = theta * (1 - ramp) + theta / 40 * ramp
    got = yarn_inverse_frequencies(64, base, 40, 4096, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    # the fast coordinates keep their frequency, the slow ones are divided
    assert got[:10].tolist() == theta[:10].tolist()
    np.testing.assert_allclose(got[23:], theta[23:] / 40, rtol=1e-12)
    # the reference's own reckoning says the same
    ref, ratio = dm.yarn_frequencies(ARGS)
    np.testing.assert_allclose(ref, want, rtol=1e-12)
    assert ratio == 1.0                 # mscale == mscale_all_dim
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(m) \
        == pytest.approx(1.26080, abs=1e-5)
    assert dm.score_scale(ARGS) == pytest.approx(192 ** -0.5 * m * m) \
        == pytest.approx(0.114721, abs=1e-6)
    assert scaling["type"] == "yarn" and scaling["factor"] == 40


def test_rotary_with_yarn_turns_by_the_scaled_frequencies():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 9, 3, 8)).astype(np.float32)
    kw = dict(scaling_factor=4.0, original_max_position_embeddings=8,
              beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=0.5)
    got = ops.get("_contrib_rotary_embedding").fn(jnp.asarray(x), **kw)
    inv = yarn_inverse_frequencies(8, 10000.0, 4.0, 8, 32, 1)
    ratio = yarn_mscale(4.0, 1.0) / yarn_mscale(4.0, 0.5)
    angles = np.arange(9)[:, None] * inv
    cos, sin = (f(angles)[:, None, :] * ratio for f in (np.cos, np.sin))
    lo, up = x[..., :4], x[..., 4:]
    want = np.concatenate([lo * cos - up * sin, up * cos + lo * sin], -1)
    assert close(got, want, 1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rotary_without_yarn_keys_is_to_the_bit_the_plain_op(dtype):
    """No YaRN key: the op traces the plain rotary, whose program the
    Brumby cell runs (its jaxpr is held below, with the other cells')."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 16, 4, 8)), dtype)
    rot = ops.get("_contrib_rotary_embedding").fn
    half = 4
    inv_freq = 1e6 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(16, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    lo, hi = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    before = jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                             axis=-1).astype(dtype)
    assert np.array_equal(np.asarray(rot(x, theta=1e6)), np.asarray(before))


# -- attention with a value head of its own -------------------------------------

@pytest.mark.parametrize("seq", [256, 1280], ids=["dense", "portable"])
def test_flash_attention_takes_a_value_head_smaller_than_the_query(seq):
    """q and k of 192, v of 128, causal, at the MLA scale: the short-key
    path and the blockwise path against the plain definition."""
    rng = np.random.default_rng(2)
    q, k = (jnp.asarray(rng.standard_normal((1, 2, seq, 192)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, 2, seq, 128)), jnp.float32)
    scale = dm.score_scale(ARGS)
    from mxnet_tpu.ops.contrib import FLASH_COUNT_METRIC, attention_branch
    branch = "dense" if seq <= 1024 else "portable"
    assert attention_branch(q, k, v) == branch
    key = (f"branch={branch},block_q=,block_k="
           f"{'' if branch == 'dense' else 512},qk=192,v=128,padded=")

    def count():
        return observability.snapshot()["metrics"].get(
            FLASH_COUNT_METRIC, {}).get("values", {}).get(key, 0)

    before = count()
    got = nd.contrib.flash_attention(nd.array(np.asarray(q)),
                                     nd.array(np.asarray(k)),
                                     nd.array(np.asarray(v)), causal=True,
                                     sm_scale=scale).asnumpy()
    assert got.shape == (1, 2, seq, 128) and count() == before + 1
    want = np.asarray(attention_reference(q, k, v, causal=True, scale=scale))
    assert close(got, want, 1e-5)


def test_latent_attention_counts_its_layers_and_their_path():
    block = deepseek_v2.LatentAttention(
        64, 4, 48, 32, 16, 8, 12, rope_scaling=SMALL["rope_scaling"],
        heads_held=(1, 2))
    block.initialize(mx.init.Normal(0.1))
    x = nd.array(np.random.default_rng(0).standard_normal((1, 8, 64)))
    key = "heads=4,held=2,qk=24,v=12,path=dense"

    def count():
        return observability.snapshot()["metrics"].get(
            deepseek_v2.MLA_COUNT_METRIC, {}).get("values", {}).get(key, 0)

    before = count()
    block(x)                                  # eager: nothing is traced
    assert count() == before
    block.hybridize()
    assert block(x).shape == (1, 8, 64)
    assert count() == before + 1
    assert block.scale == pytest.approx(24 ** -0.5 * yarn_mscale(
        4, SMALL["rope_scaling"]["mscale_all_dim"]) ** 2)


# -- the softmax router's group-limited choice ---------------------------------

def numpy_choice(scores, n_group, topk_group, top):
    """The choice written out token by token: the groups of largest maximum,
    then the largest scores among their experts; among equals the lower
    index first."""
    out = []
    for row in scores:
        groups = row.reshape(n_group, -1)
        best = sorted(range(n_group), key=lambda g: (-groups[g].max(), g))
        allowed = {e for g in best[:topk_group]
                   for e in range(g * groups.shape[1],
                                  (g + 1) * groups.shape[1])}
        order = sorted(allowed, key=lambda e: (-row[e], e))
        out.append(order[:top])
    return np.array(out)


def route(x, router, **kw):
    return ops.get("_contrib_moe_route").fn(
        jnp.asarray(x), jnp.asarray(router), jnp.zeros(router.shape[0]),
        top_k=3, norm_topk_prob=False, scaling_factor=16.0,
        scoring="softmax", n_group=4, topk_group=2, **kw)


def test_group_limited_choice_is_the_direct_choice():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((64, 12)).astype(np.float32)
    router = rng.standard_normal((16, 12)).astype(np.float32)
    weights, ids, scores = route(x, router)
    logits = x @ router.T
    want_scores = np.exp(logits - logits.max(-1, keepdims=True))
    want_scores /= want_scores.sum(-1, keepdims=True)
    assert close(scores, want_scores, 1e-6)
    want = numpy_choice(np.asarray(scores), 4, 2, 3)
    assert (np.asarray(ids) == want).all()
    assert (np.asarray(ids) == dm.own_choice(np.asarray(scores),
                                             SMALL)[0]).all()
    # no normalisation, the scale applied
    np.testing.assert_allclose(
        np.asarray(weights),
        16 * np.take_along_axis(np.asarray(scores), want, -1), rtol=1e-6)
    # a group that holds the largest score but not enough of the top six is
    # kept, and a best expert outside the kept groups is never chosen
    chosen_groups = np.asarray(ids) // 4
    kept = np.argsort(-np.asarray(scores).reshape(64, 4, 4).max(-1), -1,
                      kind="stable")[:, :2]
    assert all(set(c) <= set(k) for c, k in zip(chosen_groups, kept))


def test_ties_go_to_the_lower_group_and_the_lower_expert():
    # equal group maxima: groups 1 and 2 tie for second place; group 1 wins
    scores = np.full((1, 16), 0.01, np.float32)
    scores[0, [0, 5, 9]] = [0.2, 0.1, 0.1]
    scores[0, [6, 10]] = [0.05, 0.05]
    scores /= scores.sum()
    # the op's router product gives these scores' logits: x = 1, W = logits
    _, ids, _ = route(np.ones((1, 1), np.float32), np.log(scores).T)
    assert numpy_choice(scores, 4, 2, 3).tolist() == [[0, 5, 6]]
    assert dm.own_choice(scores, SMALL)[0].tolist() == [[0, 5, 6]]
    assert np.asarray(ids).tolist() == [[0, 5, 6]]


def test_a_token_whose_third_and_fourth_groups_are_close_lies_inside():
    """The margin at the first stage: the reference keeps groups 0 and 1;
    the system's scores moved group 2's maximum past group 1's within what
    they moved by, so its other choice is no fault; a choice that moved
    groups farther apart than that is."""
    cfg = dict(SMALL, n_group=4, topk_group=2, num_experts_per_tok=2)
    s = np.full((2, 8), 0.05)
    s[:, 0], s[:, 2], s[:, 4] = 0.3, 0.2, 0.1999    # groups 0, 1, 2 maxima
    s[1, 4] = 0.1                                    # token 1: far apart
    system = s.copy()
    system[0, 4], system[0, 2] = 0.2001, 0.1998      # moved by 1e-4 and 2e-4
    differ, inside, by_group, by_expert = dm.route_conditions(
        s, system, np.array([[0, 4], [0, 4]]), cfg)
    assert differ.tolist() == [True, True]
    assert by_group.tolist() == [True, False]
    assert inside.tolist() == [True, False]
    # the system's own order of its scores, for the first token
    assert dm.own_choice(system, cfg)[0][0].tolist() == [0, 4]
    # stage two: equal groups, the 2nd and 3rd experts of the kept groups
    s2 = np.array([[0.3, 0.05, 0.2, 0.19995, 0.01, 0.01, 0.01, 0.01]])
    moved = s2.copy()
    moved[0, 2], moved[0, 3] = 0.19990, 0.20000
    differ, inside, by_group, by_expert = dm.route_conditions(
        s2, moved, np.array([[0, 3]]), cfg)
    assert differ.tolist() == [True] and by_expert.tolist() == [True]
    assert inside.tolist() == [True] and by_group.tolist() == [False]


# -- gated experts, and the device budget -------------------------------------

def expert_case(tokens=64, held=4, load=6, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((tokens, 16)), jnp.float32)
    w = jnp.asarray(rng.random((tokens, 3)), jnp.float32)
    # every token names three of experts 0..load-1: most land here (0..3)
    ids = jnp.asarray(np.stack([rng.permutation(load)[:3]
                                for _ in range(tokens)]), jnp.int32)
    w1 = jnp.asarray(rng.standard_normal((held, 16, 16)) * 0.3, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((held, 8, 16)) * 0.3, jnp.float32)
    return x, w, ids, w1, w2


def test_gated_experts_are_w2_of_silu_gate_times_up(monkeypatch):
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    x, w, ids, w1, w2 = expert_case()
    y, rows = moe._moe_experts(x, w, ids, w1, w2, num_experts=6, gated=True)
    xs, ws, idn = (np.asarray(t) for t in (x, w, ids))
    want = np.zeros_like(xs)
    for t in range(len(xs)):
        for j, e in enumerate(idn[t]):
            if e < 4:
                h = xs[t] @ np.asarray(w1[e])
                g, u = h[:8], h[8:]
                want[t] += ws[t, j] * ((g / (1 + np.exp(-g)) * u)
                                       @ np.asarray(w2[e]))
    assert close(y, want, 1e-5)
    assert np.asarray(rows).tolist() == np.bincount(
        idn.reshape(-1), minlength=6)[:4].tolist()
    with pytest.raises(mx.MXNetError, match="2F"):
        moe._moe_experts(x, w, ids, w1[:, :, :15], w2, gated=True)


def test_a_buffer_of_the_capacity_is_the_buffer_of_all_pairs(monkeypatch):
    """Where the router has dropped all but ``capacity`` of the pairs that
    land here, one buffer of that many rows gives what the two-size buffer
    gives: the same result, the same rows and the same gradients."""
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    x, w, ids, w1, w2 = expert_case()
    # keep the first 40 pairs that land on experts 0..3, drop the others
    flat = np.asarray(ids).reshape(-1)
    here = np.nonzero(flat < 4)[0]
    flat = np.where(np.isin(np.arange(flat.size), here[40:]), flat - 6, flat)
    dropped = jnp.asarray(flat.reshape(ids.shape), jnp.int32)

    def run(capacity):
        def loss(x, w, w1, w2):
            y, rows = moe._moe_experts(x, w, dropped, w1, w2, num_experts=6,
                                       gated=True, capacity=capacity)
            return jnp.sum(jnp.sin(y)), (y, rows)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                  has_aux=True)(x, w, w1, w2)

    (_, (y0, rows0)), grads0 = run(0)
    (_, (y40, rows40)), grads40 = run(40)
    assert int(np.asarray(rows40).sum()) == 40
    assert np.asarray(rows40).tolist() == np.asarray(rows0).tolist()
    assert close(y40, np.asarray(y0), 1e-6)
    for g40, g0 in zip(grads40, grads0):
        assert close(g40, np.asarray(g0), 1e-5)


@pytest.mark.parametrize("factor, first", [(1.0, 4), (0.5, 0), (4.0, 8)])
def test_the_device_budget_keeps_the_largest_scores(factor, first):
    """DeepSeek-V2's device-level dropping: of the pairs naming one of the
    eight experts held, ``factor`` times their share of the pairs, those of
    largest score, are kept; the others come back as their id less 16, the
    weights as chosen. The plain numpy rule of the reference agrees."""
    rng = np.random.default_rng(11)
    x = rng.standard_normal((40, 12)).astype(np.float32)
    router = rng.standard_normal((16, 12)).astype(np.float32)
    plain = route(x, router)
    weights, ids, scores = route(x, router, capacity_factor=factor,
                                 first_expert=first, experts_held=8)
    ids, chosen = np.asarray(ids), np.asarray(plain[1])
    assert (ids % 16 == chosen).all()
    assert np.array_equal(np.asarray(weights), np.asarray(plain[0]))
    here = (chosen >= first) & (chosen < first + 8)
    budget = moe.device_budget(40, 3, 8, 16, factor)
    assert budget == min(math.ceil(factor * 40 * 3 * 8 / 16), 120)
    kept = here & (ids >= 0)
    assert kept.sum() == min(budget, here.sum())
    assert ((ids < 0) == (here & ~kept)).all()
    affinity = np.take_along_axis(np.asarray(scores), chosen, -1)
    if (here & ~kept).any():
        assert affinity[kept].min() >= affinity[here & ~kept].max()
    cfg = dict(SMALL, capacity_factor=factor, first_expert=first,
               n_routed_experts=8, published_counts=dict(
                   SMALL["published_counts"], n_routed_experts=16),
               num_experts_per_tok=3)
    assert (dm.reference_drop(cfg, chosen, np.asarray(scores)) == ids).all()


def test_balance_loss_is_the_papers_terms_written_out():
    """The zoo's balance losses (one vectorised expression) against the
    reference's (each sequence's terms written out), at routes that pile
    onto one group and at scores that are not uniform; only the scores
    carry a gradient."""
    from mxnet_tpu.gluon.model_zoo.deepseek_v2 import balance_loss
    rng = np.random.default_rng(5)
    scores = jax.nn.softmax(jnp.asarray(rng.standard_normal((2, 12, 16)) * 2,
                                        jnp.float32), -1)
    routes = np.stack([np.stack([rng.permutation(8 if b else 16)[:3]
                                 for _ in range(12)]) for b in range(2)])
    cfg = dict(SMALL, balance_alphas=[0.003, 0.05, 0.02])
    got = balance_loss(jnp.asarray(routes, jnp.int32), scores, 4, 2,
                       cfg["balance_alphas"])
    want = dm.reference_balance(cfg, routes, scores)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    # uniform scores and a uniform choice: every term is its alpha
    flat = jnp.full((1, 16, 16), 1 / 16, jnp.float32)
    even = np.arange(48).reshape(1, 16, 3) % 16
    assert float(balance_loss(jnp.asarray(even), flat, 4, 2, (1, 0, 0))) \
        == pytest.approx(1.0)
    assert float(balance_loss(jnp.asarray(even), flat, 4, 2, (0, 1, 0))) \
        == pytest.approx(1.0)
    grad = jax.grad(lambda s: balance_loss(jnp.asarray(routes), s, 4, 2,
                                           (0.003, 0.05, 0.02)))(scores)
    assert np.abs(np.asarray(grad)).max() > 0
    assert dm.reference_balance(dict(SMALL, balance_alphas=None), routes,
                                scores) == 0.0


# -- the shares of a layer add up to the uncut layer ---------------------------

UNCUT = dict(SMALL, first_head=0, num_attention_heads=4, first_expert=0,
             n_routed_experts=16)


def test_head_shares_and_expert_shares_add_up_to_the_uncut_layer():
    """Four head shares of one head and four expert shares of four experts
    (the deployment's 16 x 16 in small): summed, with the shared expert
    counted once, they give what the uncut reference layer gives."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((2, 12, 64)).astype(np.float32)
    heads, nope, rope, vd = 4, 16, 8, 12
    full = deepseek_v2.LatentAttention(
        64, heads, 48, 32, nope, rope, vd, rope_scaling=SMALL["rope_scaling"],
        epsilon=SMALL["rms_norm_eps"])
    full.initialize(mx.init.Normal(0.1))
    w = {name: getattr(full, attr).weight.data().asnumpy()
         for name, attr in (("q_a", "q_a_proj"), ("q_b", "q_b_proj"),
                            ("kv_a", "kv_a_proj"), ("kv_b", "kv_b_proj"),
                            ("o", "o_proj"))}
    w["q_a_norm"] = full.q_a_norm.gamma.data().asnumpy()
    w["kv_a_norm"] = full.kv_a_norm.gamma.data().asnumpy()
    parts = []
    for first in range(heads):
        share = deepseek_v2.LatentAttention(
            64, heads, 48, 32, nope, rope, vd,
            rope_scaling=SMALL["rope_scaling"], epsilon=SMALL["rms_norm_eps"],
            heads_held=(first, 1))
        share.initialize()
        rows = slice(first * (nope + rope), (first + 1) * (nope + rope))
        kv_rows = slice(first * (nope + vd), (first + 1) * (nope + vd))
        for attr, value in (
                ("q_a_proj", w["q_a"]), ("kv_a_proj", w["kv_a"]),
                ("q_b_proj", w["q_b"][rows]), ("kv_b_proj", w["kv_b"][kv_rows]),
                ("o_proj", w["o"][:, first * vd:(first + 1) * vd])):
            getattr(share, attr).weight.set_data(nd.array(value))
        share.q_a_norm.gamma.set_data(nd.array(w["q_a_norm"]))
        share.kv_a_norm.gamma.set_data(nd.array(w["kv_a_norm"]))
        parts.append(share(nd.array(x)).asnumpy())
    want = np.asarray(dm.reference_attention(UNCUT, {
        key: jnp.asarray(value) for key, value in w.items()}, x))
    assert close(sum(parts), want)

    make = dict(units=64, hidden_size=32, num_experts=16, k=3,
                norm_topk_prob=False, scaling_factor=16.0,
                shared_hidden_size=64, scoring="softmax", n_group=4,
                topk_group=2, gated=True)
    uncut = RoutedExperts(**make)
    uncut.initialize(mx.init.Normal(0.1))
    router = uncut.router_weight.data().asnumpy()
    w1, w2 = uncut.expert_w1.data().asnumpy(), uncut.expert_w2.data().asnumpy()
    shared_in = uncut.shared.w_in.weight.data().asnumpy()
    shared_out = uncut.shared.w_out.weight.data().asnumpy()
    shared = np.asarray(dm._linear(dm._gated(dm._linear(
        jnp.asarray(x), jnp.asarray(shared_in))), jnp.asarray(shared_out)))
    routed = []
    for first in range(0, 16, 4):
        share = RoutedExperts(first_expert=first, experts_held=4, **make)
        share.initialize()
        share.router_weight.set_data(nd.array(router))
        share.expert_w1.set_data(nd.array(w1[first:first + 4]))
        share.expert_w2.set_data(nd.array(w2[first:first + 4]))
        share.shared.w_in.weight.set_data(nd.array(shared_in))
        share.shared.w_out.weight.set_data(nd.array(shared_out))
        routed.append(share(nd.array(x)).asnumpy() - shared)
    want, _ = dm.reference_experts(UNCUT, {
        "router": jnp.asarray(router), "w1": jnp.asarray(w1),
        "w2": jnp.asarray(w2), "shared_in": jnp.asarray(shared_in),
        "shared_out": jnp.asarray(shared_out)}, x)
    assert close(sum(routed) + shared, np.asarray(want))


# -- the whole model against the plain reference -------------------------------

def system_outputs_loss_and_grads(net, x, y):
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        outs = net(nd.array(x))
        loss = loss_fn(outs[0], nd.array(y)).mean()
    loss.backward()
    return outs, float(loss.asscalar()), dm.reference_params(
        net, read=lambda p: p.grad().asnumpy())


def test_the_trainers_loss_adds_the_balance_losses(mesh):
    """The loss the trainer reads is the cross entropy plus the balance
    losses of both expert layers, as the reference has them at the system's
    routes (in float32 the reference's own)."""
    assert SMALL["balance_alphas"] == [0.003, 0.05, 0.02]
    net, trainer = dm.build(SMALL, mesh, 3)
    x, y = batch(20)
    routes = [o.asnumpy() for o in net(nd.array(x))[1:3]]
    want = dm.reference_loss_and_grads(net, x, y, routes)[0]
    net.chipbench_args = dict(SMALL, balance_alphas=None)
    cross_entropy = dm.reference_loss_and_grads(net, x, y, routes)[0]
    got = float(trainer.step(x, y).asscalar())
    assert got == pytest.approx(want, rel=RTOL)
    # a router near uniform puts each layer's terms near their alphas' sum
    assert want - cross_entropy == pytest.approx(2 * 0.073, abs=0.03)


def test_logits_loss_and_every_gradient_agree_with_the_reference(mesh):
    plain = dict(SMALL, balance_alphas=None)
    net, _ = dm.build(plain, mesh, 3)
    x, y = batch(20)
    net.hybridize()
    outs, loss, grads = system_outputs_loss_and_grads(net, x, y)
    routes = [o.asnumpy() for o in outs[1:3]]
    assert len(outs) == 6 and routes[0].shape == (2, 20, 3)
    logits, own, scores = dm.forward_at(dm.reference_params(net), plain, x,
                                        routes)
    assert close(outs[0].asnumpy(), logits)
    # in float32 the system chooses what the reference chooses
    assert all((r == o).all() for r, o in zip(routes, own))
    for got, want in zip(outs[3:5], scores):
        assert got.shape == (2, 20, 16) and close(got.asnumpy(), want)
    # the rows computed are the pairs that name experts 4..11
    held = [np.bincount(r[r >= 0], minlength=16)[4:12] for r in routes]
    assert (outs[-1].asnumpy() == np.stack(held)).all()
    want_loss, want = dm.reference_loss_and_grads(net, x, y, routes)
    assert loss == pytest.approx(want_loss, rel=RTOL)
    got_leaves, treedef = jax.tree_util.tree_flatten(grads)
    want_leaves, want_treedef = jax.tree_util.tree_flatten(want)
    assert treedef == want_treedef and len(want_leaves) == 3 + 3 * 9 + 2 + 2 * 5
    for path, g, w in zip(jax.tree_util.tree_leaves_with_path(want),
                          got_leaves, want_leaves):
        assert g.shape == w.shape
        assert close(g, w), jax.tree_util.keystr(path[0])


def test_the_reference_at_other_routes_is_another_function(mesh):
    net, _ = dm.build(SMALL, mesh, 3)
    x, _ = batch(8, n=1)
    params = dm.reference_params(net)
    logits, own, _ = dm.forward_at(params, SMALL, x)
    assert close(net(nd.array(x))[0].asnumpy(), logits)
    other = [np.broadcast_to(np.array([4, 5, 6]), own[0].shape), own[1]]
    assert not close(dm.forward_at(params, SMALL, x, other)[0], logits, 1e-3)


def test_every_float_parameter_of_the_net_is_in_the_reference(mesh):
    net, _ = dm.build(SMALL, mesh, 3)
    leaves = jax.tree_util.tree_leaves(dm.reference_params(net))
    params = net.collect_params()
    # the buffers: each expert layer's bias of the choice and two counters
    buffers = [name for name, p in params.items() if p.grad_req == "null"]
    assert len(buffers) == 2 * 3 and len(leaves) == len(params) - len(buffers)
    assert sum(leaf.size for leaf in leaves) == sum(
        dm.parameter_counts(SMALL).values())


def test_the_model_refuses_what_it_does_not_implement():
    keys = {key: SMALL[key] for key in dm.MODEL_KEYS}
    for bad in ({"topk_method": "greedy"}, {"hidden_act": "gelu"},
                {"rope_scaling": dict(SMALL["rope_scaling"], type="linear")},
                {"attention_bias": True}):
        with pytest.raises(mx.MXNetError, match="group_limited_greedy"):
            deepseek_v2.deepseek_v2(n_routed_experts=16,
                                    num_attention_heads=4,
                                    **dict(keys, **bad))
    with pytest.raises(mx.MXNetError, match="not among"):
        deepseek_v2.LatentAttention(64, 4, 48, 32, 16, 8, 12,
                                    heads_held=(3, 2))
    # the published keys, all of them, build (shapes only: nothing is drawn)
    net = deepseek_v2.deepseek_v2(num_hidden_layers=2, **{
        key: value for key, value in deepseek_v2.DEEPSEEK_V2.items()
        if key != "num_hidden_layers"})
    assert len(net.layers) == 2


# -- through the trainer -------------------------------------------------------

def test_trains_through_sharded_trainer_with_recomputation(mesh):
    net, trainer = dm.build(SMALL, mesh, 3)
    x, y = batch(16)
    losses = [float(trainer.run_steps(x, y, num_steps=4).asscalar())
              for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert trainer.skipped_steps == 0
    record = [record for name, record
              in observability.device_scopes().items()
              if name.endswith("run_steps(4)")][-1]
    found = set(record["scopes"].values())
    assert {"mla", "mla.attention", "mlp", "moe.router", "moe.experts",
            "moe.shared", "lm_head"} <= found
    traced = observability.snapshot()["metrics"][moe.MOE_COUNT_METRIC][
        "values"]
    assert any(key.startswith("experts=16,held=8,top_k=3,")
               and key.endswith("expert=swiglu") for key in traced)


# -- the programs of the cells already in the benchmark ------------------------
#
# The jaxpr, forward and backward, of what the configurations measured before
# this model came trace through the code it shares with them: Nemotron's
# expert layer (sigmoid router, relu^2 experts, one pass), Brumby's plain
# rotary, attention with one head size on each of its three branches, and
# small Nemotron-H, Brumby and Granite models through the trainer's own
# functional form. The digests are those the code gave before the latent
# attention, the softmax router and the gated experts were added, so the
# programs of those cells are unchanged to the instruction. A change that
# alters one of these programs on purpose replaces its digest and says so.

BEFORE = {
    "moe": "47b1bf0578dd8b223653269629f280bfa4428fc952b324fefda1d81a22629474",
    "rotary":
        "a66680fe8c8748e1bad61b8102a9a6fcf4a47eccc82cfd45c7d0f3e4bda5122c",
    "flash_128":
        "2e501d83d2e5fcd2d6da733a8cff0dec66be435ceadc366934791ca19f68140d",
    "flash_2048":
        "bedc26cb94abf74af39d4223db4b4e2cd1799aa0e53c5076f5507cdc8c3f4d0d",
    "flash_tpu":
        "4ca907b76b18c30c5e1d2380a0e482a0c86e9bf02a235c79bea4446428ccfc7a",
    "nemotron_h":
        "c13b6959d0f220c67c9ad6b3b15f9511f79b605afee2a1d9ea0647e9aa68c491",
    "brumby":
        "7f07b6102c9ecaaf4eb625c877a4e92b8ecc9f71b63c253886b7ad2ac36f4896",
    "granite":
        "bee6dfc8812f78eeab223b0b2d3fa8b115bea328177d1ec7b74d018704bf29bb",
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
    rot = ops.get("_contrib_rotary_embedding").fn
    flash = ops.get("_contrib_flash_attention").fn
    x, r, b = a((24, 32)), a((16, 32)), jnp.zeros(16, jnp.float32)
    w1, w2 = a((8, 32, 24)), a((8, 24, 32))

    def layer(x, r, w1, w2):
        w, ids, s = route_op(x, r, b, top_k=3, norm_topk_prob=True,
                             scaling_factor=2.5)
        y, _ = experts(x, w, ids, w1, w2, first_expert=4, num_experts=16)
        return sq(y) + jnp.sum(s)

    yield "moe", jax.grad(layer, argnums=(0, 1, 2, 3)), (x, r, w1, w2)
    yield "rotary", jax.grad(lambda t: sq(rot(t, theta=1e6))), \
        (a((1, 16, 4, 8)),)

    def attention(q, k, v):
        return sq(flash(q, k, v, causal=True))

    for seq, d in ((128, 64), (2048, 64)):
        q = a((1, 2, seq, d))
        yield f"flash_{seq}", jax.grad(attention, argnums=(0, 1, 2)), \
            (q, q, q)
    q = a((1, 2, 2048, 128))
    yield "flash_tpu", jax.grad(attention, argnums=(0, 1, 2)), (q, q, q)


@pytest.mark.parametrize("name", ["moe", "rotary", "flash_128", "flash_2048",
                                  "flash_tpu"])
def test_the_shared_ops_trace_what_they_traced_before(name, monkeypatch):
    from mxnet_tpu.pallas import registry
    if name == "flash_tpu":     # the chip's branch, staged beside the other
        monkeypatch.setattr(registry, "runs_on", lambda args: ("tpu", True))
    fn, args = {n: (f, a) for n, f, a in op_programs()}[name]
    assert digest(fn, *args) == BEFORE[name]


def model_digest(net, tokens):
    net.initialize(mx.init.Normal(0.1))
    net(mx.nd.array(tokens))
    tr, aux = net._param_split()
    tr_data = [p.data()._data for p in tr]
    aux_data = [p.data()._data for p in aux]
    key = jax.random.key(0)

    def loss(tr_data):
        outs, _, _ = functional_apply(net, key, tr_data, aux_data,
                                      [jnp.asarray(tokens)], training=True)
        return sq(outs[0])

    return digest(jax.grad(loss), tr_data)


def small_model(name):
    if name == "nemotron_h":
        return nemotron_h.nemotron_h(
            experts_held=(4, 8), return_routes=True, vocab_size=64,
            hidden_size=32, hybrid_override_pattern="ME*E",
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=8,
            n_groups=2, conv_kernel=4, chunk_size=8, n_routed_experts=16,
            num_experts_per_tok=3, moe_intermediate_size=16,
            moe_shared_expert_intermediate_size=24, routed_scaling_factor=2.5,
            recompute=True)
    if name == "brumby":
        return brumby.brumby(
            vocab_size=64, hidden_size=32, intermediate_size=48,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, chunk_size=8, recompute=True)
    return granite_hybrid.granite_hybrid(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        layer_types=["mamba", "attention", "mamba"], num_attention_heads=4,
        num_key_value_heads=2, mamba_n_heads=4, mamba_d_head=8,
        mamba_d_state=8, mamba_chunk_size=8, recompute=True)


@pytest.mark.parametrize("name", ["nemotron_h", "brumby", "granite"])
def test_the_other_decoders_trace_what_they_traced_before(name):
    tokens = np.random.default_rng(1).integers(0, 64, (2, 16))
    assert model_digest(small_model(name), tokens) == BEFORE[name]
