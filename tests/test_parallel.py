"""Tests for mxnet_tpu.parallel — mesh construction and the fused SPMD
training step, run on the virtual 8-device CPU mesh (SURVEY §4: the TPU
analog of the reference's local-process fake cluster for kvstore tests)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.parallel import PartitionSpec as P


def _mlp(classes=10):
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(32, activation="relu"))
        net.add(gluon.nn.BatchNorm())
        net.add(gluon.nn.Dense(classes))
    net.initialize()
    return net


def test_make_mesh_axes():
    mesh = parallel.make_mesh({"data": 4, "model": 2})
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (4, 2)
    mesh2 = parallel.make_mesh({"data": -1, "model": 2})
    assert mesh2.devices.shape == (4, 2)
    with pytest.raises(mx.MXNetError):
        parallel.make_mesh({"data": 3, "model": 5})


def test_use_mesh_scope():
    mesh = parallel.make_mesh({"data": 4, "model": 2})
    with parallel.use_mesh(mesh) as m:
        assert parallel.current_mesh() is mesh
    # outside the scope the default (all-data) mesh is current again
    assert parallel.current_mesh().axis_names == ("data",)


def test_sharded_trainer_loss_decreases():
    net = _mlp()
    mesh = parallel.make_mesh({"data": 4, "model": 2})
    tr = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
        mesh=mesh,
        param_rules=[(r".*dense0_weight", P("model", None))])
    rng = np.random.RandomState(0)
    x = rng.randn(64, 16).astype(np.float32)
    y = rng.randint(0, 10, (64,))
    losses = [tr.step(x, y).asscalar() for _ in range(10)]
    assert losses[-1] < losses[0] * 0.7
    assert np.isfinite(losses[-1])


def test_sharded_trainer_matches_eager_sgd():
    """The fused sharded step must produce the same result as the eager
    gluon.Trainer path (the reference's check_consistency method, §4)."""
    rng = np.random.RandomState(1)
    x = rng.randn(16, 8).astype(np.float32)
    y = rng.randint(0, 4, (16,))

    def make():
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(16, activation="tanh", in_units=8))
            net.add(gluon.nn.Dense(4, in_units=16))
        net.initialize(mx.init.Xavier(rnd_type="gaussian"))
        return net

    mx.random.seed(7)
    net_a = make()
    mx.random.seed(7)
    net_b = make()
    for pa, pb in zip(net_a.collect_params().values(),
                      net_b.collect_params().values()):
        np.testing.assert_allclose(pa.data().asnumpy(), pb.data().asnumpy())

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    # eager path: forward/backward/step; grads divided by batch via step(B)
    trainer = gluon.Trainer(net_a.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    from mxnet_tpu import autograd
    for _ in range(3):
        with autograd.record():
            out = net_a(mx.nd.array(x))
            loss = loss_fn(out, mx.nd.array(y))
        loss.backward()
        trainer.step(x.shape[0])

    # fused sharded path: loss is mean over batch, rescale 1.0
    mesh = parallel.make_mesh({"data": 8})
    st = parallel.ShardedTrainer(net_b, loss_fn, "sgd",
                                 optimizer_params={"learning_rate": 0.1},
                                 mesh=mesh)
    for _ in range(3):
        st.step(x, y)

    for pa, pb in zip(net_a.collect_params().values(),
                      net_b.collect_params().values()):
        np.testing.assert_allclose(pa.data().asnumpy(), pb.data().asnumpy(),
                                   rtol=2e-4, atol=2e-5)


def test_sharded_trainer_adam_runs():
    net = _mlp(4)
    mesh = parallel.make_mesh({"data": 8})
    tr = parallel.ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 "adam", {"learning_rate": 1e-2}, mesh=mesh)
    x = np.random.randn(32, 12).astype(np.float32)
    y = np.random.randint(0, 4, (32,))
    l0 = tr.step(x, y).asscalar()
    for _ in range(5):
        l1 = tr.step(x, y).asscalar()
    assert l1 < l0


def test_evaluate_and_outputs():
    net = _mlp(6)
    mesh = parallel.make_mesh({"data": 8})
    tr = parallel.ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 "sgd", {"learning_rate": 0.1}, mesh=mesh)
    x = np.random.randn(16, 5).astype(np.float32)
    y = np.random.randint(0, 6, (16,))
    tr.step(x, y)
    ev = tr.evaluate(x, y)
    assert np.isfinite(ev.asscalar())
    assert tr.last_outputs[0].shape == (16, 6)


def test_graft_entry_dryrun():
    """The driver's multichip dry-run contract must keep working."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", "__graft_entry__.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.dryrun_multichip(8)


def test_run_steps_matches_single_steps():
    """run_steps (lax.scan fused multi-step) must be bit-equal to N single
    steps for a deterministic model."""
    def make():
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(16, activation="tanh", in_units=8))
            net.add(gluon.nn.Dense(4, in_units=16))
        net.initialize(mx.init.Xavier())
        return net

    x = np.random.RandomState(0).randn(16, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, (16,))
    mx.random.seed(3)
    a = make()
    mx.random.seed(3)
    b = make()
    mesh = parallel.make_mesh({"data": 8})
    ta = parallel.ShardedTrainer(a, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 "sgd", {"learning_rate": 0.1,
                                         "momentum": 0.9}, mesh=mesh)
    tb = parallel.ShardedTrainer(b, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 "sgd", {"learning_rate": 0.1,
                                         "momentum": 0.9}, mesh=mesh)
    for _ in range(6):
        la = ta.step(x, y)
    lb = tb.run_steps(x, y, num_steps=6)
    assert abs(la.asscalar() - lb.asscalar()) < 1e-6
    for pa, pb in zip(a.collect_params().values(),
                      b.collect_params().values()):
        np.testing.assert_allclose(pa.data().asnumpy(),
                                   pb.data().asnumpy(), rtol=1e-5,
                                   atol=1e-6)


def test_pipeline_parallel_matches_sequential():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import pipeline_apply
    P_, D, B = 4, 8, 16
    rng = np.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(P_, D, D).astype(np.float32) * 0.3)
    bs = jnp.asarray(rng.randn(P_, D).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))

    def stage(params, h):
        W, b = params
        return jnp.tanh(h @ W + b)

    mesh = parallel.make_mesh({"pipe": 4, "data": 2})
    h = x
    for i in range(P_):
        h = stage((Ws[i], bs[i]), h)
    got = pipeline_apply(stage, (Ws, bs), x, mesh=mesh,
                         num_microbatches=8)
    np.testing.assert_allclose(np.asarray(h), np.asarray(got), atol=1e-6)

    def loss_seq(Ws, bs):
        h = x
        for i in range(P_):
            h = stage((Ws[i], bs[i]), h)
        return jnp.sum(h ** 2)

    def loss_pipe(Ws, bs):
        return jnp.sum(pipeline_apply(stage, (Ws, bs), x, mesh=mesh,
                                      num_microbatches=8) ** 2)

    g1 = jax.grad(loss_seq, argnums=(0, 1))(Ws, bs)
    g2 = jax.jit(jax.grad(loss_pipe, argnums=(0, 1)))(Ws, bs)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_moe_expert_parallel():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe_apply
    E, D, B = 4, 6, 10
    rng = np.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(E, D, D).astype(np.float32) * 0.4)
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))
    gate = jnp.asarray(rng.randn(B, E).astype(np.float32))

    def expert(W, h):
        return jnp.tanh(h @ W)

    mesh = parallel.make_mesh({"expert": 4, "data": 2})
    got = moe_apply(expert, Ws, gate, x, mesh=mesh)
    probs = jax.nn.softmax(gate, -1)
    top = np.asarray(jnp.argmax(probs, -1))
    want = np.stack([np.asarray(probs[i, top[i]])
                     * np.asarray(expert(Ws[top[i]], x[i:i + 1])[0])
                     for i in range(B)])
    np.testing.assert_allclose(want, np.asarray(got), rtol=1e-5,
                               atol=1e-6)


def test_moe_topk_matches_dense_top1():
    """With k=1 and capacity ample, the all-to-all path must reproduce
    the dense-dispatch oracle exactly (VERDICT r3 #5 parity gate)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe_apply, moe_apply_topk
    E, D, B = 4, 6, 16
    rng = np.random.RandomState(1)
    Ws = jnp.asarray(rng.randn(E, D, D).astype(np.float32) * 0.4)
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))
    gate = jnp.asarray(rng.randn(B, E).astype(np.float32))

    def expert(W, h):
        return jnp.tanh(h @ W)

    mesh = parallel.make_mesh({"expert": 4, "data": 2})
    dense = moe_apply(expert, Ws, gate, x, mesh=mesh)
    sparse, aux, stats = moe_apply_topk(expert, Ws, gate, x, k=1,
                                        capacity_factor=float(E),
                                        mesh=mesh)
    assert float(stats["dropped"]) == 0.0
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=1e-5, atol=1e-6)
    assert np.isfinite(float(aux))


def test_moe_topk_top2_oracle():
    """k=2 with ample capacity == softmax-top2-renormalized mixture,
    checked against a per-token numpy oracle."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe_apply_topk
    E, D, B = 4, 5, 8
    rng = np.random.RandomState(2)
    Ws = jnp.asarray(rng.randn(E, D, D).astype(np.float32) * 0.4)
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))
    gate = jnp.asarray(rng.randn(B, E).astype(np.float32))

    def expert(W, h):
        return jnp.tanh(h @ W)

    mesh = parallel.make_mesh({"expert": 4, "data": 2})
    y, aux, stats = moe_apply_topk(expert, Ws, gate, x, k=2,
                                   capacity_factor=float(E), mesh=mesh)
    assert float(stats["dropped"]) == 0.0     # k>1 stat: per-slot fraction
    probs = np.asarray(jax.nn.softmax(gate, -1))
    want = np.zeros((B, D), np.float32)
    for i in range(B):
        top2 = np.argsort(-probs[i])[:2]
        w = probs[i, top2] / probs[i, top2].sum()
        for e, wi in zip(top2, w):
            want[i] += wi * np.asarray(expert(Ws[e], x[i:i + 1])[0])
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)


def test_moe_topk_per_device_compute_scales():
    """The defining property vs dense dispatch: each device's expert
    runs over k*B_local*cf tokens — O(tokens/E), not O(tokens)."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe_apply_topk
    D, B = 4, 32
    rng = np.random.RandomState(3)
    seen = {}

    for E, ax in ((2, {"expert": 2, "data": 4}),
                  (8, {"expert": 8})):
        Ws = jnp.asarray(rng.randn(E, D, D).astype(np.float32) * 0.3)
        x = jnp.asarray(rng.randn(B, D).astype(np.float32))
        gate = jnp.asarray(rng.randn(B, E).astype(np.float32))
        shapes = []

        def expert(W, h, _shapes=shapes):
            _shapes.append(h.shape)
            return h @ W

        mesh = parallel.make_mesh(ax)
        moe_apply_topk(expert, Ws, gate, x, k=1, capacity_factor=1.0,
                       mesh=mesh)
        seen[E] = shapes[0][0]
    # tokens processed per device = E * capacity = E * ceil(B/E^2)
    assert seen[2] == 2 * -(-32 // 4) == 16      # B/E with cf=1
    assert seen[8] == 8 * -(-32 // 64) == 8
    assert seen[8] < seen[2] < B


def test_moe_topk_capacity_drops_and_aux():
    """Adversarially skewed router: capacity 1.0 must drop overflow
    tokens (zero rows) and the Switch aux loss must exceed the balanced
    value of ~1."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe_apply_topk
    E, D, B = 4, 4, 16
    rng = np.random.RandomState(4)
    Ws = jnp.asarray(np.tile(np.eye(D, dtype=np.float32), (E, 1, 1)))
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))
    # every token prefers expert 0
    gate = jnp.asarray(np.tile([8.0, 0.0, 0.0, 0.0],
                               (B, 1)).astype(np.float32))

    def expert(W, h):
        return h @ W

    mesh = parallel.make_mesh({"expert": 4, "data": 2})
    y, aux, stats = moe_apply_topk(expert, Ws, gate, x, k=1,
                                   capacity_factor=1.0, mesh=mesh)
    # capacity = ceil(1*4*1.0/4) = 1 per expert => 4 of 16 tokens kept
    assert abs(float(stats["dropped"]) - 12 / 16) < 1e-6
    kept_rows = (np.abs(np.asarray(y)).sum(-1) > 0).sum()
    assert kept_rows == 4
    assert float(aux) > 2.0          # skew >> balanced value 1.0

    # balanced router: aux ~ 1, nothing dropped at cf=1 with uniform
    # assignment pattern
    gate_b = jnp.asarray(np.tile(np.eye(E, dtype=np.float32) * 8.0,
                                 (B // E, 1)))
    y2, aux2, stats2 = moe_apply_topk(expert, Ws, gate_b, x, k=1,
                                      capacity_factor=1.0, mesh=mesh)
    assert float(stats2["dropped"]) == 0.0
    assert abs(float(aux2) - 1.0) < 0.05
    # identity experts at gate prob ~0.999 (softmax of logit 8):
    # outputs ~= inputs
    np.testing.assert_allclose(np.asarray(y2), np.asarray(x), rtol=2e-3,
                               atol=5e-3)


def test_moe_topk_gradients_flow():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe_apply_topk
    E, D, B = 2, 4, 8
    rng = np.random.RandomState(5)
    Ws = jnp.asarray(rng.randn(E, D, D).astype(np.float32) * 0.3)
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))
    gate = jnp.asarray(rng.randn(B, E).astype(np.float32))
    mesh = parallel.make_mesh({"expert": 2, "data": 4})

    def loss(Ws, gate):
        y, aux, _ = moe_apply_topk(lambda W, h: jnp.tanh(h @ W), Ws,
                                   gate, x, k=2, capacity_factor=2.0,
                                   mesh=mesh)
        return jnp.sum(y ** 2) + 0.01 * aux

    gW, gg = jax.jit(jax.grad(loss, argnums=(0, 1)))(Ws, gate)
    assert np.isfinite(np.asarray(gW)).all()
    assert np.isfinite(np.asarray(gg)).all()
    assert np.abs(np.asarray(gW)).sum() > 0
    assert np.abs(np.asarray(gg)).sum() > 0   # gate grads via combine


def test_pipeline_interleaved_matches_sequential():
    """Circular schedule with v virtual stages per device (VERDICT r3
    #6): same numerics as sequential layer application, smaller bubble."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import pipeline_apply, pipeline_schedule_info
    P_, V, D, B, M = 4, 2, 6, 16, 8
    L = P_ * V
    rng = np.random.RandomState(6)
    Ws = jnp.asarray(rng.randn(L, D, D).astype(np.float32) * 0.3)
    bs = jnp.asarray(rng.randn(L, D).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))

    def stage(params, h):
        W, b = params
        return jnp.tanh(h @ W + b)

    # device d owns layers {d, P+d}: ring order visits 0,1,2,3,4,...,7
    h = x
    for l in range(L):
        h = stage((Ws[l], bs[l]), h)

    mesh = parallel.make_mesh({"pipe": 4, "data": 2})
    got = pipeline_apply(stage, (Ws, bs), x, mesh=mesh,
                         num_microbatches=M, num_virtual_stages=V)
    np.testing.assert_allclose(np.asarray(h), np.asarray(got), atol=1e-6)

    # gradients transpose through the wrapped schedule too
    def loss_pipe(Ws, bs):
        return jnp.sum(pipeline_apply(stage, (Ws, bs), x, mesh=mesh,
                                      num_microbatches=M,
                                      num_virtual_stages=V) ** 2)

    def loss_seq(Ws, bs):
        h = x
        for l in range(L):
            h = stage((Ws[l], bs[l]), h)
        return jnp.sum(h ** 2)

    g1 = jax.grad(loss_seq, argnums=(0, 1))(Ws, bs)
    g2 = jax.jit(jax.grad(loss_pipe, argnums=(0, 1)))(Ws, bs)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)

    # bubble accounting: interleaving divides the bubble TIME by v at
    # fixed L (GPipe tick costs v layers; circular tick costs one)
    gpipe = pipeline_schedule_info(P_, M, 1)
    inter = pipeline_schedule_info(P_, M, V)
    gpipe_bubble_layers = (P_ - 1) * V          # v layers idle per slot
    inter_bubble_layers = P_ - 1
    assert inter_bubble_layers * V == gpipe_bubble_layers
    assert inter["bubble_fraction"] < gpipe["bubble_fraction"]


def test_pipeline_heterogeneous_embed_head_trains():
    """A REAL 4-stage model — embedding -> 4 transformer-ish blocks ->
    vocab head — trains to decreasing loss on the 8-device mesh
    (VERDICT r3 #6 'Done' gate)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import pipeline_apply
    P_, D, V_TOK, B, S, M = 4, 16, 11, 8, 6, 4
    rng = np.random.RandomState(7)
    emb = jnp.asarray(rng.randn(V_TOK, D).astype(np.float32) * 0.3)
    Ws = jnp.asarray(rng.randn(P_, D, D).astype(np.float32) * 0.3)
    bs = jnp.asarray(np.zeros((P_, D), np.float32))
    head = jnp.asarray(rng.randn(D, V_TOK).astype(np.float32) * 0.3)
    toks = jnp.asarray(rng.randint(0, V_TOK, (B, S)).astype(np.int32))
    labels = jnp.asarray(rng.randint(0, V_TOK, (B, S)).astype(np.int32))

    def embed(p, t):
        return p[t]                             # (Bm, S, D)

    def block(params, h):
        W, b = params
        return h + jnp.tanh(h @ W + b)

    def head_fn(p, h):
        return h @ p                            # (N, S, V)

    mesh = parallel.make_mesh({"pipe": 4, "data": 2})

    def loss_fn(params):
        emb_p, Ws_p, bs_p, head_p = params
        logits = pipeline_apply(block, (Ws_p, bs_p), toks, mesh=mesh,
                                num_microbatches=M,
                                embed_fn=embed, embed_params=emb_p,
                                head_fn=head_fn, head_params=head_p)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None],
                                    axis=-1).mean()

    params = (emb, Ws, bs, head)
    step = jax.jit(jax.value_and_grad(loss_fn))
    losses = []
    for _ in range(20):
        l, g = step(params)
        params = jax.tree_util.tree_map(lambda p, gg: p - 0.5 * gg,
                                        params, g)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.8, losses
    # every parameter group actually learned (nonzero grads)
    _, g = step(params)
    for t in jax.tree_util.tree_leaves(g):
        assert np.abs(np.asarray(t)).sum() > 0


def test_pipeline_heterogeneous_oracle():
    """Embed/head pipeline output equals the sequential oracle."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import pipeline_apply
    P_, D, V_TOK, B, S = 4, 8, 7, 8, 3
    rng = np.random.RandomState(8)
    emb = jnp.asarray(rng.randn(V_TOK, D).astype(np.float32) * 0.5)
    Ws = jnp.asarray(rng.randn(P_, D, D).astype(np.float32) * 0.3)
    bs = jnp.asarray(rng.randn(P_, D).astype(np.float32) * 0.1)
    head = jnp.asarray(rng.randn(D, V_TOK).astype(np.float32) * 0.5)
    toks = jnp.asarray(rng.randint(0, V_TOK, (B, S)).astype(np.int32))

    def block(params, h):
        W, b = params
        return jnp.tanh(h @ W + b)

    h = emb[toks]
    for i in range(P_):
        h = block((Ws[i], bs[i]), h)
    want = h @ head

    mesh = parallel.make_mesh({"pipe": 4, "data": 2})
    got = pipeline_apply(block, (Ws, bs), toks, mesh=mesh,
                         num_microbatches=4,
                         embed_fn=lambda p, t: p[t], embed_params=emb,
                         head_fn=lambda p, hh: hh @ p, head_params=head)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_sharded_run_steps_respects_lr_schedule():
    """The scanned multi-step path must apply the scheduler's per-step lr
    (regression: a frozen first-step lr changes warmup/decay math)."""
    import numpy as np
    from mxnet_tpu import lr_scheduler

    rng = np.random.RandomState(0)
    x = rng.randn(16, 6).astype(np.float32)
    y = rng.randint(0, 4, (16,))
    mesh = parallel.make_mesh({"data": 8})

    def build():
        mx.random.seed(17)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(4))
        net.initialize()
        opt = mx.optimizer.create(
            "sgd", learning_rate=0.2, momentum=0.9,
            lr_scheduler=lr_scheduler.FactorScheduler(step=2, factor=0.5))
        return net, parallel.ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), opt, mesh=mesh)

    net_a, tr_a = build()
    for _ in range(4):
        tr_a.step(x, y)
    wa = [np.asarray(p._data[0]._data) for p in tr_a._trainable]

    net_b, tr_b = build()
    tr_b.run_steps(x, y, num_steps=4)
    wb = [np.asarray(p._data[0]._data) for p in tr_b._trainable]
    for a, b in zip(wa, wb):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_warmup_from_zero_updates_weights():
    """A warm-up that starts at lr 0 still moves the weights from the first
    step on: the per-parameter multipliers are the multipliers, not a ratio
    of learning rates (0 / 0 while the schedule starts at 0)."""
    import numpy as np
    from mxnet_tpu import lr_scheduler

    rng = np.random.RandomState(0)
    x = rng.randn(16, 6).astype(np.float32)
    y = rng.randint(0, 4, (16,))
    mesh = parallel.make_mesh({"data": 8})
    mx.random.seed(17)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    schedule = lr_scheduler.MultiFactorScheduler(
        step=[100], factor=0.5, base_lr=0.2, warmup_steps=10)
    opt = mx.optimizer.create("sgd", learning_rate=0.2,
                              lr_scheduler=schedule)
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), opt, mesh=mesh)
    trainer.prepare(x)
    before = [np.asarray(p._data[0]._data) for p in trainer._trainable]
    trainer.run_steps(x, y, num_steps=2)
    after = [np.asarray(p._data[0]._data) for p in trainer._trainable]
    assert schedule(0) == 0.0 and schedule(1) == 0.02
    assert all(np.abs(a - b).max() > 0 for a, b in zip(after, before))
