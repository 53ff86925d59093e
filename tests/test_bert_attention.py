"""Tests: flash/ring attention + BERT family (driver config #3 path;
long-context/sequence-parallel capability per SURVEY §5.7)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon.model_zoo import bert
from mxnet_tpu.parallel.ring_attention import (attention_reference,
                                               blockwise_attention,
                                               ring_attention)


def _qkv(B=2, H=4, S=32, D=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    return mk(), mk(), mk()


def test_blockwise_matches_reference():
    q, k, v = _qkv()
    for causal in (False, True):
        ref = attention_reference(q, k, v, causal=causal)
        out = blockwise_attention(q, k, v, block_size=8, causal=causal)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=1e-5, atol=1e-5)


def test_ring_matches_reference():
    q, k, v = _qkv()
    mesh = parallel.make_mesh({"data": 2, "seq": 4})
    for causal in (False, True):
        ref = attention_reference(q, k, v, causal=causal)
        out = ring_attention(q, k, v, mesh=mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=1e-5, atol=1e-5)


def test_ring_gradients_match():
    q, k, v = _qkv(S=16)
    mesh = parallel.make_mesh({"seq": 8})

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh=mesh, causal=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_attention_op_via_nd():
    q, k, v = _qkv()
    out = mx.nd.contrib.flash_attention(
        mx.nd.array(np.asarray(q)), mx.nd.array(np.asarray(k)),
        mx.nd.array(np.asarray(v)), block_size=8)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [1152, 1280, 2048, 4096, 8192, 32768])
def test_flash_tiles_are_legal_for_the_library_kernel(s, d, dtype):
    """Every tile the rule reads from the shapes is a multiple of 128 that
    divides its sequence length, each minor tile divides its major, and the
    library's own checks accepted the eleven together (``BlockSizes``
    raises from ``__post_init__`` where they do not)."""
    import dataclasses
    from mxnet_tpu.ops.contrib import _flash_tiles
    for s_q, s_kv in ((s, s), (s, 2048), (1152, s)):
        tiles = _flash_tiles(s_q, s_kv, d, dtype)
        assert tiles.has_backward_blocks and tiles.block_b == 1
        fields = dataclasses.asdict(tiles)
        for name, tile in fields.items():
            if name == "block_b":
                continue
            length = s_q if "block_q" in name else s_kv
            assert tile % 128 == 0 and length % tile == 0, (name, tile)
        for major, minor in (("block_k_major", "block_k"),
                             ("block_q_major_dkv", "block_q_dkv"),
                             ("block_k_major_dkv", "block_k_dkv"),
                             ("block_k_major_dq", "block_k_dq")):
            assert fields[major] % fields[minor] == 0, (major, minor)


def test_flash_tiles_read_the_shape():
    """Past the default: a long sequence gets tiles above the library's
    128, and a length only 128 and 384 divide gets one of those."""
    from mxnet_tpu.ops.contrib import _flash_tiles
    tiles = _flash_tiles(4096, 4096, 64, jnp.bfloat16)
    assert tiles.block_q > 128 and tiles.block_k_major > 128
    assert tiles.block_q_dkv > 128 and tiles.block_q_dq > 128
    odd = _flash_tiles(1152, 1152, 64, jnp.bfloat16)
    assert odd.block_q in (128, 384) and odd.block_k_major in (128, 384)


@pytest.mark.parametrize("s, branch, block_k",
                         [(128, "dense", ""), (2048, "portable", "512")])
def test_flash_attention_counts_the_branch_it_traced(s, branch, block_k):
    from mxnet_tpu import observability
    from mxnet_tpu.ops.contrib import FLASH_COUNT_METRIC
    key = f"branch={branch},block_q=,block_k={block_k},qk=64,v=64,padded="

    def count():
        return observability.snapshot()["metrics"].get(
            FLASH_COUNT_METRIC, {}).get("values", {}).get(key, 0)

    before = count()
    q, k, v = _qkv(B=1, H=1, S=s, D=64)
    out = mx.nd.contrib.flash_attention(
        mx.nd.array(np.asarray(q)), mx.nd.array(np.asarray(k)),
        mx.nd.array(np.asarray(v)), causal=True)
    assert count() == before + 1
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def _tiny_bert(**kw):
    cfg = dict(num_layers=2, units=32, hidden_size=64, num_heads=4,
               max_length=64, vocab_size=100, dropout=0.1)
    cfg.update(kw)
    return bert.BERTModel(**cfg)


def test_bert_forward_shapes():
    net = _tiny_bert()
    net.initialize()
    B, S = 2, 16
    tokens = mx.nd.array(np.random.randint(0, 100, (B, S)))
    types = mx.nd.array(np.zeros((B, S)))
    seq, pooled, nsp, mlm = net(tokens, types)
    assert seq.shape == (B, S, 32)
    assert pooled.shape == (B, 32)
    assert nsp.shape == (B, 2)
    assert mlm.shape == (B, S, 100)


def test_bert_mlm_gather():
    net = _tiny_bert()
    net.initialize()
    B, S, M = 2, 16, 3
    tokens = mx.nd.array(np.random.randint(0, 100, (B, S)))
    types = mx.nd.array(np.zeros((B, S)))
    positions = mx.nd.array(np.array([[1, 5, 7], [0, 2, 9]]))
    seq, pooled, nsp, mlm = net(tokens, types, masked_positions=positions)
    assert mlm.shape == (B, M, 100)


def test_bert_trains_mlm():
    """A tiny BERT must fit a toy MLM batch (loss decreases) through the
    fused SPMD path."""
    net = _tiny_bert(dropout=0.0, use_classifier=False, use_pooler=False)
    net.initialize()
    B, S = 8, 16
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 100, (B, S))
    types = np.zeros((B, S), dtype=np.int32)

    class MLMLoss(gluon.loss.Loss):
        def __init__(self):
            super().__init__(None, 0)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, pred, label):
            return self._ce(F.reshape(pred, (-1, 100)),
                            F.reshape(label, (-1,)))

    class Wrapper(gluon.HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def hybrid_forward(self, F, tokens):
            seq, mlm = self.inner(tokens)
            return mlm

    wrapper = Wrapper(net)
    tr = parallel.ShardedTrainer(
        wrapper, MLMLoss(), "adam", {"learning_rate": 3e-3},
        mesh=parallel.make_mesh({"data": 8}))
    losses = [tr.step(tokens, tokens).asscalar() for _ in range(8)]
    assert losses[-1] < losses[0] * 0.8, losses


def test_bert_named_configs():
    net = bert.get_bert_model("bert_12_768_12", vocab_size=50)
    assert net.encoder._num_layers == 12
    with pytest.raises(mx.MXNetError):
        bert.get_bert_model("bert_1_2_3")


def test_ulysses_matches_reference():
    from mxnet_tpu.parallel.ring_attention import ulysses_attention
    q, k, v = _qkv(B=2, H=4, S=32, D=16)
    mesh = parallel.make_mesh({"data": 2, "seq": 4})
    for causal in (False, True):
        ref = attention_reference(q, k, v, causal=causal)
        out = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=1e-5, atol=1e-5)


def test_ulysses_gradients():
    from mxnet_tpu.parallel.ring_attention import ulysses_attention
    q, k, v = _qkv(S=16, H=8)
    mesh = parallel.make_mesh({"seq": 8})

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    def loss_u(q, k, v):
        return jnp.sum(ulysses_attention(q, k, v, mesh=mesh,
                                         causal=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_u = jax.jit(jax.grad(loss_u, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_u):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
