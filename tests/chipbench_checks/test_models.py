"""The model files of the two configurations: the plain float32 reference
agrees with the program's imperative Gluon path on seeded weights (small
images, a shallow BERT: the CPU's share), and the operation counts are the
shapes' own."""
import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel

from chipbench import manifest
from chipbench.models import bert_12_768_12, resnet50_v1

BENCH = manifest.load_manifest()


@pytest.fixture
def mesh():
    return parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])


def test_resnet50_reference_agrees_with_the_imperative_path(mesh):
    args = dict(manifest.load_config(BENCH, "resnet50_v1")["args"],
                image_size=64, classes=10)
    net, _ = resnet50_v1.build(args, mesh, 3)
    x, y = resnet50_v1.make_batch(args, {}, 2, np.random.default_rng(3))
    assert x.shape == (2, 3, 64, 64) and x.dtype == np.float32
    want = net(mx.nd.array(x)).asnumpy()
    got = resnet50_v1.reference_logits(net, x)
    assert got.shape == (2, 10) and np.abs(want).max() > 0
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_bert_reference_agrees_with_the_imperative_path(mesh):
    args = dict(manifest.load_config(BENCH, "bert_12_768_12")["args"],
                num_layers=2, vocab_size=500)
    traffic = {"seq": 16}
    net, _ = bert_12_768_12.build(args, mesh, 3)
    toks, labels = bert_12_768_12.make_batch(args, traffic, 2,
                                             np.random.default_rng(3))
    assert toks.shape == (2, 16) and labels is toks
    want = net(mx.nd.array(toks)).asnumpy()     # predict mode: no dropout
    got = bert_12_768_12.reference_logits(net, toks)
    assert got.shape == (2, 16, 500)
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_the_same_seed_gives_the_same_weights_and_batch(mesh):
    args = dict(manifest.load_config(BENCH, "bert_12_768_12")["args"],
                num_layers=1, vocab_size=100)
    toks = bert_12_768_12.make_batch(args, {"seq": 8}, 2,
                                     np.random.default_rng(2 ** 31 + 9))[0]
    again = bert_12_768_12.make_batch(args, {"seq": 8}, 2,
                                      np.random.default_rng(2 ** 31 + 9))[0]
    assert (toks == again).all()
    logits = []
    for _ in range(2):
        net, _ = bert_12_768_12.build(args, mesh, 2 ** 31 + 9)
        net(mx.nd.array(toks))
        logits.append(bert_12_768_12.reference_logits(net, toks))
    assert (logits[0] == logits[1]).all()


def test_operation_counts_come_from_the_shapes():
    args = manifest.load_config(BENCH, "resnet50_v1")["args"]
    # 3.86 G multiply-accumulates forward (v1: the stride sits in the first
    # 1x1 of a stage), two operations each, three passes
    assert resnet50_v1.flops_per_sample(args, {}) == 23147839488
    assert resnet50_v1.conv_macs(
        224, args["stages"], args["channels"]) == 3855925248
    args = manifest.load_config(BENCH, "bert_12_768_12")["args"]
    flops = bert_12_768_12.flops_per_sample(args, {"seq": 128})
    assert flops == 85497348096
    assert abs(flops / (6 * 110e6 * 128) - 1) < 0.02
    # attention's share grows with the sequence
    assert bert_12_768_12.flops_per_sample(args, {"seq": 512}) > 4 * flops
