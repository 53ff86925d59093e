#!/usr/bin/env python
"""Throughput of the non-headline driver configs on the real chip —
BASELINE.md asks for these to be recorded once the models run:
  nmt        Sockeye-geometry transformer (6L/512/2048/8h), seq 64,
             teacher-forced train step, tokens/sec
  ssd        SSD-512-style resnet18 detector train step, images/sec
  bert_large bert_24_1024_16 MLM train step (batch sized to fit HBM),
             samples/sec

Same staged-batch k-step methodology as bench.py. Prints one JSON line
per model.

Usage: python benchmarks/model_zoo_throughput.py [nmt ssd bert_large]
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _measure(trainer, batch, per_step, unit, name, k, dispatches=4,
             windows=3):
    # stage the batch on device once (bench.py's staged-batch protocol —
    # steady-state steps must not pay the host->device upload; a
    # production input pipeline double-buffers these transfers)
    trainer._prepare(batch[:-1])
    batch = tuple(trainer._shard_batch_arg(b) for b in batch)
    np.asarray(trainer.run_steps(*batch, num_steps=k).asnumpy())
    best = None
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(dispatches):
            loss = trainer.run_steps(*batch, num_steps=k)
        np.asarray(loss.asnumpy())
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    import jax
    rate = per_step * dispatches * k / best / len(jax.devices())
    print(json.dumps({"metric": name, "value": round(rate, 1),
                      "unit": unit,
                      "ms_per_step": round(best / dispatches / k * 1e3,
                                           2)}))


def bench_nmt(on_tpu):
    import jax
    from mxnet_tpu import gluon, parallel
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import transformer

    vocab = 32000 if on_tpu else 128
    batch, seq = (64, 64) if on_tpu else (2, 8)
    net = transformer.TransformerModel(
        src_vocab=vocab, tgt_vocab=vocab,
        num_layers=6 if on_tpu else 1, units=512 if on_tpu else 32,
        hidden_size=2048 if on_tpu else 64,
        num_heads=8 if on_tpu else 2, dropout=0.1,
        max_length=max(512, seq))
    net.initialize(mx.init.Xavier())

    class Seq2SeqWrapper(gluon.HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def hybrid_forward(self, F, src, tgt):
            return self.inner(src, tgt)       # (B, T, V) logits

    mesh = parallel.make_mesh({"data": len(jax.devices())})
    trainer = parallel.ShardedTrainer(
        Seq2SeqWrapper(net),
        gluon.loss.SoftmaxCrossEntropyLoss(label_smoothing=0.1),
        "adam", {"learning_rate": 1e-4},
        mesh=mesh, compute_dtype="bfloat16" if on_tpu else None,
        master_dtype="bfloat16" if on_tpu else None)
    rng = np.random.RandomState(0)
    src = rng.randint(1, vocab, (batch, seq))
    tgt = rng.randint(1, vocab, (batch, seq))
    _measure(trainer, (src, tgt, tgt), batch * seq,
             f"target tokens/sec/chip (batch={batch}, seq={seq})",
             "nmt_transformer_train_tokens_per_sec", k=8 if on_tpu else 2)


def bench_ssd(on_tpu):
    import jax
    from mxnet_tpu import gluon, parallel
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import ssd as ssd_zoo

    batch = 32 if on_tpu else 2
    shape = 512 if on_tpu else 64
    classes = 20
    # the NAMED zoo config: ssd_512_resnet18_v1 is 5-scale
    net = ssd_zoo.get_ssd("resnet18_v1", classes=classes,
                          num_scales=5 if on_tpu else 3,
                          thumbnail=not on_tpu)
    net.initialize(mx.init.Xavier())
    loss_fn = ssd_zoo.SSDMultiBoxLoss()

    class SSDTrainBlock(gluon.HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def hybrid_forward(self, F, x, labels):
            anchors, cls_preds, box_preds = self.inner(x)
            loc_t, loc_m, cls_t = F.contrib.MultiBoxTarget(
                anchors, labels, cls_preds, negative_mining_ratio=3.0)
            return F.stack(*loss_fn(cls_preds, box_preds, cls_t, loc_t,
                                    loc_m), axis=0)

    class PassThrough(gluon.loss.Loss):
        amp_safe = True

        def __init__(self):
            super().__init__(None, 0)

        def hybrid_forward(self, F, pred, label):
            return F.sum(pred)

    mesh = parallel.make_mesh({"data": len(jax.devices())})
    trainer = parallel.ShardedTrainer(
        SSDTrainBlock(net), PassThrough(), "sgd",
        {"learning_rate": 5e-3, "momentum": 0.9, "wd": 5e-4},
        mesh=mesh, compute_dtype="bfloat16" if on_tpu else None,
        master_dtype="bfloat16" if on_tpu else None)
    rng = np.random.RandomState(0)
    x = rng.rand(batch, 3, shape, shape).astype(np.float32)
    labels = np.full((batch, 4, 5), -1.0, np.float32)
    labels[:, 0] = [0, 0.2, 0.2, 0.6, 0.7]
    _measure(trainer, (x, labels, labels), batch,
             f"images/sec/chip (batch={batch}, {shape}x{shape})",
             "ssd512_resnet18_train_images_per_sec", k=8 if on_tpu else 2)


def bench_bert_large(on_tpu):
    import jax
    from mxnet_tpu import gluon, parallel
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import bert

    vocab = 30522 if on_tpu else 256
    batch, seq = (32, 128) if on_tpu else (2, 16)
    if on_tpu:
        net = bert.get_bert_model("bert_24_1024_16", vocab_size=vocab,
                                  max_length=512, dropout=0.1,
                                  use_pooler=False, use_classifier=False)
    else:
        net = bert.BERTModel(num_layers=2, units=64, hidden_size=128,
                             num_heads=4, max_length=128,
                             vocab_size=vocab, use_pooler=False,
                             use_classifier=False)
    net.initialize(mx.init.Normal(0.02))

    class MLMWrapper(gluon.HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def hybrid_forward(self, F, tokens):
            _, mlm = self.inner(tokens)
            return mlm

    mesh = parallel.make_mesh({"data": len(jax.devices())})
    trainer = parallel.ShardedTrainer(
        MLMWrapper(net), gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-4},
        mesh=mesh, compute_dtype="bfloat16" if on_tpu else None,
        master_dtype="bfloat16" if on_tpu else None)
    toks = np.random.RandomState(0).randint(0, vocab, (batch, seq))
    _measure(trainer, (toks, toks), batch,
             f"samples/sec/chip (batch={batch}, seq={seq})",
             "bert_large_train_samples_per_sec", k=8 if on_tpu else 2)


def main():
    from mxnet_tpu import runtime
    runtime.enable_compile_cache()
    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    which = sys.argv[1:] or ["nmt", "ssd", "bert_large"]
    for name in which:
        {"nmt": bench_nmt, "ssd": bench_ssd,
         "bert_large": bench_bert_large}[name](on_tpu)


if __name__ == "__main__":
    main()
