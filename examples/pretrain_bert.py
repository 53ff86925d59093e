#!/usr/bin/env python
"""Config #3 — BERT-base masked-LM pretraining (GluonNLP's
scripts/bert/run_pretraining.py shape).

Runs the fused SPMD step over a dp(×sp) mesh; --seq-parallel shards long
sequences over the `seq` axis with ring attention (net-new TPU capability,
SURVEY §5.7). Synthetic corpus by default.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon.model_zoo import bert


class MLMWrapper(gluon.HybridBlock):
    """Keeps the logits 3-D (B, S, V): the CE loss reduces over the last
    axis in place — flattening forced a logits relayout on TPU
    (docs/perf_notes.md round 4)."""

    def __init__(self, inner):
        super().__init__()
        self.inner = inner

    def hybrid_forward(self, F, tokens):
        seq, mlm = self.inner(tokens)
        return mlm


def main():
    mx.runtime.enable_compile_cache()
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="bert_12_768_12")
    p.add_argument("--vocab-size", type=int, default=30522)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--seq-length", type=int, default=128)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--num-layers", type=int, default=None,
                   help="override the config (tiny CI runs)")
    p.add_argument("--units", type=int, default=None)
    p.add_argument("--num-heads", type=int, default=None)
    p.add_argument("--hidden-size", type=int, default=None)
    p.add_argument("--seq-parallel", type=int, default=1,
                   help="size of the seq mesh axis (ring attention)")
    p.add_argument("--bf16", action="store_true", default=True)
    p.add_argument("--no-bf16", dest="bf16", action="store_false")
    args = p.parse_args()

    import jax
    n_dev = len(jax.devices())
    axes = {"data": n_dev // args.seq_parallel}
    if args.seq_parallel > 1:
        axes["seq"] = args.seq_parallel
    mesh = parallel.make_mesh(axes)

    overrides = {k: v for k, v in dict(
        num_layers=args.num_layers, units=args.units,
        num_heads=args.num_heads, hidden_size=args.hidden_size).items()
        if v is not None}
    net = bert.get_bert_model(
        args.model, vocab_size=args.vocab_size,
        max_length=max(512, args.seq_length),
        use_pooler=False, use_classifier=False,
        seq_parallel=args.seq_parallel > 1, **overrides)
    net.initialize(mx.init.Normal(0.02))
    trainer = parallel.ShardedTrainer(
        MLMWrapper(net),
        gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        optimizer_params={"learning_rate": args.lr},
        mesh=mesh, compute_dtype="bfloat16" if args.bf16 else None)

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, args.vocab_size,
                         (args.batch_size, args.seq_length))
    tic, seen = time.time(), 0
    for step in range(args.steps):
        loss = trainer.step(tokens, tokens)
        seen += args.batch_size
        if step == 2:            # drop compile time from throughput
            tic, seen = time.time(), 0
        if step % 10 == 0:
            logging.info("Batch [%d]\tmlm_loss=%.4f", step,
                         loss.asscalar())
    dt = time.time() - tic
    logging.info("final mlm_loss=%.4f", loss.asscalar())
    logging.info("Speed: %.2f samples/sec (%d chips, seq=%d)",
                 seen / dt, n_dev, args.seq_length)


if __name__ == "__main__":
    main()
