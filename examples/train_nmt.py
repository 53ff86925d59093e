#!/usr/bin/env python
"""Config #4 — transformer NMT (Sockeye shape: sockeye.train). Trains the
base transformer on a synthetic reversal task and greedy-decodes samples;
swap in real parallel text by replacing ``make_batch``.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon.model_zoo import transformer

BOS, EOS = 1, 2


def make_batch(rng, batch_size, seq_len, vocab):
    src = rng.randint(3, vocab, (batch_size, seq_len))
    tgt = src[:, ::-1].copy()                     # reversal task
    tgt_in = np.concatenate(
        [np.full((batch_size, 1), BOS), tgt[:, :-1]], axis=1)
    return src, tgt_in, tgt


def main():
    mx.runtime.enable_compile_cache()
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=10)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--units", type=int, default=128)
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--label-smoothing", type=float, default=0.0,
                   help="Sockeye-style smoothed CE (e.g. 0.1)")
    p.add_argument("--beam", type=int, default=1,
                   help="beam size for the sample decode (1 = greedy)")
    args = p.parse_args()

    net = transformer.TransformerModel(
        args.vocab, args.vocab, num_layers=args.num_layers,
        units=args.units, hidden_size=args.units * 4, num_heads=8,
        max_length=64, dropout=0.1)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss(
        label_smoothing=args.label_smoothing)
    rng = np.random.RandomState(0)
    for step in range(args.steps):
        src, tgt_in, tgt = make_batch(rng, args.batch_size, args.seq_len,
                                      args.vocab)
        with autograd.record():
            logits = net(mx.nd.array(src), mx.nd.array(tgt_in))
            loss = loss_fn(logits.reshape((-1, args.vocab)),
                           mx.nd.array(tgt.reshape(-1)))
        loss.backward()
        trainer.step(tgt.size)
        if step % 50 == 0:
            logging.info("Batch [%d]\tloss=%.4f", step,
                         float(loss.asnumpy().mean()))
    # sample decode (greedy by default; --beam K runs beam search)
    src, _, tgt = make_batch(rng, 2, args.seq_len, args.vocab)
    out = net.translate(mx.nd.array(src), bos_id=BOS, eos_id=EOS,
                        max_steps=args.seq_len, beam_size=args.beam)
    acc = float((out[:, :args.seq_len] == tgt[:, :out.shape[1]]).mean())
    mode = "greedy" if args.beam <= 1 else f"beam-{args.beam}"
    # test_examples.py parses the "greedy-decode" line; keep it for the
    # default mode and label beam runs by their actual mode
    if args.beam <= 1:
        logging.info("greedy-decode token accuracy: %.3f", acc)
    else:
        logging.info("%s decode token accuracy: %.3f", mode, acc)


if __name__ == "__main__":
    main()
