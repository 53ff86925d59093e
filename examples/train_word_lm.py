#!/usr/bin/env python
"""Word-level LSTM language model (ref: example/rnn/word_lm/train.py —
embedding → multi-layer LSTM → tied/untied softmax over the vocab,
truncated-BPTT training with perplexity reporting).

Synthetic corpus by default: a fixed random "grammar" (each token
deterministically keyed to its predecessor pair) so the model's
perplexity floor is ~1 when it learns and stays near vocab-size when it
doesn't — the CI gate reads the printed final perplexity. The fused
lax.scan LSTM op is the compute path (SURVEY §2 row 14).
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
from mxnet_tpu import autograd, gluon, nd


class WordLM(gluon.HybridBlock):
    def __init__(self, vocab, embed, hidden, layers, dropout=0.2,
                 tie_weights=False, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embedding = gluon.nn.Embedding(vocab, embed)
            self.lstm = gluon.rnn.LSTM(hidden, num_layers=layers,
                                       layout="NTC", dropout=dropout)
            self.drop = gluon.nn.Dropout(dropout) if dropout else None
            if tie_weights and embed != hidden:
                raise mx.base.MXNetError(
                    "tie_weights needs embed == hidden")
            self.decoder = gluon.nn.Dense(vocab, flatten=False,
                                          params=self.embedding.params
                                          if tie_weights else None)

    def hybrid_forward(self, F, tokens):
        x = self.embedding(tokens)            # (N, T, E)
        h = self.lstm(x)                      # (N, T, H)
        if self.drop is not None:
            h = self.drop(h)
        return self.decoder(h)                # (N, T, V) — 3-D logits


def synthetic_corpus(vocab, n_tokens, seed=0):
    """Deterministic bigram chain: next = perm[(cur + prev) % vocab].
    Fully learnable by a 2-token context model; chance ppl = vocab."""
    rng = np.random.RandomState(seed)
    perm = rng.permutation(vocab)
    toks = np.zeros(n_tokens, np.int64)
    toks[0], toks[1] = 1, 2
    for i in range(2, n_tokens):
        toks[i] = perm[(toks[i - 1] + toks[i - 2]) % vocab]
    return toks


def batchify(toks, batch, seq):
    n = (len(toks) - 1) // (batch * seq) * (batch * seq)
    x = toks[:n].reshape(batch, -1)
    y = toks[1:n + 1].reshape(batch, -1)
    for i in range(0, x.shape[1] - seq + 1, seq):
        yield x[:, i:i + seq], y[:, i:i + seq]


def main():
    mx.runtime.enable_compile_cache()
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=50)
    p.add_argument("--embed", type=int, default=64)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--seq-len", type=int, default=32)
    p.add_argument("--epochs", type=int, default=6)
    p.add_argument("--tokens", type=int, default=20000)
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--dropout", type=float, default=0.0)
    p.add_argument("--tied", action="store_true")
    args = p.parse_args()

    net = WordLM(args.vocab, args.embed, args.hidden, args.layers,
                 dropout=args.dropout, tie_weights=args.tied)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    toks = synthetic_corpus(args.vocab, args.tokens)
    for epoch in range(args.epochs):
        total, count, tic = 0.0, 0, time.time()
        for x, y in batchify(toks, args.batch_size, args.seq_len):
            xb = nd.array(x.astype(np.float32))
            yb = nd.array(y.astype(np.float32))
            with autograd.record():
                loss = loss_fn(net(xb), yb)
            loss.backward()
            trainer.step(args.batch_size)
            total += float(loss.mean().asscalar()) * x.size
            count += x.size
        ppl = float(np.exp(min(total / count, 20.0)))
        logging.info("Epoch [%d] train ppl=%.2f (%.1fs)", epoch, ppl,
                     time.time() - tic)
    logging.info("final perplexity=%.2f", ppl)


if __name__ == "__main__":
    main()
