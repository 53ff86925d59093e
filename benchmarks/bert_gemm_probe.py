#!/usr/bin/env python
"""BERT MXU-utilization experiment matrix (round-3 verdict #2).

Round 2 measured the BERT-base MLM step at ~42% MXU utilization on the
matmul fusions with the layout levers exhausted (einsum QKV measured
perf-neutral). The levers tried here attack GEMM shapes and epilogues:

  baseline        bert_12_768_12, vocab 30522, batch 128, seq 128
  vocab_pad       decoder/embedding padded to vocab 30528 (128-multiple)
                  — logits GEMM N-dim tiles evenly
  batch_256       batch 256: M-dim 32768 rows for every GEMM
  seq_pack        batch 64 x seq 256 (same tokens/step as baseline,
                  longer rows — fewer, larger attention GEMMs)
  remat_dots      jax.checkpoint(dots_saveable): recompute elementwise
                  chains in backward, keep matmul outputs

Each config reports samples/s with bench-style k-step scan timing (async
back-to-back dispatches, one sync per window, best of 3 windows).

Usage: PYTHONPATH=. python benchmarks/bert_gemm_probe.py
       [--configs baseline vocab_pad ...]
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def measure(name, batch, seq, vocab, on_tpu, remat=None, dropout=0.1,
            master_dtype=None, flatten=True):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import bert

    if on_tpu:
        net = bert.get_bert_model(
            "bert_12_768_12", vocab_size=vocab, max_length=max(512, seq),
            dropout=dropout, use_pooler=False, use_classifier=False)
    else:
        net = bert.BERTModel(num_layers=2, units=64, hidden_size=128,
                             num_heads=4, max_length=max(128, seq),
                             vocab_size=vocab, use_pooler=False,
                             use_classifier=False)
    net.initialize(mx.init.Normal(0.02))

    class MLMWrapper(gluon.HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def hybrid_forward(self, F, tokens):
            _, mlm = self.inner(tokens)
            return F.reshape(mlm, (-1, vocab)) if flatten else mlm

    class FlatCE(gluon.loss.Loss):
        amp_safe = property(lambda self: self._ce.amp_safe)

        def __init__(self):
            super().__init__(None, 0)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, pred, label):
            if flatten:
                label = F.reshape(label, (-1,))
            return self._ce(pred, label)

    mesh = parallel.make_mesh({"data": len(jax.devices())})
    trainer = parallel.ShardedTrainer(
        MLMWrapper(net), FlatCE(), "adam", {"learning_rate": 1e-4},
        mesh=mesh, compute_dtype="bfloat16" if on_tpu else None,
        remat=remat, master_dtype=master_dtype)
    toks = np.random.randint(0, min(vocab, 30000), (batch, seq))

    k = 8 if on_tpu else 2
    dispatches = 4 if on_tpu else 1
    np.asarray(trainer.run_steps(toks, toks, num_steps=k).asnumpy())
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(dispatches):
            loss = trainer.run_steps(toks, toks, num_steps=k)
        np.asarray(loss.asnumpy())
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    tokens_per_step = batch * seq
    sps128 = tokens_per_step / 128 * dispatches * k / best  # seq-128-equiv
    print(f"{name:<12} batch={batch:<4} seq={seq:<4} vocab={vocab:<6} "
          f"{best / (dispatches * k) * 1e3:8.1f} ms/step "
          f"{sps128:8.1f} samples(seq128-equiv)/s", flush=True)
    return sps128


def main():
    from mxnet_tpu import runtime
    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", nargs="+", default=None)
    args = ap.parse_args()
    import jax
    on_tpu = jax.devices()[0].platform == "tpu"
    V = 30522 if on_tpu else 512
    VP = 30528 if on_tpu else 512
    B = 128 if on_tpu else 4
    S = 128 if on_tpu else 32
    matrix = {
        "baseline": dict(batch=B, seq=S, vocab=V),
        "vocab_pad": dict(batch=B, seq=S, vocab=VP),
        "batch_256": dict(batch=2 * B, seq=S, vocab=V),
        "seq_pack": dict(batch=B // 2, seq=2 * S, vocab=V),
        "remat_dots": dict(batch=B, seq=S, vocab=V, remat="dots"),
        "no_dropout": dict(batch=B, seq=S, vocab=V, dropout=0.0),
        "bf16_master": dict(batch=B, seq=S, vocab=V,
                            master_dtype="bfloat16"),
        "loss3d": dict(batch=B, seq=S, vocab=V, flatten=False),
        "bf16m_loss3d": dict(batch=B, seq=S, vocab=V, flatten=False,
                             master_dtype="bfloat16"),
    }
    names = args.configs or list(matrix)
    print(f"platform={jax.devices()[0].platform}", flush=True)
    results = {}
    for n in names:
        results[n] = measure(n, on_tpu=on_tpu, **matrix[n])
    if "baseline" in results:
        for n, v in results.items():
            print(f"{n:<12} vs baseline: {v / results['baseline']:.3f}x")


if __name__ == "__main__":
    main()
