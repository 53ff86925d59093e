#!/usr/bin/env python
"""BERT-base pretraining throughput (BASELINE.md metric of record #2:
samples/sec/chip at seq 128; derived 50%-MFU ceiling ≈ 1.2k/chip on v5e).

Same methodology as bench.py: fused multi-step dispatch, windows that end
in ``block_until_ready``, the median window reported. One process, which
owns the chip; without a TPU it refuses. Prints one JSON line.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

METRIC = "bert_base_train_samples_per_sec_per_chip"
BATCH, SEQ, VOCAB = 128, 128, 30522
FLOPS_PER_SAMPLE = 6 * 110e6 * SEQ    # 6·N·T, N = 110 M params (BASELINE.md)


def build_trainer(num_layers=12, vocab=VOCAB, mesh=None):
    """BERT-base masked-LM trainer at its published width (units 768,
    12 heads, FFN 3072; ``num_layers`` is the only cut): bf16 compute, bf16
    master weights + adam moments. ``chip_smoke.py`` takes its steps on
    this same construction."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import bert

    net = bert.get_bert_model(
        "bert_12_768_12", vocab_size=vocab, max_length=512, dropout=0.1,
        use_pooler=False, use_classifier=False, num_layers=num_layers)
    net.initialize(mx.init.Normal(0.02))

    class MLMWrapper(gluon.HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def hybrid_forward(self, F, tokens):
            # keep the logits 3-D (B, S, V): the CE loss picks/reduces over
            # the last axis in place — flattening to (B*S, V) forced XLA to
            # relayout the 1 GB logits tensor (copy.1217, 2 GB of HBM
            # traffic, docs/perf_notes.md round 4)
            _, mlm = self.inner(tokens)
            return mlm

    # bf16 master weights + adam moments: adam state is 3×fp32 tensors of
    # param size — on a 110 M-param model that is ~2.6 GB/step of optimizer
    # traffic, +10.5% measured when halved (perf_notes round 4); conver-
    # gence-gated against fp32 masters in tests/test_convergence.py
    if mesh is None:
        mesh = parallel.make_mesh({"data": len(jax.devices())})
    return parallel.ShardedTrainer(
        MLMWrapper(net), gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-4},
        mesh=mesh, compute_dtype="bfloat16", master_dtype="bfloat16")


def main():
    from mxnet_tpu import runtime

    runtime.enable_compile_cache()
    devices = runtime.tpu_devices(METRIC)   # raises without a TPU
    # the ceiling BASELINE.md derives: 50% MFU at the chip's published peak
    # (1166 samples/s on a v5e); a chip the peaks table lacks is an error
    ceiling = (0.5 * runtime.device_peaks(devices[0])["bf16_flops_per_s"]
               / FLOPS_PER_SAMPLE)
    k, steps, windows = 8, 4, 3

    trainer = build_trainer()
    toks = np.random.randint(0, VOCAB, (BATCH, SEQ))
    trainer.run_steps(toks, toks, num_steps=k).wait_to_read()
    rates = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = trainer.run_steps(toks, toks, num_steps=k)
        loss.wait_to_read()
        dt = time.perf_counter() - t0
        rates.append(BATCH * steps * k / dt / len(devices))
    sps = statistics.median(rates)
    print(json.dumps({
        "metric": METRIC,
        "value": round(sps, 2),
        "unit": f"samples/sec/chip (batch={BATCH}, seq={SEQ}, bf16)",
        "device": runtime.device_record(devices),
        "windows": [round(r, 2) for r in rates],
        "vs_baseline": round(sps / ceiling, 4),
        "baseline": {"samples_per_sec_per_chip": round(ceiling, 1),
                     "what": "50% MFU at the chip's peak bf16 FLOP/s"},
    }))


if __name__ == "__main__":
    main()
