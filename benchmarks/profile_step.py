#!/usr/bin/env python
"""Capture a hardware profile of a train step and print an HBM traffic
budget per HLO op class (round-4 verdict #2: "HLO-level traffic table").

Captures an xplane trace of k scanned train steps with jax.profiler,
then converts it with xprof's raw_to_tool_data (the same machinery the
tensorboard profile plugin uses) into hlo_stats, and aggregates
time and bytes-accessed per op category.

Usage:
  python benchmarks/profile_step.py rn50
  python benchmarks/profile_step.py bert \
      [--master-dtype bfloat16]

Prints: per-category table (self time ms, GB accessed per step, % of
step) + the top 15 individual HLO fusions by bytes.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_rn50(master_dtype):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    net = resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    mesh = parallel.make_mesh({"data": len(jax.devices())})
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh, compute_dtype="bfloat16",
        master_dtype=master_dtype)
    x = np.random.uniform(-1, 1, (256, 3, 224, 224)).astype(np.float32)
    y = np.random.randint(0, 1000, (256,))
    return trainer, (x, y)


def build_bert(master_dtype):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import bert

    vocab = 30522
    net = bert.get_bert_model(
        "bert_12_768_12", vocab_size=vocab, max_length=512,
        dropout=0.1, use_pooler=False, use_classifier=False)
    net.initialize(mx.init.Normal(0.02))

    class MLMWrapper(gluon.HybridBlock):
        # 3-D logits, same as benchmarks/bert.py's shipped config (the
        # flat reshape forced a 2 GB logits relayout — perf_notes round 4)
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
        mesh=mesh, compute_dtype="bfloat16", master_dtype=master_dtype)
    toks = np.random.randint(0, 30000, (128, 128))
    return trainer, (toks, toks)


def main():
    from mxnet_tpu import runtime
    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("model", choices=["rn50", "bert"])
    ap.add_argument("--master-dtype", default=None)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the raw trace in")
    args = ap.parse_args()

    import jax

    trainer, batch = (build_rn50 if args.model == "rn50"
                      else build_bert)(args.master_dtype)
    k = args.steps
    # warm up / compile outside the trace
    np.asarray(trainer.run_steps(*batch, num_steps=k).asnumpy())

    tracedir = args.keep_trace or tempfile.mkdtemp(prefix="mxtpu_trace_")
    with jax.profiler.trace(tracedir):
        np.asarray(trainer.run_steps(*batch, num_steps=k).asnumpy())

    # same xprof hlo_stats pipeline mx.profiler.device_stats uses
    from mxnet_tpu.profiler import _parse_hlo_stats
    rows = _parse_hlo_stats(tracedir)

    def field(row, label, default=0.0):
        v = row.get(label)
        if v in (None, ""):
            return default
        try:
            return float(v)
        except (TypeError, ValueError):
            return v

    total_time = 0.0
    cats = {}
    tops = []
    for r in rows:
        name = field(r, "HLO op name", "")
        cat = field(r, "HLO op category", "") or "uncategorized"
        t = field(r, "Total self time (us)")
        occ = field(r, "#Occurrences", 1.0)
        hbm_bw = field(r, "HBM BW (GiB/s)")       # GiB/s of self time
        mem_bw = field(r, "Measured memory BW (GiB/s)")
        bound = field(r, "Bound by", "")
        hbm_gb = hbm_bw * (t / 1e6) * 1.073741824
        c = cats.setdefault(cat, [0.0, 0.0, 0.0])
        c[0] += t
        c[1] += hbm_gb
        c[2] += occ
        total_time += t
        tops.append((t, hbm_gb, name, cat, bound, mem_bw))

    per_step = k
    print(f"model={args.model} master_dtype={args.master_dtype} "
          f"steps_traced={k}")
    print(f"{'category':<28} {'ms/step':>9} {'HBM GB/step':>12} "
          f"{'%time':>6} {'#ops':>6}")
    for label, (t, g, n) in sorted(cats.items(), key=lambda kv: -kv[1][0]):
        print(f"{label:<28} {t / 1e3 / per_step:9.3f} "
              f"{g / per_step:12.2f} {100 * t / total_time:6.1f} "
              f"{int(n / per_step):>6}")
    print(f"{'TOTAL':<28} {total_time / 1e3 / per_step:9.3f} "
          f"{sum(c[1] for c in cats.values()) / per_step:12.2f}")
    print("\ntop HLO ops by self time:")
    for t, g, name, label, bound, mem_bw in sorted(tops, reverse=True)[:20]:
        print(f"  {t / 1e3 / per_step:7.3f} ms/step {g / per_step:7.2f} "
              f"HBM-GB  bound:{str(bound):<11} {label:<22} {name[:58]}")
    if not args.keep_trace:
        import shutil
        shutil.rmtree(tracedir, ignore_errors=True)


if __name__ == "__main__":
    main()
