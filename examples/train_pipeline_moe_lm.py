#!/usr/bin/env python
"""Gluon-level pipeline + expert parallelism (SURVEY §7 P7: pp/ep "exposed
as Gluon-level options"; net-new vs the reference, whose closest tool is
hand ``ctx_group`` placement in example/model-parallel-lstm).

Trains a small transformer LM two ways on one script:
  --mode pp    PipelinedTrainer: [Embedding, N x TransformerEncoderCell,
               Dense head] partitioned onto a pipe x data mesh — no
               hand-written stage closures
  --mode moe   ShardedTrainer over a data x expert mesh with the FFN
               replaced by gluon.contrib.nn.MoEFFN (top-k all-to-all
               dispatch + Switch aux loss, auto-added to the objective)

Synthetic word-LM data; CPU-mesh friendly (the same code drives a real
TPU pod by changing the mesh dict).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# default to a virtual 8-device CPU mesh (the tests/conftest.py recipe)
# when nothing chose a platform — the default meshes need 8 devices; a
# real TPU run sets JAX_PLATFORMS/XLA_FLAGS itself and is left alone
if "jax" not in sys.modules and not os.environ.get("JAX_PLATFORMS") and \
        "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu import gluon, parallel                     # noqa: E402
from mxnet_tpu.gluon.contrib.nn import MoEFFN             # noqa: E402
from mxnet_tpu.gluon.model_zoo.bert import (              # noqa: E402
    TransformerEncoderCell)
from mxnet_tpu.parallel import PartitionSpec as P         # noqa: E402


def synthetic_batches(vocab, batch, seq, steps, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(vocab, vocab)
    for _ in range(steps):
        toks = rng.randint(0, vocab, (batch, seq))
        yield toks, w[toks].argmax(-1)


def run_pp(args):
    mesh = parallel.make_mesh({"pipe": args.pipe, "data": args.data})
    mx.random.seed(1)
    emb = gluon.nn.Embedding(args.vocab, args.units)
    body = [TransformerEncoderCell(args.units, 2 * args.units, 4,
                                   dropout=0.0)
            for _ in range(args.layers)]
    head = gluon.nn.Dense(args.vocab, flatten=False)
    for b in [emb] + body + [head]:
        b.initialize()
    trainer = parallel.PipelinedTrainer(
        emb, body, head, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": args.lr}, mesh=mesh,
        num_microbatches=args.microbatches,
        num_virtual_stages=args.layers // args.pipe)
    info = parallel.pipeline_schedule_info(
        args.pipe, args.microbatches, args.layers // args.pipe)
    print(f"pipeline schedule: {info}")
    return trainer, mesh


def run_moe(args):
    mesh = parallel.make_mesh({"data": args.data, "expert": args.experts})

    class MoELM(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.emb = gluon.nn.Embedding(args.vocab, args.units)
                self.cell = TransformerEncoderCell(args.units,
                                                   2 * args.units, 4,
                                                   dropout=0.0)
                self.moe = MoEFFN(units=args.units,
                                  hidden_size=2 * args.units,
                                  num_experts=args.experts, k=2,
                                  capacity_factor=2.0)
                self.head = gluon.nn.Dense(args.vocab, flatten=False)

        def hybrid_forward(self, F, x):
            h = self.cell(self.emb(x))
            return self.head(h + self.moe(h))

    mx.random.seed(1)
    net = MoELM()
    net.initialize()
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": args.lr}, mesh=mesh,
        param_rules=[(r".*expert_.*", P("expert"))])
    return trainer, mesh


def main():
    mx.runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["pp", "moe"], default="pp")
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--units", type=int, default=32)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--pipe", type=int, default=2)
    ap.add_argument("--data", type=int, default=None,
                    help="data-parallel ranks (default: 4 for pp, 2 for "
                         "moe — both fill the 8-device default mesh)")
    ap.add_argument("--experts", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--lr", type=float, default=2e-3)
    args = ap.parse_args()
    if args.data is None:
        args.data = 4 if args.mode == "pp" else 2

    trainer, mesh = run_pp(args) if args.mode == "pp" else run_moe(args)
    print(f"mode={args.mode} mesh={dict(zip(mesh.axis_names, mesh.shape.values()))}")
    t0, first = time.time(), None
    for i, (x, y) in enumerate(synthetic_batches(
            args.vocab, args.batch, args.seq, args.steps)):
        loss = float(trainer.step(x, y).asscalar())
        first = first if first is not None else loss
        if i % 10 == 0:
            print(f"step {i:3d}  loss {loss:.4f}")
    print(f"loss {first:.4f} -> {loss:.4f} in {time.time()-t0:.1f}s")
    assert loss < first, "loss did not decrease"


if __name__ == "__main__":
    main()
