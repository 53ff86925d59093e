#!/usr/bin/env python
"""Config #2 — ResNet-50 classification at scale (ref: example/
image-classification/train_imagenet.py).

The whole train step — forward, loss, backward, gradient all-reduce over
the `data` mesh axis, SGD update — is ONE jitted SPMD program
(parallel.ShardedTrainer). Feed real data with --rec (an ImageRecordIter
pack made by tools/im2rec.py); otherwise synthetic batches measure the
compute path like the reference's benchmark_score.py.
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
from mxnet_tpu import gluon, io, parallel
from mxnet_tpu.gluon.model_zoo import vision


def main():
    mx.runtime.enable_compile_cache()
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser()
    p.add_argument("--network", default="resnet50_v1")
    p.add_argument("--batch-size", type=int, default=256,
                   help="global batch size")
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--image-shape", default="3,224,224")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--wd", type=float, default=1e-4)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--steps-per-epoch", type=int, default=50)
    p.add_argument("--rec", default=None, help="path to .rec pack")
    p.add_argument("--idx", default=None)
    p.add_argument("--bf16", action="store_true", default=True)
    p.add_argument("--no-bf16", dest="bf16", action="store_false")
    p.add_argument("--model-parallel", type=int, default=1,
                   help="tensor-parallel mesh axis size")
    p.add_argument("--checkpoint", default=None,
                   help="prefix for periodic ShardedTrainer checkpoints "
                        "(bit-exact resume incl. optimizer state + RNG)")
    p.add_argument("--checkpoint-every", type=int, default=1,
                   help="epochs between checkpoints (>= 1)")
    p.add_argument("--resume", action="store_true",
                   help="load <prefix>.params/.states before training "
                        "(keep --steps-per-epoch identical to the saved "
                        "run: the resume epoch derives from it)")
    args = p.parse_args()
    if args.checkpoint and args.checkpoint_every < 1:
        p.error("--checkpoint-every must be >= 1")

    import jax
    shape = tuple(int(s) for s in args.image_shape.split(","))
    n_dev = len(jax.devices())
    mesh = parallel.make_mesh({"data": n_dev // args.model_parallel,
                               "model": args.model_parallel})
    net = vision.get_model(args.network, classes=args.num_classes)
    net.initialize(mx.init.Xavier())
    rules = []
    if args.model_parallel > 1:
        from mxnet_tpu.parallel import PartitionSpec as P
        rules = [(r".*dense\d+_weight", P("model", None)),
                 (r".*stage4_.*conv2d\d+_weight", P("model", None, None,
                                                  None))]
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        optimizer_params={"learning_rate": args.lr, "momentum": 0.9,
                          "wd": args.wd},
        mesh=mesh, param_rules=rules,
        compute_dtype="bfloat16" if args.bf16 else None)

    if args.rec:
        data = io.ImageRecordIter(
            path_imgrec=args.rec, path_imgidx=args.idx,
            data_shape=shape, batch_size=args.batch_size, shuffle=True,
            rand_crop=True, rand_mirror=True, resize=256,
            mean_r=123.68, mean_g=116.28, mean_b=103.53,
            std_r=58.4, std_g=57.1, std_b=57.4)
        data = io.PrefetchingIter(data)
    else:
        logging.warning("no --rec given: synthetic data (compute bench)")
        data = None
        rng = np.random.RandomState(0)   # fixed batch: CI gates on loss
        x = rng.randn(args.batch_size, *shape).astype(np.float32)
        y = rng.randint(0, args.num_classes, (args.batch_size,))

    import json
    start_epoch = 0
    if args.resume:
        if not args.checkpoint:
            p.error("--resume needs --checkpoint <prefix>")
        example = (x if data is None else
                   np.zeros((args.batch_size,) + shape, np.float32))
        trainer.prepare(example)
        trainer.load_checkpoint(args.checkpoint)
        # epoch count comes from the progress sidecar, NOT from
        # num_update // steps_per_epoch: a real-data epoch can end early
        # (iterator exhaustion), which would under-count completed epochs
        try:
            with open(args.checkpoint + ".progress") as f:
                start_epoch = json.load(f)["epoch"]
        except FileNotFoundError:
            start_epoch = trainer.num_update // args.steps_per_epoch
        logging.info("resumed from %s at update %d (epoch %d)",
                     args.checkpoint, trainer.num_update, start_epoch)

    def save(epoch):
        trainer.save_checkpoint(args.checkpoint)
        with open(args.checkpoint + ".progress", "w") as f:
            json.dump({"epoch": epoch + 1}, f)
        logging.info("checkpointed to %s.{params,states} (epoch %d done)",
                     args.checkpoint, epoch)

    for epoch in range(start_epoch, args.epochs):
        tic = time.time()
        seen = 0
        if data is not None:
            data.reset()
            it = iter(data)
        for step in range(args.steps_per_epoch):
            if data is not None:
                try:
                    batch = next(it)
                except StopIteration:
                    break
                loss = trainer.step(batch.data[0], batch.label[0])
            else:
                loss = trainer.step(x, y)
            seen += args.batch_size
            if step % 20 == 0:
                logging.info("Epoch[%d] Batch [%d]\tloss=%.4f", epoch,
                             step, loss.asscalar())
        dt = time.time() - tic
        logging.info("Epoch[%d] final loss=%.4f", epoch, loss.asscalar())
        logging.info("Epoch[%d] Speed: %.2f samples/sec (%d chips)",
                     epoch, seen / dt, n_dev)
        if args.checkpoint and ((epoch + 1) % args.checkpoint_every == 0
                                or epoch + 1 == args.epochs):
            save(epoch)   # always checkpoint the final epoch too


if __name__ == "__main__":
    main()
