#!/usr/bin/env python
"""Faster R-CNN training (driver config #5, second family; ref: the
reference's example/rcnn). Synthetic boxes by default — swap in an
ImageDetRecordIter pack for real data (see train_ssd.py).

Usage: python examples/train_faster_rcnn.py [--steps 50] [--image-size 128]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    from mxnet_tpu import runtime
    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--image-size", type=int, default=128)
    ap.add_argument("--classes", type=int, default=3)
    ap.add_argument("--lr", type=float, default=5e-4)
    args = ap.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo.faster_rcnn import (FasterRCNNLoss,
                                                       faster_rcnn_resnet)

    np.random.seed(0)
    H = args.image_size
    net = faster_rcnn_resnet(classes=args.classes,
                             rpn_pre_nms_top_n=200,
                             rpn_post_nms_top_n=32)
    net.initialize(mx.init.Xavier())
    net.hybridize()   # loss matching is in-graph since round 4
    loss_fn = FasterRCNNLoss(net)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})

    def synth_batch(rng=np.random):
        x = rng.rand(args.batch, 3, H, H).astype(np.float32)
        gt = np.full((args.batch, 2, 5), -1.0, np.float32)
        for i in range(args.batch):
            cls = rng.randint(0, args.classes)
            x0, y0 = rng.randint(0, H // 2, 2)
            w, h = rng.randint(H // 4, H // 2, 2)
            gt[i, 0] = [cls, x0, y0, min(x0 + w, H - 1),
                        min(y0 + h, H - 1)]
            # paint the object region so there is signal to localize
            x[i, cls % 3, y0:y0 + h, x0:x0 + w] += 1.0
        return x, gt

    im_info = np.array([[H, H, 1.0]] * args.batch, np.float32)

    # progress is read on batches held out before training: every training
    # batch is fresh and its loss swings by a factor of two, so the lines
    # printed along the way say little about learning
    held_rng = np.random.RandomState(12345)
    held_out = [synth_batch(held_rng) for _ in range(4)]

    def held_out_loss():
        total = 0.0
        for x, gt in held_out:
            with autograd.record():     # batch statistics, as in training
                outs = net(nd.array(x), nd.array(im_info))
                total += float(loss_fn(outs, nd.array(gt),
                                       (H, H)).asscalar())
        return total / len(held_out)

    before = held_out_loss()
    t0 = time.time()
    for step in range(args.steps):
        x, gt = synth_batch()
        with autograd.record():
            outs = net(nd.array(x), nd.array(im_info))
            loss = loss_fn(outs, nd.array(gt), (H, H))
        loss.backward()
        trainer.step(args.batch)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(loss.asscalar()):8.4f}  "
                  f"({time.time() - t0:.1f}s)")
    print(f"held-out loss {before:.4f} -> {held_out_loss():.4f}")
    print("done")


if __name__ == "__main__":
    main()
