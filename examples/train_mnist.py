#!/usr/bin/env python
"""Config #1 — LeNet-5 on MNIST (ref: example/image-classification/
train_mnist.py). Both worlds: Gluon (default) and symbolic Module
(--module). Uses real MNIST files under --data-dir when present, else a
synthetic stand-in so the script always runs.
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
from mxnet_tpu import autograd, gluon, io


def lenet_gluon():
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Conv2D(20, 5, activation="tanh"),
                gluon.nn.MaxPool2D(2, 2),
                gluon.nn.Conv2D(50, 5, activation="tanh"),
                gluon.nn.MaxPool2D(2, 2),
                gluon.nn.Flatten(),
                gluon.nn.Dense(500, activation="tanh"),
                gluon.nn.Dense(10))
    return net


def lenet_symbol():
    from mxnet_tpu import sym
    data = sym.var("data")
    c1 = sym.Activation(sym.Convolution(data, kernel=(5, 5), num_filter=20),
                        act_type="tanh")
    p1 = sym.Pooling(c1, pool_type="max", kernel=(2, 2), stride=(2, 2))
    c2 = sym.Activation(sym.Convolution(p1, kernel=(5, 5), num_filter=50),
                        act_type="tanh")
    p2 = sym.Pooling(c2, pool_type="max", kernel=(2, 2), stride=(2, 2))
    f = sym.Flatten(p2)
    fc1 = sym.Activation(sym.FullyConnected(f, num_hidden=500),
                         act_type="tanh")
    fc2 = sym.FullyConnected(fc1, num_hidden=10)
    return sym.SoftmaxOutput(fc2, name="softmax")


def get_iters(args):
    img = os.path.join(args.data_dir, "train-images-idx3-ubyte")
    lbl = os.path.join(args.data_dir, "train-labels-idx1-ubyte")
    if os.path.exists(img) or os.path.exists(img + ".gz"):
        train = io.MNISTIter(image=img, label=lbl,
                             batch_size=args.batch_size)
        timg = os.path.join(args.data_dir, "t10k-images-idx3-ubyte")
        tlbl = os.path.join(args.data_dir, "t10k-labels-idx1-ubyte")
        val = io.MNISTIter(image=timg, label=tlbl,
                           batch_size=args.batch_size, shuffle=False)
        return train, val
    logging.warning("MNIST files not found under %s — synthetic data",
                    args.data_dir)
    rng = np.random.RandomState(0)
    n = 2048
    x = rng.rand(n, 1, 28, 28).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.float32)
    # make it learnable: brighten a quadrant per class
    for i in range(n):
        c = int(y[i])
        x[i, 0, (c // 4) * 7:(c // 4) * 7 + 7, (c % 4) * 7:(c % 4) * 7 + 7] += 2.0
    split = n - 512
    return (io.NDArrayIter(x[:split], y[:split], args.batch_size,
                           shuffle=True),
            io.NDArrayIter(x[split:], y[split:], args.batch_size))


def train_gluon(args, train, val):
    net = lenet_gluon()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = mx.metric.Accuracy()
    for epoch in range(args.epochs):
        train.reset()
        metric.reset()
        for batch in train:
            x, y = batch.data[0], batch.label[0]
            with autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(x.shape[0])
            metric.update([y], [out])
        logging.info("Epoch[%d] Train-%s=%f", epoch, *metric.get())
        val.reset()
        metric.reset()
        for batch in val:
            metric.update([batch.label[0]], [net(batch.data[0])])
        logging.info("Epoch[%d] Validation-%s=%f", epoch, *metric.get())
    return metric.get()[1]


def train_module(args, train, val):
    mod = mx.mod.Module(lenet_symbol(), context=mx.context.current_context())
    mod.fit(train, eval_data=val, num_epoch=args.epochs, optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9},
            initializer=mx.init.Xavier(),
            batch_end_callback=mx.callback.Speedometer(args.batch_size, 50))
    return mod.score(val, "acc")[0][1]


def main():
    mx.runtime.enable_compile_cache()
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser()
    p.add_argument("--data-dir", default=os.path.expanduser(
        "~/.mxnet/datasets/mnist"))
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--module", action="store_true",
                   help="use the symbolic Module API path")
    args = p.parse_args()
    train, val = get_iters(args)
    acc = (train_module if args.module else train_gluon)(args, train, val)
    print(f"final accuracy: {acc:.4f}")


if __name__ == "__main__":
    main()
