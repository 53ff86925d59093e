#!/usr/bin/env python
"""INT8 vs fp32 inference throughput on the chip (SURVEY §2 row 19's
perf story: the reference quantizes with cuDNN/MKLDNN int8 kernels;
here int8 lowers to XLA `dot_general`/conv with int32 accumulation).

Measures resnet50_v1 batch-256 inference in both precisions plus the
speedup ratio and a top-1 agreement check; prints one JSON line each.

Usage: python benchmarks/int8_inference.py
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def measure(name, fn, x, k, dispatches=4, windows=3):
    """Async back-to-back dispatches, one hard sync per window (the
    bert_gemm_probe methodology — PjRt pipelines the queue so host
    dispatch overlaps the device)."""
    import jax

    xd = jax.device_put(x)
    np.asarray(fn(xd))                          # compile + warm
    best = None
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(dispatches * k):
            out = fn(xd)
        np.asarray(out)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    ips = x.shape[0] * dispatches * k / best / len(jax.devices())
    print(json.dumps({
        "metric": f"resnet50_infer_{name}_images_per_sec",
        "value": round(ips, 1),
        "unit": f"images/sec/chip (batch={x.shape[0]})",
        "ms_per_batch": round(best / dispatches / k * 1e3, 2)}))
    return ips


def main():
    from mxnet_tpu import runtime
    runtime.enable_compile_cache()
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.contrib import quantization as q
    from mxnet_tpu.gluon.model_zoo import vision

    on_tpu = jax.devices()[0].platform == "tpu"
    batch = 256 if on_tpu else 4
    size = 224 if on_tpu else 32
    k = 8 if on_tpu else 2

    net = vision.resnet50_v1() if on_tpu else \
        vision.resnet18_v1(classes=10, thumbnail=True)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    rng = np.random.RandomState(0)
    x = rng.rand(batch, 3, size, size).astype(np.float32)
    net(nd.array(x[:2]))                       # materialize params

    # fp32 path straight off the hybridized block's traced fn
    def run_fp32(xx):
        return net(nd.NDArray(xx))._data

    qnet = q.quantize_net(net, calib_data=[x[:64]], calib_mode="minmax")

    def run_int8(xx):
        return qnet(nd.NDArray(xx))._data

    r32 = measure("fp32", run_fp32, x, k)
    r8 = measure("int8", run_int8, x, k)
    print(json.dumps({"metric": "int8_speedup_vs_fp32",
                      "value": round(r8 / r32, 3), "unit": "x"}))
    # accuracy drift check on the same batch
    p32 = net(nd.array(x[:64])).asnumpy().argmax(1)
    p8 = qnet(nd.array(x[:64])).asnumpy().argmax(1)
    print(json.dumps({"metric": "int8_top1_agreement",
                      "value": round(float((p32 == p8).mean()), 4),
                      "unit": "fraction"}))


if __name__ == "__main__":
    main()
