"""Real-input-pipeline ResNet-50 training throughput.

The headline bench (bench.py) stages one synthetic batch on-device; this
variant feeds the SAME fused train step from an actual RecordIO pack
through ImageRecordIter / a raw-record reader + PrefetchingIter —
measuring the trainable end-to-end rate (SURVEY §2 #34's double-buffered
host→device pipeline, ref: src/io/iter_image_recordio_2.cc +
iter_prefetcher.h).

Two pack formats:
  --format jpeg  JPEG-encoded records (the reference's ImageRecordIO):
                 decode+augment dominates on weak hosts.
  --format raw   uint8 CHW tensors in the records; normalization runs ON
                 DEVICE as the first op of the compiled step (cast+scale
                 fused into the first conv) — the TPU-idiomatic split:
                 the host only reads, batches, and ships bytes.

Prints per-variant images/sec/chip next to the synthetic-batch number so
the input-pipeline overhead is explicit. On a host with few cores the
jpeg variant is decode-bound by design — the number demonstrates overlap,
not the TPU's ceiling.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np


def make_packs(tmpdir, n, shape_hw, fmt):
    """Generate a labeled pack of random images (once, cached)."""
    import mxnet_tpu as mx
    from mxnet_tpu import recordio
    h, w = shape_hw
    path = os.path.join(tmpdir, f"bench_{fmt}_{n}_{h}.rec")
    idxp = path.replace(".rec", ".idx")
    if os.path.exists(path) and os.path.exists(idxp):
        return path, idxp
    rec = recordio.MXIndexedRecordIO(idxp, path, "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        label = float(rng.randint(0, 1000))
        header = recordio.IRHeader(0, label, i, 0)
        img = rng.randint(0, 256, (h, w, 3), dtype=np.uint8)
        if fmt == "jpeg":
            s = recordio.pack_img(header, img, quality=90)
        else:
            s = recordio.pack(header, img.tobytes())
        rec.write_idx(i, s)
    rec.close()
    return path, idxp


class RawRecordIter:
    """Minimal raw-uint8 record iterator: read, batch, ship — all
    augment/normalize deferred to the device (the TPU-side of the
    reference's decode pipeline split)."""

    def __init__(self, path_imgrec, path_imgidx, data_shape, batch_size):
        from mxnet_tpu import io as mio
        from mxnet_tpu import recordio
        self.batch_size = batch_size
        self._shape = data_shape            # (C, H, W) logical
        self._rec = recordio.MXIndexedRecordIO(path_imgidx, path_imgrec,
                                               "r")
        self._keys = list(self._rec.keys)
        self._pos = 0
        self._unpack = recordio.unpack

    def reset(self):
        self._pos = 0

    def __iter__(self):
        return self

    def __next__(self):
        import mxnet_tpu as mx
        from mxnet_tpu.io import DataBatch
        if self._pos + self.batch_size > len(self._keys):
            raise StopIteration
        c, h, w = self._shape
        datas = np.empty((self.batch_size, h, w, c), np.uint8)
        labels = np.empty((self.batch_size,), np.float32)
        for j in range(self.batch_size):
            header, payload = self._unpack(
                self._rec.read_idx(self._keys[self._pos + j]))
            datas[j] = np.frombuffer(payload, np.uint8).reshape(h, w, c)
            labels[j] = header.label
        self._pos += self.batch_size
        return DataBatch(data=[mx.nd.array(datas)],
                         label=[mx.nd.array(labels)])

    def next(self):
        return self.__next__()


def decode_scaling(tmpdir, n_images, hw, batch, threads_list):
    """Host-only decode+augment scaling curve vs preprocess_threads —
    the reference's parser→augmenter thread pipeline knob
    (src/io/iter_image_recordio_2.cc). No device involved: measures the
    iterator's own throughput."""
    from mxnet_tpu import io as mio
    rec_path, idx_path = make_packs(tmpdir, n_images, hw, "jpeg")
    base = None
    print(f"decode scaling (jpeg {hw[0]}x{hw[1]}, {n_images} imgs, "
          f"host cores={os.cpu_count()}):")
    for t in threads_list:
        it = mio.ImageRecordIter(
            path_imgrec=rec_path, path_imgidx=idx_path,
            data_shape=(3,) + hw, batch_size=batch, shuffle=True,
            rand_crop=True, rand_mirror=True, preprocess_threads=t,
            mean_r=127.5, mean_g=127.5, mean_b=127.5,
            std_r=127.5, std_g=127.5, std_b=127.5)
        for trial in range(2):                  # 2nd pass = warm page cache
            it.reset()
            n = 0
            t0 = time.perf_counter()
            for b in it:
                n += b.data[0].shape[0]
            dt = time.perf_counter() - t0
        ips = n / dt
        if t == threads_list[0]:
            base = ips
        print(f"  preprocess_threads={t}: {ips:8.1f} img/s "
              f"({ips / base:.2f}x vs {threads_list[0]} thread)")


def main():
    from mxnet_tpu import runtime
    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--n-images", type=int, default=None)
    ap.add_argument("--format", choices=["jpeg", "raw", "both"],
                    default="both")
    ap.add_argument("--decode-scaling", action="store_true",
                    help="host-only preprocess_threads scaling curve")
    ap.add_argument("--threads", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--tmpdir", default="/tmp/mxtpu_bench_data")
    args = ap.parse_args()

    if args.decode_scaling:
        batch = args.batch or 64
        n_images = args.n_images or 1024
        decode_scaling(args.tmpdir, n_images, (224, 224), batch,
                       args.threads)
        return

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, io as mio, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    on_tpu = jax.devices()[0].platform == "tpu"
    batch = args.batch or (256 if on_tpu else 8)
    n_images = args.n_images or (batch * (12 if on_tpu else 3))
    hw = (224, 224) if on_tpu else (64, 64)
    os.makedirs(args.tmpdir, exist_ok=True)

    class OnDeviceNormalize(gluon.HybridBlock):
        """uint8 NHWC -> normalized NCHW in the compute dtype, inside the
        compiled step (fuses into the first conv's operand read)."""

        def __init__(self, inner, dtype):
            super().__init__()
            self.inner = inner
            self._dtype = dtype

        def hybrid_forward(self, F, x):
            import jax
            # compute dtype applies inside the traced step (weights are
            # bf16 there); the eager shape-resolution pass runs fp32
            traced = isinstance(getattr(x, "_data", None),
                                jax.core.Tracer)
            x = F.cast(x, self._dtype if traced else "float32")
            x = F.transpose(x, axes=(0, 3, 1, 2))
            x = x * (1.0 / 127.5) - 1.0
            return self.inner(x)

    def run(fmt):
        rec_path, idx_path = make_packs(args.tmpdir, n_images, hw, fmt)
        net = vision.resnet50_v1() if on_tpu else \
            vision.resnet18_v1(classes=16, thumbnail=True)
        net.initialize()
        raw = fmt == "raw"
        block = OnDeviceNormalize(
            net, "bfloat16" if on_tpu else "float32") if raw else net
        trainer = parallel.ShardedTrainer(
            block, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "wd": 1e-4},
            mesh=parallel.make_mesh({"data": len(jax.devices())}),
            compute_dtype="bfloat16" if on_tpu else None)

        def fresh_iter():
            if raw:
                inner = RawRecordIter(rec_path, idx_path,
                                      (3,) + hw, batch)
            else:
                inner = mio.ImageRecordIter(
                    path_imgrec=rec_path, path_imgidx=idx_path,
                    data_shape=(3,) + hw, batch_size=batch,
                    shuffle=True, rand_mirror=True,
                    mean_r=127.5, mean_g=127.5, mean_b=127.5,
                    std_r=127.5, std_g=127.5, std_b=127.5)
            return mio.PrefetchingIter(inner, prefetch_depth=3)

        # warm: one epoch compiles the step and fills caches
        it = fresh_iter()
        n_warm = 0
        for b in it:
            trainer.step(b.data[0], b.label[0])
            n_warm += batch
            if n_warm >= 2 * batch:
                break
        # steady state: full pass, async dispatch, one sync at the end
        it = fresh_iter()
        n_done = 0
        t0 = time.perf_counter()
        loss = None
        for b in it:
            loss = trainer.step(b.data[0], b.label[0])
            n_done += batch
        np.asarray(loss.asnumpy())          # hard sync
        dt = time.perf_counter() - t0
        ips = n_done / dt / len(jax.devices())
        print(f"  {fmt:5s}: {ips:8.1f} img/s/chip "
              f"({n_done} imgs in {dt:.2f}s, batch={batch})")
        return ips

    print(f"platform={'tpu' if on_tpu else 'cpu'} "
          f"(host cores={os.cpu_count()})")
    fmts = ["jpeg", "raw"] if args.format == "both" else [args.format]
    for fmt in fmts:
        run(fmt)


if __name__ == "__main__":
    main()
