"""box_nms micro-benchmark: fixed-point matrix NMS (shipped) vs the
round-1 sequential fori_loop formulation, at SSD-like sizes.

Run: PYTHONPATH=. python benchmarks/nms_bench.py [--n 400]
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def _sequential_nms_one(rows, overlap_thresh, k):
    """The round-1 formulation: O(topk) serial fori_loop (baseline)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops.contrib import _box_iou_corner
    scores = rows[:, 1]
    boxes = rows[:, 2:6]
    valid = scores > 0.0
    order = jnp.argsort(-jnp.where(valid, scores, -jnp.inf))
    n = rows.shape[0]
    iou = _box_iou_corner(boxes[order], boxes[order])
    valid_sorted = valid[order]

    def body(i, keep):
        sup = (iou[i] > overlap_thresh) & keep[i] & (jnp.arange(n) > i)
        return jnp.where(sup, False, keep)

    keep = lax.fori_loop(0, k, body, valid_sorted)
    keep &= jnp.arange(n) < k
    perm = jnp.argsort(~keep, stable=True)
    return jnp.where(jnp.sort(~keep, stable=True)[:, None],
                     -jnp.ones_like(rows), rows[order][perm])


def main():
    from mxnet_tpu import runtime
    runtime.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=400)
    ap.add_argument("--batch", type=int, default=32)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp


    rng = np.random.RandomState(0)
    n, b = args.n, args.batch
    ctr = rng.rand(b, n, 2) * 100
    wh = rng.rand(b, n, 2) * 20 + 1
    boxes = np.concatenate([ctr - wh / 2, ctr + wh / 2], -1)
    ids = rng.randint(0, 20, (b, n, 1)).astype(np.float32)
    scores = rng.rand(b, n, 1).astype(np.float32)
    data = np.concatenate([ids, scores, boxes.astype(np.float32)], -1)

    from jax import lax

    def scan_time(core, k1=4, k2=64):
        """Per-call device time with the dispatch round-trip differenced
        out (same methodology as perf_probe.py)."""
        def make(k):
            def run(d):
                def body(c, _):
                    out = core(d + (c * 1e-30).astype(d.dtype))
                    return jnp.sum(out[..., 0]).astype(jnp.float32), None
                c, _ = lax.scan(body, jnp.zeros(()), None, length=k)
                return c
            return jax.jit(run)
        f1, f2 = make(k1), make(k2)
        xd = jnp.asarray(data)
        np.asarray(f1(xd)), np.asarray(f2(xd))

        def tmin(f, it=4):
            best = None
            for _ in range(it):
                t0 = time.perf_counter()
                np.asarray(f(xd))
                dt = time.perf_counter() - t0
                best = dt if best is None or dt < best else best
            return best
        return (tmin(f2) - tmin(f1)) / (k2 - k1)

    from mxnet_tpu.ops.contrib import _box_nms
    t_new = scan_time(lambda d: _box_nms(
        d, overlap_thresh=0.5, topk=n, coord_start=2, score_index=1,
        id_index=0, force_suppress=True))
    t_old = scan_time(jax.vmap(lambda r: _sequential_nms_one(r, 0.5, n)))

    print(f"n={n} batch={b}: sequential {t_old*1e3:8.2f} ms | "
          f"fixed-point {t_new*1e3:8.2f} ms | speedup "
          f"{t_old/t_new:5.1f}x")


if __name__ == "__main__":
    main()
