#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once on one TPU chip, through the entry points a user
calls, at the published width of the models the repo is measured on:

  imperative   nd ops / autograd / a Gluon net through gluon.Trainer, on
               ``mx.tpu(0)``; ``hybridize()`` equal to eager
  resnet50     ``vision.resnet50_v1()``, batch 256, 3x224x224, bf16,
               ``ShardedTrainer`` on a one-device mesh (built as
               examples/train_imagenet.py builds it): >=3 ``step()`` and a
               ``run_steps(k)``, loss finite and falling on a fixed batch, no
               compile after the first step
  bert         ``bert_12_768_12`` masked-LM, batch 128 x seq 128, bf16 (built
               by benchmarks/bert.py): the same checks
  kernels      every registered kernel, COMPILED (not interpreted): its own
               examples and one real-width shape, against its
               ``xla_reference`` within its registered tolerance; and the
               library flash-attention call at S=2048
  server       ``serving.Server`` over ``resnet50_v1`` (bf16, batch buckets up
               to 8) in this same process answers a few ``submit()`` requests
               equal to a direct ``net(x)``; compiles = buckets hit

Weights and data are random, from ``--seed``. It is ONE process — a chip
belongs to one process at a time — and starts no children. It exits non-zero
at the first phase that fails, and fails if any kernel fell back for a
``backend:`` reason (every other fallback reason is printed).

The last line of stdout is the result and nothing else:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Without an accelerator it exits non-zero and prints no result; so it does in
a directory that holds this file and nothing else of the repo.

``--multichip`` (four chips, run by hand: ``chiprun --chips 4``) runs ONLY the
sharded trainer — ResNet-50 bs256 bf16 for 3 steps on ``{"data": 4}`` and on
``{"data": 2, "model": 2}`` with tensor-parallel rules, against the same
model, seed and global batch on a one-device mesh — and ends with count 4.

``--rehearse`` runs the same phases at toy sizes on whatever backend there is
(kernels in interpret mode), to find wrong paths and arguments before a chip
call is spent. It proves nothing about the chip: it prints no result line and
exits 3, never 0.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<checkout>/.jax_cache`` (``mxnet_tpu.runtime.enable_compile_cache``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# "chip": the published widths; "rehearsal": toy sizes for a CPU dry run of
# the control flow (BERT keeps its widths and loses depth and vocabulary)
SIZES = {
    "chip": dict(vision_net="resnet50_v1", classes=1000, image=224,
                 train_batch=256, bert_layers=12, bert_vocab=30522,
                 bert_batch=128, bert_seq=128, scan_k=2, serve_requests=8),
    "rehearsal": dict(vision_net="resnet18_v1", classes=10, image=32,
                      train_batch=8, bert_layers=1, bert_vocab=512,
                      bert_batch=4, bert_seq=16, scan_k=2, serve_requests=8),
}
# tensor-parallel rules of __graft_entry__.dryrun_multichip phase 1:
# classifier + the widest convs sharded over `model`
TP_RULES = [(r".*dense\d+_weight", ("model", None)),
            (r".*dense\d+_bias", ("model",)),
            (r".*stage4_.*conv2d\d+_weight", ("model", None, None, None))]
BF16_EPS = 2.0 ** -7          # spacing of bfloat16 relative to magnitude


def say(tag, **facts):
    print(json.dumps({"smoke": tag, **facts}, default=str), flush=True)


class CompileLog:
    """What JAX itself reports about compilation: every program it builds
    (a persistent-cache hit included) with its seconds, and the cache's hits
    and misses."""

    def __init__(self):
        from jax import monitoring
        self.programs, self.seconds, self.hits, self.misses = 0, 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"programs": self.programs,
                "compile_s": round(self.seconds, 1),
                "cache_hits": self.hits, "cache_misses": self.misses}

    def since(self, before):
        now = self.snapshot()
        return {k: round(now[k] - before[k], 1) for k in now}


class HostInit:
    """What the kernel tier recorded while a trainer resolved its deferred
    shapes: that eager pass runs on the default context — the host CPU, as
    in the reference, where ``net.initialize()`` names no ctx — so its
    ``backend:cpu`` fallbacks are about the host. They are shown apart and
    taken out of the count the chip is held to."""

    def __init__(self):
        self.reasons = {}

    def prepare(self, trainer, *example):
        from mxnet_tpu import pallas
        before = pallas.tier_provenance()
        trainer.prepare(*example)
        for name, rec in pallas.tier_provenance().items():
            was = before.get(name, {"fallback_reasons": {}})
            for reason, n in rec["fallback_reasons"].items():
                n -= was["fallback_reasons"].get(reason, 0)
                if n:
                    mine = self.reasons.setdefault(name, {})
                    mine[reason] = mine.get(reason, 0) + n

    def on_the_chip(self, prov):
        """``prov`` without what the host passes put into it."""
        out = {}
        for name, rec in prov.items():
            reasons = {r: n - self.reasons.get(name, {}).get(r, 0)
                       for r, n in rec["fallback_reasons"].items()}
            reasons = {r: n for r, n in reasons.items() if n}
            out[name] = {"pallas": rec["pallas"],
                         "xla": sum(reasons.values()),
                         "fallback_reasons": reasons}
        return out


HOST_INIT = HostInit()


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def on_device(arr, dev, what):
    got = arr._data.devices() if hasattr(arr, "_data") else arr.devices()
    check(got == {dev}, f"{what} lives on {got}, not on {dev}")


# ---------------------------------------------------------------------------
# phase: imperative + Gluon
# ---------------------------------------------------------------------------
def phase_imperative(ctx, S, log, seed):
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    dev = ctx.jax_device
    rng = np.random.RandomState(seed)

    a_np = rng.randn(64, 128).astype(np.float32)
    b_np = rng.randn(128, 32).astype(np.float32)
    a, b = nd.array(a_np, ctx=ctx), nd.array(b_np, ctx=ctx)
    c = nd.relu(nd.dot(a, b) + 1.0)
    on_device(c, dev, "nd op result")
    np.testing.assert_allclose(c.asnumpy(),
                               np.maximum(a_np @ b_np + 1.0, 0.0),
                               rtol=1e-4, atol=1e-4)

    x = nd.array(rng.randn(32, 16).astype(np.float32), ctx=ctx)
    x.attach_grad()
    with autograd.record():
        y = (x * x).sum()
    y.backward()
    on_device(x.grad, dev, "gradient")
    np.testing.assert_allclose(x.grad.asnumpy(), 2 * x.asnumpy(),
                               rtol=1e-5, atol=1e-5)

    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(64, activation="relu"), gluon.nn.Dense(1))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    loss_fn = gluon.loss.L2Loss()
    xs = rng.randn(256, 8).astype(np.float32)
    xb = nd.array(xs, ctx=ctx)
    yb = nd.array((2 * xs[:, 0] + xs[:, 1])[:, None], ctx=ctx)
    losses = []
    for _ in range(25):
        with autograd.record():
            loss = loss_fn(net(xb), yb)     # per-sample: step() rescales
        loss.backward()
        trainer.step(len(xs))
        losses.append(float(loss.mean().asscalar()))
    check(np.isfinite(losses).all() and losses[-1] < 0.5 * losses[0],
          f"gluon.Trainer loss did not halve: {losses[0]} -> {losses[-1]}")
    for name, p in net.collect_params().items():
        on_device(p.data(), dev, f"parameter {name}")

    eager = net(xb).asnumpy()
    net.hybridize()
    hybrid = net(xb)
    on_device(hybrid, dev, "hybridized output")
    np.testing.assert_allclose(hybrid.asnumpy(), eager, rtol=1e-5, atol=1e-5)
    return {"ctx": str(ctx), "device": str(dev),
            "gluon_loss": [round(losses[0], 4), round(losses[-1], 4)]}


# ---------------------------------------------------------------------------
# phases: trainers
# ---------------------------------------------------------------------------
def take_steps(trainer, batch, k, log, dev):
    """>=3 step() and a run_steps(k) on one fixed batch. One compile for
    step() and one for run_steps(k): none after the first call of each."""
    HOST_INIT.prepare(trainer, *(b[:2] for b in batch[:-1]))
    losses, t0 = [], time.perf_counter()
    loss = trainer.step(*batch)
    loss.wait_to_read()
    first_s = time.perf_counter() - t0
    losses.append(float(loss.asscalar()))
    mark, t0 = log.programs, time.perf_counter()
    for _ in range(2):
        loss = trainer.step(*batch)
        loss.wait_to_read()
        losses.append(float(loss.asscalar()))
    step_s = (time.perf_counter() - t0) / 2
    check(log.programs == mark,
          f"{log.programs - mark} program(s) compiled after the first step")
    t0 = time.perf_counter()
    loss = trainer.run_steps(*batch, num_steps=k)
    loss.wait_to_read()
    first_scan_s = time.perf_counter() - t0
    losses.append(float(loss.asscalar()))
    mark = log.programs
    loss = trainer.run_steps(*batch, num_steps=k)
    loss.wait_to_read()
    losses.append(float(loss.asscalar()))
    check(log.programs == mark,
          f"{log.programs - mark} program(s) compiled by the second "
          f"run_steps({k})")
    check(np.isfinite(losses).all(), f"loss not finite: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a fixed batch: {losses}")
    for p in trainer._trainable[:3]:
        on_device(p._data[0], dev, f"trained parameter {p.name}")
    # seconds with compilation and upload of the batch in them: a smoke's
    # phase times, not a throughput (bench.py measures that)
    return {"steps": 3 + 2 * k, "losses": [round(v, 4) for v in losses],
            "first_step_s": round(first_s, 1),
            "later_step_s": round(step_s, 3),
            "first_run_steps_s": round(first_scan_s, 1)}


def vision_trainer(S, mesh, rules=(), seed=0):
    """ResNet-50 ShardedTrainer, as examples/train_imagenet.py builds it."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import PartitionSpec as P
    mx.random.seed(seed)
    net = vision.get_model(S["vision_net"], classes=S["classes"])
    net.initialize(mx.init.Xavier())
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        optimizer_params={"learning_rate": 0.01, "momentum": 0.9,
                          "wd": 1e-4},
        mesh=mesh, param_rules=[(pat, P(*spec)) for pat, spec in rules],
        compute_dtype="bfloat16")


def vision_batch(S, seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(S["train_batch"], 3, S["image"],
                  S["image"]).astype(np.float32)
    y = rng.randint(0, S["classes"], (S["train_batch"],))
    return x, y


def kernels_in(trainer, batch):
    """Custom kernels IN the compiled step the trainer runs, which a
    provenance count alone does not show."""
    return trainer.step_program_text(*batch).count("tpu_custom_call")


def phase_resnet50(ctx, S, log, seed, chip):
    from mxnet_tpu import parallel
    dev = ctx.jax_device
    trainer = vision_trainer(S, parallel.make_mesh({"data": 1},
                                                   devices=[dev]), seed=seed)
    batch = vision_batch(S, seed)
    facts = take_steps(trainer, batch, S["scan_k"], log, dev)
    # the conv path is plain XLA: BatchNorm act_type= and the residual
    # epilogue dispatch no kernel (PERF.md §6, PR 26)
    n = facts["custom_kernels_in_step"] = kernels_in(trainer, batch)
    check(n == 0, f"{n} custom kernel(s) in a step that dispatches none")
    return facts


def phase_bert(ctx, S, log, seed, chip):
    import mxnet_tpu as mx
    from benchmarks.bert import build_trainer
    from mxnet_tpu import parallel
    dev = ctx.jax_device
    mx.random.seed(seed)
    trainer = build_trainer(
        num_layers=S["bert_layers"], vocab=S["bert_vocab"],
        mesh=parallel.make_mesh({"data": 1}, devices=[dev]))
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, S["bert_vocab"], (S["bert_batch"], S["bert_seq"]))
    facts = take_steps(trainer, (toks, toks), S["scan_k"], log, dev)
    n = facts["custom_kernels_in_step"] = kernels_in(trainer, (toks, toks))
    check(n > 0 or not chip, "no custom kernel in the compiled step")
    from mxnet_tpu import _rng
    facts["prng_impl"] = _rng.get_state()[1]
    check(not chip or facts["prng_impl"] == "rbg",
          f"dropout masks drawn with {facts['prng_impl']}, not the chip's "
          "generator (_rng.py)")
    return facts


# ---------------------------------------------------------------------------
# phase: kernels on the chip
# ---------------------------------------------------------------------------
def real_width_cases(chip):
    """One main-path shape per kernel: BERT-base's FFN hidden at 128x128
    tokens with dropout, BERT-base heads at S=2048, Nemotron's expert
    product and scan, Brumby's retention, DeepSeek-V2's embedding gradient.
    bf16 where the model runs bf16."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.pallas import dropout_bits
    r, c = (16384, 3072) if chip else (72, 300)
    s = 2048 if chip else 128
    k = jax.random.split(jax.random.key(0), 4)
    y = jax.random.normal(k[0], (r, c), jnp.bfloat16)
    b = (jax.random.normal(k[1], (1, c)) * 0.1).astype(jnp.bfloat16)
    bits = dropout_bits(k[2], (r, c), layer=1, tick=2)
    q = jax.random.normal(k[3], (1, 12, s, 64), jnp.float32)
    # the routed-expert layer's first product: 16 experts of 2688 -> 1856,
    # a buffer of 12288 rows of which the groups own half
    rows, u, f, groups = (12288, 2688, 1856, 16) if chip else (64, 128, 256, 4)
    lhs = jax.random.normal(k[0], (rows, u), jnp.bfloat16)
    rhs = (jax.random.normal(k[1], (groups, u, f)) * 0.02).astype(jnp.bfloat16)
    sizes = jnp.full((groups,), rows // (2 * groups), jnp.int32)
    # one Mamba-2 layer's scan of the nemotron cell: 8192 positions, 8 groups
    # of 8 heads of 64, state 128, chunk 128
    length, h, g = (8192, 64, 8) if chip else (256, 4, 2)
    x = jax.random.normal(k[0], (1, length, h, 64), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, length, h)) - 2.0)
    cs = jnp.cumsum((dt * -jnp.linspace(1.0, 16.0, h)).reshape(
        1, length // 128, 128, h), axis=2).reshape(dt.shape)
    bc = (jax.random.normal(k[2], (2, 1, length, g, 128)) * 0.3).astype(
        jnp.bfloat16)
    # one layer's retention of the brumby cell: 8192 rows, 40 query and 8
    # key/value heads of 128, chunk 1024, seeded gates (sigmoid of a normal)
    length, heads, kv, rows = (8192, 40, 8, 1024) if chip else (256, 4, 2, 128)

    def unit(key, n):
        t = jax.random.normal(key, (1, length, n, 128))
        return (t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True))).astype(
            jnp.bfloat16)

    log_g = jax.nn.log_sigmoid(jax.random.normal(
        k[3], (1, kv, length // rows, rows)))
    keys = unit(k[1], kv)
    # the first row's queries are its key: that row has one weight, (q_0 .
    # k_0)^2 / d, and a draw where it nearly vanishes is damped by eps and
    # ill-conditioned in both paths (PERF.md sec. 7)
    queries = unit(k[0], heads).at[:, 0].set(
        jnp.repeat(keys[:, 0], heads // kv, axis=1))
    retention = ((queries, keys,
                  jax.random.normal(k[2], (1, length, kv, 128), jnp.bfloat16),
                  jnp.cumsum(log_g, axis=-1)), {"chunk_size": rows})
    # the deepseek_v2 cell's embedding gradient: 8192 ids over 12800 rows
    # of 5120, bf16
    tokens, vocab, units = (8192, 12800, 5120) if chip else (64, 600, 256)
    embedding = ((jax.random.randint(k[0], (tokens,), 0, vocab),
                  jax.random.normal(k[1], (tokens, units), jnp.bfloat16)),
                 {"rows": vocab})
    return {
        "embedding_grad": embedding,
        "power_retention": retention,
        "matmul_epilogue": ((y, b, bits), {"act_type": "gelu", "p": 0.1}),
        "blockwise_attention": ((q, q * 0.5, q + 1.0),
                                {"block_size": 512 if chip else 32,
                                 "causal": True}),
        "grouped_matmul": ((lhs, rhs, sizes), {}),
        "mamba2_ssd": ((x, dt, cs, bc[0], bc[1], jnp.ones((h,))),
                       {"chunk_size": 128}),
    }


# A real-width case judged as a share of the reference's largest entry, not
# element by element. The retention rounds the chunk's weights a[t, s] to
# bfloat16 before the product over them, in the kernel as in the scan; where
# their float32 values differ in the last bits a weight rounds the other way,
# and a row that averages two or three values moves by more than a spacing of
# its own small entries (read on a v5e: 3.1e-3 of the largest; PERF.md sec. 6,
# PR 35). The embedding gradient's kernel adds a row's cotangents in float32
# and rounds once; autodiff's scatter-add rounds each sum to bf16, so a row hit
# three times or more may sit several of its own spacings from the kernel's
REAL_CASE_SHARE = {"power_retention": 1e-2, "embedding_grad": 1e-2}


def kernel_cases_match(spec, cases, dev, chip):
    """Every case of one kernel against its reference, compiled; the last
    case is the real-width one."""
    import jax
    import jax.numpy as jnp
    name = spec.name
    worst, differing = 0.0, 0
    for i, (args, params) in enumerate(cases):
        args = jax.device_put(args, dev)
        reason = spec.supports(*args, **params)
        check(reason is None, f"{name} case {i}: supports says {reason}")
        live = [a for a in args if a is not None]
        slots = [a is not None for a in args]

        def fill(vals, slots=slots):
            it = iter(vals)
            return [next(it) if s else None for s in slots]

        got = jax.jit(lambda *v: spec.pallas_impl(
            *fill(v), interpret=not chip, **params))(*live)
        want = jax.jit(lambda *v: spec.xla_reference(
            *fill(v), **params))(*live)
        on_device(got, dev, f"{name} output")
        got32 = np.asarray(got.astype(jnp.float32))
        want32 = np.asarray(want.astype(jnp.float32))
        check(np.isfinite(got32).all(), f"{name} case {i}: not finite")
        err = np.abs(got32 - want32)
        # a bf16 result may round the other way where fp32 values
        # differ in their last bits: one bf16 spacing is allowed
        # on top of the registered tolerance
        slack = (BF16_EPS * np.abs(want32)
                 if got.dtype == jnp.bfloat16 else 0.0)
        if i == len(cases) - 1 and name in REAL_CASE_SHARE:
            slack = REAL_CASE_SHARE[name] * np.abs(want32).max()
        check((err <= spec.tolerance + slack).all(),
              f"{name} case {i}: max abs err {err.max()} over "
              f"tolerance {spec.tolerance}")
        if got.dtype != jnp.bfloat16:
            worst = max(worst, float(err.max()))
        differing += int((err > spec.tolerance).sum())
    return {"cases": len(cases), "tolerance": spec.tolerance,
            "max_abs_err_fp32_cases": worst,
            "bf16_elements_one_spacing_off": differing}


def phase_kernels(ctx, S, log, seed, chip):
    """Every registered kernel at its examples and one real-width case; a
    kernel that fails is named and the others are still checked."""
    import jax
    from mxnet_tpu import pallas
    dev = ctx.jax_device
    report, failed = {}, {}
    real = real_width_cases(chip)
    with jax.default_device(dev):
        for name, spec in pallas.kernels().items():
            try:
                report[name] = kernel_cases_match(
                    spec, list(spec.example()) + [real[name]], dev, chip)
                if name == "power_retention":
                    report[name]["backward"] = retention_backward_check(
                        dev, chip, *real[name])
            except AssertionError as e:
                failed[name] = str(e)
            say("kernel_checked", kernel=name,
                **(report.get(name) or {"failed": failed[name]}))
        report["library_flash_attention"] = flash_check(dev, chip)
    check(not failed, f"kernels differ from their references: {failed}")
    return report


# the retention's gradients, kernel against scan, as a share of the largest
# entry: both compute in bfloat16; the kernel rounds a cotangent where it is
# the operand of a product, autodiff of the scan multiplies it in float32
# (read on a v5e at this case: 0.2e-2 to 1.4e-2; on draws whose first row is
# not pinned as here, up to 9e-2 at that row; PERF.md sec. 6, PR 35)
RETENTION_GRAD_BOUND = 5e-2


def retention_backward_check(dev, chip, args, params):
    """The backward kernel of ``power_retention`` at the real-width case:
    every gradient (q, k, v and the cumulative log-decay) of the kernel
    against autodiff of the ``jax.numpy`` scan."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import pallas
    spec = pallas.get_kernel("power_retention")
    args = jax.device_put(args, dev)

    def grads(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.square(
            fn(*a).astype(jnp.float32))), argnums=(0, 1, 2, 3)))(*args)

    got = grads(lambda *a: spec.pallas_impl(*a, interpret=not chip, **params))
    want = grads(lambda *a: spec.xla_reference(*a, **params))
    shares = {}
    for name, g, w in zip(("q", "k", "v", "cs"), got, want):
        on_device(g, dev, f"power_retention d{name}")
        g, w = (np.asarray(t.astype(jnp.float32)) for t in (g, w))
        check(np.isfinite(g).all(), f"power_retention d{name}: not finite")
        shares[name] = float(np.abs(g - w).max() / np.abs(w).max())
        check(shares[name] <= RETENTION_GRAD_BOUND,
              f"power_retention d{name}: {shares[name]} of the largest "
              f"entry from the scan's, over {RETENTION_GRAD_BOUND}")
    return {"share_of_largest_entry": shares, "bound": RETENTION_GRAD_BOUND}


def flash_check(dev, chip):
    """The library flash-attention kernel ``_contrib_flash_attention`` takes
    on a TPU past 1024 keys: forward against dense attention, backward
    finite. Off the chip the op takes its portable path, so there is nothing
    of the kernel to rehearse."""
    if not chip:
        return "not run (needs the chip)"
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import nd
    from mxnet_tpu.parallel.ring_attention import attention_reference
    k = jax.random.split(jax.random.key(1), 3)
    q, kk, v = (jax.device_put(
        jax.random.normal(ki, (1, 12, 2048, 64), jnp.bfloat16), dev)
        for ki in k)
    out = nd.contrib.flash_attention(
        *(nd.NDArray(t, _skip_device_put=True) for t in (q, kk, v)),
        causal=True)._data
    want = attention_reference(q.astype(jnp.float32), kk.astype(jnp.float32),
                               v.astype(jnp.float32), causal=True,
                               scale=64 ** -0.5)
    err = float(jnp.abs(out.astype(jnp.float32) - want).max())
    check(err < 3e-2, f"flash attention differs from dense by {err}")
    from mxnet_tpu.ops.contrib import _tpu_flash_attention
    grads = jax.jit(jax.grad(
        lambda q, k, v: _tpu_flash_attention(q, k, v, True, 0.125)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, kk, v)
    check(all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
              for g in grads), "flash attention backward not finite")
    return {"max_abs_err_vs_dense": err, "backward": "finite"}


# ---------------------------------------------------------------------------
# phase: server
# ---------------------------------------------------------------------------
def phase_server(ctx, S, log, seed):
    import mxnet_tpu as mx
    from mxnet_tpu import nd, observability, serving
    from mxnet_tpu.gluon.model_zoo import vision
    dev = ctx.jax_device
    mx.random.seed(seed)
    net = vision.get_model(S["vision_net"], classes=S["classes"])
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.cast("bfloat16")
    rng = np.random.RandomState(seed)
    n = S["serve_requests"]
    xs = rng.randn(n, 3, S["image"], S["image"]).astype(np.float32)
    x_dev = nd.array(xs, ctx=ctx, dtype="bfloat16")
    net(x_dev[:1])                       # materialize the deferred shapes
    for name, p in list(net.collect_params().items())[:3]:
        on_device(p.data(), dev, f"served parameter {name}")

    before = observability.compile_stats()["by_site"].get(
        "serving_predictor", 0)
    server = serving.Server(
        # two buckets, so that however the bursts coalesce only batches of
        # 1 and of 8 are ever compiled
        net, serving.ServerConfig(max_batch=8, batch_buckets=(1, 8),
                                  window_ms=50.0, dtype="bfloat16"),
        ctx=ctx).start()
    try:
        # deadline 0 = none: the first request of a bucket waits for its
        # compile, which no latency budget of a warm server covers
        first = server.submit(xs[0], deadline_ms=0).result(600)
        burst = [server.submit(x, deadline_ms=0) for x in xs]
        outs = [np.asarray(r.result(600), np.float32) for r in burst]
        mark = log.programs
        again = [server.submit(x, deadline_ms=0) for x in xs]
        outs2 = [np.asarray(r.result(600), np.float32) for r in again]
        warm_programs = log.programs - mark
        counters = dict(server.counters)
        buckets = len(server.cache)
        misses = server.cache.misses
    finally:
        server.stop()
    compiles = observability.compile_stats()["by_site"].get(
        "serving_predictor", 0) - before
    check(counters["served"] == 1 + 2 * n and counters["errors"] == 0,
          f"server counters: {counters}")
    check(compiles == buckets == misses,
          f"compiles {compiles} != buckets hit {buckets} (cache misses "
          f"{misses})")
    check(warm_programs == 0,
          f"{warm_programs} program(s) compiled by requests whose buckets "
          "were warm")

    net.hybridize()
    direct = np.asarray(net(x_dev).asnumpy(), np.float32)
    scale = float(np.abs(direct).max())
    tol = 0.05 * scale + 1e-3        # bf16 through ~50 layers, by batch size
    for got in ([np.asarray(first, np.float32)], outs, outs2):
        want = direct[:len(got)]
        err = float(np.abs(np.stack(got) - want).max())
        check(np.isfinite(np.stack(got)).all() and err <= tol,
              f"served output differs from net(x) by {err} (tolerance {tol})")
    return {"requests": 1 + 2 * n, "batches": counters["batches"],
            "buckets_hit": buckets, "compiles": compiles,
            "max_abs_logit": round(scale, 3)}


# ---------------------------------------------------------------------------
# --multichip
# ---------------------------------------------------------------------------
def run_multichip(devices, S, log, seed, chip):
    """ResNet-50 sharded over four chips against one: nothing else."""
    from mxnet_tpu import parallel
    check(len(devices) >= 4, f"--multichip needs 4 devices, JAX found "
                             f"{len(devices)}")
    four = list(devices[:4])
    layouts = [("one_device", {"data": 1}, four[:1], ()),
               ("data4", {"data": 4}, four, ()),
               ("data2_model2", {"data": 2, "model": 2}, four, TP_RULES)]
    batch = vision_batch(S, seed)
    losses = {}
    for name, axes, devs, rules in layouts:
        t0, before = time.perf_counter(), log.snapshot()
        trainer = vision_trainer(
            S, parallel.make_mesh(axes, devices=devs), rules, seed=seed)
        HOST_INIT.prepare(trainer, batch[0][:2])
        ls = []
        for _ in range(3):
            loss = trainer.step(*batch)
            loss.wait_to_read()
            ls.append(float(loss.asscalar()))
        check(np.isfinite(ls).all(), f"{name}: loss not finite: {ls}")
        losses[name] = ls
        facts = {"losses": [round(v, 4) for v in ls]}
        if len(devs) > 1:
            params = [p._data[0]._data for p in trainer._trainable]
            for p, arr in zip(trainer._trainable, params):
                held = {s.device for s in arr.addressable_shards}
                check(held == set(devs),
                      f"{name}: {p.name} has shards on {held} only")
            split = sum(
                1 for arr in params
                if arr.addressable_shards[0].data.shape != arr.shape)
            check(bool(rules) == bool(split),
                  f"{name}: {split} parameters are partitioned")
            facts["partitioned_params"] = f"{split} of {len(params)}"
            if chip:
                in_use = [d.memory_stats()["bytes_in_use"] for d in devs]
                check(all(b > 0 for b in in_use),
                      f"{name}: live bytes per device {in_use}")
                facts["bytes_in_use"] = in_use
            text = trainer.step_program_text(*batch)
            check("all-reduce" in text,
                  f"{name}: the compiled step has no all-reduce")
            facts["all_reduce_ops"] = text.count(" all-reduce(") \
                + text.count(" all-reduce-start(")
        say("multichip", layout=name, axes=axes,
            seconds=round(time.perf_counter() - t0, 1),
            **log.since(before), **facts)
        del trainer
        gc.collect()
    # a toy net memorizes its 8 samples within three steps and bf16
    # rounding decides how fast, so a rehearsal compares the first loss only
    n = 3 if chip else 1
    base = np.asarray(losses["one_device"][:n])
    for name in ("data4", "data2_model2"):
        diff = np.abs(np.asarray(losses[name][:n]) - base)
        check((diff <= 2e-2 * np.abs(base)).all(),
              f"{name} losses {losses[name]} differ from one device's "
              f"{losses['one_device']} beyond bf16 tolerance")
        say("multichip_match", layout=name,
            max_rel_diff=float((diff / np.abs(base)).max()))


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the sharded ResNet-50 trainer "
                         "against a one-device run")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend; prints no result and "
                         "exits 3")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    try:
        import mxnet_tpu as mx
    except ImportError as e:
        print(f"chip_smoke: the repo is not beside this file ({e})",
              file=sys.stderr)
        return 2
    import jax
    from mxnet_tpu import pallas, runtime

    t_start = time.perf_counter()
    cache_dir = runtime.enable_compile_cache()
    log = CompileLog()
    devices = jax.devices()
    dev = devices[0]
    device = runtime.device_record(devices)
    chip = dev.platform == "tpu"
    if not chip and not args.rehearse:
        print(f"chip_smoke: JAX found no accelerator ({device}); this "
              "script passes on a TPU only (--rehearse for a dry run of "
              "its control flow)", file=sys.stderr)
        return 2
    S = SIZES["chip" if chip else "rehearsal"]
    # mx.tpu(0) on the chip; a rehearsal takes the LAST cpu device, so that
    # with several virtual devices an array made without its ctx shows up
    ctx = mx.tpu(0) if chip else mx.cpu(len(devices) - 1)
    say("start", device=device, compile_cache=cache_dir, sizes=S,
        mode="multichip" if args.multichip else "default",
        rehearsal=not chip, jax=jax.__version__,
        device_dial_s=round(time.perf_counter() - t_start, 1))

    if args.multichip:
        phases = [("multichip", lambda: run_multichip(devices, S, log,
                                                      args.seed, chip))]
    else:
        phases = [
            ("imperative", lambda: phase_imperative(ctx, S, log, args.seed)),
            ("resnet50", lambda: phase_resnet50(ctx, S, log, args.seed,
                                                chip)),
            ("bert", lambda: phase_bert(ctx, S, log, args.seed, chip)),
            ("kernels", lambda: phase_kernels(ctx, S, log, args.seed, chip)),
            ("server", lambda: phase_server(ctx, S, log, args.seed)),
        ]
    for name, run in phases:
        t0, before = time.perf_counter(), log.snapshot()
        try:
            facts = run() or {}
        except Exception:
            traceback.print_exc()
            say("phase_failed", phase=name,
                seconds=round(time.perf_counter() - t0, 1))
            return 1
        gc.collect()
        stats = dev.memory_stats() or {}
        say("phase_ok", phase=name,
            seconds=round(time.perf_counter() - t0, 1), **log.since(before),
            peak_bytes_in_use=stats.get("peak_bytes_in_use"), **facts)

    say("host_init_provenance", ctx=str(mx.current_context()),
        fallback_reasons=HOST_INIT.reasons)
    prov = HOST_INIT.on_the_chip(pallas.tier_provenance())
    say("tier_provenance", mode=pallas.mode(), ops=prov)
    hidden = {k: r for k, v in prov.items()
              for r in v["fallback_reasons"] if r.startswith("backend:")}
    if chip and hidden:
        say("failed", why="a kernel fell back because of the backend",
            kernels=hidden)
        return 1
    say("done", seconds=round(time.perf_counter() - t_start, 1),
        **log.snapshot())
    if not chip:
        print("chip_smoke: rehearsal finished — every phase passed at toy "
              f"sizes on {device}; this is not a chip run", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
