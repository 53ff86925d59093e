"""The ``train`` runner: one trainer, one program, one window.

Parameters, all from the traffic file: ``mode`` (``fused`` | ``loop``),
``k`` (optimizer steps per dispatch, ``fused``), ``batch_per_chip``,
``mesh`` (``{"data": "all"}``), ``sync_every``, ``ahead`` and ``ring``
(``loop``), ``trace_dispatches`` / ``trace_steps`` (length of the traced
window).

Set-up builds the trainer as the configuration says, resolves its shapes
(``trainer.prepare``) and warms exactly the program the window uses:
``fused`` two dispatches of ``run_steps(k)``, ``loop`` three ``step()``s,
each ended by reading its loss. Then

- ``fused`` (device-bound; ``bench.py``'s discipline): the seeded batch is
  staged on the device once, ``run_steps`` is dispatched back to back with
  at most two dispatches in flight until the time is up, and the rate counts
  whole dispatches from the first dispatch to the last completion;
- ``loop`` (host in the loop; what ``Module.fit`` and the Gluon tutorials
  do): every step takes the next of ``ring`` host batches, calls
  ``trainer.step`` and reads every ``sync_every``-th loss, ``ahead`` of
  them late (MXNet's engine runs ahead of the Python loop in the same way);
  a turn of the loop is timed from the call to the return of its read. When
  the time is up nothing more is sent, every loss still due is read, and
  the rate counts all steps over all of that time.

``correct``: every loss read is finite, the guard skipped no step, nothing
compiled inside the window, and on every batch the loss read last is below
the loss read first. A traced run also holds the system's compiled forward
pass to the configuration's plain float32 reference (``FORWARD_TOLERANCE``):
every logit to the reference's (``forward_check``), or, where the
configuration file names a ``compare`` beside its ``reference``, through
that comparison and under the same tolerance (``configured_check``).
"""
from __future__ import annotations

import collections
import contextlib
import math
import time

import numpy as np

from .. import manifest, reduce_trace
from ..compile_log import CompileLog

# Largest |system - reference| over the compared logits, as a share of the
# largest |reference| logit. The system computes in bfloat16 (8 bits of
# mantissa: 2**-8 = 3.9e-3 per rounding) from bfloat16 copies of the
# parameters, the reference in float32 from the float32 originals, so the
# difference is bfloat16 rounding carried through the depth of the model.
# Read on a v5e (PERF.md, Findings, PR 24): 5.6e-3 and 6.4e-3 for the
# convolutional configuration over 32 images (four chips, one chip), 1.14e-2
# and 1.17e-2 for the transformer over 4 sequences (two seeds). The
# tolerance leaves those readings a factor of 2.5. An 8-bit float (3 bits of
# mantissa: 6e-2 per rounding) or int8 compute, or a dropped term (a bias, a
# residual, a normalisation), moves logits by a tenth or more and fails.
FORWARD_TOLERANCE = 0.03


class Spans:
    """The runner's own host spans: timed with ``perf_counter`` and written
    into the profiler's trace as ``chipbench.<name>``, so that the reducer
    can say what the host was doing in a gap of the device."""

    def __init__(self):
        self.ms = collections.defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench." + name):
            try:
                yield
            finally:
                self.ms[name].append((time.perf_counter() - t0) * 1e3)

    def clear(self):
        self.ms.clear()


def percentile(sorted_values, q):
    """Nearest-rank percentile of an ascending list."""
    rank = max(1, math.ceil(q / 100.0 * len(sorted_values)))
    return sorted_values[rank - 1]


def summarize(ms) -> dict:
    s = sorted(ms)
    return {"count": len(s), "sum": sum(s), "mean": sum(s) / len(s),
            "p50": percentile(s, 50), "p95": percentile(s, 95),
            "max": s[-1]}


def phase_ms(snapshot, phase) -> float:
    """Milliseconds one of the program's step phases has taken so far, from
    ``observability.snapshot()``."""
    values = (snapshot["metrics"].get("mxnet_tpu_step_phase_ms") or {}) \
        .get("values") or {}
    return sum(v["sum"] for key, v in values.items()
               if key.endswith("phase=" + phase))


def fallback_count(provenance) -> int:
    return sum(sum(rec["fallback_reasons"].values())
               for rec in provenance.values())


def kernel_count(provenance) -> int:
    return sum(rec["pallas"] for rec in provenance.values())


def peak_bytes(stats) -> int:
    """Peak of one chip's memory from ``device.memory_stats()``. The TPU
    runtime counts live arrays under ``peak_bytes_in_use`` and what it
    reserves for the programs' temporaries apart, under
    ``peak_bytes_reserved`` (free memory is the limit less both: read on a
    v5e, PERF.md, Findings, PR 24), so the chip's peak is their sum."""
    return stats.get("peak_bytes_in_use", 0) \
        + stats.get("peak_bytes_reserved", 0)


def fused_window(trainer, x, y, k, spans, reads, seconds=None,
                 dispatches=None):
    """Dispatch ``run_steps(k)`` with two in flight until ``seconds`` have
    passed (or ``dispatches`` are sent), then wait for the last. Returns
    ``(dispatches sent, seconds from first dispatch to last completion)``.
    Every loss read goes to ``reads`` as ``(batch index, loss)``."""
    inflight = collections.deque()
    sent = 0
    t_first = time.perf_counter()
    while True:
        with spans("dispatch"):
            inflight.append(trainer.run_steps(x, y, num_steps=k))
        sent += 1
        if len(inflight) == 2:
            with spans("wait_loss"):
                reads.append((0, inflight.popleft().asscalar()))
        if dispatches is not None:
            if sent >= dispatches:
                break
        elif time.perf_counter() - t_first >= seconds:
            break
    while inflight:
        with spans("wait_loss"):
            reads.append((0, inflight.popleft().asscalar()))
    return sent, time.perf_counter() - t_first


def loop_window(trainer, ring, start, sync_every, ahead, spans, reads,
                step_ms, seconds=None, steps=None):
    """``step()`` on the next host batch of ``ring``, every ``sync_every``-th
    loss read, ``ahead`` such losses late: the host waits for a loss only
    once ``ahead`` later ones are dispatched, so the device has that many
    steps queued while the host stands still (``ahead`` 0: each loss is read
    before the next step is sent). ``start`` is the index of the first batch
    taken. When the time is up nothing more is sent, every loss still due is
    read, and the clock is read after that. Returns ``(steps taken, seconds
    from the first call to the last loss read)``; the time of each turn of
    the loop, from the call of ``step()`` to the return of the read it
    makes, goes to ``step_ms``."""
    taken = 0
    loss = None
    due = collections.deque()
    t_first = time.perf_counter()
    while True:
        with spans("next_batch"):
            at = (start + taken) % len(ring)
            x, y = ring[at]
        t0 = time.perf_counter()
        with spans("dispatch"):
            loss = trainer.step(x, y)
        taken += 1
        if taken % sync_every == 0:
            due.append((at, loss))
            loss = None
        if len(due) > ahead:
            with spans("wait_loss"):
                read_at, read = due.popleft()
                reads.append((read_at, read.asscalar()))
        now = time.perf_counter()
        step_ms.append((now - t0) * 1e3)
        if steps is not None:
            if taken >= steps:
                break
        elif now - t_first >= seconds:
            break
    if loss is not None:
        due.append((at, loss))
    while due:
        with spans("wait_loss"):
            read_at, read = due.popleft()
            reads.append((read_at, read.asscalar()))
    return taken, time.perf_counter() - t_first


def losses_fell(reads) -> bool:
    """On every batch read more than once, the last loss is below the
    first."""
    first, last = {}, {}
    for at, loss in reads:
        first.setdefault(at, loss)
        last[at] = loss
    seen = collections.Counter(at for at, _ in reads)
    twice = [at for at, n in seen.items() if n > 1]
    return bool(twice) and all(last[at] < first[at] for at in twice)


def plain_reference(config, net, x):
    """What the configuration's plain float32 reference gives on the first
    ``reference_samples`` of ``x``, with the net's parameters as drawn (the
    caller has resolved their shapes): its logits, or, for a configuration
    that brings its own ``compare``, whatever that needs kept from before
    the cast (the float32 parameters)."""
    return manifest.resolve(config["reference"])(
        net, x[:config["reference_samples"]])


def system_outputs(trainer, args, x, y, rows=None):
    """Every output of the system's own compiled forward pass over the whole
    batch (``trainer.evaluate``: its mesh, dtype and kernel tier), as host
    arrays in their own dtypes, cut to the first ``rows`` rows where given
    (in predict mode samples are independent, so a slice compares exactly).
    One call is one run of one compiled program: a ``compare`` that reads
    logits and routes calls it once, and both come from that run.
    Two things ``evaluate()`` leaves undone that ``step()`` does, both
    listed in PERF.md for the program to repair: it does not cast its inputs
    (float32 images fail against bfloat16 master weights), so it gets the
    batch in the compute dtype; and it does not trace under the trainer's
    mesh, so on several chips the kernel tier would pick a Mosaic kernel
    that the compiler cannot partition — it is called under ``use_mesh``,
    as ``step()`` traces."""
    import jax.numpy as jnp
    from mxnet_tpu import parallel
    if np.issubdtype(x.dtype, np.floating) and args.get("compute_dtype"):
        x = x.astype(jnp.dtype(args["compute_dtype"]))
    with parallel.use_mesh(trainer.mesh):
        trainer.evaluate(x, y)
    return [(out if rows is None else out[:rows]).asnumpy()
            for out in trainer.last_outputs]


def system_logits(trainer, args, x, y, n):
    """The first ``n`` rows of the system's logits, its first output, in
    float32."""
    return system_outputs(trainer, args, x, y, rows=n)[0].astype(np.float32)


def bounded(samples, error, scale, sound) -> dict:
    """The runner's bound on a forward comparison, the same for every
    configuration: the largest error over the compared logits is within
    ``FORWARD_TOLERANCE`` of the largest reference logit, and the comparison
    is ``sound`` (finite, and whatever else its caller holds it to)."""
    return {"samples": samples, "max_abs_error": error,
            "max_abs_reference": scale,
            "share": error / scale if scale else None,
            "tolerance": FORWARD_TOLERANCE,
            "ok": bool(sound and error <= FORWARD_TOLERANCE * scale)}


def forward_check(system, reference) -> dict:
    """The system's logits against the plain float32 reference's."""
    return bounded(len(reference), float(np.max(np.abs(system - reference))),
                   float(np.max(np.abs(reference))),
                   np.isfinite(system).all())


COMPARE_KEYS = ("samples", "compared", "max_abs_error", "max_abs_reference",
                "conditions")
CONDITION_KEYS = ("value", "limit", "ok", "why")


def configured_check(config, kept, trainer, args, x, y) -> dict:
    """The forward check of a configuration that brings its own comparison.

    Where the forward pass is not continuous in its inputs (a top-k router:
    two scores closer than bfloat16 rounding send a token to another expert
    in the system than in the float32 reference, and both are right), every
    logit cannot be held to the plain reference. Such a configuration names
    ``compare(kept, trainer, args, x, y)``: it runs the system's forward
    once (``system_outputs``), evaluates its reference **at the system's own
    choices**, and says how far the logits of the first ``reference_samples``
    samples lie apart (``max_abs_error``, ``max_abs_reference``), how many
    it compared (``compared``), and what it holds the choices to
    (``conditions``: name -> ``value``, ``limit``, ``ok``, ``why``).

    The bound stays here. ``share``, the tolerance and ``ok`` are worked out
    by ``bounded`` from those numbers, and anything else in the dict is
    ignored: a configuration can add conditions, it cannot widen the 3%,
    compare fewer logits or leave samples out. A dict that lacks a key, or
    counts other than all the logits of ``reference_samples`` samples, is an
    error of the run, not a verdict."""
    said = manifest.resolve(config["compare"])(kept, trainer, args, x, y)
    missing = [key for key in COMPARE_KEYS if key not in said]
    if missing:
        raise ValueError(f"{config['compare']} left out {missing}")
    samples = config["reference_samples"]
    # the compare's one evaluate() left the system's logits with the trainer
    per_sample = int(np.prod(trainer.last_outputs[0].shape[1:]))
    if (said["samples"], said["compared"]) != (samples, samples * per_sample):
        raise ValueError(
            f"{config['compare']} compared {said['compared']} logits of "
            f"{said['samples']} samples: all {samples * per_sample} of the "
            f"first {samples} are due")
    conditions = {}
    for name, condition in said["conditions"].items():
        missing = [key for key in CONDITION_KEYS if key not in condition]
        if missing:
            raise ValueError(f"{config['compare']}: condition {name!r} "
                             f"left out {missing}")
        conditions[name] = {"value": float(condition["value"]),
                            "limit": float(condition["limit"]),
                            "ok": bool(condition["ok"]),
                            "why": str(condition["why"])}
    error = float(said["max_abs_error"])
    scale = float(said["max_abs_reference"])
    check = bounded(samples, error, scale,
                    math.isfinite(error) and math.isfinite(scale)
                    and all(c["ok"] for c in conditions.values()))
    check.update(compared=int(said["compared"]), conditions=conditions)
    return check


def run(config, traffic, devices, seed, seconds, trace_dir=None) -> dict:
    """One run of one cell on ``devices``. ``trace_dir``: where to write a
    profiler trace of a short steady window, or ``None`` for a timed run of
    ``seconds``."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import observability, pallas, parallel

    log = CompileLog()
    spans, reads, step_ms = Spans(), [], []
    chips = len(devices)
    mode = traffic["mode"]
    mesh = parallel.make_mesh(
        {axis: (chips if n == "all" else n)
         for axis, n in traffic["mesh"].items()}, devices=devices)
    args = config["args"]
    net, trainer = manifest.resolve(config["build"])(args, mesh, seed)
    rng = np.random.default_rng(seed)
    batch = traffic["batch_per_chip"] * chips
    make_batch = manifest.resolve(config["make_batch"])
    ring = [make_batch(args, traffic, batch, rng)
            for _ in range(traffic["ring"] if mode == "loop" else 1)]
    x0, y0 = ring[0]

    # A traced run first holds the system's compiled forward pass to the
    # plain reference. The reference (or what a configuration's own compare
    # needs of it) comes before prepare(), which casts the
    # parameters to the master dtype and moves them onto the mesh. The
    # check's programs are not the cell's set-up: they are taken out of the
    # compile counters.
    checks = {}
    aside = dict.fromkeys(log.KEYS, 0)

    def set_aside(since):
        for key, n in log.since(since).items():
            aside[key] += n

    before_prepare = pallas.tier_provenance()
    if trace_dir:
        # the deferred-shape pass on the host, which prepare() makes in a
        # timed run and finds done here: it counts as set-up in both
        net(mx.nd.array(x0[:1]))
        mark = log.snapshot()
        reference = plain_reference(config, net, x0)
        set_aside(mark)
    trainer.prepare(x0[:1])
    host_fallbacks = fallback_count(pallas.tier_provenance()) \
        - fallback_count(before_prepare)
    if trace_dir:
        mark = log.snapshot()
        if "compare" in config:
            checks["forward"] = configured_check(config, reference, trainer,
                                                 args, x0, y0)
        else:
            checks["forward"] = forward_check(
                system_logits(trainer, args, x0, y0, len(reference)),
                reference)
        set_aside(mark)
    before_warm = pallas.tier_provenance()

    # warm exactly the program the window uses
    if mode == "fused":
        k = traffic["k"]
        x = trainer._shard_batch_arg(x0)
        y = trainer._shard_batch_arg(y0)
        for _ in range(2):
            reads.append((0, trainer.run_steps(x, y, num_steps=k).asscalar()))
        steps_per_call, trace_length = k, {
            "dispatches": traffic["trace_dispatches"]}
    elif mode == "loop":
        sync_every = traffic["sync_every"]
        for i in range(3):
            reads.append((i % len(ring),
                          trainer.step(*ring[i % len(ring)]).asscalar()))
        steps_per_call, trace_length = 1, {"steps": traffic["trace_steps"]}
    else:
        raise ValueError(f"unknown mode {mode!r}")
    setup_end = time.monotonic()

    at_setup_end = log.snapshot()
    setup_log = {key: at_setup_end[key] - aside[key] for key in log.KEYS}
    provenance = pallas.tier_provenance()
    wait0 = phase_ms(observability.snapshot(), "data_wait")
    spans.clear()

    def window(**length):
        if mode == "fused":
            return fused_window(trainer, x, y, k, spans, reads, **length)
        return loop_window(trainer, ring, 3, sync_every,
                           traffic.get("ahead", 0), spans, reads, step_ms,
                           **length)

    if trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            calls, elapsed = window(**trace_length)
        finally:
            jax.profiler.stop_trace()
    else:
        calls, elapsed = window(seconds=seconds)
    in_window = log.since(at_setup_end)
    steps = calls * steps_per_call
    summary = reduce_trace.reduce_dir(trace_dir) if trace_dir else None

    wait1 = phase_ms(observability.snapshot(), "data_wait")
    skipped = int(trainer.skipped_steps)
    losses = [loss for _, loss in reads]
    finite = all(math.isfinite(v) for v in losses)
    fell = losses_fell(reads)
    checks.update(losses_finite=finite, losses_fell=fell, skipped=skipped,
                  programs_in_window=in_window["programs"],
                  first_loss=losses[0], last_loss=losses[-1],
                  loss_reads=len(losses), calls=calls, steps=steps,
                  window_s=elapsed)
    memory = [d.memory_stats() or {} for d in devices]
    checks["memory_stats_first_device"] = memory[0]

    values = {
        "train_samples_per_s_per_chip": steps * batch / elapsed / chips,
        "flops_per_sample": manifest.resolve(
            config["flops_per_sample"])(args, traffic),
        "steps_traced": steps if trace_dir else None,
        "compile_s": setup_log["compile_s"],
        "programs": setup_log["programs"],
        "cache_hits": setup_log["cache_hits"],
        "cache_misses": setup_log["cache_misses"],
        # what the tier chose while the window's program was traced
        "tier_kernel_dispatches": (kernel_count(provenance)
                                   - kernel_count(before_warm)),
        "tier_fallbacks": (fallback_count(provenance)
                           - fallback_count(before_warm)),
        "tier_host_fallbacks": host_fallbacks,
        "data_wait_ms": (wait1 - wait0) / steps,
    }
    if step_ms and mode == "loop" and traffic["sync_every"] == 1:
        values["step_ms_p95"] = percentile(sorted(step_ms), 95)
        values["step_ms_p50"] = percentile(sorted(step_ms), 50)
    return {
        "correct": (finite and fell and skipped == 0
                    and in_window["programs"] == 0
                    and checks.get("forward", {"ok": True})["ok"]),
        "attempted": steps,
        "failed": skipped,
        "checks": checks,
        "setup_end": setup_end,
        "values": values,
        "spans": {name: summarize(ms) for name, ms in spans.ms.items()},
        "trace": summary,
        "memory_peak_bytes": max(map(peak_bytes, memory)),
    }
