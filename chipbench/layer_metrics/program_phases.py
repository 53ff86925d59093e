"""The trainer's own phases, read from the profiler trace of a traced run.

The program annotates every trainer call (``mxnet_tpu.sharded_trainer.step``
/ ``.run_steps``) and, inside it, its phases (``.data_wait``,
``.host_args``, ``.compiled_step``, ``.guard_fetch``) with
``jax.profiler.TraceAnnotation``: they land on ``/host:CPU`` beside JAX's
``PjitFunction(<program>)`` events, on the clock of the device planes. From
them, the first device's ``XLA Modules`` line (one event for each run of a
program) and its ``XLA Ops`` leaves:

- the mean length of each phase, and of what is left of a call when its
  phases are taken out (the trainer's self time: re-binding parameters);
- the device's idle time (``reduce_trace``: no leaf op runs) split into
  *between programs* (no program runs: the host was late) and *inside a
  program* (bubbles between ops: the compiler's and the kernels');
- the idle time between programs split into the part under a trainer call
  (what the trainer's host code can give back) and the rest (the caller's
  loop: reading the loss, fetching a batch).

The annotation prefix, the trainer and the names of its calls are data in
the metric files (``annotations``, ``calls``); ``quantity`` says which number
of :func:`quantities` a metric takes. A program without the annotations (an
older commit) gives ``None`` for what needs them. One line, ``chipbench:
program_phases {...}``, says more than the metrics: every phase's mean,
median and p95, the idle time between programs by phase, the longest such
gaps with the programs on either side, and which phase starts which program.

The harness hands a reader the reduced summary only, so this one opens the
newest ``.xplane.pb`` under ``<checkout>/.chipbench_trace/`` itself, and
trusts it only if its window is the summary's. The arithmetic works on plain
tuples (times in ns), as ``reduce_trace.summarize`` does.
"""
from __future__ import annotations

import bisect
import functools
import json
import os

from .. import manifest, reduce_trace
from ..runners.train import summarize

CALL = "PjitFunction("      # JAX's host event around each call of a program
SELF = "self"               # what is left of a trainer call without its phases
OUTSIDE = "outside"         # under no trainer call


def length(intervals):
    return sum(end - start for start, end in intervals)


def idle_split(leaves, programs, window):
    """``(gaps in which no program runs, ns idle inside a program run)``:
    the idle gaps of ``reduce_trace`` (no leaf op runs), parted by whether a
    program run (``[(start, end), ...]``) covers them."""
    between = reduce_trace.gaps(list(leaves) + list(programs), window)
    return between, length(reduce_trace.gaps(leaves, window)) - length(between)


def self_intervals(outer, phases):
    """What the phases leave of ``outer``: ``[(start, end), ...]``."""
    _, start, end = outer
    return reduce_trace.gaps([(s, e) for _, s, e in phases], (start, end))


def covered(interval, labelled):
    """``{label: ns of the interval under it}``, ``outside`` for the rest."""
    out = {}
    for label, start, end in labelled:
        cover = min(end, interval[1]) - max(start, interval[0])
        if cover > 0:
            out[label] = out.get(label, 0) + cover
    rest = interval[1] - interval[0] - sum(out.values())
    if rest > 0:
        out[OUTSIDE] = rest
    return out


def label_of(interval, labelled):
    """The label that covers most of the interval."""
    cover = covered(interval, labelled)
    return max(cover, key=cover.get) if cover else OUTSIDE


def outermost(calls):
    """Of ``[(name, start, end), ...]``, those not inside another of the
    same name: one call of a program shows as two nested host events (four,
    the first time it is called)."""
    out, reach = [], {}
    for name, start, end in sorted(calls, key=lambda c: (c[1], -c[2])):
        if end > reach.get(name, float("-inf")):
            reach[name] = end
            out.append((name, start, end))
    return out


def neighbours(gap, programs):
    """Names of the program run that ends before ``gap`` and of the one that
    starts after it (``programs`` sorted by start; ``None`` at an edge)."""
    at = bisect.bisect_left([start for _, start, _ in programs], gap[1])
    return (programs[at - 1][0] if at else None,
            programs[at][0] if at < len(programs) else None)


def reduce(ops, annotations, programs, spans, calls, outer_names) -> dict:
    """``ops``: the first device's ``[(name, category, start, end), ...]``;
    ``annotations``: the benchmark's own host spans, which bound the window;
    ``programs``: the first device's program runs and ``spans``: the
    program's annotations without their prefix, both ``[(name, start, end),
    ...]``; ``calls``: ``PjitFunction`` host events by program name;
    ``outer_names``: the spans that are a whole trainer call. Seconds and
    milliseconds in the result, as the names say."""
    window = (min(a[1] for a in annotations), max(a[2] for a in annotations))

    def inside(events):
        return sorted((e for e in events
                       if e[1] >= window[0] and e[2] <= window[1]),
                      key=lambda e: e[1])

    leaves = [(start, end) for _, _, start, end, _, leaf
              in reduce_trace.self_times(ops) if leaf]
    programs = sorted(programs, key=lambda p: p[1])
    between, in_program_ns = idle_split(
        leaves, [(start, end) for _, start, end in programs], window)
    out = {"window_s": (window[1] - window[0]) / 1e9,
           "idle_between_s": length(between) / 1e9,
           "idle_in_program_s": in_program_ns / 1e9,
           "idle_gaps_between": len(between)}
    spans = inside(spans)
    outers = [s for s in spans if s[0] in outer_names]
    if not outers:      # a program without the annotations
        return out
    phases = [s for s in spans if s[0] not in outer_names]
    rest = [self_intervals(outer, phases) for outer in outers]
    # trainer time by its innermost label: the phases as they are and, as
    # ``self``, the rest of each call
    labelled = phases + [(SELF, start, end) for intervals in rest
                         for start, end in intervals]
    by_label = {}
    for gap in between:
        for label, ns in covered(gap, labelled).items():
            by_label[label] = by_label.get(label, 0) + ns
    out["idle_outside_trainer_s"] = by_label.get(OUTSIDE, 0) / 1e9
    out["idle_in_trainer_s"] = out["idle_between_s"] \
        - out["idle_outside_trainer_s"]
    out["idle_between_by_phase_s"] = {
        label: ns / 1e9 for label, ns in by_label.items()}
    out["longest_gaps_between"] = [
        dict(zip(("after", "before"), neighbours(gap, programs)),
             ms=(gap[1] - gap[0]) / 1e6, phase=label_of(gap, labelled))
        for gap in sorted(between, key=lambda g: g[0] - g[1])[:5]]
    lengths = {}
    for name, start, end in spans:
        lengths.setdefault(name, []).append(end - start)
    lengths[SELF] = [length(intervals) for intervals in rest]
    out["trainer_calls"] = len(outers)
    out["phases_ms"] = {name: summarize([n / 1e6 for n in ns])
                        for name, ns in lengths.items()}
    started = {}
    for name, start, end in outermost(inside(calls)):
        per_label = started.setdefault(label_of((start, end), labelled), {})
        per_label[name] = per_label.get(name, 0) + 1
    out["programs_started_per_call"] = {
        label: {name: n / len(outers) for name, n in sorted(count.items())}
        for label, count in started.items()}
    return out


def read_host(path, prefix):
    """``(spans, calls)`` of :func:`reduce` from the host plane: events named
    ``<prefix>...``, and the ``PjitFunction(...)`` events of the threads
    that hold any."""
    from jax.profiler import ProfileData
    spans, calls = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != reduce_trace.HOST_PLANE:
            continue
        for line in plane.lines:
            mine, started = [], []
            for ev in line.events:
                if ev.name.startswith(prefix):
                    mine.append((ev.name[len(prefix):], ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
                elif ev.name.startswith(CALL):
                    started.append((ev.name[len(CALL):-1], ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
            if mine:
                spans += mine
                calls += started
    return spans, calls


def reduce_file(path, prefix, outer_names) -> dict | None:
    """:func:`reduce` of a ``.xplane.pb``; ``None`` where the trace holds no
    device operation or none of the benchmark's spans."""
    devices, annotations, _, programs = reduce_trace.read_planes(path)
    if not annotations or not devices or not devices[min(devices)]:
        return None
    return reduce(devices[min(devices)], annotations, programs,
                  *read_host(path, prefix), outer_names)


@functools.lru_cache(maxsize=1)
def kept(path, modified, prefix, outer_names, window_s):
    """The reduction of this run's trace, read once for all the metrics of
    a run (``modified``, the file's time, is part of the cache's key):
    ``None`` unless the file's window is ``window_s``, the window of the
    summary the harness reduced."""
    reduced = reduce_file(path, prefix, outer_names)
    if reduced is None or abs(reduced["window_s"] - window_s) > 1e-9:
        return None
    print(f"chipbench: program_phases {json.dumps(reduced, sort_keys=True)}",
          flush=True)
    return reduced


def quantities(reduced, values) -> dict:
    """Every number a metric file may name as its ``quantity``."""
    window_s = reduced["window_s"]
    out = {"idle_between_programs_share":
           100.0 * reduced["idle_between_s"] / window_s,
           "idle_in_program_share":
           100.0 * reduced["idle_in_program_s"] / window_s}
    for name, summary in reduced.get("phases_ms", {}).items():
        out["phase_ms." + name] = summary["mean"]
    steps = values.get("steps_traced")
    if steps and "idle_in_trainer_s" in reduced:
        for where in ("in_trainer", "outside_trainer"):
            out[f"idle_{where}_ms_per_step"] = \
                reduced[f"idle_{where}_s"] * 1e3 / steps
    return out


def metric(summary, spec, values):
    try:
        path = reduce_trace.newest_xplane(
            os.path.join(manifest.ROOT, ".chipbench_trace"))
    except FileNotFoundError:
        return None
    reduced = kept(path, os.path.getmtime(path), spec["annotations"],
                   tuple(spec["calls"]), summary["window_s"])
    if reduced is None:
        return None
    return quantities(reduced, values).get(spec["quantity"])


def read(summary, spec, values):
    """The harness executes this file anew for every metric that names it
    (``layer_metrics._sibling_reader``), so the work is handed to the module
    as a normal import gives it, whose cache stays."""
    from chipbench.layer_metrics import program_phases
    return program_phases.metric(summary, spec, values)
