"""From a profiler trace to numbers, with ``jax.profiler.ProfileData`` and
nothing else.

Per device plane (``/device:TPU:<n>``): the op events of its ``XLA Ops``
line are the device's work. ``busy`` is the union of the intervals of the
leaves (an op that holds others, such as the ``while`` around the steps of a
multi-step program, counts only its own time and is not busy time), so
overlapping or nested ops are not counted twice. The window is the span of
the benchmark's own host annotations (``chipbench.*``), which the runner
writes around the traced dispatches and waits — the same window on every
device. Each op has a category: its HLO opcode, read from the HLO line that
names the event (``fusion:Output``, ``custom-call``, ``all-reduce``, ``copy``
...; a v5e trace carries no ``hlo_category`` stat). Idle gaps
on the first device are labelled with the host annotation that covers most
of the gap. The first device's asynchronous ops (``Async XLA Ops``: copies
and collectives that run beside the ops) and program runs (``XLA Modules``)
are totalled apart; they are not part of busy.

``summarize`` works on plain tuples so that the arithmetic is tested
without a trace; ``reduce_file`` reads a ``.xplane.pb`` into those tuples.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"        # copies and collectives that overlap the ops
PROGRAMS_LINE = "XLA Modules"       # one event for each run of a program
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "chipbench."


def union_length(intervals) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def gaps(intervals, window):
    """``[(start, end), ...]`` inside ``window`` that no interval covers."""
    lo, hi = window
    out, reach = [], lo
    for start, end in sorted(intervals):
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        if start > reach:
            out.append((reach, start))
        reach = max(reach, end)
    if hi > reach:
        out.append((reach, hi))
    return out


OPCODE = re.compile(r"(?<![\w.%\-])([a-z][a-z\-]*)\(")
FUSION_KIND = re.compile(r"\bkind=k(\w+)")


def instruction_of(text: str) -> str:
    """The instruction's name: an ``XLA Ops`` event is named by the whole HLO
    line, ``%fusion.12 = bf16[...] fusion(...), kind=kOutput, ...``."""
    return text.split(" = ", 1)[0].strip()


def category_of(text: str) -> str:
    """The HLO opcode of an op event, the first lower-case word that opens a
    parenthesis after the ``=`` (shapes and layouts hold none: ``T(8,128)``,
    ``S(1)``); ``-start`` and ``-done`` halves count under the op they belong
    to. A fusion also says its kind (``fusion:Output`` holds the
    convolutions and matrix products, ``fusion:Loop`` the elementwise work,
    ``fusion:Input`` the reductions), and one that the compiler named after a
    convolution is ``fusion:convolution``. An event that is no HLO line (a
    bare ``fusion.12``) falls back to the name without its number."""
    name, _, rest = text.partition(" = ")
    m = OPCODE.search(rest)
    if m:
        opcode = m.group(1)
    else:
        opcode = re.sub(r"[.\d]+$", "", name.lstrip("%").split(" ")[0])
    for suffix in ("-start", "-done"):
        if opcode.endswith(suffix):
            opcode = opcode[:-len(suffix)]
    if opcode == "fusion":
        if "convolution" in name:
            return "fusion:convolution"
        kind = FUSION_KIND.search(rest)
        return "fusion:" + kind.group(1) if kind else "fusion"
    return opcode or name


def self_times(ops):
    """``[(name, category, start, end, self_ns, is_leaf), ...]``. An op that
    holds others inside its interval (a ``while`` around the steps of a
    multi-step program, a ``conditional``) is a container: its own time is
    its length less that of the ops directly inside it, and it is no leaf.
    Only leaves count as the device being busy."""
    out, stack = [], []
    for name, category, start, end in sorted(
            ops, key=lambda op: (op[2], -op[3])):
        while stack and stack[-1][3] < end:     # not wholly inside it
            stack.pop()
        if stack:
            parent = stack[-1]
            parent[4] -= end - start
            parent[5] = False
        stack.append([name, category, start, end, end - start, True])
        out.append(stack[-1])
    return [(n, c, s, e, max(own, 0), leaf) for n, c, s, e, own, leaf in out]


def label_gap(gap, annotations) -> str:
    """Name of the host annotation that covers most of ``gap``."""
    best, best_cover = "unlabelled", 0.0
    for name, start, end in annotations:
        cover = min(end, gap[1]) - max(start, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def totals(events, window) -> dict:
    """``{key: seconds inside the window}`` of ``[(key, start, end), ...]``."""
    out = {}
    for key, start, end in events:
        seconds = (min(end, window[1]) - max(start, window[0])) / 1e9
        if seconds > 0:
            out[key] = out.get(key, 0.0) + seconds
    return out


def summarize(devices, annotations, overlapped=(), programs=()) -> dict:
    """``devices``: ``{ordinal: [(name, category, start_ns, end_ns), ...]}``;
    ``annotations``: ``[(name, start_ns, end_ns), ...]`` from the host, the
    benchmark's own; ``overlapped`` and ``programs``, both of the first
    device: ``[(category, start_ns, end_ns), ...]`` of the asynchronous ops
    and ``[(program, start_ns, end_ns), ...]`` of the program runs. Times in
    the result are seconds."""
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operation")
    if annotations:
        window = (min(a[1] for a in annotations),
                  max(a[2] for a in annotations))
    else:       # no host span to bound it: first op start to last op end
        every = [e for ops in devices.values() for e in ops]
        window = (min(e[2] for e in every), max(e[3] for e in every))
    window_ns = window[1] - window[0]

    def clip(start, end):
        return max(start, window[0]), min(end, window[1])

    first = min(devices)
    timed = {ordinal: self_times(ops) for ordinal, ops in devices.items()}
    leaves = {ordinal: [clip(s, e) for _, _, s, e, _, leaf in ops if leaf]
              for ordinal, ops in timed.items()}
    per_device = {ordinal: union_length([(s, e) for s, e in spans if e > s])
                  / 1e9 for ordinal, spans in sorted(leaves.items())}
    categories, ops_time = {}, {}
    for name, category, start, end, own, _ in timed[first]:
        lo, hi = clip(start, end)
        if hi <= lo or own <= 0:
            continue
        seconds = own * (hi - lo) / (end - start) / 1e9
        categories[category] = categories.get(category, 0.0) + seconds
        ops_time[name] = ops_time.get(name, 0.0) + seconds
    idle = sorted(gaps(leaves[first], window), key=lambda g: g[0] - g[1])
    by_label = {}
    for gap in idle:
        label = label_gap(gap, annotations)
        by_label[label] = by_label.get(label, 0.0) + (gap[1] - gap[0]) / 1e9
    # a nanosecond between two host spans is no finding
    by_label = {k: v for k, v in by_label.items() if v >= 1e-6}

    def top(d, n):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:n]]

    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(per_device.values()) / len(per_device),
        "busy_s_first_device": per_device[first],
        "busy_s_per_device": per_device,
        "categories": categories,
        "device_ops": top(categories, 10),
        "top_ops": top(ops_time, 10),
        # per host span: all the idle time under it, then the longest gaps
        "idle_gaps": top(by_label, 5) + [
            [label_gap(g, annotations) + ":longest", (g[1] - g[0]) / 1e9]
            for g in idle[:5]],
        "n_idle_gaps": len(idle),
        "overlapped": top(totals(overlapped, window), 10),
        "programs": top(totals(programs, window), 10),
        "program_runs": sum(1 for _, start, end in programs
                            if start >= window[0] and end <= window[1]),
        "n_ops_first_device": len(devices[first]),
    }


def newest_xplane(trace_dir) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read_planes(path):
    """The arguments of :func:`summarize`, from a ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, annotations, lines = {}, [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines[int(m.group(1))] = {line.name: line for line in plane.lines}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        annotations.append(
                            (ev.name[len(ANNOTATION_PREFIX):], ev.start_ns,
                             ev.start_ns + ev.duration_ns))

    def events(ordinal, line_name, key):
        line = lines[ordinal].get(line_name)
        return [(key(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in (line.events if line else ())]

    for ordinal in lines:
        devices[ordinal] = [
            (instruction_of(text), category_of(text), start, end)
            for text, start, end in events(ordinal, OPS_LINE, str)]
    if not lines:
        return devices, annotations, [], []
    first = min(lines)
    return (devices, annotations,
            events(first, ASYNC_LINE, category_of),
            events(first, PROGRAMS_LINE, lambda n: n.split("(")[0]))


def reduce_file(path) -> dict:
    return summarize(*read_planes(path))


def reduce_dir(trace_dir) -> dict:
    return reduce_file(newest_xplane(trace_dir))


# -- readers for ``trace:<reader>`` per-layer metrics ------------------------

def _steps(values):
    return values.get("steps_traced") or None


def busy_ms_per_step(summary, spec, values):
    steps = _steps(values)
    return summary["busy_s_first_device"] * 1e3 / steps if steps else None


def idle_share(summary, spec, values):
    return 100.0 * (1.0 - summary["busy_s_first_device"]
                    / summary["window_s"])


def category_ms_per_step(summary, spec, values):
    """Device time of the categories the metric's file lists (a category
    matches when it equals, or starts with, a listed name), per step."""
    steps = _steps(values)
    if not steps:
        return None
    wanted = tuple(spec["categories"])
    seconds = sum(s for c, s in summary["categories"].items()
                  if c.startswith(wanted))
    return seconds * 1e3 / steps


def program_runs_per_step(summary, spec, values):
    """Device programs started per optimizer step: the step itself (a tenth
    of one in a ten-step dispatch) and every small program the trainer's
    host code launches beside it."""
    steps = _steps(values)
    return summary["program_runs"] / steps if steps else None


READERS = {"busy_ms_per_step": busy_ms_per_step,
           "program_runs_per_step": program_runs_per_step,
           "idle_share": idle_share,
           "category_ms_per_step": category_ms_per_step}
