"""``mx.profiler`` — profiling facade (ref: python/mxnet/profiler.py over
src/profiler/profiler.cc).

The reference's profiler instruments the engine's op execution and writes
chrome://tracing JSON (SURVEY §5.1). On TPU the equivalent truth source is
the XLA/JAX profiler (xplane traces viewable in TensorBoard/Perfetto,
including per-op device timing), so this facade drives ``jax.profiler``
under the reference's API: ``set_config`` + ``set_state('run'/'stop')``,
scoped ``Marker``/``scope`` (→ ``jax.profiler.TraceAnnotation`` so Gluon
block names appear on device traces), and ``dumps()`` for a host-side
aggregate table.
"""
from __future__ import annotations

import os
import time
from collections import defaultdict

from .base import MXNetError

__all__ = ["set_config", "set_state", "state", "dumps", "dump", "pause",
           "resume", "Marker", "scope", "device_stats"]

_config = {"filename": "profile.json", "profile_all": False,
           "profile_symbolic": True, "profile_imperative": True,
           "profile_memory": True, "profile_api": True,
           "aggregate_stats": False}
_state = "stop"
_trace_dir = None
_agg = defaultdict(lambda: [0, 0.0])    # name -> [count, total_sec]


def set_config(**kwargs):
    """ref: profiler.py set_config(filename=..., profile_all=...)."""
    _config.update(kwargs)


def set_state(state_name="stop", profile_process="worker"):
    """'run' starts a JAX profiler trace; 'stop' ends it. The trace
    directory derives from the configured filename."""
    global _state, _trace_dir
    import jax
    if state_name == _state:
        return
    if state_name == "run":
        # starting a device trace is a backend touch: route it through
        # the diagnostics guard so a stalled device runtime leaves a
        # journaled breadcrumb instead of hanging the profiler silently
        from .diagnostics import guard
        guard.ensure_backend(tag="profiler-start-trace")
        base = _config.get("filename", "profile.json")
        _trace_dir = os.path.splitext(base)[0] + "_trace"
        os.makedirs(_trace_dir, exist_ok=True)
        jax.profiler.start_trace(_trace_dir)
        _state = "run"
    elif state_name == "stop":
        try:
            jax.profiler.stop_trace()
        except RuntimeError:
            pass
        _state = "stop"
    else:
        raise MXNetError(f"invalid profiler state {state_name!r}")


def state():
    return _state


def pause(profile_process="worker"):
    set_state("stop")


def resume(profile_process="worker"):
    set_state("run")


def dump(finished=True, profile_process="worker"):
    """Finish the trace and write the configured ``filename`` as
    chrome://tracing JSON (ref: profiler.cc DumpProfile — the reference
    writes profile.json in the same format; here it is converted from
    the captured xplane with xprof's trace_viewer tool). The raw xplane
    stays under <filename>_trace for TensorBoard."""
    set_state("stop")
    if not _trace_dir:
        return
    import json as _json
    try:
        from xprof.convert import raw_to_tool_data
        data, _ = raw_to_tool_data.xspace_to_tool_data(
            [_latest_xplane(_trace_dir)], "trace_viewer", {})
        if isinstance(data, bytes):
            data = data.decode()
        _json.loads(data)       # must be valid chrome-trace JSON
    except Exception as e:      # conversion unavailable: keep raw xplane
        import logging
        logging.getLogger(__name__).warning(
            "profiler.dump(): chrome-trace conversion unavailable (%s); "
            "raw xplane kept under %s", e, _trace_dir)
        return
    from .resilience.atomic import atomic_write
    with atomic_write(_config.get("filename", "profile.json"), "w") as f:
        f.write(data)


def dumps(reset=False, format="table"):
    """Host-side aggregate of Marker/scope timings (the reference's
    aggregate_stats table, ref: src/profiler/aggregate_stats.cc)."""
    lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>12}"]
    for name, (count, total) in sorted(_agg.items()):
        avg = total / count * 1e3 if count else 0.0
        lines.append(f"{name:<40}{count:>8}{total * 1e3:>12.3f}{avg:>12.3f}")
    if reset:
        _agg.clear()
    return "\n".join(lines)


def _latest_xplane(trace_dir):
    """Newest xplane capture under ``trace_dir``."""
    import glob

    xplanes = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    if not xplanes:
        raise MXNetError(f"no xplane capture under {trace_dir!r}; run "
                         "set_state('run') … set_state('stop') around "
                         "device work first")
    return max(xplanes, key=os.path.getmtime)


def _parse_tool_stats(trace_dir, tool="hlo_stats"):
    """Parse the newest xplane capture under ``trace_dir`` with one of
    xprof's converters (the exact pipeline the TensorBoard profile
    plugin runs). Returns a list of per-op dicts."""
    import json

    xplane = _latest_xplane(trace_dir)
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError as e:                          # pragma: no cover
        raise MXNetError("device_stats needs the xprof package "
                         "(tensorboard profile plugin)") from e
    data, _ = raw_to_tool_data.xspace_to_tool_data([xplane], tool, {})
    j = json.loads(data if isinstance(data, str) else data.decode())
    if isinstance(j, list):                # framework_op_stats wraps in []
        j = j[0]
    cols = [c["label"] for c in j["cols"]]
    rows = []
    for r in j["rows"]:
        rows.append({label: (cell.get("v") if cell else None)
                     for label, cell in zip(cols, r["c"])})
    return rows


def _parse_hlo_stats(trace_dir):
    return _parse_tool_stats(trace_dir, "hlo_stats")


def _load_xplane_pb2():
    """Load the XSpace protobuf bindings standalone (the generated module
    only needs google.protobuf — importing it by path avoids pulling the
    whole tensorflow package in)."""
    import importlib.util
    import glob as _glob
    import sysconfig
    for root in {sysconfig.get_paths()["purelib"],
                 sysconfig.get_paths().get("platlib", "")}:
        hits = _glob.glob(os.path.join(
            root, "**", "profiler", "protobuf", "xplane_pb2.py"),
            recursive=True)
        if hits:
            spec = importlib.util.spec_from_file_location(
                "mxnet_tpu._xplane_pb2", hits[0])
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise MXNetError("xplane_pb2 bindings not found")


def _parse_xplane_events(trace_dir):
    """Last-resort op stats straight from the raw xplane proto: per-op
    SELF time (nested child events subtracted stack-wise per line) over
    the device planes, or the XLA runtime line of the host plane when no
    device plane exists (XLA:CPU)."""
    pb2 = _load_xplane_pb2()
    space = pb2.XSpace()
    with open(_latest_xplane(trace_dir), "rb") as f:
        space.ParseFromString(f.read())
    planes = [p for p in space.planes if p.name.startswith("/device:")]
    if not planes:
        planes = [p for p in space.planes if p.name.startswith("/host:")
                  and any("XLA" in ln.name or "PjRt" in ln.name
                          for ln in p.lines)]
    events = defaultdict(list)      # name -> [(dur_ps, children_ps_box)]
    for plane in planes:
        md = plane.event_metadata
        for line in plane.lines:
            if not ("XLA" in line.name or "PjRt" in line.name
                    or plane.name.startswith("/device:")):
                continue
            evs = sorted(line.events, key=lambda e: (e.offset_ps,
                                                     -e.duration_ps))
            stack = []                        # (end_ps, children_ps_box)
            for e in evs:
                name = md[e.metadata_id].name
                start, dur = e.offset_ps, e.duration_ps
                while stack and stack[-1][0] <= start:
                    stack.pop()
                if name.startswith("end: "):  # paired marker, not an op
                    continue
                if stack:
                    stack[-1][1][0] += dur    # credit to parent's children
                children = [0.0]
                stack.append((start + dur, children))
                events[name].append((dur, children))
    rows = []
    for name, recs in events.items():
        self_ps = sum(dur - ch[0] for dur, ch in recs)
        rows.append({"Operation Name": name,
                     "Operation Type": name.rstrip("0123456789.")
                     or name,
                     "Total self-time (us)": max(self_ps, 0.0) / 1e6,
                     "#Occurrences": len(recs),
                     "Bound by": ""})
    return rows


def device_stats(trace_dir=None, top=20):
    """Per-HLO-op device-time table from the last captured trace — the
    TPU analog of the reference profiler's per-operator stats (ref:
    src/profiler/aggregate_stats.cc; here the truth source is the
    hardware xplane, aggregated per HLO category with self time and HBM
    traffic). Returns the formatted table string.

    Usage::

        mx.profiler.set_state('run')
        train_step(...)            # device work
        mx.profiler.set_state('stop')
        print(mx.profiler.device_stats())
    """
    tdir = trace_dir or _trace_dir or "."

    def num(row, label):
        v = row.get(label)
        try:
            return float(v)
        except (TypeError, ValueError):
            return 0.0

    rows = _parse_hlo_stats(tdir)
    if rows:                        # TPU/GPU: full per-HLO device stats
        time_col, name_col, cat_col = ("Total self time (us)",
                                       "HLO op name", "HLO op category")
        header = "HLO category"
    else:
        # XLA:CPU emits no HLO device plane for the stats tools — read
        # the raw xplane (XLA runtime events, nesting-corrected self
        # time). framework_op_stats is tried first in case a backend
        # serves it without hlo_stats; any converter failure falls
        # through to the raw-xplane tier.
        try:
            fw = _parse_tool_stats(tdir, "framework_op_stats")
        except Exception:
            fw = []
        rows = [r for r in fw if r.get("Operation Type") != "IDLE"
                and num(r, "Total self-time (us)") > 0]
        if not rows:
            rows = _parse_xplane_events(tdir)
        time_col, name_col, cat_col = ("Total self-time (us)",
                                       "Operation Name", "Operation Type")
        header = "framework op type"

    cats = defaultdict(lambda: [0.0, 0.0, 0])
    total = 0.0
    for r in rows:
        t = num(r, time_col)
        gb = num(r, "HBM BW (GiB/s)") * (t / 1e6) * 1.073741824
        c = cats[r.get(cat_col) or "uncategorized"]
        c[0] += t
        c[1] += gb
        c[2] += int(num(r, "#Occurrences") or 1)
        total += t
    lines = [f"{header:<28}{'self ms':>10}{'HBM GB':>9}"
             f"{'%time':>7}{'ops':>6}"]
    for name, (t, gb, n) in sorted(cats.items(), key=lambda kv: -kv[1][0]):
        pct = 100.0 * t / total if total else 0.0
        lines.append(f"{name:<28}{t / 1e3:>10.3f}{gb:>9.2f}"
                     f"{pct:>7.1f}{n:>6}")
    lines.append(f"{'TOTAL':<28}{total / 1e3:>10.3f}")
    lines.append("")
    lines.append(f"top {top} ops by self time:")
    by_time = sorted(rows, key=lambda r: -num(r, time_col))
    for r in by_time[:top]:
        t = num(r, time_col)
        lines.append(f"  {t / 1e3:>9.3f} ms  "
                     f"{(r.get('Bound by') or ''):<12}"
                     f"{(r.get(name_col) or '')[:60]}")
    return "\n".join(lines)


class Marker:
    """Scoped annotation: host-side aggregate timing + device-trace
    annotation (ref: profiler.py Marker / mx.profiler.scope)."""

    def __init__(self, name, scope_name="<unk>"):
        self.name = name
        self._ann = None
        self._t0 = None

    def __enter__(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        entry = _agg[self.name]
        entry[0] += 1
        entry[1] += dt
        self._ann.__exit__(*exc)

    # one-shot API parity (ref: Marker.mark)
    def mark(self, scope_name="process"):
        entry = _agg[self.name]
        entry[0] += 1


def scope(name="<unk>:"):
    return Marker(name)
