"""Stdlib-only trace/metrics summaries (``doctor --trace`` /
``doctor --metrics``).

``trace_report`` reduces a JSONL journal's ``kind="span"`` records
(written with ``MXNET_TPU_TRACE=journal``) to the operator signals:
span/trace counts, per-name duration stats, the slowest spans.
``metrics_report`` reads a metrics snapshot back out of a JSON file —
either a raw ``observability.snapshot()`` dump or a BENCH artifact
carrying one under ``"observability"`` — and summarizes compile
counts/times, step-phase percentiles and the set-up stages.
``setup_table`` prints a ``setup_report()`` (from such a snapshot, or
rebuilt from a journal's ``setup.<stage>`` spans by
``setup_from_journal``) as the table an operator reads a slow start from
(``python -m mxnet_tpu.observability setup``).

Same contract as serving/guardrails reports: no jax, junk lines
tolerated, always returns a dict with ``ok``.
"""
from __future__ import annotations

import json

from .stages import CACHE, PHASES, Books

__all__ = ["metrics_report", "read_span_records", "setup_from_journal",
           "setup_table", "trace_report"]


def _iter_records(path):
    """Parsed dict records of a JSONL journal, junk/torn lines
    tolerated.  Raises OSError when the file is unreadable."""
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue                     # torn tail of a killed writer
            if isinstance(rec, dict):
                yield rec


def read_span_records(path) -> list:
    """``kind="span"`` records of a JSONL journal, junk/torn lines
    tolerated — THE span scanner, shared with the Perfetto exporter
    (export.chrome_trace_from_journal) so the doctor report and the
    dump can never diverge on what counts as a span.  Raises OSError
    when the file is unreadable."""
    return [r for r in _iter_records(path) if r.get("kind") == "span"]


def trace_report(path) -> dict:
    """Summarize the ``span`` records of a journal file.  One pass
    collects both the spans and the run's highest journaled
    ``trace_ring_drops`` marker (the counts are cumulative so
    max == total) — journals are unbounded, the report must not scale
    at 2x the file."""
    spans: list = []
    ring_drops = 0
    try:
        for rec in _iter_records(path):
            kind = rec.get("kind")
            if kind == "span":
                spans.append(rec)
            elif kind == "trace_ring_drops":
                try:
                    ring_drops = max(ring_drops,
                                     int(rec.get("dropped") or 0))
                except (TypeError, ValueError):
                    pass         # junk-tolerant, like every other line
    except OSError as e:
        return {"ok": False, "path": path,
                "error": f"cannot read {path}: {e.strerror or e}"}
    if not spans:
        return {"ok": False, "path": path,
                "error": "no span records in journal (was "
                         "MXNET_TPU_TRACE=journal set?)"}
    by_name: dict = {}
    traces = set()
    for s in spans:
        traces.add(s.get("trace_id"))
        durs = by_name.setdefault(s.get("name", "?"), [])
        if s.get("dur_s") is not None:
            durs.append(float(s["dur_s"]))

    def _stats(durs):
        if not durs:
            return {"count": 0}
        ds = sorted(durs)
        return {"count": len(ds),
                "total_s": round(sum(ds), 6),
                "p50_s": round(ds[len(ds) // 2], 6),
                "max_s": round(ds[-1], 6)}

    slowest = sorted((s for s in spans if s.get("dur_s") is not None),
                     key=lambda s: -float(s["dur_s"]))[:5]
    return {"ok": True, "path": path,
            "spans": len(spans), "traces": len(traces),
            "ring_drops": ring_drops,
            "by_name": {n: _stats(d) for n, d in sorted(by_name.items())},
            "slowest": [{"name": s.get("name"),
                         "dur_s": round(float(s["dur_s"]), 6),
                         "trace_id": s.get("trace_id")}
                        for s in slowest]}


def metrics_report(path) -> dict:
    """Summarize a metrics snapshot JSON file (raw ``snapshot()`` dump
    or a BENCH artifact with an ``observability`` section)."""
    try:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    except OSError as e:
        return {"ok": False, "path": path,
                "error": f"cannot read {path}: {e.strerror or e}"}
    # whole-file parse first (a pretty-printed snapshot dump), then a
    # per-line scan (a JSONL artifact stream / one-line-per-record file)
    doc = None
    try:
        parsed = json.loads(text)
        if isinstance(parsed, dict):
            doc = parsed
    except ValueError:
        pass
    if doc is None:
        for candidate in text.splitlines():
            candidate = candidate.strip()
            if not candidate.startswith("{"):
                continue
            try:
                parsed = json.loads(candidate)
            except ValueError:
                continue
            if isinstance(parsed, dict):
                doc = parsed
                break
    if doc is None:
        return {"ok": False, "path": path, "error": "no JSON object found"}
    obs = doc.get("observability", doc)
    metrics = obs.get("metrics", obs) if isinstance(obs, dict) else {}
    if not isinstance(metrics, dict) or not metrics:
        return {"ok": False, "path": path,
                "error": "no metrics snapshot in file"}
    out = {"ok": True, "path": path, "families": len(metrics)}
    compiles = metrics.get("mxnet_tpu_xla_compiles_total", {})
    if isinstance(compiles.get("values"), dict):
        out["compiles"] = {k or "total": v
                           for k, v in compiles["values"].items()}
        out["compiles_total"] = sum(
            float(v) for v in compiles["values"].values())
    compile_ms = metrics.get("mxnet_tpu_xla_compile_ms", {})
    if isinstance(compile_ms.get("values"), dict):
        out["compile_ms"] = compile_ms["values"]
    phases = metrics.get("mxnet_tpu_step_phase_ms", {})
    if isinstance(phases.get("values"), dict):
        out["step_phase_ms"] = phases["values"]
    if isinstance(obs, dict) and isinstance(obs.get("setup"), dict):
        out["setup"] = obs["setup"]
    return out


def setup_from_journal(path) -> dict:
    """A ``setup_report()``-shaped dict rebuilt from the ``setup.<stage>``
    spans of a journal (``MXNET_TPU_TRACE=journal``): the stages in the
    order they first opened, with what each span carried (``self_s``,
    JAX's seconds by phase, programs by what the cache did). What fell
    outside every stage and the table by ``fun_name`` are not in a
    journal. Raises OSError when the file is unreadable."""
    spans = [r for r in read_span_records(path)
             if str(r.get("name", "")).startswith("setup.")
             and r.get("dur_s") is not None]
    stages: dict = {}
    for rec in sorted(spans, key=lambda r: r.get("start_s") or 0.0):
        carried = rec.get("attrs") or {}
        key = carried.get("stage") or rec["name"][len("setup."):]
        books = stages.setdefault(key, Books())
        books.count += 1
        books.inclusive_s += float(rec["dur_s"])
        books.self_s += float(carried.get("self_s", rec["dur_s"]))
        for phase in PHASES:
            books.jax_s[phase] += float(carried.get(phase + "_s", 0.0))
        for cache in CACHE:
            books.programs[cache] += int(carried.get("programs_" + cache, 0))
    return {"stages": {key: books.to_dict()
                       for key, books in stages.items()}}


def setup_table(report) -> str:
    """A ``setup_report()`` as text: a row a stage (in the order they first
    opened) and one for ``outside``, then the longest rows of the table by
    ``fun_name``."""
    head = (f"{'stage':<28}{'n':>4}{'incl s':>9}{'self s':>9}{'trace':>8}"
            f"{'lower':>8}{'compile':>8}{'load':>8}{'hit':>6}{'miss':>6}"
            f"{'quick':>6}")

    def row(name, st):
        jax_s, progs = st.get("jax_s") or {}, st.get("programs") or {}
        return (f"{name:<28}{st.get('count', 0):>4}"
                f"{st.get('inclusive_s', 0.0):>9.3f}"
                f"{st.get('self_s', 0.0):>9.3f}"
                + "".join(f"{jax_s.get(p, 0.0):>8.3f}" for p in PHASES)
                + "".join(f"{progs.get(c, 0):>6}" for c in CACHE))

    lines = [head] + [row(name, st) for name, st
                      in (report.get("stages") or {}).items()]
    if report.get("outside"):
        lines.append(row("(outside every stage)", report["outside"]))
    rows = report.get("programs") or []
    if rows:
        lines += ["", f"{'program (fun_name)':<36}{'n':>4}{'trace':>8}"
                      f"{'lower':>8}{'compile':>8}{'load':>8}{'hit':>5}"
                      f"{'miss':>5}  first built in"]
        for r in rows:
            lines.append(
                f"{str(r.get('fun_name'))[:35]:<36}{r.get('count', 0):>4}"
                + "".join(f"{r.get(p + '_s', 0.0):>8.3f}" for p in PHASES)
                + f"{r.get('hits', 0):>5}{r.get('misses', 0):>5}"
                  f"  {r.get('stage')}")
    return "\n".join(lines)
