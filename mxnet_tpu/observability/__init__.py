"""mxnet_tpu.observability — unified telemetry: span tracing, a metrics
registry, and exporters (docs/observability.md).

One substrate every subsystem records into:

- :mod:`.trace` — ``span(name, **attrs)`` context managers with
  process-unique trace/span IDs, cross-thread parent propagation and
  rank tagging; bounded in-memory ring + optional JSONL journal
  streaming (``MXNET_TPU_TRACE=off|ring|journal``).  Off-by-default
  cheap: disabled tracing is one shared no-op and zero device reads.
- :mod:`.metrics` — counters, gauges and histogram summaries
  (``LatencySummary`` as the backend) with labeled families and a
  process-wide default registry; always-on host counters feed the
  compile/step-phase provenance even with tracing off.
- :mod:`.export` — Chrome trace-event JSON (Perfetto-loadable) from the
  ring or a journal file; a stdlib ``/metrics`` HTTP endpoint.
- :mod:`.report` — stdlib ``doctor --trace`` / ``doctor --metrics``
  summaries.
- :mod:`.instrument` — the shared step-phase / compile-span helpers the
  four trainers, serving and checkpointing use.
- :mod:`.scopes` — ``device_scopes()``: which HLO instruction of a live
  trainer's programs belongs to which ``jax.named_scope``, for joining
  with a profiler trace.
- :mod:`.stages` — ``setup_report()``: set-up by stage (import,
  initialize, deferred shapes, placement, each program's first call) with
  JAX's own trace / lower / compile / cache events booked to the stage
  that was open.

Every journal record written inside a span carries ``trace_id``/
``span_id`` (the provider hook in diagnostics.journal), so the
historically separate journals — ``serving_batch``, ``nonfinite_grad``,
``ckpt_fallback``, ``pallas_fallback`` — correlate against one trace.

Stdlib-only: importable (and exportable) while jax or the backend is
wedged.
"""
from __future__ import annotations

from . import (aggregate, export, flight, instrument, metrics, report,
               scopes, stages, trace)
from .aggregate import (aggregate_chrome, critical_path, scan_run_dir,
                        timeline_report)
from .export import (chrome_trace_from_journal, export_chrome,
                     serve_metrics, to_chrome_trace)
from .flight import FlightRecorder, install_from_env
from .metrics import (Counter, Gauge, LatencySummary, MetricsRegistry,
                      Summary, default_registry, prometheus_text,
                      reset_metrics)
from .scopes import device_scopes
from .stages import setup_report
from .trace import (SpanContext, Tracer, adopt_trace, annotate, configure,
                    current_context, current_ids, current_span, enabled,
                    event, get_tracer, identity, reset_tracer, span,
                    start_span)

__all__ = [
    "Counter", "FlightRecorder", "Gauge", "LatencySummary",
    "MetricsRegistry", "Summary", "SpanContext", "Tracer", "adopt_trace",
    "aggregate", "aggregate_chrome", "annotate",
    "chrome_trace_from_journal", "compile_stats", "configure",
    "critical_path", "current_context", "current_ids", "current_span",
    "default_registry", "device_scopes", "enabled", "event", "export",
    "export_chrome",
    "flight", "get_tracer", "identity", "install_from_env", "instrument",
    "metrics", "prometheus_text", "report", "reset_metrics",
    "reset_tracer", "scan_run_dir", "scopes", "serve_metrics",
    "setup_report", "snapshot", "span", "stages",
    "start_span", "timeline_report", "to_chrome_trace", "trace",
]


def snapshot() -> dict:
    """One JSON-able telemetry snapshot: the full metrics registry, the
    set-up stages and tracer accounting — the provenance block
    ``bench.py`` embeds in BENCH artifacts (``"observability": ...``) and
    ``doctor --metrics`` reads back."""
    return {"metrics": default_registry().snapshot(),
            "setup": setup_report(),
            "trace": get_tracer().stats()}


def _site_family(metrics_d, count_metric, ms_metric):
    """(total count, total ms, per-site counts) for one count+ms
    metric-family pair out of a snapshot dict."""
    counts = (metrics_d.get(count_metric) or {}).get("values") or {}
    times = (metrics_d.get(ms_metric) or {}).get("values") or {}
    total_ms = 0.0
    for v in times.values():
        if isinstance(v, dict) and v.get("count"):
            if v.get("sum") is not None:
                total_ms += v["sum"]
            else:          # pre-sum snapshot (old BENCH artifact)
                total_ms += v["count"] * (v.get("mean") or 0.0)
    return (int(sum(float(v) for v in counts.values())),
            round(total_ms, 1),
            {k.replace("site=", "", 1): int(v)
             for k, v in sorted(counts.items())})


def compile_stats(snap=None) -> dict:
    """Compile accounting out of a snapshot (default: the live
    registry): total count, total ms, and the per-site split — the
    one-line summary a bench run prints.  Deserialized AOT-cache loads
    are reported as their OWN family (``aot_loads``/``aot_load_ms``/
    ``aot_by_site``), never folded into ``compiles`` — a warm start's
    zero-compile claim stays honest (docs/observability.md)."""
    snap = snap if snap is not None else snapshot()
    metrics_d = snap.get("metrics", snap)
    compiles, total_ms, by_site = _site_family(
        metrics_d, instrument.COMPILE_COUNT_METRIC,
        instrument.COMPILE_MS_METRIC)
    aot_loads, aot_ms, aot_by_site = _site_family(
        metrics_d, instrument.AOT_LOAD_COUNT_METRIC,
        instrument.AOT_LOAD_MS_METRIC)
    return {"compiles": compiles, "total_ms": total_ms,
            "by_site": by_site,
            "aot_loads": aot_loads, "aot_load_ms": aot_ms,
            "aot_by_site": aot_by_site}
