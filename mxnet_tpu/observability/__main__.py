"""Trace CLI: ``python -m mxnet_tpu.observability
dump|report|setup|aggregate|timeline``.

``dump``       convert ONE JSONL journal's ``kind="span"`` records
               (written with ``MXNET_TPU_TRACE=journal``) to Chrome
               trace-event JSON loadable in Perfetto
               (ui.perfetto.dev → Open trace).
``report``     print the stdlib trace summary (``doctor --trace`` body)
               as one JSON line.
``setup``      print the set-up stages as a table (where a slow start
               went): from a metrics snapshot JSON (``--metrics``, a
               dump of ``observability.snapshot()``) with JAX's events
               outside every stage and the longest programs by name, or
               from a journal's ``setup.<stage>`` spans (``--journal``).
``aggregate``  merge a POD RUN DIRECTORY (per-process journals +
               flight-recorder dumps, ``MXNET_TPU_TRACE_DIR`` during
               the run) into one anchor-aligned Perfetto trace — one
               pid per process, SIGKILLed replicas' flight tails
               included (docs/observability.md).
``timeline``   the cross-process critical-path summary of one trace
               (default: the slowest routed request) as ONE JSON line —
               the ``doctor --timeline`` body.

All read files only — no jax, usable from a wedged environment.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import aggregate, export, report


def _write_doc(doc, out) -> None:
    if out:
        from ..resilience.atomic import atomic_write
        with atomic_write(out, "w") as f:
            json.dump(doc, f)
        print(json.dumps({"ok": True, "out": out,
                          "events": len(doc["traceEvents"])}),
              flush=True)
    else:
        json.dump(doc, sys.stdout)
        sys.stdout.write("\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.observability",
        description="trace export/report tools (docs/observability.md)")
    sub = ap.add_subparsers(dest="cmd", required=True)
    d = sub.add_parser("dump", help="journal span records -> Chrome "
                                    "trace-event JSON (Perfetto)")
    d.add_argument("--journal", required=True,
                   help="JSONL journal path (MXNET_TPU_JOURNAL=<file> + "
                        "MXNET_TPU_TRACE=journal during the run)")
    d.add_argument("--out", default=None,
                   help="output path (default: stdout)")
    r = sub.add_parser("report", help="summarize journal span records; "
                                      "ONE JSON line on stdout")
    r.add_argument("--journal", required=True)
    st = sub.add_parser("setup", help="set-up stages as a table, from a "
                                      "metrics snapshot or a journal")
    src = st.add_mutually_exclusive_group(required=True)
    src.add_argument("--metrics", help="snapshot JSON "
                     "(observability.snapshot() dump or a BENCH artifact)")
    src.add_argument("--journal", help="JSONL journal of a run with "
                     "MXNET_TPU_TRACE=journal")
    a = sub.add_parser("aggregate",
                       help="merge a pod run dir (per-process journals "
                            "+ flight dumps) into one Perfetto trace")
    a.add_argument("--dir", required=True,
                   help="run directory (MXNET_TPU_TRACE_DIR of the run)")
    a.add_argument("--out", default=None,
                   help="output path (default: stdout)")
    t = sub.add_parser("timeline",
                       help="cross-process critical path of one trace; "
                            "ONE JSON line on stdout")
    t.add_argument("--dir", required=True)
    t.add_argument("--trace-id", default=None,
                   help="trace to follow (default: slowest routed "
                        "request)")
    args = ap.parse_args(argv)

    if args.cmd == "dump":
        try:
            doc = export.chrome_trace_from_journal(args.journal)
        except OSError as e:
            print(json.dumps({"ok": False, "error": str(e)}), flush=True)
            return 1
        _write_doc(doc, args.out)
        return 0

    if args.cmd == "setup":
        if args.metrics:
            rep = report.metrics_report(args.metrics)
            setup = rep.get("setup") if rep.get("ok") else None
            error = rep.get("error", "no set-up stages in the snapshot")
        else:
            try:
                setup = report.setup_from_journal(args.journal)
            except OSError as e:
                setup, error = None, str(e)
            else:
                error = ("no setup.<stage> spans in journal (was "
                         "MXNET_TPU_TRACE=journal set?)")
        if not setup or not setup.get("stages"):
            print(json.dumps({"ok": False, "error": error}), flush=True)
            return 1
        print(report.setup_table(setup), flush=True)
        return 0

    if args.cmd == "aggregate":
        try:
            doc = aggregate.aggregate_chrome(args.dir)
        except OSError as e:
            print(json.dumps({"ok": False, "error": str(e)}), flush=True)
            return 1
        _write_doc(doc, args.out)
        return 0

    if args.cmd == "timeline":
        rep = aggregate.timeline_report(args.dir, trace_id=args.trace_id)
        print(json.dumps(rep), flush=True)
        return 0 if rep.get("ok") else 1

    rep = report.trace_report(args.journal)
    print(json.dumps(rep), flush=True)
    return 0 if rep.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
