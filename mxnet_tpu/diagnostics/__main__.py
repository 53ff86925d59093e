"""Health CLI: ``python -m mxnet_tpu.diagnostics probe|doctor``.

One-command hermetic environment report for drivers and CI. Both
commands print exactly ONE JSON line on stdout (the artifact contract);
human-readable detail goes to stderr.

``probe``   — dial the backend in a throwaway subprocess under a hard
              deadline (``--deadline``, default MXNET_TPU_PROBE_DEADLINE
              or 150 s). rc 0 = reachable, 1 = unreachable.
``doctor``  — full report: import-time audit (``-X importtime`` in a
              subprocess; the import must complete WITHOUT backend init
              — the round-5 wedge was exactly an import-time dial),
              backend probe, device/mesh shape, relevant env vars.
              rc 0 = healthy, 1 = backend unreachable, 2 = the package
              itself cannot be imported hermetically.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from . import guard

_ENV_KEYS = ("JAX_PLATFORMS", "XLA_FLAGS", "MXNET_TPU_PROBE_DEADLINE",
             "MXNET_TPU_JOURNAL", "MXNET_TPU_HEARTBEAT_S",
             "MXNET_TPU_STALL_S", "MXNET_PRNG_IMPL",
             "MXNET_MATMUL_PRECISION", "MXNET_ENGINE_TYPE",
             "MXTPU_COORD_ADDR", "MXTPU_NUM_PROC", "MXTPU_PROC_ID")


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _env_report() -> dict:
    return {k: os.environ[k] for k in _ENV_KEYS if k in os.environ}


def _import_audit(deadline_s: float) -> dict:
    """Import the package in a child with ``-X importtime`` and report
    wall time + the slowest modules. The child runs with the CURRENT env
    — if the import dials the backend and the device runtime stalls, the
    child times out and the report says so instead of this process
    hanging."""
    t0 = time.perf_counter()
    try:
        out = subprocess.run(
            [sys.executable, "-X", "importtime", "-c",
             "import mxnet_tpu"],
            capture_output=True, text=True, timeout=deadline_s)
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": "import_timeout",
                "detail": f"import mxnet_tpu exceeded {deadline_s:g}s — "
                          "something dials the backend at import time"}
    dt = time.perf_counter() - t0
    if out.returncode != 0:
        return {"ok": False, "error": "import_failed",
                "rc": out.returncode,
                "stderr_tail": out.stderr.strip()[-500:]}
    total_us, slowest = 0, []
    for line in out.stderr.splitlines():
        # "import time: self [us] | cumulative | imported package"
        parts = line.split("|")
        if len(parts) != 3 or "import time:" not in parts[0]:
            continue
        try:
            self_us = int(parts[0].split(":", 1)[1].strip())
            cum_us = int(parts[1].strip())
        except ValueError:
            continue
        total_us += self_us
        name = parts[2].rstrip()
        # top-level imports only: the name field is " <module>" with two
        # MORE leading spaces per nesting level, so any extra space after
        # the first marks a nested import
        if not name[1:].startswith(" "):
            slowest.append((cum_us, name.strip()))
    slowest.sort(reverse=True)
    return {"ok": True, "import_s": round(dt, 2),
            "import_self_total_s": round(total_us / 1e6, 2),
            "slowest_toplevel": [
                {"module": n, "cumulative_s": round(us / 1e6, 2)}
                for us, n in slowest[:5]]}


def cmd_probe(args) -> int:
    try:
        info = guard.probe_backend(deadline_s=args.deadline,
                                   backoff_s=(0.0,) * args.attempts)
    except guard.DeviceUnreachable as e:
        _emit({"ok": False, **e.to_dict()})
        return 1
    _emit({"ok": True, **info})
    return 0


def _checkpoint_report(root: str) -> dict:
    """Manifest-validity summary of a commit-protocol checkpoint root
    (resilience.commit is stdlib-only: this works even when jax or the
    runtime package is broken)."""
    from ..resilience import commit
    return commit.doctor_report(root)


def _serving_report(path: str) -> dict:
    from ..serving import report
    return report.serving_report(path)


def _guardrails_report(path: str) -> dict:
    from ..elastic import report as elastic_report
    from ..guardrails import report
    out = report.guard_report(path)
    if out.get("ok"):
        # cohort events ride the same journal: rank losses, resizes,
        # resharded restores, and their trace linkage (docs/elastic.md)
        out["elastic"] = elastic_report.elastic_report(path)
    return out


def _trace_report(path: str) -> dict:
    from ..observability import report
    return report.trace_report(path)


def _metrics_report(path: str) -> dict:
    from ..observability import report
    return report.metrics_report(path)


def _timeline_report(run_dir: str) -> dict:
    from ..observability import aggregate
    return aggregate.timeline_report(run_dir)


def _aot_report(dirpath: str) -> dict:
    # imported directly (not via the serving package's heavy siblings):
    # aot_report is stdlib-only, so the audit runs while jax is wedged
    from ..serving import aot_report
    return aot_report.aot_report(dirpath)


def _lint_report(root: str) -> dict:
    from ..analysis import report
    return report.lint_report(root)


def _summ_checkpoint(ck) -> str:
    if ck.get("newest_step") is None:
        return f"checkpoint root {ck['root']}: no committed steps"
    if ck.get("newest_valid"):
        return (f"checkpoint OK: step {ck['newest_step']} manifest + "
                "CRCs valid")
    return (f"checkpoint step {ck['newest_step']} INVALID "
            f"({ck.get('newest_error')}); restorable: "
            f"{ck.get('restorable_step')}")


def _summ_serving(sv) -> str:
    base = (f"serving: {sv['served']} served in {sv['batches']} batches, "
            f"shed-rate {sv['shed_rate']}, cache hit-rate "
            f"{sv['cache_hit_rate']} ({sv['compiles']} compiles), "
            f"{sv['deadline_miss_total']} deadline misses, "
            f"{len(sv['reloads'])} reloads")
    rt = sv.get("router")
    if rt:
        base += (f"; pool: {len(rt['replicas_lost'])} replicas lost, "
                 f"{rt['restarts']} restarts, "
                 f"{len(rt['readmitted'])} re-admitted, "
                 f"{rt['retries']} retries, {rt['hedges']} hedges, "
                 f"{len(rt['breaker_transitions'])} breaker transitions")
    tn = sv.get("tenants")
    if tn:
        # a tenant is "quarantined" per its trail's LAST transition — a
        # sticky re-admitted flag would hide a tenant that re-quarantined
        # after an earlier successful probe
        quarantined = sorted(
            t for t, row in tn.items()
            if row["quarantine_trail"]
            and row["quarantine_trail"][-1]["to"] == "quarantined")
        readmitted = sorted(t for t, row in tn.items()
                            if row["readmitted"])
        trail = sum(len(row["quarantine_trail"]) for row in tn.values())
        pages = sum(row["page_ins"] for row in tn.values())
        base += (f"; fleet: {len(tn)} tenants, {trail} quarantine "
                 f"transitions (quarantined: {quarantined or 'none'}, "
                 f"re-admitted: {readmitted or 'none'}), "
                 f"{pages} page-ins")
    dp = sv.get("deploy")
    if dp:
        last = dp.get("last") or {}
        base += (f"; deploy: step {last.get('from_step')}"
                 f"->{last.get('to_step')} {last.get('result', '?')}"
                 + (f" ({last['reason']})" if last.get("reason") else "")
                 + f", {dp['gate_evals']} gate evals "
                 f"({dp['gate_breaches']} breaches), "
                 f"{dp['mirror_mismatches']} parity mismatches, "
                 f"{dp['rollbacks']} rollbacks")
    return base


def _summ_guardrails(gr) -> str:
    base = (f"guardrails: {gr['skipped_steps']} skipped steps (worst run "
            f"{gr['worst_consecutive_skips']}), {gr['loss_spikes']} loss "
            f"spikes, {len(gr['rollbacks'])} rollbacks, "
            f"{len(gr['diverged_errors'])} diverged")
    el = gr.get("elastic")
    if el and el.get("ok") and any(el["counts"].values()):
        last = el.get("last_resize") or {}
        base += (f"; elastic: {el['counts']['rank_lost']} rank losses, "
                 f"{el['counts']['cohort_resize']} resizes"
                 + (f" (last -> {last.get('members')})"
                    if last else "")
                 + f", {el['counts']['reshard_restore']} reshard "
                   f"restores ({el['correlated_recoveries']} correlated)")
    return base


def _summ_trace(tr) -> str:
    top = ", ".join(f"{s['name']}={s['dur_s']}s" for s in tr["slowest"][:3])
    drops = tr.get("ring_drops", 0)
    return (f"trace: {tr['spans']} spans in {tr['traces']} traces"
            + (f", {drops} ring drops (raise MXNET_TPU_TRACE_RING)"
               if drops else "")
            + f"; slowest: {top or 'n/a'}")


def _summ_timeline(tl) -> str:
    cp = tl.get("critical_path") or {}
    flights = tl.get("flight_dumps") or []
    base = (f"timeline: {len(tl['processes'])} processes "
            f"({tl['traced_processes']} traced) in {tl['path']}"
            + (f"; flight dumps: {', '.join(flights)}" if flights
               else ""))
    if cp.get("ok"):
        chain = " -> ".join(
            f"{s['name']}@{s['proc']}" for s in cp["steps"][:6])
        base += (f"; trace {cp['trace_id']}: {cp['wall_ms']}ms across "
                 f"{len(cp['processes'])} processes: {chain}")
    return base


def _summ_aot(ar) -> str:
    envs = len(ar.get("envelopes") or {})
    return (f"aot-cache: {ar['entries']} entries, {ar['bytes']} bytes, "
            f"{envs} envelope version(s), {ar['stale']} stale, "
            f"{ar['corrupt_total']} corrupt"
            + (f" ({ar['corrupt']})" if ar["corrupt"] else ""))


def _summ_metrics(mt) -> str:
    stages = (mt.get("setup") or {}).get("stages") or {}
    slowest = sorted(stages.items(), key=lambda kv: -kv[1]["self_s"])[:3]
    return (f"metrics: {mt['families']} families, "
            f"{int(mt.get('compiles_total', 0))} compiles"
            + ("; set-up: " + ", ".join(
                f"{name} {st['self_s']:.1f}s" for name, st in slowest)
               if slowest else ""))


def _summ_lint(lt) -> str:
    rules = ", ".join(f"{k}={v}" for k, v in sorted(lt["rules"].items()))
    cache = lt.get("cache") or {}
    hr = cache.get("hit_rate")
    return (f"lint: {lt['files']} files in {lt['wall_s']}s, "
            f"{lt['new']} new / {lt['baselined']} baselined"
            + (f" ({rules})" if rules else "")
            + f"; summary-cache hit-rate "
              f"{'n/a' if hr is None else hr}")


def _tuned_report(path) -> dict:
    from ..autotune import table
    return table.audit_table(path)


def _chaos_report(dirpath) -> dict:
    from ..chaos import report
    return report.chaos_report(dirpath)


def _summ_chaos(cr) -> str:
    from ..chaos import report
    return report.summarize(cr)


def _summ_tuned(tt) -> str:
    knobs = tt.get("knobs") or {}
    env = tt.get("envelope") or {}
    shown = ", ".join(f"{k}={v}" for k, v in sorted(knobs.items())[:4])
    more = len(knobs) - 4
    return (f"tuned: {tt['format']} crc={tt['crc32']} "
            f"[{env.get('platform')}/{env.get('device_kind')}/"
            f"jax {env.get('jax')}], {tt.get('trials')} trials; "
            f"{shown}" + (f" (+{more} more)" if more > 0 else ""))


# One row per report surface: adding a reporter means adding one row
# here, not editing three code paths (argument registration, report
# assembly, and the stderr summary all iterate this table).
# (key, flag, env default, metavar, help, load, summarize)
_REPORT_TABLE = (
    ("checkpoint", "--ckpt-dir", "MXNET_TPU_CKPT_DIR", "DIR",
     "commit-protocol checkpoint root: report the latest step's manifest "
     "validity and the newest restorable step (default MXNET_TPU_CKPT_DIR)",
     _checkpoint_report, _summ_checkpoint),
    ("serving", "--serving-journal", None, "PATH",
     "JSONL journal from a serving run (MXNET_TPU_JOURNAL=<file>): "
     "summarize the last run's shed-rate, compile-cache hit-rate, and "
     "deadline-miss count (docs/serving.md)",
     _serving_report, _summ_serving),
    ("guardrails", "--journal", None, "PATH",
     "JSONL journal from a training run (MXNET_TPU_JOURNAL=<file>): "
     "summarize anomaly guardrail records - nonfinite_grad skips, loss "
     "spikes, divergence rollbacks (docs/guardrails.md)",
     _guardrails_report, _summ_guardrails),
    ("trace", "--trace", None, "PATH",
     "JSONL journal from a traced run (MXNET_TPU_TRACE=journal): "
     "summarize span records - counts, per-name durations, slowest "
     "spans (docs/observability.md)",
     _trace_report, _summ_trace),
    ("metrics", "--metrics", None, "PATH",
     "metrics snapshot JSON (a BENCH artifact or a raw "
     "observability.snapshot() dump): summarize compile counts/times "
     "and step-phase percentiles (docs/observability.md)",
     _metrics_report, _summ_metrics),
    ("timeline", "--timeline", "MXNET_TPU_TRACE_DIR", "DIR",
     "pod run directory of per-process journals + flight dumps "
     "(MXNET_TPU_TRACE_DIR during the run): assemble the cross-process "
     "critical path of the slowest routed request — including any "
     "SIGKILLed replica's flight-recorder tail (docs/observability.md)",
     _timeline_report, _summ_timeline),
    ("aot", "--aot-dir", "MXNET_TPU_AOT_CACHE_DIR", "DIR",
     "persistent AOT executable-cache root: audit entry/byte counts, "
     "envelope versions, stale and corrupt entries — CRC-validated "
     "without deserializing anything (docs/serving.md AOT cache)",
     _aot_report, _summ_aot),
    ("lint", "--lint", None, "DIR",
     "repo checkout root: run graftlint (all tiers incl. the "
     "interprocedural G15-G19) and summarize per-rule finding counts "
     "and the summary-cache hit rate (docs/static_analysis.md)",
     _lint_report, _summ_lint),
    ("tuned", "--tuned", "MXNET_TPU_TUNED_TABLE", "PATH",
     "autotuner tuned-table file: validate format/CRC/schema and report "
     "its envelope, trial provenance refs, and per-knob values — "
     "stdlib-only, nothing is applied and no backend is dialed "
     "(docs/autotune.md)",
     _tuned_report, _summ_tuned),
    ("chaos", "--chaos", "MXNET_TPU_CHAOS_DIR", "DIR",
     "directory of chaos-campaign artifacts (CHAOS_rNN.json from "
     "python -m mxnet_tpu.chaos run): summarize campaigns, failed "
     "invariants, and shrunk reproducers — stdlib-only, nothing is "
     "executed (docs/chaos.md)",
     _chaos_report, _summ_chaos),
)


def cmd_doctor(args) -> int:
    deadline = guard.probe_deadline_s(args.deadline)
    report = {"python": sys.version.split()[0],
              "pid": os.getpid(),
              "env": _env_report()}
    for key, flag, _env, _mv, _help, load, _summ in _REPORT_TABLE:
        value = getattr(args, flag.lstrip("-").replace("-", "_"))
        if value:
            report[key] = load(value)
    print(f"doctor: import audit (deadline {deadline:g}s) ...",
          file=sys.stderr)
    report["import_audit"] = _import_audit(deadline)
    print(f"doctor: backend probe (deadline {deadline:g}s) ...",
          file=sys.stderr)
    try:
        info = guard.probe_backend(deadline_s=deadline)
        report["backend"] = {"ok": True, **info}
        flags = os.environ.get("XLA_FLAGS", "")
        report["mesh"] = {
            "devices": info["n"],
            "platform": info["platform"],
            "processes": info.get("process_count", 1),
            "forced_host_device_count":
                "xla_force_host_platform_device_count" in flags}
    except guard.DeviceUnreachable as e:
        report["backend"] = {"ok": False, **e.to_dict()}
    imp, dev = report["import_audit"]["ok"], report["backend"]["ok"]
    report["healthy"] = bool(imp and dev)
    _emit(report)
    if imp:
        print(f"doctor: import OK in "
              f"{report['import_audit']['import_s']}s", file=sys.stderr)
    else:
        print(f"doctor: IMPORT BROKEN: {report['import_audit']}",
              file=sys.stderr)
    if dev:
        print(f"doctor: backend OK: {report['backend']['n']}x "
              f"{report['backend']['platform']} in "
              f"{report['backend']['probe_s']}s", file=sys.stderr)
    else:
        print("doctor: BACKEND UNREACHABLE: "
              f"{report['backend']['detail']}", file=sys.stderr)
    for key, _flag, _env, _mv, _help, _load, summ in _REPORT_TABLE:
        sec = report.get(key)
        if sec is None:
            continue
        if sec.get("ok") is False:
            print(f"doctor: {key}: {sec.get('error')}", file=sys.stderr)
        else:
            print(f"doctor: {summ(sec)}", file=sys.stderr)
    return 0 if report["healthy"] else (2 if not imp else 1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.diagnostics",
        description="runtime health checks (see docs/diagnostics.md)")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("probe", help="subprocess backend dial under a "
                                     "deadline; ONE JSON line on stdout")
    p.add_argument("--deadline", type=float, default=None,
                   help="seconds per attempt (default "
                        "MXNET_TPU_PROBE_DEADLINE or 150)")
    p.add_argument("--attempts", type=int, default=1)
    p.set_defaults(fn=cmd_probe)
    d = sub.add_parser("doctor", help="hermetic environment report: "
                                      "import audit + probe + env")
    d.add_argument("--deadline", type=float, default=None)
    for _key, flag, env, metavar, help_text, _load, _summ in _REPORT_TABLE:
        d.add_argument(flag, metavar=metavar, help=help_text,
                       default=os.environ.get(env) if env else None)
    d.set_defaults(fn=cmd_doctor)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
