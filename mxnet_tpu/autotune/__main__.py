"""Autotuner CLI: ``python -m mxnet_tpu.autotune search|show|apply``.

``search``  — closed-loop search over ≥2 knob families (Pallas block
              shape for one kernel×shape-class + the serving window/
              queue knobs) against the real harnesses; commits a tuned
              table + a BENCH-schema artifact.  Budget-bounded (trial
              count AND wall-clock), seeded, every trial journaled.
``show``    — stdlib audit of a table (the ``doctor --tuned`` body).
``apply``   — validate a candidate table end to end, then atomically
              install it at the active path (old-or-new under any
              crash or concurrent reader).
``_trial``  — internal: one kernel trial in a child process (the
              deadlined-subprocess contract's far side).

Artifact contract (bench.py): exactly ONE JSON line on stdout;
failures emit a structured error line, never a hang.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

METRIC = "autotune_search_trials"


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _diagnostic(error: str, detail: str) -> dict:
    return {"metric": METRIC, "value": None, "unit": "trials",
            "error": error, "detail": detail}


def _parse_rc(spec: str):
    try:
        r, c = (int(v) for v in str(spec).lower().split("x"))
        if r <= 0 or c <= 0:
            raise ValueError
        return r, c
    except ValueError:
        raise ValueError(f"bad RxC spec {spec!r}") from None


# ---------------------------------------------------------------------------
# _trial: one kernel evaluation in THIS (child) process
# ---------------------------------------------------------------------------
def cmd_trial(args) -> int:
    import jax.numpy as jnp
    import numpy as np

    from ..observability import compile_stats
    from ..pallas import registry

    spec = registry.get_kernel(args.kernel)
    r, c = _parse_rc(args.shape)
    rng = np.random.RandomState(0)
    if args.kernel != "matmul_epilogue":
        _emit({"metric": "autotune_kernel_elems_per_sec", "value": None,
               "error": "unknown_kernel", "detail": args.kernel})
        return 1
    call_args = (jnp.asarray(rng.randn(r, c), jnp.float32),
                 jnp.asarray(rng.randn(1, c) * 0.1, jnp.float32),
                 None)
    params = {"act_type": "gelu"}
    block = None
    if args.block:
        block = _parse_rc(args.block)
        params["block"] = block

    def run():
        return registry.dispatch(args.kernel, *call_args,
                                 interpret=args.interpret, **params)

    out = run()
    ref = spec.xla_reference(*call_args, **{k: v for k, v in params.items()
                                            if k != "block"})
    max_err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
    parity_ok = bool(max_err <= spec.tolerance)
    iters = max(1, int(args.iters))
    t0 = time.perf_counter()
    for _ in range(iters):
        run().block_until_ready()
    elapsed = time.perf_counter() - t0
    value = round(r * c * iters / elapsed, 2) if elapsed else None
    prov = registry.tier_provenance().get(args.kernel, {})
    _emit({"metric": "autotune_kernel_elems_per_sec", "value": value,
           "unit": f"elems/s ({args.kernel} {r}x{c}, "
                   f"block={block}, iters={iters})",
           "max_err": max_err, "tolerance": spec.tolerance,
           "parity_ok": parity_ok, "iters": iters,
           "block": list(block) if block else None,
           "pallas_dispatches": prov.get("pallas", 0),
           "xla_dispatches": prov.get("xla", 0),
           "compiles": compile_stats().get("compiles", 0)})
    return 0 if parity_ok else 1


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------
def cmd_search(args) -> int:
    from ..diagnostics import get_journal
    from ..resilience.atomic import atomic_write
    from . import runner as _runner
    from . import search as _search
    from . import space as _space
    from . import table as _table

    j = get_journal()
    j.install_handlers(final_cb=lambda: _emit(_diagnostic(
        "search_killed", f"killed at phase {j.last_phase!r} before "
        "completion; see the journal for autotune_trial breadcrumbs")))
    j.set_phase("autotune_setup")
    t_start = time.monotonic()
    deadline = t_start + args.budget_s
    r, c = _parse_rc(args.kernel_shape)
    families = [f.strip() for f in args.families.split(",") if f.strip()]
    unknown = sorted(set(families) - {"kernel", "serving"})
    if unknown:
        _emit(_diagnostic("bad_families", f"unknown families {unknown}"))
        return 1
    per_family = max(2, args.trials // max(1, len(families)))
    j.event("autotune_search_start", families=families,
            trials=args.trials, budget_s=args.budget_s, seed=args.seed,
            kernel=args.kernel, kernel_shape=f"{r}x{c}")

    plans = {}
    if "kernel" in families:
        plans["kernel"] = (
            _space.pallas_block_space(args.kernel, r, c),
            _runner.TrialRunner(_runner.KernelObjective(
                kernel=args.kernel, r=r, c=c, iters=args.kernel_iters,
                deadline_s=args.trial_deadline_s), workdir=args.workdir))
    if "serving" in families:
        plans["serving"] = (
            _space.serving_space(),
            _runner.TrialRunner(_runner.ServingObjective(
                seconds=args.bench_seconds, clients=args.clients,
                dim=args.dim, max_batch=args.max_batch,
                shed_ceiling=args.shed_ceiling, arrival=args.arrival,
                deadline_s=args.trial_deadline_s), workdir=args.workdir))

    results, knobs = {}, {}
    for family, (space, trunner) in plans.items():
        j.set_phase(f"autotune_search_{family}")
        wall_left = max(1.0, deadline - time.monotonic())
        budget = _search.Budget(max_trials=per_family, wall_s=wall_left)
        _search.run_search(space, trunner.evaluate, budget,
                           seed=args.seed, halving_n0=args.halving,
                           descent_rounds=args.descent_rounds)
        best = trunner.best()
        base = trunner.baseline(space.default)
        results[family] = {
            "space": space.name,
            **trunner.summary(),
            "budget_exhausted": budget.exhausted(),
            "baseline": None if base is None else {
                "config": base.config, "fitness": base.fitness,
                "trial": base.trial_id},
            "best": None if best is None else {
                "config": best.config, "fitness": best.fitness,
                "trial": best.trial_id},
            "tuned_ge_default": (
                best is not None
                and (base is None or base.fitness is None
                     or best.fitness >= base.fitness)),
        }
        if best is None:
            continue
        if family == "kernel":
            knobs.setdefault("pallas", {})[args.kernel] = {
                f"{r}x{c}": {"block": [int(best.config["block_r"]),
                                       int(best.config["block_c"])]}}
        else:
            knobs["serving"] = {
                "window_ms": float(best.config["window_ms"]),
                "max_queue": int(best.config["max_queue"])}

    j.set_phase("autotune_commit")
    elapsed = round(time.monotonic() - t_start, 2)
    total = sum(f["trials"] for f in results.values())
    table_path = None
    if knobs:
        provenance = {
            "search": {"seed": args.seed, "trials": args.trials,
                       "budget_s": args.budget_s,
                       "halving": args.halving,
                       "descent_rounds": args.descent_rounds},
            "trials": total,
            "trial_ids": {f: results[f]["trial_ids"] for f in results},
            "journal": os.environ.get("MXNET_TPU_JOURNAL", "stderr"),
            "artifact": args.out or None,
        }
        doc = _table.build_table(knobs, provenance=provenance)
        table_path = _table.commit_table(doc, args.table)

    j.set_phase("autotune_report")
    artifact = {
        "metric": METRIC, "value": total, "unit": "trials",
        "elapsed_s": elapsed, "budget_s": args.budget_s,
        "seed": args.seed, "families": results,
        "table": table_path,
        "tuned_ge_default": all(f.get("tuned_ge_default")
                                for f in results.values()),
    }
    if args.out:
        with atomic_write(args.out, "w") as f:
            json.dump(artifact, f, indent=1, sort_keys=True)
        print(f"autotune search: artifact written to {args.out}",
              file=sys.stderr)
    _emit(artifact)
    j.mark_clean()
    return 0 if table_path is not None else 1


# ---------------------------------------------------------------------------
# show / apply
# ---------------------------------------------------------------------------
def cmd_show(args) -> int:
    from . import table as _table
    path = args.table or os.environ.get(_table.ENV_TABLE, "")
    if not path:
        _emit({"ok": False, "error": "no_table",
               "detail": f"pass --table or set {_table.ENV_TABLE}"})
        return 1
    report = _table.audit_table(path)
    _emit(report)
    return 0 if report.get("ok") else 1


def cmd_apply(args) -> int:
    from ..diagnostics import get_journal
    from . import table as _table
    doc, reason = _table.read_table(args.src)
    if doc is None:
        _emit({"ok": False, "error": f"invalid_table:{reason}",
               "src": args.src})
        return 1
    if args.check_envelope:
        _doc, reason = _table.read_table(
            args.src, envelope=_table.current_envelope())
        if reason is not None:
            _emit({"ok": False, "error": f"envelope:{reason}",
                   "src": args.src,
                   "table_envelope": doc.get("envelope"),
                   "host_envelope": _table.current_envelope()})
            return 1
    dest = args.dest or os.environ.get(_table.ENV_TABLE, "")
    if not dest:
        _emit({"ok": False, "error": "no_dest",
               "detail": f"pass --dest or set {_table.ENV_TABLE}"})
        return 1
    _table.commit_table(doc, dest)
    get_journal().event("tuned_apply", src=args.src, dest=dest,
                        crc32=doc["crc32"])
    _emit({"ok": True, "src": args.src, "dest": dest,
           "crc32": doc["crc32"], "families": sorted(doc["knobs"])})
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.autotune",
        description="closed-loop autotuner (docs/autotune.md)")
    sub = ap.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("search", help="search the knob space against "
                                      "the real harnesses; commit a "
                                      "tuned table + BENCH artifact")
    s.add_argument("--table", default="tuned_table.json",
                   help="tuned-table output path (the file "
                        "MXNET_TPU_TUNED_TABLE should point at)")
    s.add_argument("--out", default="BENCH_autotune.json",
                   help="BENCH-schema artifact path ('' disables)")
    s.add_argument("--trials", type=int,
                   default=int(os.environ.get(
                       "MXNET_TPU_AUTOTUNE_TRIALS", 16)),
                   help="total trial budget across families (default "
                        "MXNET_TPU_AUTOTUNE_TRIALS or 16)")
    s.add_argument("--budget-s", type=float,
                   default=float(os.environ.get(
                       "MXNET_TPU_AUTOTUNE_BUDGET_S", 120.0)),
                   help="wall-clock budget in seconds (default "
                        "MXNET_TPU_AUTOTUNE_BUDGET_S or 120)")
    s.add_argument("--seed", type=int, default=0)
    s.add_argument("--families", default="kernel,serving",
                   help="comma list of knob families to search "
                        "(kernel, serving)")
    s.add_argument("--kernel", default="matmul_epilogue",
                   help="registered Pallas kernel to tune")
    s.add_argument("--kernel-shape", default="256x128",
                   help="RxC shape class to tune the kernel at")
    s.add_argument("--kernel-iters", type=int, default=30)
    s.add_argument("--bench-seconds", type=float, default=1.5,
                   help="closed-loop serving bench seconds per trial")
    s.add_argument("--clients", type=int, default=4)
    s.add_argument("--dim", type=int, default=16)
    s.add_argument("--max-batch", type=int, default=8)
    s.add_argument("--shed-ceiling", type=float, default=0.2,
                   help="serving gate: max tolerated shed rate")
    s.add_argument("--arrival", default=None,
                   help="recorded arrival trace for the serving trials "
                        "(serving bench --arrival)")
    s.add_argument("--halving", type=int, default=0,
                   help="> 0 seeds successive halving with N configs "
                        "instead of plain random sampling")
    s.add_argument("--descent-rounds", type=int, default=1)
    s.add_argument("--trial-deadline-s", type=float, default=150.0,
                   help="hard per-trial subprocess deadline")
    s.add_argument("--workdir", default=None,
                   help="trial scratch dir (shared AOT trial cache "
                        "lives here; default a fresh tempdir)")
    s.set_defaults(fn=cmd_search)

    sh = sub.add_parser("show", help="stdlib audit of a tuned table "
                                     "(no backend dial, nothing applied)")
    sh.add_argument("--table", default=None,
                    help="table path (default MXNET_TPU_TUNED_TABLE)")
    sh.set_defaults(fn=cmd_show)

    a = sub.add_parser("apply", help="validate a candidate table and "
                                     "atomically install it at the "
                                     "active path")
    a.add_argument("--src", required=True, help="candidate table path")
    a.add_argument("--dest", default=None,
                   help="install path (default MXNET_TPU_TUNED_TABLE)")
    a.add_argument("--check-envelope", action="store_true",
                   help="also require the table's envelope to match "
                        "THIS host (one guarded backend dial)")
    a.set_defaults(fn=cmd_apply)

    t = sub.add_parser("_trial")   # internal: runner.py's child
    t.add_argument("--kernel", required=True)
    t.add_argument("--shape", required=True)
    t.add_argument("--block", default=None)
    t.add_argument("--iters", type=int, default=30)
    t.add_argument("--interpret", action="store_true")
    t.set_defaults(fn=cmd_trial)

    args = ap.parse_args(argv)
    try:
        return args.fn(args)
    except Exception as e:          # structured line, never a bare crash
        from ..diagnostics import get_journal
        get_journal().crash(e)
        _emit(_diagnostic("autotune_crashed", f"{type(e).__name__}: {e}"))
        get_journal().mark_clean()
        return 1


if __name__ == "__main__":
    sys.exit(main())
