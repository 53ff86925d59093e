"""Serving bench CLI: ``python -m mxnet_tpu.serving bench``.

Closed-loop load generator against a small Gluon MLP behind the full
serving stack (bounded admission, dynamic batching, compiled-predictor
cache, deadlines).  Each client thread submits a request, waits for the
response, and immediately submits the next — the closed loop measures
end-to-end capacity, not queue theatre.

Artifact contract (same as bench.py): exactly ONE JSON line on stdout —
``{"metric": "serving_requests_per_sec", "value": ...}`` with latency
percentiles, shed/deadline counters, and the compile-count-vs-grid-bound
proof — plus the same document written atomically to ``--out``
(default ``BENCH_serving.json``).  Failures emit a structured error
line, never a hang: journal breadcrumbs + SIGTERM finalizer ride the
diagnostics journal exactly like bench.py.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time

METRIC = "serving_requests_per_sec"


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _setup_trace_dir(trace_dir, label):
    """``--trace-dir``: make this bench a traced pod run — journal +
    spans stream to ``<dir>/journal-<label>.jsonl``, the flight
    recorder runs, and subprocess workers (a proc-replica pool) inherit
    the dir through ``MXNET_TPU_TRACE_DIR``.  Returns the recorder (or
    None).  Call BEFORE get_journal() so the handlers bind to the
    run-dir sink."""
    if not trace_dir:
        return None
    import os

    from ..diagnostics.journal import reset_journal
    from ..observability import flight
    from ..observability import trace as obtrace
    os.makedirs(trace_dir, exist_ok=True)
    os.environ["MXNET_TPU_TRACE_DIR"] = str(trace_dir)
    reset_journal(os.path.join(str(trace_dir),
                               f"journal-{label}.jsonl"))
    obtrace.configure(mode="journal")
    return flight.FlightRecorder(str(trace_dir), label=label).install()


def _embed_distributed_trace(doc, trace_dir, recorder):
    """Fold the assembled cross-process snapshot into a BENCH artifact:
    the ``doctor --timeline`` body (per-process span counts, flight
    dumps, the slowest request's cross-process critical path)."""
    if not trace_dir:
        return
    if recorder is not None:
        recorder.stop(dump=True)
    from ..observability import aggregate
    doc["distributed_trace"] = aggregate.timeline_report(str(trace_dir))


def _diagnostic(error: str, detail: str) -> dict:
    return {"metric": METRIC, "value": None, "unit": "req/s",
            "error": error, "detail": detail}


ARRIVAL_FORMAT = "mxtpu-arrival-v1"


def _load_arrival(path):
    """Parse a recorded arrival trace: ``{"format": "mxtpu-arrival-v1",
    "events": [{"dt_ms": float[, "dim": int]}, ...]}``.  Each client
    thread replays the inter-arrival gaps (and per-event feature dims,
    which must match the served model) in order, looping until
    ``--seconds`` expires — the same burst structure every run, so two
    benches under different knobs see identical offered load.  Returns
    ``(events, None)`` or ``(None, reason)`` — a malformed trace is a
    structured bench error, never a crash mid-run."""
    import os
    if not os.path.exists(path):
        return None, f"missing:{path}"
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return None, f"unparseable:{type(e).__name__}"
    if not isinstance(doc, dict) or doc.get("format") != ARRIVAL_FORMAT:
        return None, f"format:{doc.get('format') if isinstance(doc, dict) else type(doc).__name__}"
    events = doc.get("events")
    if not isinstance(events, list) or not events:
        return None, "no_events"
    out = []
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            return None, f"event:{i}:not_object"
        dt = ev.get("dt_ms")
        if not isinstance(dt, (int, float)) or isinstance(dt, bool) \
                or dt < 0 or dt > 60_000:
            return None, f"event:{i}:dt_ms:{dt!r}"
        dim = ev.get("dim")
        if dim is not None and (not isinstance(dim, int)
                                or isinstance(dim, bool) or dim <= 0):
            return None, f"event:{i}:dim:{dim!r}"
        out.append((float(dt), dim))
    return out, None


def _build_model(dim):
    from ..gluon import nn
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu", in_units=dim))
        net.add(nn.Dense(8, in_units=32))
    net.initialize()
    return net


def cmd_bench(args) -> int:
    import numpy as np

    from ..diagnostics import get_journal
    from ..metric import LatencySummary
    from ..observability import snapshot
    from ..resilience.atomic import atomic_write
    from .server import Server, ServerConfig

    if getattr(args, "deploy", False):
        return _bench_deploy(args)
    if args.decode > 0:
        return _bench_decode(args)
    if args.tenants > 0:
        return _bench_tenants(args)
    if args.replicas > 1:
        return _bench_pool(args)

    arrival = None
    if args.arrival:
        arrival, why = _load_arrival(args.arrival)
        if arrival is None:
            _emit(_diagnostic("bad_arrival_trace",
                              f"{args.arrival}: {why}"))
            return 1

    recorder = _setup_trace_dir(args.trace_dir, "serving-bench")
    j = get_journal()
    j.install_handlers(final_cb=lambda: _emit(_diagnostic(
        "bench_killed", f"killed at phase {j.last_phase!r} before "
        "completion; see stderr journal for breadcrumbs")))
    j.set_phase("serving_bench_setup")
    net = _build_model(args.dim)
    cfg = ServerConfig(max_batch=args.max_batch, max_queue=args.queue,
                       window_ms=args.window_ms,
                       default_deadline_ms=args.deadline_ms)
    server = Server(net, config=cfg)
    server.start()

    client_lat = LatencySummary("client_latency_ms")
    stop_at = time.monotonic() + args.seconds
    ok = [0] * args.clients
    shed = [0] * args.clients
    missed = [0] * args.clients
    errored = [0] * args.clients

    def client(idx):
        from .batcher import (DeadlineExceeded, RequestError,
                              ServerOverloaded)
        rng = np.random.default_rng(idx)
        pos = idx % len(arrival) if arrival else 0
        while time.monotonic() < stop_at:
            dim = args.dim
            if arrival:
                # replay mode: honor the recorded inter-arrival gap (and
                # per-event dim) instead of the closed loop's immediate
                # resubmit; the trace loops until --seconds expires
                dt_ms, ev_dim = arrival[pos]
                pos = (pos + 1) % len(arrival)
                if ev_dim:
                    dim = ev_dim
                if dt_ms > 0:
                    time.sleep(min(dt_ms / 1000.0,
                                   max(0.0, stop_at - time.monotonic())))
                    if time.monotonic() >= stop_at:
                        break
            x = rng.standard_normal(dim).astype(np.float32)
            t0 = time.perf_counter()
            try:
                server.predict(x)
            except ServerOverloaded:
                shed[idx] += 1
                time.sleep(0.002)           # closed-loop backoff
                continue
            except DeadlineExceeded:
                missed[idx] += 1
                continue
            except RequestError as e:
                # predictor failure / stopped server: a dead client
                # thread must show in the artifact, never silently
                # deflate req/s
                errored[idx] += 1
                print(f"serving bench: client {idx}: {e}",
                      file=sys.stderr)
                time.sleep(0.01)
                continue
            client_lat.observe((time.perf_counter() - t0) * 1000.0)
            ok[idx] += 1

    j.set_phase("serving_bench_run")
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=args.seconds + 30)
    elapsed = time.monotonic() - t_start
    j.set_phase("serving_bench_report")
    server.stop(timeout_s=30)

    stats = server.stats()
    total_ok = sum(ok)
    doc = {
        "metric": METRIC,
        "value": round(total_ok / elapsed, 2) if elapsed else None,
        "unit": f"req/s (clients={args.clients}, dim={args.dim}, "
                f"max_batch={args.max_batch})",
        "elapsed_s": round(elapsed, 2),
        "completed": total_ok,
        "client_shed": sum(shed),
        "client_deadline_miss": sum(missed),
        "client_errors": sum(errored),
        "latency_ms": client_lat.summary(),
        "server": stats,
        "compiles": stats["cache"]["misses"],
        "grid_bound": server.grid.grid_bound(),
        "compile_bound_ok":
            stats["cache"]["misses"] <= server.grid.grid_bound(),
        "observability": snapshot(),
    }
    if arrival:
        doc["arrival"] = {"trace": args.arrival, "events": len(arrival),
                          "mode": "replay"}
    if args.warm_start:
        j.set_phase("serving_bench_warm_start")
        doc["warm_start"] = _warm_start_ab(args)
    _embed_distributed_trace(doc, args.trace_dir, recorder)
    if args.out:
        with atomic_write(args.out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True, default=str)
        print(f"serving bench: artifact written to {args.out}",
              file=sys.stderr)
    _emit(doc)
    j.mark_clean()
    return 0


def _warm_start_ab(args) -> dict:
    """Cold-vs-warm startup A/B on a fresh AOT cache dir: phase 1
    builds + starts + prewarms a server against an EMPTY store (pays
    the compiles, writes through), phase 2 repeats on the SAME store
    (loads).  Startup ms covers construct → start (incl. prewarm) →
    first response — the operator-visible restart cost; the compile/
    load split comes from ``observability.compile_stats()`` deltas."""
    import os
    import shutil
    import tempfile

    import numpy as np

    from ..observability import compile_stats, reset_metrics
    from .aotcache import AOTCache
    from .server import Server, ServerConfig

    aot_dir = tempfile.mkdtemp(prefix="mxtpu-aot-ab-")
    probe = AOTCache.maybe(aot_dir)
    if probe is None or probe.mode != "rw":
        # the kill switch / ro mode makes the A/B meaningless — report
        # that instead of KeyError-ing mid-phase or measuring a no-op
        shutil.rmtree(aot_dir, ignore_errors=True)
        return {"disabled": True,
                "reason": "MXNET_TPU_AOT_CACHE="
                          f"{os.environ.get('MXNET_TPU_AOT_CACHE')!r} "
                          "(warm-start A/B needs a writable cache)"}
    x = np.ones(args.dim, dtype=np.float32)

    def phase():
        reset_metrics()
        t0 = time.perf_counter()
        net = _build_model(args.dim)
        cfg = ServerConfig(max_batch=args.max_batch,
                           window_ms=args.window_ms,
                           default_deadline_ms=args.deadline_ms,
                           aot_dir=aot_dir,
                           aot_prewarm=((args.dim,),))
        server = Server(net, config=cfg).start()
        server.predict(x)
        ms = round((time.perf_counter() - t0) * 1000.0, 2)
        cs = compile_stats()
        aot = server.stats()["aot"]
        server.stop(timeout_s=30)
        return {"startup_ms": ms, "compiles": cs["compiles"],
                "aot_loads": cs["aot_loads"],
                "aot_load_ms": cs["aot_load_ms"], "cache": aot}

    try:
        cold = phase()
        warm = phase()
    finally:
        shutil.rmtree(aot_dir, ignore_errors=True)
    out = {"cold": cold, "warm": warm,
           "cold_startup_ms": cold["startup_ms"],
           "warm_startup_ms": warm["startup_ms"],
           "warm_zero_compiles": warm["compiles"] == 0}
    if warm["startup_ms"]:
        out["speedup"] = round(cold["startup_ms"] / warm["startup_ms"], 2)
    return out


DECODE_METRIC = "serving_decode_tokens_per_sec"


def _bench_decode(args) -> int:
    """--decode S: closed-loop autoregressive streams against one
    Server's continuous batcher (S decode slots, ``--clients`` stream
    generators, staggered prompt/generation lengths).  The artifact
    (BENCH_serving_decode.json) carries tokens/s, the decode journal
    reduction (steps/s, slot-occupancy histogram) and the zero-mid-run-
    compile proof: after warmup, ``counters["compiles"]`` must not move
    (docs/serving.md continuous batching)."""
    import numpy as np   # noqa: F401  (parity with siblings)

    from ..diagnostics import get_journal
    from ..metric import LatencySummary
    from ..resilience.atomic import atomic_write
    from .batcher import (DeadlineExceeded, RequestError, ServerOverloaded,
                          SlotsExhausted)
    from .decode import DecodeConfig, TinyLM
    from .server import Server, ServerConfig

    j = get_journal()
    j.install_handlers(final_cb=lambda: _emit(
        {"metric": DECODE_METRIC, "value": None, "unit": "tok/s",
         "error": "bench_killed",
         "detail": f"killed at phase {j.last_phase!r}"}))
    j.set_phase("serving_decode_bench_setup")
    model = TinyLM()
    cfg = ServerConfig(
        max_batch=args.max_batch, max_queue=args.queue,
        window_ms=args.window_ms,
        default_deadline_ms=args.deadline_ms,
        decode_model=model,
        decode=DecodeConfig(slots=args.decode,
                            default_deadline_ms=args.deadline_ms))
    server = Server(_build_model(args.dim), config=cfg)
    server.start()
    compiles_at_ready = server.decoder.counters["compiles"]

    stream_lat = LatencySummary("stream_latency_ms")
    stop_at = time.monotonic() + args.seconds
    ok = [0] * args.clients
    toks = [0] * args.clients
    shed = [0] * args.clients
    missed = [0] * args.clients
    errored = [0] * args.clients
    corrupt = []

    def client(idx):
        import numpy as np
        rng = np.random.default_rng(idx)
        while time.monotonic() < stop_at:
            # staggered lengths: prompts 1..16, generations 4..32
            prompt = [int(t) for t in
                      rng.integers(0, model.vocab,
                                   size=int(rng.integers(1, 17)))]
            n = int(rng.integers(4, 33))
            t0 = time.perf_counter()
            try:
                got = server.decode(prompt, max_new_tokens=n)
            except (ServerOverloaded, SlotsExhausted):
                shed[idx] += 1
                time.sleep(0.002)
                continue
            except DeadlineExceeded:
                missed[idx] += 1
                continue
            except RequestError as e:
                errored[idx] += 1
                print(f"decode bench: client {idx}: {e}",
                      file=sys.stderr)
                time.sleep(0.01)
                continue
            if list(got) != model.reference(prompt, n):
                corrupt.append(prompt)    # bit-exactness is the contract
            stream_lat.observe((time.perf_counter() - t0) * 1000.0)
            ok[idx] += 1
            toks[idx] += len(got)

    j.set_phase("serving_decode_bench_run")
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=args.seconds + 30)
    elapsed = time.monotonic() - t_start
    j.set_phase("serving_decode_bench_report")
    dstats = server.decoder.stats()
    server.stop(timeout_s=30)

    total_tok = sum(toks)
    doc = {
        "metric": DECODE_METRIC,
        "value": round(total_tok / elapsed, 2) if elapsed else None,
        "unit": f"tok/s (slots={args.decode}, clients={args.clients})",
        "elapsed_s": round(elapsed, 2),
        "streams_completed": sum(ok),
        "tokens_out": total_tok,
        "client_shed": sum(shed),
        "client_deadline_miss": sum(missed),
        "client_errors": sum(errored),
        "corrupt_streams": len(corrupt),
        "stream_latency_ms": stream_lat.summary(),
        "decode": dstats,
        "compiles_after_warmup":
            dstats["compiles"] - compiles_at_ready,
        "compile_bound_ok": dstats["compiles"] == compiles_at_ready,
    }
    out = args.out or ""
    if out:
        with atomic_write(out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True, default=str)
        print(f"decode bench: artifact written to {out}",
              file=sys.stderr)
    _emit(doc)
    j.mark_clean()
    # corrupt output or a mid-run compile is a failed bench, not a
    # slower one — the exit code is the gate
    return 0 if not corrupt and doc["compile_bound_ok"] else 1


TENANT_METRIC = "serving_tenant_requests_per_sec"


def _bench_tenants(args) -> int:
    """--tenants N: closed-loop mixed-tenant load against one Fleet —
    N tenants on one worker/queue/cache, clients spread round-robin.
    The artifact (BENCH_serving_tenants.json) carries per-tenant
    p50/p95/p99, shed/quarantine/page-in counters and the observability
    snapshot — the capacity-and-isolation profile of multi-tenant
    serving (docs/serving.md)."""
    import numpy as np

    from ..diagnostics import get_journal
    from ..metric import LatencySummary
    from ..observability import snapshot
    from ..resilience.atomic import atomic_write
    from .batcher import (DeadlineExceeded, RequestError, ServerOverloaded)
    from .fleet import Fleet, FleetConfig

    recorder = _setup_trace_dir(args.trace_dir, "tenant-bench")
    j = get_journal()
    j.install_handlers(final_cb=lambda: _emit(
        {"metric": TENANT_METRIC, "value": None, "unit": "req/s",
         "error": "bench_killed",
         "detail": f"killed at phase {j.last_phase!r}"}))
    j.set_phase("serving_tenant_bench_setup")
    cfg = FleetConfig(max_batch=args.max_batch, max_queue=args.queue,
                      window_ms=args.window_ms,
                      default_deadline_ms=args.deadline_ms)
    fleet = Fleet(cfg)
    names = [f"t{i}" for i in range(args.tenants)]
    for name in names:
        fleet.add_tenant(name,
                         factory=(lambda: _build_model(args.dim)))
    fleet.start()

    client_lat = {n: LatencySummary(f"client_{n}_ms") for n in names}
    stop_at = time.monotonic() + args.seconds
    ok = [0] * args.clients
    shed = [0] * args.clients
    missed = [0] * args.clients
    errored = [0] * args.clients

    def client(idx):
        tenant = names[idx % len(names)]
        rng = np.random.default_rng(idx)
        while time.monotonic() < stop_at:
            x = rng.standard_normal(args.dim).astype(np.float32)
            t0 = time.perf_counter()
            try:
                fleet.predict(x, tenant=tenant)
            except ServerOverloaded:
                shed[idx] += 1
                time.sleep(0.002)
                continue
            except DeadlineExceeded:
                missed[idx] += 1
                continue
            except RequestError as e:
                errored[idx] += 1
                print(f"tenant bench: client {idx} ({tenant}): {e}",
                      file=sys.stderr)
                time.sleep(0.01)
                continue
            client_lat[tenant].observe(
                (time.perf_counter() - t0) * 1000.0)
            ok[idx] += 1

    j.set_phase("serving_tenant_bench_run")
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=args.seconds + 30)
    elapsed = time.monotonic() - t_start
    j.set_phase("serving_tenant_bench_report")
    fleet.stop(timeout_s=30)

    stats = fleet.stats()
    total_ok = sum(ok)
    per_tenant = {}
    for name in names:
        row = stats["tenants"][name]
        per_tenant[name] = {
            "served": row["served"], "shed": row["shed"],
            "quarantines": row["quarantines"],
            "readmissions": row["readmissions"],
            "page_ins": row["page_ins"],
            "p50_ms": row["latency_ms"]["p50"],
            "p95_ms": row["latency_ms"]["p95"],
            "p99_ms": row["latency_ms"]["p99"],
            "client_latency_ms": client_lat[name].summary()}
    doc = {
        "metric": TENANT_METRIC,
        "value": round(total_ok / elapsed, 2) if elapsed else None,
        "unit": f"req/s (tenants={args.tenants}, "
                f"clients={args.clients}, dim={args.dim})",
        "elapsed_s": round(elapsed, 2),
        "completed": total_ok,
        "client_shed": sum(shed),
        "client_deadline_miss": sum(missed),
        "client_errors": sum(errored),
        "tenants": per_tenant,
        "server": {k: v for k, v in stats.items() if k != "tenants"},
        "compiles": stats["cache"]["misses"],
        "observability": snapshot(),
    }
    _embed_distributed_trace(doc, args.trace_dir, recorder)
    out = args.out or ""
    if out:
        with atomic_write(out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True, default=str)
        print(f"tenant bench: artifact written to {out}",
              file=sys.stderr)
    _emit(doc)
    j.mark_clean()
    return 0


POOL_METRIC = "serving_pool_requests_per_sec"


def _bench_pool(args) -> int:
    """--replicas N: the closed loop runs through the health-routed
    front door (Router over a ReplicaPool of N in-process replicas),
    and the artifact carries the router attempt/hedge/breaker counters
    plus the observability snapshot — BENCH_serving_pool.json."""
    import tempfile

    import numpy as np

    from ..diagnostics import get_journal
    from ..metric import LatencySummary
    from ..observability import snapshot
    from ..resilience.atomic import atomic_write
    from .batcher import (DeadlineExceeded, RequestError, ServerOverloaded)
    from .pool import PoolConfig, ReplicaPool
    from .router import Router, RouterConfig
    from .server import Server, ServerConfig

    recorder = _setup_trace_dir(args.trace_dir, "router-bench")
    j = get_journal()
    j.install_handlers(final_cb=lambda: _emit(
        {"metric": POOL_METRIC, "value": None, "unit": "req/s",
         "error": "bench_killed",
         "detail": f"killed at phase {j.last_phase!r}"}))
    j.set_phase("serving_pool_bench_setup")
    scfg = ServerConfig(max_batch=args.max_batch, max_queue=args.queue,
                        window_ms=args.window_ms,
                        default_deadline_ms=args.deadline_ms)

    def factory():
        return Server(_build_model(args.dim), config=scfg)

    root = tempfile.mkdtemp(prefix="mxtpu-pool-bench-")
    pool = ReplicaPool(root, PoolConfig(heartbeat_s=0.2, deadline_s=1.5))
    for i in range(args.replicas):
        pool.add_local(f"r{i}", factory)
    pool.start()
    router = Router(pool, RouterConfig(
        hedge_ms=args.hedge_ms, default_deadline_ms=args.deadline_ms))

    client_lat = LatencySummary("client_latency_ms")
    stop_at = time.monotonic() + args.seconds
    ok = [0] * args.clients
    shed = [0] * args.clients
    missed = [0] * args.clients
    errored = [0] * args.clients

    def client(idx):
        rng = np.random.default_rng(idx)
        while time.monotonic() < stop_at:
            x = rng.standard_normal(args.dim).astype(np.float32)
            t0 = time.perf_counter()
            try:
                router.predict(x)
            except ServerOverloaded:
                shed[idx] += 1
                time.sleep(0.002)
                continue
            except DeadlineExceeded:
                missed[idx] += 1
                continue
            except RequestError as e:
                errored[idx] += 1
                print(f"pool bench: client {idx}: {e}", file=sys.stderr)
                time.sleep(0.01)
                continue
            client_lat.observe((time.perf_counter() - t0) * 1000.0)
            ok[idx] += 1

    j.set_phase("serving_pool_bench_run")
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=args.seconds + 30)
    elapsed = time.monotonic() - t_start
    j.set_phase("serving_pool_bench_report")
    router_stats = router.stats()
    pool_view = [vars(s) for s in pool.view()]   # BEFORE stop: beacons
    router.stop()                                # resign at shutdown
    pool.stop()

    total_ok = sum(ok)
    doc = {
        "metric": POOL_METRIC,
        "value": round(total_ok / elapsed, 2) if elapsed else None,
        "unit": f"req/s (replicas={args.replicas}, "
                f"clients={args.clients}, dim={args.dim})",
        "elapsed_s": round(elapsed, 2),
        "completed": total_ok,
        "client_shed": sum(shed),
        "client_deadline_miss": sum(missed),
        "client_errors": sum(errored),
        "latency_ms": client_lat.summary(),
        "router": router_stats,
        "pool": pool_view,
        "observability": snapshot(),
    }
    _embed_distributed_trace(doc, args.trace_dir, recorder)
    out = args.out or ""
    if out:
        with atomic_write(out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True, default=str)
        print(f"pool bench: artifact written to {out}", file=sys.stderr)
    _emit(doc)
    j.mark_clean()
    return 0


DEPLOY_METRIC = "serving_deploy_rollback_ms"


def _bench_deploy(args) -> int:
    """--deploy: canary-gated deployment drill under closed-loop load —
    one GOOD deploy (identical weights recommitted: parity mirrors
    agree, gates pass, promote) and one BAD deploy (regress_params-
    poisoned step: parity gate trips, auto-rollback), with every
    response's version stamp checked against its value.  The artifact
    (BENCH_serving_deploy.json) carries gate-eval and rollback counters;
    the exit code is the gate: nonzero when the good deploy failed to
    promote, the bad deploy failed to roll back, or ANY response's
    value contradicted its stamp."""
    import os
    import tempfile

    import numpy as np

    from .. import nd
    from ..diagnostics import get_journal
    from ..resilience import commit
    from ..resilience.atomic import atomic_write
    from ..testing import faults
    from .batcher import (DeadlineExceeded, RequestError, ServerOverloaded)
    from .deploy import DeployConfig, DeployController
    from .pool import PoolConfig, ReplicaPool
    from .reload import ParamStore
    from .router import Router, RouterConfig
    from .server import Server, ServerConfig

    j = get_journal()
    j.install_handlers(final_cb=lambda: _emit(
        {"metric": DEPLOY_METRIC, "value": None, "unit": "ms",
         "error": "bench_killed",
         "detail": f"killed at phase {j.last_phase!r}"}))
    j.set_phase("serving_deploy_bench_setup")

    from ..gluon.block import HybridBlock

    class Scale(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.w = self.params.get("w", shape=(1,), init="ones")

        def hybrid_forward(self, F, x, w):
            return x * w

    def commit_scale(root, step, value):
        stage = commit.prepare_stage(root, step)
        nd.save(os.path.join(stage, "net.params"),
                {"w": nd.array(np.asarray([value], np.float32))})
        return commit.finalize(root, step)

    ck = tempfile.mkdtemp(prefix="mxtpu-deploy-bench-ckpt-")
    commit_scale(ck, 1, 3.0)
    scfg = ServerConfig(max_batch=args.max_batch, max_queue=args.queue,
                        window_ms=args.window_ms,
                        default_deadline_ms=args.deadline_ms)

    def factory():
        net = Scale()
        net.initialize()
        return Server(net, config=scfg, param_store=ParamStore(ck))

    n = max(args.replicas, 3)
    root = tempfile.mkdtemp(prefix="mxtpu-deploy-bench-")
    pool = ReplicaPool(root, PoolConfig(heartbeat_s=0.2, deadline_s=1.5))
    for i in range(n):
        pool.add_local(f"r{i}", factory)
    pool.start()
    router = Router(pool, RouterConfig(
        default_deadline_ms=args.deadline_ms))
    base_deadline = time.monotonic() + 30.0
    while time.monotonic() < base_deadline:      # baseline adoption
        if all(s.params_step == 1 for s in pool.view()):
            break
        time.sleep(0.05)
    else:
        _emit({"metric": DEPLOY_METRIC, "value": None, "unit": "ms",
               "error": "baseline_never_adopted",
               "detail": "replicas never converged on step 1"})
        return 1

    # every response's value must match its version stamp's weight —
    # a stamped-3 answer computed with w=3's weights is the one
    # corruption class a canary may NEVER leak
    w_by_step = {None: 1.0, 1: 3.0, 2: 3.0, 3: 30.0}
    stop = threading.Event()
    ok = [0] * args.clients
    shed = [0] * args.clients
    errored = [0] * args.clients
    corrupt = [0] * args.clients
    stamps = [dict() for _ in range(args.clients)]

    def client(idx):
        rng = np.random.default_rng(idx)
        while not stop.is_set():
            x = rng.standard_normal(args.dim).astype(np.float32)
            try:
                resp = router.call(x)     # RouterResponse: value + stamp
            except (ServerOverloaded, DeadlineExceeded):
                shed[idx] += 1
                time.sleep(0.002)
                continue
            except RequestError:
                errored[idx] += 1
                time.sleep(0.01)
                continue
            st = resp.params_step
            want = x * w_by_step.get(st, float("nan"))
            got = resp.value
            got = got.asnumpy() if hasattr(got, "asnumpy") else got
            if not np.allclose(np.asarray(got).ravel(), want,
                               rtol=1e-4, atol=1e-5):
                corrupt[idx] += 1
            stamps[idx][st] = stamps[idx].get(st, 0) + 1
            ok[idx] += 1

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()

    dcfg = DeployConfig(canary_k=1, window_s=0.4, promote_after=2,
                        min_samples=5, mirror_fraction=0.25,
                        rollback_s=15.0, deadline_s=30.0)
    ctl = DeployController(pool, router, ck, dcfg)

    j.set_phase("serving_deploy_bench_good")
    commit_scale(ck, 2, 3.0)          # same weights: parity must agree
    good = ctl.deploy(2)

    j.set_phase("serving_deploy_bench_bad")
    commit_scale(ck, 3, 3.0)
    faults.regress_params(ck, 3, scale=10.0)   # CRC-valid, wrong answers
    bad = ctl.deploy(3)

    j.set_phase("serving_deploy_bench_report")
    time.sleep(0.5)                   # post-rollback traffic window
    stop.set()
    for t in threads:
        t.join(timeout=30)
    elapsed = time.monotonic() - t_start
    router.stop()
    pool.stop()

    merged = {}
    for d in stamps:
        for k, v in d.items():
            merged[k] = merged.get(k, 0) + v
    total_corrupt = sum(corrupt)
    passed = (good.get("result") == "promoted"
              and bad.get("result") == "rolled_back"
              and total_corrupt == 0)
    doc = {
        "metric": DEPLOY_METRIC,
        "value": bad.get("rollback_ms"),
        "unit": f"ms (replicas={n}, clients={args.clients}, "
                f"canary_k={dcfg.canary_k})",
        "elapsed_s": round(elapsed, 2),
        "completed": sum(ok),
        "client_shed": sum(shed),
        "client_errors": sum(errored),
        "corrupt_responses": total_corrupt,
        "responses_by_step": {str(k): v for k, v in merged.items()},
        "good_deploy": good,
        "bad_deploy": bad,
        "gate_evals": (good.get("gate_evals", 0)
                       + bad.get("gate_evals", 0)),
        "rollbacks": int(bad.get("result") == "rolled_back"),
        "promotions": int(good.get("result") == "promoted"),
        "rollback_reason": bad.get("reason"),
        "passed": passed,
    }
    out = args.out or ""
    if out:
        with atomic_write(out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True, default=str)
        print(f"deploy bench: artifact written to {out}", file=sys.stderr)
    _emit(doc)
    j.mark_clean()
    return 0 if passed else 1


WARM_METRIC = "aot_warm_entries"


def _parse_shapes(spec: str) -> tuple:
    """``"16"`` / ``"8x128,8x256"`` → feature shapes (no batch axis)."""
    shapes = []
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        try:
            shapes.append(tuple(int(d) for d in part.split("x")))
        except ValueError:
            raise ValueError(f"bad --shapes entry {part!r}: expected "
                             "comma-separated DxDx... ints") from None
    if not shapes:
        raise ValueError(f"--shapes {spec!r} names no shapes")
    return tuple(shapes)


def cmd_warm(args) -> int:
    """``warm --dir ROOT``: offline prewarm — compile + persist a
    model's bucket lattice ahead of deploy, so the FIRST serving start
    on that cache dir is already warm.  Emits one JSON line (entry
    counts, loaded/compiled split, directory audit) and exits 0 on a
    fully-warmed lattice."""
    from ..diagnostics import get_journal
    from . import aot_report
    from .server import Server, ServerConfig
    from .worker import _build_block

    j = get_journal()
    j.install_handlers(final_cb=lambda: _emit(_diagnostic(
        "warm_killed", f"killed at phase {j.last_phase!r}")))
    j.set_phase("aot_warm_setup")
    shapes = _parse_shapes(args.shapes if args.shapes is not None
                           else str(args.dim))
    net = _build_block(args.model, args.dim)
    cfg = ServerConfig(max_batch=args.max_batch, aot_dir=args.dir)
    server = Server(net, config=cfg)     # never started: no worker, no
    # fail BEFORE the lattice compile: warming with the cache switched
    # off (or read-only) would pay every compile and persist nothing —
    # a deploy that trusts the exit code would then start cold
    if server.aot is None or server.aot.mode != "rw":
        mode = None if server.aot is None else server.aot.mode
        _emit(_diagnostic(
            "aot_cache_not_writable",
            f"MXNET_TPU_AOT_CACHE mode {mode!r} — `warm` needs a "
            "writable cache; nothing would be persisted"))
        j.mark_clean()
        return 1
    j.set_phase("aot_warm_run")          # traffic — just the lattice
    res = server.prewarm(shapes)
    j.set_phase("aot_warm_report")
    aot_stats = server.aot.stats()
    doc = {"metric": WARM_METRIC,
           "value": res["warmed"],
           "unit": f"entries (model={args.model}, dim={args.dim}, "
                   f"shapes={[list(s) for s in shapes]})",
           **res,
           "aot": aot_stats,
           "dir_report": aot_report.aot_report(args.dir)}
    _emit(doc)
    j.mark_clean()
    # the exit code is the deploy gate: a backend that cannot serialize
    # its executables compiles the lattice but persists NOTHING
    # (journaled aot_store_failed) — that must not read as warmed
    if aot_stats["store_failures"] > 0:
        print(f"warm: {aot_stats['store_failures']} store(s) failed — "
              "the cache dir is NOT fully seeded (see aot_store_failed "
              "journal records)", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.serving",
        description="serving subsystem CLI (docs/serving.md)")
    sub = ap.add_subparsers(dest="cmd", required=True)
    b = sub.add_parser("bench", help="closed-loop load generator; ONE "
                                     "JSON line on stdout + --out artifact")
    b.add_argument("--seconds", type=float, default=3.0)
    b.add_argument("--clients", type=int, default=4)
    b.add_argument("--dim", type=int, default=16)
    b.add_argument("--max-batch", type=int, default=8)
    b.add_argument("--queue", type=int, default=64)
    b.add_argument("--window-ms", type=float, default=2.0)
    b.add_argument("--deadline-ms", type=float, default=5000.0)
    b.add_argument("--replicas", type=int, default=1,
                   help="> 1 routes the closed loop through a Router "
                        "over N in-process replicas and writes the "
                        "BENCH_serving_pool artifact")
    b.add_argument("--tenants", type=int, default=0,
                   help="> 0 runs the closed loop as mixed-tenant load "
                        "against one Fleet of N tenants and writes the "
                        "BENCH_serving_tenants artifact (per-tenant "
                        "p99/shed/quarantine counters)")
    b.add_argument("--decode", type=int, default=0,
                   help="> 0 runs the closed loop as autoregressive "
                        "decode streams against one Server's continuous "
                        "batcher with N slots and writes the "
                        "BENCH_serving_decode artifact (tokens/s, "
                        "occupancy, zero-mid-run-compile proof)")
    b.add_argument("--deploy", action="store_true",
                   help="run the canary-gated deployment drill instead "
                        "of the raw closed loop: one good deploy "
                        "(promote) + one regress_params-poisoned deploy "
                        "(parity gate trips, auto-rollback) under load, "
                        "with stamp-vs-value corruption checks; writes "
                        "BENCH_serving_deploy.json and exits nonzero "
                        "when any gate outcome or response is wrong")
    b.add_argument("--arrival", default=None,
                   help="replay a recorded arrival trace (JSON: "
                        "{'format': 'mxtpu-arrival-v1', 'events': "
                        "[{'dt_ms': F[, 'dim': N]}, ...]}) instead of "
                        "the closed loop's immediate resubmit: each "
                        "client honors the recorded inter-arrival gaps "
                        "in order, looping until --seconds expires — "
                        "identical offered load across A/B runs "
                        "(benchmarks/arrival_smoke.json)")
    b.add_argument("--hedge-ms", type=float, default=0.0,
                   help="tail-latency hedge delay for --replicas mode "
                        "(0 = off)")
    b.add_argument("--trace-dir", default=None,
                   help="run the bench as a traced pod run: spans + "
                        "journal stream into this directory, the "
                        "flight recorder runs, and the artifact embeds "
                        "the assembled cross-process snapshot "
                        "(doctor --timeline body) under "
                        "'distributed_trace'")
    b.add_argument("--warm-start", action="store_true",
                   help="run a cold-vs-warm startup A/B on a fresh AOT "
                        "cache dir after the closed loop and embed "
                        "cold/warm startup ms + the zero-compile proof "
                        "under 'warm_start' in the artifact "
                        "(docs/serving.md AOT cache)")
    b.add_argument("--out", default=None,
                   help="artifact path ('' disables; default "
                        "BENCH_serving.json, BENCH_serving_pool.json "
                        "with --replicas > 1, or "
                        "BENCH_serving_tenants.json with --tenants)")
    b.set_defaults(fn=cmd_bench)
    wm = sub.add_parser(
        "warm", help="offline prewarm: compile + persist a model's "
                     "bucket lattice into an AOT cache dir ahead of "
                     "deploy; ONE JSON line on stdout (docs/serving.md)")
    wm.add_argument("--dir", required=True,
                    help="AOT cache root (MXNET_TPU_AOT_CACHE_DIR of "
                         "the serving processes that should start warm)")
    wm.add_argument("--model", default="mlp", help="scale|mlp (the "
                    "worker model zoo; serving/worker.py)")
    wm.add_argument("--dim", type=int, default=16)
    wm.add_argument("--max-batch", type=int, default=8)
    wm.add_argument("--shapes", default=None,
                    help="comma-separated feature shapes to warm, each "
                         "DxDx... (no batch axis; default the model "
                         "--dim)")
    wm.set_defaults(fn=cmd_warm)
    w = sub.add_parser("worker", help="replica worker process behind a "
                                      "loopback socket (serving/pool.py "
                                      "spawns these; docs/serving.md)")
    from .worker import add_worker_args, cmd_worker
    add_worker_args(w)
    w.set_defaults(fn=cmd_worker)
    args = ap.parse_args(argv)
    if getattr(args, "out", None) is None and args.cmd == "bench":
        args.out = ("BENCH_serving_deploy.json" if args.deploy
                    else "BENCH_serving_decode.json" if args.decode > 0
                    else "BENCH_serving_tenants.json" if args.tenants > 0
                    else "BENCH_serving_pool.json" if args.replicas > 1
                    else "BENCH_serving.json")
    from .. import runtime
    runtime.enable_compile_cache()
    try:
        return args.fn(args)
    except Exception as e:              # structured line, never a bare crash
        from ..diagnostics import get_journal
        get_journal().crash(e)
        _emit(_diagnostic("bench_crashed", f"{type(e).__name__}: {e}"))
        get_journal().mark_clean()
        return 1


if __name__ == "__main__":
    sys.exit(main())
