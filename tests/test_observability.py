"""Unified telemetry (docs/observability.md): span tracing semantics
(nesting, thread propagation, ring bounds), the metrics registry and
its Prometheus exposition, the Chrome-trace/Perfetto export golden
tests, the journal trace-id correlation, the serving per-request span
tree, and the disabled-overhead transfer-guard contract across all four
training paths.

The ``*smoke*`` tests are CI's tier-0.5 observability smoke
(ci/run_tests.sh): one traced training step + one traced serving
request, both exporters parsed.
"""
import json
import re
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, io, observability, parallel, sym
from mxnet_tpu.diagnostics import journal
from mxnet_tpu.guardrails import GuardConfig
from mxnet_tpu.observability import (export, instrument, metrics, stages,
                                     trace)
from mxnet_tpu.observability.report import metrics_report, trace_report
from mxnet_tpu.serving import Server, ServerConfig
from mxnet_tpu.testing import faults


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Every test starts from the env default (tracing off) and a clean
    metrics registry, and leaves no tracer/journal state behind."""
    trace.reset_tracer()
    metrics.reset_metrics()
    yield
    trace.reset_tracer()
    metrics.reset_metrics()


@pytest.fixture
def ring():
    return trace.configure(mode="ring")


@pytest.fixture
def jfile(tmp_path):
    jf = str(tmp_path / "journal.jsonl")
    journal.reset_journal(jf)
    try:
        yield jf
    finally:
        journal.reset_journal()


def _read_journal(path):
    out = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            if line.strip():
                out.append(json.loads(line))
    return out


def _mlp():
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu", in_units=8))
        net.add(gluon.nn.Dense(4, in_units=16))
    net.initialize()
    return net


def _sharded(guard=None, **kw):
    net = _mlp()
    mesh = parallel.make_mesh({"data": -1})
    tr = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh, guard=guard, **kw)
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    y = rng.randint(0, 4, (16,))
    return tr, x, y


# -- span semantics ----------------------------------------------------------

def test_span_nesting_ids_and_ring(ring):
    with trace.span("outer", a=1) as outer:
        assert trace.current_span() is outer
        with trace.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
        trace.event("pt", x=2)
    assert trace.current_span() is None
    spans = {s["name"]: s for s in ring.spans()}
    assert set(spans) == {"outer", "inner", "pt"}
    assert spans["outer"]["parent_id"] is None
    assert spans["outer"]["attrs"] == {"a": 1}
    assert spans["pt"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["pt"]["dur_s"] == 0.0
    assert all(s["dur_s"] >= 0 for s in spans.values())
    # two separate roots get distinct trace ids (process-token prefixed)
    with trace.span("other"):
        pass
    other = [s for s in ring.spans() if s["name"] == "other"][0]
    assert other["trace_id"] != spans["outer"]["trace_id"]


def test_ring_is_bounded_and_counts_drops():
    tr = trace.configure(mode="ring", ring=4)
    for i in range(10):
        with trace.span(f"s{i}"):
            pass
    assert len(tr.spans()) == 4
    assert tr.recorded == 10 and tr.dropped == 6
    assert [s["name"] for s in tr.spans()] == ["s6", "s7", "s8", "s9"]


def test_thread_parent_propagation(ring):
    """contextvars don't cross threads: the capture token does — the
    serving-worker pattern."""
    got = {}

    def worker(ctx):
        with trace.span("child", parent=ctx) as sp:
            got["trace"] = sp.trace_id
            got["parent"] = sp.parent_id

    with trace.span("root") as root:
        ctx = trace.current_context()
        t = threading.Thread(target=worker, args=(ctx,))
        t.start()
        t.join(10)
    assert got["trace"] == root.trace_id
    assert got["parent"] == root.span_id
    child = [s for s in ring.spans() if s["name"] == "child"][0]
    assert child["thread"] != "MainThread"


def test_disabled_tracing_is_inert_noop():
    assert trace.mode() == "off"
    sp = trace.span("x", a=1)
    sp2 = trace.span("y")
    assert sp is sp2                         # one shared no-op object
    with sp:
        assert trace.current_ids() == {}
        assert trace.annotate(k=1) is False
    assert trace.get_tracer().recorded == 0


def test_bad_trace_mode_degrades_off(monkeypatch, jfile):
    monkeypatch.setenv("MXNET_TPU_TRACE", "bogus")
    tr = trace.reset_tracer()
    assert tr.mode == "off"
    recs = [r for r in _read_journal(jfile) if r["kind"] == "trace_bad_mode"]
    assert recs and recs[0]["value"] == "bogus"


# -- journal correlation (the satellite: one trace across journals) ----------

def test_journal_records_carry_trace_ids_inside_spans(ring, jfile):
    j = journal.get_journal()
    j.event("plain")                         # outside any span
    with trace.span("scope") as sp:
        j.event("inside", foo=1)
        # explicit fields always win over the provider
        j.event("explicit", trace_id="mine")
    recs = {r["kind"]: r for r in _read_journal(jfile)}
    assert "trace_id" not in recs["plain"]   # bit-identical when off-span
    assert recs["inside"]["trace_id"] == sp.trace_id
    assert recs["inside"]["span_id"] == sp.span_id
    assert recs["inside"]["foo"] == 1
    assert recs["explicit"]["trace_id"] == "mine"


def test_guardrail_skip_record_correlates_with_step_trace(ring, jfile):
    tr, x, y = _sharded(guard=True)
    tr.step(x, y)
    tr.step(faults.poison_batch(x), y)
    skip = [r for r in _read_journal(jfile)
            if r["kind"] == "nonfinite_grad"][0]
    assert "trace_id" in skip and "span_id" in skip
    steps = [s for s in trace.get_tracer().spans()
             if s["name"] == "sharded_trainer.step"]
    assert skip["trace_id"] in {s["trace_id"] for s in steps}


# -- metrics registry + exposition -------------------------------------------

def test_metrics_registry_families_and_snapshot():
    reg = metrics.MetricsRegistry()
    c = reg.counter("req_total", "requests", ("route",))
    c.labels(route="a").inc()
    c.labels(route="a").inc(2)
    c.labels(route="b").inc()
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    s = reg.summary("lat_ms", "latency", ())
    for v in (1.0, 2.0, 10.0):
        s.observe(v)
    snap = reg.snapshot()
    assert snap["req_total"]["values"] == {"route=a": 3.0, "route=b": 1.0}
    assert snap["depth"]["values"][""] == 7.0
    assert snap["lat_ms"]["values"][""]["count"] == 3
    # idempotent getter; kind mismatch is structural
    assert reg.counter("req_total", labelnames=("route",)) is c
    with pytest.raises(Exception, match="already registered"):
        reg.gauge("req_total")
    with pytest.raises(Exception, match="takes labels"):
        c.labels(wrong="x")


def test_prometheus_exposition_format():
    reg = metrics.MetricsRegistry()
    reg.counter("c_total", "a counter", ("site",)).labels(
        site='we"ird\\x').inc(5)
    reg.gauge("g", "a gauge").set(1.5)
    s = reg.summary("s_ms", "a summary")
    s.observe(4.0)
    text = reg.prometheus_text()
    lines = text.splitlines()
    assert "# TYPE c_total counter" in lines
    assert "# HELP c_total a counter" in lines
    assert 'c_total{site="we\\"ird\\\\x"} 5' in lines
    assert "g 1.5" in lines
    assert 's_ms{quantile="0.5"} 4' in lines
    assert "s_ms_sum 4" in lines and "s_ms_count 1" in lines
    # every non-comment line is `name{labels} value`
    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? '
        r'(NaN|[+-]?Inf|[-+0-9.e]+)$')
    for ln in lines:
        if ln and not ln.startswith("#"):
            assert sample_re.match(ln), ln


def test_latency_summary_is_reexported_for_compat():
    from mxnet_tpu.metric import LatencySummary
    assert LatencySummary is metrics.LatencySummary
    ls = LatencySummary(reservoir_size=4)
    for v in range(100):
        ls.observe(float(v))
    assert ls.count == 100
    assert len(ls._buf) == 4
    with pytest.raises(mx.MXNetError):
        LatencySummary(reservoir_size=0)


def test_step_phase_metrics_are_always_on_even_with_trace_off():
    """The bench provenance path: compile counts and step-phase
    summaries accumulate with tracing disabled."""
    assert trace.mode() == "off"
    tr, x, y = _sharded()
    tr.step(x, y)
    tr.step(x, y)
    snap = observability.snapshot()
    phases = snap["metrics"][instrument.PHASE_METRIC]["values"]
    key = "trainer=sharded_trainer,phase=compiled_step"
    assert phases[key]["count"] == 2
    comp = observability.compile_stats(snap)
    assert comp["compiles"] == 1
    assert comp["by_site"] == {"sharded_trainer.step": 1}
    assert snap["trace"]["recorded"] == 0


# -- Perfetto / Chrome-trace export golden -----------------------------------

def _assert_chrome_doc(doc):
    """The format contract Perfetto's JSON importer needs: a
    traceEvents list of complete events with name/ph/ts/dur/pid/tid."""
    assert set(doc) >= {"traceEvents"}
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], str)
        assert "span_id" in ev["args"] and "trace_id" in ev["args"]
    json.loads(json.dumps(doc))              # round-trips as pure JSON


def _containment(doc, child_name, parent_name):
    """Child events sit inside their parent's [ts, ts+dur] window."""
    evs = doc["traceEvents"]
    by_id = {e["args"]["span_id"]: e for e in evs}
    checked = 0
    for e in evs:
        if e["name"] != child_name:
            continue
        parent = by_id.get(e["args"].get("parent_id"))
        if parent is None or parent["name"] != parent_name:
            continue
        eps = 1e3  # 1 ms slack for rounding
        assert e["ts"] >= parent["ts"] - eps
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + eps
        checked += 1
    assert checked > 0, f"no {child_name} under {parent_name}"


def test_smoke_traced_training_step_perfetto_export(tmp_path, ring):
    """Acceptance: a traced training run exports Chrome-trace JSON with
    compile events, step phases and checkpoint commits as nested
    spans."""
    tr, x, y = _sharded(guard=True)
    tr.step(x, y)
    tr.step(x, y)
    tr.checkpoint(str(tmp_path / "ckpt"))
    out = str(tmp_path / "trace.json")
    n = export.export_chrome(out)
    with open(out, encoding="utf-8") as f:
        doc = json.load(f)
    assert len(doc["traceEvents"]) == n
    _assert_chrome_doc(doc)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"sharded_trainer.step", "sharded_trainer.data_wait",
            "sharded_trainer.compiled_step",
            "sharded_trainer.guard_fetch", "xla_compile",
            "ckpt_commit"} <= names
    _containment(doc, "sharded_trainer.compiled_step",
                 "sharded_trainer.step")
    # the program's first call is a set-up stage, which nests the compile
    _containment(doc, "setup.first_call", "sharded_trainer.compiled_step")
    _containment(doc, "xla_compile", "setup.first_call")
    # exactly one compile event for two same-shape steps
    compiles = [e for e in doc["traceEvents"] if e["name"] == "xla_compile"]
    assert len(compiles) == 1
    assert compiles[0]["args"]["shapes"] == [[16, 8], [16]]


def _fit_mod(tmp_path=None, num_epoch=2, prefix=None):
    rng = np.random.RandomState(0)
    x = rng.randn(40, 6).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.float32)
    it = io.NDArrayIter(x, y, batch_size=10)
    data = sym.var("data")
    fc = sym.FullyConnected(data, num_hidden=2, name="fc")
    net = sym.SoftmaxOutput(fc, name="softmax")
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",))
    mod.fit(it, num_epoch=num_epoch, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            checkpoint_prefix=prefix)
    return mod, it


def test_traced_module_fit_epoch_perfetto_export(tmp_path, ring):
    """Acceptance: a traced module.fit epoch exports a Perfetto-valid
    trace with the epoch/step/phase/compile/checkpoint span tree."""
    _fit_mod(prefix=str(tmp_path / "ck" / "mlp"))
    doc = export.to_chrome_trace()
    _assert_chrome_doc(doc)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"module_fit.epoch", "module_fit.step",
            "module_fit.forward_backward", "module_fit.update",
            "module_fit.data_wait", "xla_compile",
            "ckpt_commit"} <= names
    _containment(doc, "module_fit.step", "module_fit.epoch")
    _containment(doc, "module_fit.forward_backward", "module_fit.step")
    _containment(doc, "ckpt_commit", "module_fit.epoch")
    # the bind compile is tagged with the module site
    sites = {e["args"].get("site") for e in doc["traceEvents"]
             if e["name"] == "xla_compile"}
    assert "module_bind" in sites


def test_chrome_trace_from_journal_roundtrip(tmp_path, jfile):
    trace.configure(mode="journal")
    with trace.span("a", k=1):
        with trace.span("b"):
            pass
    doc = export.chrome_trace_from_journal(jfile)
    _assert_chrome_doc(doc)
    assert {e["name"] for e in doc["traceEvents"]} == {"a", "b"}
    # journal mode also keeps the ring populated
    assert len(trace.get_tracer().spans()) == 2


# -- serving: one linked span tree per request --------------------------------

class _Scale(gluon.block.HybridBlock):
    def __init__(self, k=3.0, **kw):
        super().__init__(**kw)
        self.k = k

    def hybrid_forward(self, F, x):
        return x * self.k


def test_smoke_serving_request_linked_span_tree(ring, jfile):
    """Acceptance: each served request owns one span tree —
    serving_request root with enqueue/execute/respond children — and
    the execute child names the shared batch span; the serving_batch
    journal record carries the batch span's ids."""
    net = _Scale()
    net.initialize()
    srv = Server(net, ServerConfig(max_batch=4, window_ms=2.0)).start()
    try:
        outs = [srv.predict(np.ones((3,), np.float32) * i)
                for i in range(2)]
    finally:
        srv.stop()
    for i, o in enumerate(outs):
        np.testing.assert_allclose(np.asarray(o), np.ones(3) * i * 3.0)

    spans = trace.get_tracer().spans()
    roots = [s for s in spans if s["name"] == "serving_request"]
    assert len(roots) == 2
    batch_ids = {s["span_id"] for s in spans if s["name"] == "serving_batch"}
    for root in roots:
        kids = {s["name"]: s for s in spans
                if s.get("parent_id") == root["span_id"]}
        assert {"enqueue", "execute", "respond"} <= set(kids)
        assert all(s["trace_id"] == root["trace_id"]
                   for s in kids.values())
        assert kids["execute"]["attrs"]["batch_span"] in batch_ids
        assert root["attrs"]["status"] == "ok"
    # batch journal record carries the batch span ids (worker thread)
    recs = [r for r in _read_journal(jfile) if r["kind"] == "serving_batch"]
    assert recs and all(r.get("span_id") in batch_ids for r in recs)
    # and the whole ring exports as a Perfetto-valid doc
    _assert_chrome_doc(export.to_chrome_trace())


def test_serving_shed_record_carries_request_trace(ring, jfile):
    from mxnet_tpu.serving import ServerOverloaded
    net = _Scale()
    net.initialize()
    srv = Server(net, ServerConfig(max_batch=2, max_queue=1))
    # not started: the queue fills and the second submit sheds
    srv.submit(np.ones((3,), np.float32))
    with pytest.raises(ServerOverloaded):
        srv.submit(np.ones((3,), np.float32))
    shed = [r for r in _read_journal(jfile) if r["kind"] == "serving_shed"]
    sheds = [s for s in trace.get_tracer().spans()
             if s["name"] == "serving_request"
             and s["attrs"].get("status") == "shed"]
    assert shed and sheds
    assert shed[0]["trace_id"] == sheds[0]["trace_id"]
    srv._fail_remaining([])                 # drain the queued request


# -- Prometheus endpoint on the serving server -------------------------------

def test_server_metrics_text_and_http_endpoint():
    import http.client
    net = _Scale()
    net.initialize()
    srv = Server(net, ServerConfig(max_batch=4, window_ms=2.0)).start()
    try:
        srv.predict(np.ones((3,), np.float32))
        text = srv.metrics_text()
        sid = srv._metrics_id
        assert "# TYPE mxnet_tpu_serving_queue_depth gauge" in text
        assert (f'mxnet_tpu_serving_events{{server="{sid}",'
                f'event="served"}} 1') in text
        assert (f'mxnet_tpu_serving_cache_events{{server="{sid}",'
                f'event="misses"}} 1') in text
        # the shared registry rides along: the serving compile is there
        assert 'mxnet_tpu_xla_compiles_total{site="serving_predictor"} 1' \
            in text
        httpd = srv.start_metrics_server(port=0)
        assert srv.start_metrics_server() is httpd      # idempotent
        port = httpd.server_address[1]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = resp.read().decode("utf-8")
        assert resp.status == 200
        assert "text/plain" in resp.getheader("Content-Type")
        assert "mxnet_tpu_serving_events" in body
        conn.request("GET", "/nope")
        assert conn.getresponse().status == 404
        conn.close()
    finally:
        srv.stop()
    assert srv._metrics_httpd is None       # stop() shut the endpoint


# -- the disabled-overhead contract ------------------------------------------

def test_trace_off_zero_host_reads_sharded_and_pipelined():
    """With MXNET_TPU_TRACE=off the instrumented compiled step paths
    add ZERO device→host transfers: the fused trainers run under
    transfer_guard(disallow) (the guardrails technique)."""
    import jax
    assert trace.mode() == "off"
    tr, x, y = _sharded(guard=GuardConfig(mode="deferred"))
    tr.step(x, y)                           # compile + warm
    xb = [tr._shard_batch_arg(b) for b in (x, y)]
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(2):
            tr.step(*xb)

    mesh = parallel.make_mesh({"pipe": 2, "data": 4})
    emb = gluon.nn.Embedding(16, 8)
    body = [gluon.nn.Dense(8, in_units=8, flatten=False)
            for _ in range(2)]
    head = gluon.nn.Dense(16, in_units=8, flatten=False)
    for b in (emb, *body, head):
        b.initialize()
    ptr = parallel.PipelinedTrainer(
        emb, body, head, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, mesh=mesh, num_microbatches=2)
    tok = np.arange(32, dtype=np.int32).reshape(8, 4) % 16
    lab = tok.copy()
    ptr.step(tok, lab)                      # compile + warm
    import jax.numpy as jnp
    tokd, labd = jnp.asarray(tok), jnp.asarray(lab)
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(2):
            ptr.step(tokd, labd)


def test_trace_off_zero_host_reads_gluon_trainer_and_module():
    """The eager paths: gluon Trainer.step (no guard/scaler) and the
    module fit step loop (no metric sync) also add zero transfers."""
    import jax
    from mxnet_tpu import autograd
    assert trace.mode() == "off"
    net = _mlp()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1}, kvstore=None)
    x = mx.nd.array(np.random.RandomState(0).randn(8, 8)
                    .astype(np.float32))
    y = mx.nd.array(np.random.RandomState(1).randint(0, 4, (8,)))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def one_step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        tr.step(batch_size=8)

    one_step()                              # warm every jitted kernel
    with jax.transfer_guard_device_to_host("disallow"):
        one_step()

    # module fit's instrumented batch loop (_fit_epoch), metric no-op'd
    class _NoSync(mx.metric.EvalMetric):
        def update(self, labels, preds):
            pass

    mod, it = _fit_mod(num_epoch=1)
    it.reset()
    with jax.transfer_guard_device_to_host("disallow"):
        stopped, steps = mod._fit_epoch(
            it, _NoSync("nosync"), epoch=1, monitor=None,
            anomaly_monitor=None, checkpoint_prefix=None,
            batch_end_callback=None, watch=None, global_step=0)
    assert not stopped and steps == 4


# -- the phases on the profiler's clock (mxnet_tpu.* annotations) ------------

def _dropout_sharded(seed):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu", in_units=8))
        net.add(gluon.nn.Dropout(0.5))
        net.add(gluon.nn.Dense(4, in_units=16))
    net.initialize()
    tr = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        mesh=parallel.make_mesh({"data": -1}))
    rng = np.random.RandomState(0)
    return tr, rng.randn(16, 8).astype(np.float32), rng.randint(0, 4, (16,))


def _profiled_host_events(tmp_path, work):
    """``[(name, start_ns, end_ns, stats), ...]`` of the ``mxnet_tpu.*``
    annotations and ``PjitFunction(...)`` calls that ``work()`` leaves on
    ``/host:CPU`` of a ``jax.profiler`` session."""
    import glob

    import jax
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    (host,) = [p for p in ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    return sorted(
        ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
         for line in host.lines for ev in line.events
         if ev.name.startswith((instrument.ANNOTATION_PREFIX,
                                "PjitFunction("))),
        key=lambda e: e[1])


@pytest.mark.parametrize("call, program", [("step", "step"),
                                           ("run_steps", "multi")])
def test_profiler_session_holds_one_span_tree_per_trainer_call(
        tmp_path, call, program):
    assert trace.mode() == "off"            # no MXNET_TPU_TRACE needed
    tr, x, y = _sharded()

    def work():
        if call == "step":
            return tr.step(x, y).asscalar()
        return tr.run_steps(x, y, num_steps=2).asscalar()

    work()                                  # compile + warm
    events = _profiled_host_events(tmp_path, work)
    prefix = instrument.ANNOTATION_PREFIX + "sharded_trainer."
    (outer,) = [e for e in events if e[0] == prefix + call]
    want = {"step": {"step": 2},
            "run_steps": {"start_step": 3, "num_steps": 2}}[call]
    assert outer[3] == want                 # host scalars, the span's own
    phases = [e for e in events
              if e[0].startswith(prefix) and e is not outer]
    assert [e[0][len(prefix):] for e in phases] == [
        "data_wait", "host_args", "compiled_step", "guard_fetch"]
    reach = outer[1]
    for _, start, end, _ in phases:         # in that order, inside the call
        assert reach <= start <= end <= outer[2]
        reach = end
    # the call starts one device program, the compiled step, in
    # compiled_step: the scalars are host values and the key is split inside
    # the program, so host_args starts none; and the loss read that follows
    # the call starts none either (run_steps returns its last loss from the
    # program, no eager ``losses[-1]``). A set: the profiler holds one call
    # as two nested events
    programs = {(name, next((p[0][len(prefix):] for p in phases
                             if p[1] <= start and end <= p[2]), None))
                for name, start, end, _ in events
                if name.startswith("PjitFunction(")
                and outer[1] <= start <= outer[2] + 5_000_000}
    assert programs == {(f"PjitFunction({program})", "compiled_step")}


def test_no_profiler_session_no_ring_span_no_device_read():
    import jax
    assert trace.mode() == "off"
    tr, x, y = _sharded(guard=GuardConfig(mode="deferred"))
    tr.step(x, y)
    tr.run_steps(x, y, num_steps=2)         # compile + warm both programs
    xb = [tr._shard_batch_arg(b) for b in (x, y)]
    with jax.transfer_guard_device_to_host("disallow"):
        tr.step(*xb)
        tr.run_steps(*xb, num_steps=2)
    snap = observability.snapshot()
    assert snap["trace"]["recorded"] == 0 and trace.get_tracer().spans() == []
    phases = snap["metrics"][instrument.PHASE_METRIC]["values"]
    for phase in ("data_wait", "host_args", "compiled_step", "guard_fetch"):
        assert phases[f"trainer=sharded_trainer,phase={phase}"]["count"] == 4


def test_ring_holds_the_host_args_span_between_its_neighbours(ring):
    tr, x, y = _sharded()
    tr.step(x, y)
    names = [s["name"] for s in sorted(ring.spans(), key=lambda s: s["span_id"])
             if s["name"].startswith("sharded_trainer.")]
    assert names == ["sharded_trainer.step", "sharded_trainer.data_wait",
                     "sharded_trainer.host_args",
                     "sharded_trainer.compiled_step",
                     "sharded_trainer.guard_fetch"]


def test_fixed_seed_losses_are_those_of_the_parent_commit():
    """``host_args`` draws the key at the point of the RNG stream where
    the call of the compiled program drew it: three ``step()`` losses and
    one ``run_steps(4)`` loss of a net with dropout, bit for bit as
    commit 7f8459b gave them on the CPU."""
    tr, x, y = _dropout_sharded(1234)
    got = [float(tr.step(x, y).asscalar()).hex() for _ in range(3)]
    got.append(float(tr.run_steps(x, y, num_steps=4).asscalar()).hex())
    assert got == ["0x1.619db00000000p+0", "0x1.61e60c0000000p+0",
                   "0x1.600cc00000000p+0", "0x1.56dac80000000p+0"]
    tr2, _, _ = _dropout_sharded(4321)      # the key does reach the loss
    assert float(tr2.step(x, y).asscalar()).hex() != got[0]


# -- the key is split inside the program; the scalars are host values ---------

def _trainer_call(tr, call, x, y):
    return tr.step(x, y) if call == "step" else tr.run_steps(x, y,
                                                             num_steps=2)


@pytest.mark.parametrize("call", ["step", "run_steps"])
def test_program_split_leaves_the_eager_stream_where_next_key_would(call):
    """On a mesh of 8 devices the program returns the new root replicated
    and committed; ``_rng`` keeps a one-device uncommitted view of it, so
    the state is the parent's after the same number of splits and eager
    samplers still run, beside arrays on any device."""
    import jax
    from mxnet_tpu import _rng, autograd
    tr, x, y = _dropout_sharded(99)
    assert tr.mesh.devices.size == 8
    tr.prepare(x)
    for _ in range(2):                      # the compiling call and a warm one
        data, impl = _rng.get_state()
        root, _ = jax.random.split(jax.random.wrap_key_data(data, impl=impl))
        _trainer_call(tr, call, x, y)
        got, got_impl = _rng.get_state()
        assert got_impl == impl
        np.testing.assert_array_equal(got, jax.random.key_data(root))
    key = _rng._ensure_key_locked()
    assert len(key.devices()) == 1 and not key.committed
    root, sub = jax.random.split(root)      # the draw an eager sampler makes
    np.testing.assert_array_equal(
        mx.nd.random.uniform(shape=(2,)).asnumpy(),
        jax.random.uniform(sub, (2,)))
    for ctx in (mx.cpu(0), mx.cpu(3)):
        ones = mx.nd.ones((64,), ctx=ctx)
        with autograd.train_mode():
            dropped = mx.nd.Dropout(ones, p=0.5).asnumpy()
        assert set(np.unique(dropped)) == {0.0, 2.0}
        root, _ = jax.random.split(root)
    np.testing.assert_array_equal(_rng.get_state()[0],
                                  jax.random.key_data(root))


@pytest.mark.parametrize("call", ["step", "run_steps"])
def test_checkpoint_resumes_the_dropout_stream_bit_for_bit(tmp_path, call):
    tr, x, y = _dropout_sharded(5)
    _trainer_call(tr, call, x, y)
    tr.save_checkpoint(str(tmp_path / "ck"))

    def three():
        return [float(_trainer_call(tr, call, x, y).asscalar()).hex()
                for _ in range(3)]

    first = three()
    mx.random.seed(77)                      # not the ambient seed's doing
    tr.load_checkpoint(str(tmp_path / "ck"))
    assert three() == first
    assert len(set(first)) == 3             # a new mask each call


def _sgd_weights(tr):
    return [p._data[0].asnumpy() for p in tr._trainable]


@pytest.mark.parametrize("what", ["learning_rate", "scheduler", "loss_scale"])
def test_new_scalar_values_reach_the_update_and_compile_nothing(what):
    """lr, the scheduler's value and the fp16 loss scale are traced float32
    inputs built on the host: a new value is a new argument, never a new
    program, for ``step()`` and ``run_steps()`` alike."""
    net = _mlp()
    lr_of = {"lr": 0.1}
    tr = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        optimizer_params=dict(
            {"learning_rate": 0.1},
            **({"lr_scheduler": lambda t: lr_of["lr"]}
               if what == "scheduler" else {})),
        mesh=parallel.make_mesh({"data": -1}),
        compute_dtype="float16" if what == "loss_scale" else None)
    rng = np.random.RandomState(0)
    x, y = rng.randn(16, 8).astype(np.float32), rng.randint(0, 4, (16,))
    tr.step(x, y)
    tr.run_steps(x, y, num_steps=2)         # both programs compiled
    programs = (tr._step_fn, tr._multi_fns["multi2"])
    assert [f._cache_size() for f in programs] == [1, 1]
    before = _sgd_weights(tr)

    def set_lr(value):
        if what == "scheduler":
            lr_of["lr"] = value
        else:
            tr.set_learning_rate(value)

    if what == "loss_scale":                # fp16 gradients overflow: skipped
        tr._scaler.loss_scale = 2.0 ** 40
    else:                                   # an update of lr 0 moves nothing
        set_lr(0.0)
    tr.step(x, y)
    tr.run_steps(x, y, num_steps=2)
    for a, b in zip(before, _sgd_weights(tr)):
        np.testing.assert_array_equal(a, b)
    if what == "loss_scale":
        assert tr.skipped_steps == 3 and tr._scaler.loss_scale < 2.0 ** 40
    else:                                   # and back: the update moves again
        set_lr(0.1)
        tr.step(x, y)
        assert any((a != b).any() for a, b in zip(before, _sgd_weights(tr)))
    assert [f._cache_size() for f in programs] == [1, 1]


# -- reports + doctor surfaces ------------------------------------------------

def test_trace_report_summarizes_journal(tmp_path, jfile):
    trace.configure(mode="journal")
    with trace.span("stepish"):
        with trace.span("phase"):
            pass
    rep = trace_report(jfile)
    assert rep["ok"] and rep["spans"] == 2 and rep["traces"] == 1
    assert set(rep["by_name"]) == {"stepish", "phase"}
    assert rep["slowest"][0]["name"] in ("stepish", "phase")
    bad = trace_report(str(tmp_path / "missing.jsonl"))
    assert bad["ok"] is False
    empty = trace_report(__file__)
    assert empty["ok"] is False and "no span records" in empty["error"]


def test_metrics_report_reads_bench_artifact(tmp_path):
    tr, x, y = _sharded()
    tr.step(x, y)
    artifact = {"metric": "whatever", "value": 1,
                "observability": observability.snapshot()}
    p = str(tmp_path / "BENCH_x.json")
    with open(p, "w", encoding="utf-8") as f:
        json.dump(artifact, f)
    rep = metrics_report(p)
    assert rep["ok"]
    assert rep["compiles_total"] == 1
    assert any("sharded_trainer" in k for k in rep["step_phase_ms"])
    bad = metrics_report(str(tmp_path / "missing.json"))
    assert bad["ok"] is False


def test_doctor_dispatch_table_covers_all_reporters():
    """The doctor cleanup satellite: one table row per report surface,
    and the new --trace/--metrics surfaces are rows in it."""
    from mxnet_tpu.diagnostics import __main__ as dmain
    keys = [row[0] for row in dmain._REPORT_TABLE]
    assert keys == ["checkpoint", "serving", "guardrails", "trace",
                    "metrics", "timeline", "aot", "lint", "tuned",
                    "chaos"]
    for _key, flag, _env, _mv, _help, load, summ in dmain._REPORT_TABLE:
        assert flag.startswith("--") and callable(load) and callable(summ)


@pytest.mark.slow
def test_observability_cli_dump_and_report(tmp_path):
    import subprocess
    import sys
    jf = str(tmp_path / "j.jsonl")
    out = str(tmp_path / "trace.json")
    code = ("from mxnet_tpu.observability import trace\n"
            "with trace.span('cli_root'):\n"
            "    with trace.span('cli_child'):\n"
            "        pass\n")
    env = dict(__import__('os').environ, JAX_PLATFORMS="cpu",
               MXNET_TPU_JOURNAL=jf, MXNET_TPU_TRACE="journal")
    r = subprocess.run([sys.executable, "-c", code], env=env, timeout=240)
    assert r.returncode == 0
    # the tools read files: run with tracing on they would journal their
    # own import beside the run's spans
    env = dict(__import__('os').environ, JAX_PLATFORMS="cpu")
    env.pop("MXNET_TPU_TRACE", None)
    r = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.observability", "dump",
         "--journal", jf, "--out", out],
        env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr
    with open(out, encoding="utf-8") as f:
        doc = json.load(f)
    _assert_chrome_doc(doc)
    # importing the package is a set-up stage, so a span of the journal
    assert {e["name"] for e in doc["traceEvents"]} == {
        "setup.import", "cli_root", "cli_child"}
    r = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.observability", "report",
         "--journal", jf],
        env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout.strip().splitlines()[-1])
    assert rep["ok"] and rep["spans"] == 3
    r = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.observability", "setup",
         "--journal", jf],
        env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr
    table = r.stdout.splitlines()
    assert table[0].split()[:2] == ["stage", "n"]
    assert table[1].split()[:2] == ["import", "1"]


# -- set-up stages (observability/stages.py) ----------------------------------

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def fresh_stages():
    stages.reset()
    yield
    stages.reset()


def test_setup_stages_nest_and_self_time_excludes_the_children(fresh_stages):
    import time
    with instrument.setup_stage("place"):
        time.sleep(0.02)
        with instrument.setup_stage("initialize"):
            with instrument.setup_stage("initialize"):    # re-entered: no-op
                assert stages.current() == "initialize"
                time.sleep(0.03)
        assert stages.current() == "place"
    assert stages.current() is None
    rep = observability.setup_report()["stages"]
    assert [k for k in rep if k != "import"] == ["place", "initialize"]
    place, init = rep["place"], rep["initialize"]
    assert place["count"] == init["count"] == 1
    assert init["inclusive_s"] == pytest.approx(init["self_s"])
    assert init["self_s"] >= 0.03
    assert place["inclusive_s"] >= 0.05
    assert place["self_s"] == pytest.approx(
        place["inclusive_s"] - init["inclusive_s"])
    # the registry family is fed, tracing off, and no span was made
    assert trace.mode() == "off" and trace.get_tracer().spans() == []
    values = metrics.snapshot()[stages.STAGE_METRIC]["values"]
    assert values["stage=place,time=inclusive"]["count"] == 1
    assert values["stage=initialize,time=self"]["sum"] == pytest.approx(
        init["self_s"], abs=1e-3)


def test_setup_stage_is_a_span_with_what_it_held_when_tracing(fresh_stages,
                                                             ring):
    from jax import monitoring
    with instrument.setup_stage("first_call", program="step"):
        monitoring.record_event_duration_secs(TRACE_EVENT, 0.5,
                                              fun_name="step")
    (span,) = ring.spans()
    assert span["name"] == "setup.first_call"
    assert span["attrs"]["program"] == "step"
    assert span["attrs"]["stage"] == "first_call{step}"
    assert span["attrs"]["trace_s"] == 0.5
    assert 0 <= span["attrs"]["self_s"] <= span["dur_s"] + 1e-6


def test_jax_events_book_to_the_innermost_stage_or_outside(fresh_stages):
    from jax import monitoring
    monitoring.record_event_duration_secs(COMPILE_EVENT, 0.25,
                                          fun_name="jit(loose)")
    with instrument.setup_stage("place"):
        monitoring.record_event_duration_secs(LOWER_EVENT, 1.0,
                                              fun_name="jit(put)")
        with instrument.setup_stage("first_call", program="step"):
            # as JAX raises them: a helper traced inside the step's trace
            monitoring.record_scalar(TRACE_EVENT, 0.0, fun_name="step")
            monitoring.record_scalar(TRACE_EVENT, 0.0, fun_name="helper")
            monitoring.record_event_duration_secs(TRACE_EVENT, 1.5,
                                                  fun_name="helper")
            monitoring.record_event_duration_secs(TRACE_EVENT, 4.0,
                                                  fun_name="step")
            monitoring.record_event_duration_secs(COMPILE_EVENT, 2.0,
                                                  fun_name="jit(step)")
    rep = observability.setup_report()
    first = rep["stages"]["first_call{step}"]
    assert first["jax_s"] == {"trace": 4.0, "lower": 0.0, "compile": 2.0,
                              "cache_load": 0.0}
    assert first["programs"] == {"hit": 0, "miss": 0, "uncached": 1}
    assert rep["stages"]["place"]["jax_s"]["lower"] == 1.0
    assert rep["stages"]["place"]["programs"]["uncached"] == 0
    assert rep["outside"]["jax_s"]["compile"] == 0.25
    assert rep["outside"]["programs"]["uncached"] == 1
    rows = {r["fun_name"]: r for r in rep["programs"]}
    assert rows["step"]["trace_s"] == 2.5 and rows["helper"]["trace_s"] == 1.5
    assert rows["step"]["compile_s"] == 2.0 and rows["step"]["count"] == 1
    assert rows["step"]["stage"] == "first_call{step}"
    assert rows["loose"]["stage"] == "outside"
    assert rep["programs"][0]["fun_name"] == "step"     # longest first
    snap = metrics.snapshot()
    assert snap[stages.PROGRAM_S_METRIC]["values"][
        "stage=first_call{step},phase=trace"] == 4.0
    assert snap[stages.PROGRAMS_METRIC]["values"][
        "stage=outside,cache=uncached"] == 1.0


def test_a_real_jit_is_booked_to_the_stage_it_ran_in(fresh_stages):
    import jax
    import jax.numpy as jnp

    def fresh_function_of_this_test(x):
        return jnp.tanh(x) * 3.0 + 1.0

    with instrument.setup_stage("first_call", program="fresh"):
        jax.jit(fresh_function_of_this_test)(jnp.ones((3, 5)))
    jax.jit(lambda x: x - 2.5)(jnp.ones((3, 5)))
    rep = observability.setup_report()
    stage = rep["stages"]["first_call{fresh}"]
    assert sum(stage["programs"].values()) >= 1
    assert stage["jax_s"]["trace"] > 0 and stage["jax_s"]["lower"] > 0
    assert stage["jax_s"]["compile"] + stage["jax_s"]["cache_load"] > 0
    # the phases of a stage are parts of its wall time
    assert sum(stage["jax_s"].values()) <= stage["inclusive_s"] + 1e-3
    rows = {r["fun_name"]: r for r in observability.setup_report(
        top=stages.MAX_NAMES)["programs"]}
    row = rows["fresh_function_of_this_test"]
    assert row["stage"] == "first_call{fresh}" and row["count"] == 1
    assert rows["<lambda>"]["stage"] == "outside"
    assert sum(rep["outside"]["programs"].values()) >= 1


def test_cache_hit_miss_and_uncached_are_told_apart(fresh_stages, tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}

    def fresh():
        # a new function each time, so that JAX's in-memory caches miss
        # and the persistent cache is asked: same name, same program
        def cached_function_of_this_test(x):
            return jnp.sin(x) @ jnp.cos(x).T + 7.0
        return jax.jit(cached_function_of_this_test)

    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        x = jnp.ones((4, 6))
        # too quick to be written: built, and not a miss
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e6)
        with instrument.setup_stage("first_call", program="quick"):
            fresh()(x)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        with instrument.setup_stage("first_call", program="cold"):
            fresh()(x)
        with instrument.setup_stage("first_call", program="warm"):
            fresh()(x)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
    rep = observability.setup_report()["stages"]
    assert rep["first_call{quick}"]["programs"] == {
        "hit": 0, "miss": 0, "uncached": 1}
    assert rep["first_call{cold}"]["programs"] == {
        "hit": 0, "miss": 1, "uncached": 0}
    warm = rep["first_call{warm}"]
    assert warm["programs"] == {"hit": 1, "miss": 0, "uncached": 0}
    assert warm["jax_s"]["cache_load"] > 0 and warm["jax_s"]["compile"] == 0
    assert rep["first_call{cold}"]["jax_s"]["cache_load"] == 0
    (row,) = [r for r in observability.setup_report()["programs"]
              if r["fun_name"] == "cached_function_of_this_test"]
    assert (row["count"], row["hits"], row["misses"]) == (3, 1, 1)
    assert row["stage"] == "first_call{quick}"


def test_the_table_by_fun_name_is_bounded(fresh_stages):
    from jax import monitoring
    for i in range(stages.MAX_NAMES + 40):
        monitoring.record_event_duration_secs(COMPILE_EVENT, 0.001,
                                              fun_name=f"jit(f{i})")
    rep = observability.setup_report(top=10 * stages.MAX_NAMES)
    assert rep["names"] == stages.MAX_NAMES + 1
    rows = {r["fun_name"]: r for r in rep["programs"]}
    assert rows[stages.OTHER]["count"] == 40
    assert rep["outside"]["programs"]["uncached"] == stages.MAX_NAMES + 40
    assert len(observability.setup_report(top=5)["programs"]) == 5


def test_stages_of_many_threads_lose_no_update(fresh_stages):
    """More threads than cores open stages and raise JAX's events at once:
    each thread's events go to its own innermost stage, and no count or
    second is lost."""
    import sys
    from jax import monitoring
    threads, turns = 24, 150
    failed = []

    def work(i):
        mine = f"first_call{{t{i}}}"
        try:
            for _ in range(turns):
                with instrument.setup_stage("place"):
                    with instrument.setup_stage("first_call",
                                                program=f"t{i}"):
                        assert stages.current() == mine
                        monitoring.record_event_duration_secs(
                            COMPILE_EVENT, 0.5, fun_name="jit(shared)")
                    monitoring.record_event_duration_secs(
                        LOWER_EVENT, 0.25, fun_name="jit(shared)")
        except Exception as e:      # reported by the main thread
            failed.append(repr(e))

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=work, args=(i,))
                for i in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(before)
    assert not failed and not any(t.is_alive() for t in pool)
    rep = observability.setup_report(top=5)
    place = rep["stages"]["place"]
    assert place["count"] == threads * turns
    assert place["jax_s"]["lower"] == pytest.approx(0.25 * threads * turns)
    assert place["programs"]["uncached"] == 0
    for i in range(threads):
        own = rep["stages"][f"first_call{{t{i}}}"]
        assert own["count"] == turns
        assert own["programs"]["uncached"] == turns
        assert own["jax_s"]["compile"] == pytest.approx(0.5 * turns)
    (row,) = [r for r in rep["programs"] if r["fun_name"] == "shared"]
    assert row["count"] == threads * turns
    assert sum(rep["outside"]["programs"].values()) == 0


def test_sharded_trainer_leaves_its_stages_in_order(fresh_stages):
    """import, initialize, deferred_shapes, place, build_step and the first
    call, in the order they opened; made under the zero-device-read
    contract; ``program_texts()`` is booked to ``inspect``."""
    import jax
    assert trace.mode() == "off"
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu"))     # shape deferred
        net.add(gluon.nn.Dense(4))
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    y = rng.randint(0, 4, (16,))
    with jax.transfer_guard_device_to_host("disallow"):
        net.initialize()
        tr = parallel.ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            optimizer_params={"learning_rate": 0.1},
            mesh=parallel.make_mesh({"data": -1}))
        tr.step(x, y)
        tr.step(x, y)
        tr.run_steps(x, y, num_steps=2)
    rep = observability.setup_report()
    named = ["import", "initialize", "deferred_shapes", "place",
             "build_step", "first_call{step}", "first_call{run_steps(2)}"]
    assert [k for k in rep["stages"] if k in named] == named
    assert trace.get_tracer().spans() == []
    s = rep["stages"]
    assert s["import"]["count"] == 1 and s["import"]["inclusive_s"] > 0
    # the deferred parameters were initialized inside the shape pass
    assert s["deferred_shapes"]["count"] == 1
    assert s["deferred_shapes"]["self_s"] < s["deferred_shapes"]["inclusive_s"]
    assert s["place"]["count"] == 1 and s["build_step"]["count"] == 2
    assert s["first_call{step}"]["count"] == 1      # the second step: none
    assert s["first_call{step}"]["programs"]["uncached"] >= 1
    assert s["first_call{step}"]["jax_s"]["trace"] > 0
    assert "inspect" not in s
    before = sum(s["first_call{step}"]["jax_s"].values())
    assert set(tr.program_texts()) == {"step", "run_steps(2)"}
    after = observability.setup_report()["stages"]
    assert after["inspect"]["count"] == 2
    assert sum(after["first_call{step}"]["jax_s"].values()) == before
    assert observability.snapshot()["setup"]["stages"].keys() == after.keys()


def test_an_eager_call_that_resolves_shapes_is_the_same_stage(fresh_stages):
    from mxnet_tpu.gluon import parameter
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(6), gluon.nn.Dense(2, in_units=6))
    net.initialize()
    assert len(parameter.DEFERRED) >= 1
    net(mx.nd.ones((3, 5)))                 # resolves the first layer's
    rep = observability.setup_report()["stages"]
    assert rep["deferred_shapes"]["count"] == 1
    assert not [p for p in net.collect_params().values()
                if p in parameter.DEFERRED]
    net(mx.nd.ones((3, 5)))                 # nothing left to resolve
    assert observability.setup_report()["stages"][
        "deferred_shapes"]["count"] == 1


def test_setup_table_and_the_journal_say_the_same(fresh_stages, jfile):
    from mxnet_tpu.observability.report import (setup_from_journal,
                                                setup_table)
    trace.configure(mode="journal")
    tr, x, y = _sharded()
    tr.step(x, y)
    live = observability.setup_report()
    told = setup_from_journal(jfile)["stages"]
    mine = {k: v for k, v in live["stages"].items() if k != "import"}
    assert list(told) == list(mine)
    for key, stage in mine.items():
        assert told[key]["count"] == stage["count"]
        assert told[key]["self_s"] == pytest.approx(stage["self_s"],
                                                    abs=1e-4)
        assert told[key]["programs"] == stage["programs"]
    table = setup_table(live).splitlines()
    assert table[0].split()[0] == "stage"
    assert [line.split()[0] for line in table[1:1 + len(live["stages"])]] \
        == list(live["stages"])
    assert any(line.startswith("step ") for line in table)


def test_observability_imports_without_jax():
    """No module of the package imports JAX when it is loaded: the
    exporters must work while everything else is wedged."""
    import ast
    import os
    here = os.path.dirname(observability.__file__)
    for name in sorted(os.listdir(here)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(here, name), encoding="utf-8") as f:
            tree = ast.parse(f.read())
        for node in tree.body:          # module level only
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module or ""]
            assert not [n for n in names if n.split(".")[0] == "jax"], name
