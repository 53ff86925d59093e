"""Closed-loop autotuner (mxnet_tpu/autotune/, docs/autotune.md).

Acceptance criteria under test: tuned tables are CRC/format/schema/
envelope-validated BEFORE any knob is believed, every failure degrades
to built-in defaults with ONE journaled ``tuned_fallback{reason}``
(never a crash); runtime consumers (pallas.dispatch, Server, Router)
demonstrably read tuned values with journaled ``tuned_load`` and
explicit env/constructor values win over the table; a concurrent
``apply`` against a reading runtime always lands intact old-or-new; a
``block=`` override through the Pallas registry is bit-identical to the
default; and the ``search`` CLI explores ≥ 2 knob families end to end
on CPU with every trial journaled and the committed winner measuring
≥ the built-in default on the same harness (the default is trial #1 by
construction).  The ``smoke`` tests run in CI tier 0.5.
"""
import json
import os
import random
import subprocess
import sys
import threading

import numpy as np
import pytest

from mxnet_tpu.autotune import runner as atrunner
from mxnet_tpu.autotune import search as atsearch
from mxnet_tpu.autotune import space as atspace
from mxnet_tpu.autotune import table as attable
from mxnet_tpu.diagnostics.journal import reset_journal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def journal_file(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    reset_journal(path)
    try:
        yield path
    finally:
        reset_journal("stderr")


@pytest.fixture
def tuned_env(tmp_path):
    """Point MXNET_TPU_TUNED_TABLE at a scratch path and reset every
    tuned cache; restore on exit."""
    from mxnet_tpu.pallas import registry
    path = str(tmp_path / "tuned_table.json")
    old = os.environ.get(attable.ENV_TABLE)
    old_mode = os.environ.pop("MXNET_TPU_PALLAS", None)  # order-proof
    os.environ[attable.ENV_TABLE] = path
    attable.reset_cache()
    registry.reset_provenance()
    try:
        yield path
    finally:
        if old is None:
            os.environ.pop(attable.ENV_TABLE, None)
        else:
            os.environ[attable.ENV_TABLE] = old
        if old_mode is not None:
            os.environ["MXNET_TPU_PALLAS"] = old_mode
        attable.reset_cache()
        registry.reset_provenance()


def _records(path, kind=None):
    out = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if kind is None or rec.get("kind") == kind:
                out.append(rec)
    return out


def _table_doc(**knobs):
    knobs = knobs or {"serving": {"window_ms": 2.0, "max_queue": 64}}
    return attable.build_table(knobs, provenance={"trials": 1},
                               envelope=attable.current_envelope())


def _mlp(dim=8):
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4, in_units=dim))
    net.initialize()
    return net


# ---------------------------------------------------------------------------
# table: roundtrip + audit
# ---------------------------------------------------------------------------
class TestTableRoundtrip:
    def test_build_commit_read_smoke(self, tmp_path, journal_file):
        doc = _table_doc(pallas={"matmul_epilogue":
                                 {"64x32": {"block": [16, 16]}}},
                         serving={"window_ms": 2.0})
        path = str(tmp_path / "t.json")
        attable.commit_table(doc, path)
        got, reason = attable.read_table(
            path, envelope=attable.current_envelope())
        assert reason is None
        assert got == doc
        assert attable.pallas_entry(got, "matmul_epilogue",
                                    "64x32")["block"] == [16, 16]
        assert attable.knob(got, "serving", "window_ms") == 2.0
        kinds = [r["kind"] for r in _records(journal_file)]
        assert "tuned_commit" in kinds

    def test_wildcard_shape_class(self):
        doc = _table_doc(pallas={"matmul_epilogue":
                                 {"*": {"block": [8, 8]}}})
        assert attable.pallas_entry(doc, "matmul_epilogue",
                                    "999x999")["block"] == [8, 8]
        assert attable.pallas_entry(doc, "other_kernel", "8x8") is None

    def test_builder_rejects_malformed(self):
        with pytest.raises(ValueError):
            attable.build_table({"serving": {"window_ms": "fast"}},
                                envelope={"platform": "cpu",
                                          "device_kind": "cpu",
                                          "jax": "x"})
        with pytest.raises(ValueError):
            attable.build_table({"nonsense_family": {"x": 1}},
                                envelope={"platform": "cpu",
                                          "device_kind": "cpu",
                                          "jax": "x"})

    def test_commit_refuses_stale_crc(self, tmp_path):
        doc = _table_doc()
        doc["knobs"]["serving"]["window_ms"] = 9.0   # crc now stale
        with pytest.raises(ValueError):
            attable.commit_table(doc, str(tmp_path / "t.json"))

    def test_audit_is_stdlib_and_reports_knobs(self, tmp_path):
        doc = _table_doc(serving={"window_ms": 3.0},
                         router={"hedge_ms": 5.0})
        path = str(tmp_path / "t.json")
        attable.commit_table(doc, path)
        rep = attable.audit_table(path)
        assert rep["ok"] and rep["envelope_checked"] is False
        assert rep["knobs"]["serving.window_ms"] == 3.0
        assert rep["knobs"]["router.hedge_ms"] == 5.0
        bad = attable.audit_table(str(tmp_path / "nope.json"))
        assert bad == {"ok": False, "path": str(tmp_path / "nope.json"),
                       "error": "missing"}


# ---------------------------------------------------------------------------
# corruption / truncation / envelope fuzz matrix (satellite 3)
# ---------------------------------------------------------------------------
def _mutations():
    """(name, mutate(path), expected_reason) matrix over one committed
    table file."""
    def truncate(path):
        raw = open(path, "rb").read()
        open(path, "wb").write(raw[:len(raw) // 2])

    def bitflip(path):
        raw = bytearray(open(path, "rb").read())
        # flip inside a knob value, far from the braces, keeping JSON
        # parseable most of the time — the CRC must catch it either way
        idx = raw.rindex(b"window_ms") + len(b"window_ms") + 3
        raw[idx] ^= 0x01
        open(path, "wb").write(bytes(raw))

    def garbage(path):
        open(path, "wb").write(b"\x00\xffnot json at all")

    def wrong_format(path):
        doc = json.load(open(path))
        doc["format"] = "mxtpu-tuned-v999"
        json.dump(doc, open(path, "w"))

    def bad_schema(path):
        doc = json.load(open(path))
        doc["knobs"]["serving"]["window_ms"] = "fast"
        doc["crc32"] = attable.table_crc(doc)   # valid CRC, bad schema
        json.dump(doc, open(path, "w"))

    def oversize(path):
        with open(path, "ab") as f:
            f.write(b" " * (attable.MAX_TABLE_BYTES + 1))

    def delete(path):
        os.remove(path)

    return [
        ("truncated", truncate, ("json", "crc")),
        ("bitflip", bitflip, ("crc", "json")),
        ("garbage", garbage, ("json",)),
        ("wrong_format", wrong_format, ("format",)),
        ("bad_schema", bad_schema, ("schema:serving.window_ms",)),
        ("oversize", oversize, ("too_large",)),
        ("deleted", delete, ("missing",)),
    ]


class TestCorruptionMatrix:
    @pytest.mark.parametrize(
        "name,mutate,expected",
        _mutations(), ids=[m[0] for m in _mutations()])
    def test_fuzz_degrades_with_exact_reason_smoke(
            self, name, mutate, expected, tuned_env, journal_file):
        attable.commit_table(_table_doc(), tuned_env)
        mutate(tuned_env)
        attable.reset_cache()
        doc = attable.tuned_for("test")       # must not raise
        assert doc is None
        falls = _records(journal_file, "tuned_fallback")
        assert len(falls) == 1, falls
        assert falls[0]["reason"] in expected
        assert falls[0]["fallback"] == "builtin_defaults"
        assert falls[0]["site"] == "test"
        # deduped: consulting again journals nothing new
        attable.tuned_for("test")
        assert len(_records(journal_file, "tuned_fallback")) == 1

    def test_envelope_mismatch_and_stale(self, tuned_env, journal_file):
        env = dict(attable.current_envelope())
        for mutated, expected in (
                (dict(env, platform="tpu"), "envelope"),
                (dict(env, device_kind="TPU v4"), "envelope"),
                (dict(env, jax=env["jax"] + ".post1"), "stale")):
            attable.commit_table(
                attable.build_table(
                    {"serving": {"window_ms": 2.0}}, envelope=mutated),
                tuned_env)
            attable.reset_cache()
            with open(journal_file, "w"):
                pass                          # truncate between cases
            assert attable.tuned_for("test") is None
            falls = _records(journal_file, "tuned_fallback")
            assert [f["reason"] for f in falls] == [expected]

    def test_loader_picks_up_recommit(self, tuned_env):
        attable.commit_table(_table_doc(serving={"window_ms": 2.0}),
                             tuned_env)
        attable.reset_cache()
        assert attable.knob(attable.tuned_for("t"), "serving",
                            "window_ms") == 2.0
        attable.commit_table(_table_doc(serving={"window_ms": 9.0}),
                             tuned_env)
        attable.reset_cache()                 # bypass the 1s throttle
        assert attable.knob(attable.tuned_for("t"), "serving",
                            "window_ms") == 9.0


class TestConcurrentApply:
    def test_apply_vs_read_lands_old_or_new(self, tmp_path):
        """A writer re-committing A/B tables while readers validate:
        every successful read is exactly doc A or doc B — never torn,
        never a crash (the atomic_write + CRC contract)."""
        path = str(tmp_path / "t.json")
        doc_a = _table_doc(serving={"window_ms": 1.0})
        doc_b = _table_doc(serving={"window_ms": 20.0})
        attable.commit_table(doc_a, path)
        stop = threading.Event()
        bad = []

        def writer():
            i = 0
            while not stop.is_set():
                attable.commit_table(doc_b if i % 2 else doc_a, path)
                i += 1

        def reader():
            while not stop.is_set():
                doc, reason = attable.read_table(path)
                if reason is not None:
                    bad.append(("reason", reason))
                elif doc not in (doc_a, doc_b):
                    bad.append(("torn", doc))

        threads = [threading.Thread(target=writer)] + \
            [threading.Thread(target=reader) for _ in range(3)]
        for t in threads:
            t.start()
        import time
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not bad, bad[:3]


# ---------------------------------------------------------------------------
# spaces + search (stdlib)
# ---------------------------------------------------------------------------
class TestSpacesAndSearch:
    def test_pallas_space_only_valid_tilings_smoke(self):
        # valid = what the chip's compiler takes: rows in multiples of 8
        # and lanes in multiples of 128, or the whole dim
        sp = atspace.pallas_block_space("matmul_epilogue", 48, 20)
        rng = random.Random(0)
        for _ in range(50):
            cfg = sp.sample(rng)
            assert cfg["block_r"] % 8 == 0 and cfg["block_c"] == 20
        assert sp.reason({"block_r": 7, "block_c": 4}) is not None
        assert sp.reason({"block_r": 16, "block_c": 10}) is not None
        assert sp.reason(dict(sp.default)) is None
        # a real width whose default does not divide it: BERT's vocabulary
        # projection (30522 = 119.2x256)
        big = atspace.pallas_block_space("matmul_epilogue", 16384, 30522)
        assert big.default == {"block_r": 512, "block_c": 256}
        assert big.reason(dict(big.default)) is None
        assert big.reason({"block_r": 512, "block_c": 96}) is not None

    def test_bucket_space_enforces_grid_bound(self):
        sp = atspace.bucket_space(max_batch=8, compile_cap=2)
        # the full 1..8 lattice busts a compile cap of 2
        assert sp.reason(
            {"batch_buckets": tuple(range(1, 9))}) is not None
        assert sp.reason({"batch_buckets": (8,)}) is None

    def test_random_search_includes_default_first(self):
        sp = atspace.serving_space()
        seen = []

        class R:
            def __init__(self, cfg, fitness):
                self.config, self.fitness = cfg, fitness

        def ev(cfg, resource=1.0):
            seen.append(dict(cfg))
            return R(cfg, -cfg["window_ms"])

        budget = atsearch.Budget(max_trials=5, wall_s=30.0)
        hist = atsearch.random_search(sp, ev, budget, random.Random(1))
        assert seen[0] == sp.default              # the A/B anchor
        assert len(hist) == 5
        assert len({tuple(sorted(c.items())) for c in seen}) == 5

    def test_budget_bounds_trials_and_wall(self):
        b = atsearch.Budget(max_trials=3, wall_s=0.0).start()
        assert b.exhausted() is not None          # wall already gone
        b2 = atsearch.Budget(max_trials=2, wall_s=60.0).start()
        assert b2.allow() and b2.allow() and not b2.allow()
        assert b2.exhausted().startswith("trials:")

    def test_run_search_converges_to_optimum(self):
        sp = atspace.serving_space()

        class R:
            def __init__(self, cfg, fitness):
                self.config, self.fitness = cfg, fitness

        def ev(cfg, resource=1.0):
            return R(dict(cfg), -(abs(cfg["window_ms"] - 2.0)
                                  + abs(cfg["max_queue"] - 64) / 64.0))

        budget = atsearch.Budget(max_trials=40, wall_s=60.0)
        hist = atsearch.run_search(sp, ev, budget, seed=3,
                                   descent_rounds=2)
        best = max(hist, key=lambda r: r.fitness)
        assert best.config == {"window_ms": 2.0, "max_queue": 64}

    def test_successive_halving_scales_resource(self):
        sp = atspace.serving_space()
        calls = []

        class R:
            def __init__(self, cfg, fitness):
                self.config, self.fitness = cfg, fitness

        def ev(cfg, resource=1.0):
            calls.append(resource)
            return R(dict(cfg), -cfg["window_ms"])

        budget = atsearch.Budget(max_trials=30, wall_s=60.0)
        atsearch.successive_halving(sp, ev, budget, random.Random(0),
                                    n0=6, resource0=0.25)
        assert min(calls) == 0.25 and max(calls) <= 1.0
        assert len(set(calls)) >= 2               # rungs grew


# ---------------------------------------------------------------------------
# runner (deadlined subprocess contract)
# ---------------------------------------------------------------------------
class TestRunner:
    def test_deadline_gates_a_wedged_child(self, tmp_path, journal_file):
        class Wedge(atrunner._Objective):
            name = "wedge"

            def argv(self, config, resource, workdir):
                return [sys.executable, "-c",
                        "import time; time.sleep(60)"]

            def score(self, doc, config, workdir):
                return 1.0, None, {}

        r = atrunner.TrialRunner(Wedge(deadline_s=1.0),
                                 workdir=str(tmp_path))
        res = r.evaluate({"x": 1})
        assert res.fitness is None and res.gate == "deadline:1s"
        rec = _records(journal_file, "autotune_trial")[-1]
        assert rec["gate"] == "deadline:1s" and rec["ok"] is False

    def test_garbage_child_output_is_a_gate_not_a_crash(self, tmp_path):
        class Garbage(atrunner._Objective):
            name = "garbage"

            def argv(self, config, resource, workdir):
                return [sys.executable, "-c",
                        "print('no json here'); raise SystemExit(3)"]

            def score(self, doc, config, workdir):
                return 1.0, None, {}

        res = atrunner.TrialRunner(
            Garbage(deadline_s=30.0),
            workdir=str(tmp_path)).evaluate({})
        assert res.fitness is None
        assert res.gate == "no_metric_line:rc=3"

    def test_memoized_revisit_journals_cached(self, tmp_path,
                                              journal_file):
        class Echo(atrunner._Objective):
            name = "echo"

            def argv(self, config, resource, workdir):
                return [sys.executable, "-c",
                        "print('{\"value\": 5}')"]

            def score(self, doc, config, workdir):
                return float(doc["value"]), None, {}

        r = atrunner.TrialRunner(Echo(deadline_s=30.0),
                                 workdir=str(tmp_path))
        a = r.evaluate({"k": 1})
        b = r.evaluate({"k": 1})
        assert a.fitness == b.fitness == 5.0
        assert not a.cached and b.cached
        recs = _records(journal_file, "autotune_trial")
        assert [r_["cached"] for r_ in recs] == [False, True]
        assert r.summary()["cached"] == 1

    def test_kernel_objective_parity_gate_end_to_end_smoke(
            self, tmp_path, journal_file):
        """One REAL kernel trial through the subprocess harness: the
        parity gate runs in the child and a fitness comes back."""
        obj = atrunner.KernelObjective(kernel="matmul_epilogue", r=32,
                                       c=16, iters=2, deadline_s=120.0)
        res = atrunner.TrialRunner(
            obj, workdir=str(tmp_path)).evaluate(
                {"block_r": 16, "block_c": 16})
        assert res.ok, res.gate
        assert res.fitness > 0
        assert res.metrics["max_err"] <= res.metrics["tolerance"]


# ---------------------------------------------------------------------------
# runtime consumers read tuned values (regression: tuned_load + changed
# effective knob)
# ---------------------------------------------------------------------------
class TestConsumers:
    def test_server_reads_tuned_and_env_wins_smoke(self, tuned_env,
                                                   journal_file):
        from mxnet_tpu.serving.server import Server, ServerConfig
        attable.commit_table(
            _table_doc(serving={"window_ms": 2.5, "max_queue": 64},
                       buckets={"batch": [1, 2, 8]}), tuned_env)
        attable.reset_cache()
        net = _mlp()
        s = Server(net)                       # never started
        assert s.config.window_ms == 2.5      # changed effective knob
        assert s.config.max_queue == 64
        assert s.grid.batch_buckets == (1, 2, 8)
        loads = [r for r in _records(journal_file, "tuned_load")
                 if r["site"] == "server"]
        assert loads and loads[0]["window_ms"] == 2.5
        # explicit constructor value wins over the table
        s2 = Server(net, config=ServerConfig(window_ms=1.25))
        assert s2.config.window_ms == 1.25
        # env var wins over the table
        os.environ["MXNET_TPU_SERVING_WINDOW_MS"] = "7.5"
        try:
            s3 = Server(net, config=ServerConfig())
            assert s3.config.window_ms == 7.5
        finally:
            del os.environ["MXNET_TPU_SERVING_WINDOW_MS"]

    def test_router_reads_tuned_hedge(self, tuned_env, journal_file):
        from mxnet_tpu.serving.router import (RouterConfig,
                                              _apply_tuned_router)
        attable.commit_table(_table_doc(router={"hedge_ms": 12.5}),
                             tuned_env)
        attable.reset_cache()
        cfg = RouterConfig()
        _apply_tuned_router(cfg)
        assert cfg.hedge_ms == 12.5
        loads = [r for r in _records(journal_file, "tuned_load")
                 if r["site"] == "router"]
        assert loads and loads[0]["hedge_ms"] == 12.5
        # constructor-provided hedge wins
        cfg2 = RouterConfig(hedge_ms=3.0)
        _apply_tuned_router(cfg2)
        assert cfg2.hedge_ms == 3.0

    def test_dispatch_reads_tuned_block_bit_identical_smoke(
            self, tuned_env, journal_file):
        import jax.numpy as jnp
        from mxnet_tpu.pallas import registry
        rng = np.random.RandomState(0)
        y = jnp.asarray(rng.randn(64, 32), np.float32)
        b = jnp.asarray(rng.randn(1, 32) * 0.1, np.float32)
        args = (y, b, None)
        base = registry.dispatch("matmul_epilogue", *args,
                                 act_type="relu", interpret=True)
        attable.commit_table(
            _table_doc(pallas={"matmul_epilogue":
                               {"64x32": {"block": [16, 32]}}}),
            tuned_env)
        attable.reset_cache()
        registry.reset_provenance()
        tuned = registry.dispatch("matmul_epilogue", *args,
                                  act_type="relu", interpret=True)
        assert (np.asarray(base) == np.asarray(tuned)).all()
        loads = [r for r in _records(journal_file, "tuned_load")
                 if r["site"] == "pallas"]
        assert loads and loads[0]["block"] == [16, 32]
        assert loads[0]["kernel"] == "matmul_epilogue"
        assert loads[0]["shape_class"] == "64x32"

    def test_dispatch_refuses_invalid_tuned_block(self, tuned_env,
                                                  journal_file):
        import jax.numpy as jnp
        from mxnet_tpu.pallas import registry
        rng = np.random.RandomState(1)
        y = jnp.asarray(rng.randn(64, 32), np.float32)
        b = jnp.asarray(rng.randn(1, 32) * 0.1, np.float32)
        # 16 lanes of 32 is neither a multiple of 128 nor the whole dim:
        # the table is schema-valid but the chip's compiler would refuse
        # the block — dispatch must refuse it first, journaled
        attable.commit_table(
            _table_doc(pallas={"matmul_epilogue":
                               {"64x32": {"block": [48, 16]}}}),
            tuned_env)
        attable.reset_cache()
        registry.reset_provenance()
        out = registry.dispatch("matmul_epilogue", y, b, None,
                                act_type="relu", interpret=True)
        assert out.shape == (64, 32)
        falls = [r for r in _records(journal_file, "tuned_fallback")
                 if r.get("site") == "pallas"]
        assert falls and falls[0]["reason"] == "invalid_block"
        assert not [r for r in _records(journal_file, "tuned_load")
                    if r.get("site") == "pallas"]

    def test_explicit_block_override_bit_identical_and_grad(self):
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.pallas import registry
        rng = np.random.RandomState(2)
        y = jnp.asarray(rng.randn(32, 16), np.float32)
        b = jnp.asarray(rng.randn(1, 16) * 0.1, np.float32)
        base = registry.dispatch("matmul_epilogue", y, b, None,
                                 act_type="relu", interpret=True)
        for blk in ((8, 16), (32, 16), (24, 16), (7, 3)):  # last clamps
            out = registry.dispatch("matmul_epilogue", y, b, None,
                                    act_type="relu", interpret=True,
                                    block=blk)
            assert (np.asarray(base) == np.asarray(out)).all(), blk
        g = jax.grad(lambda a: registry.dispatch(
            "matmul_epilogue", a, b, None, act_type="relu",
            interpret=True, block=(8, 8)).sum())(y)
        assert g.shape == y.shape


# ---------------------------------------------------------------------------
# CLI: search end to end (CPU, tiny budget), show/apply
# ---------------------------------------------------------------------------
def _run_cli(argv, cwd, extra_env=None):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.autotune"] + argv,
        capture_output=True, text=True, timeout=600, cwd=cwd, env=env)


@pytest.mark.slow
class TestSearchCLI:
    def test_search_two_families_commits_and_runtime_loads_smoke(
            self, tmp_path):
        """The acceptance loop: search ≥2 knob families on CPU (≤8
        trials), every trial journaled with gates enforced, table
        committed with provenance, tuned ≥ default on the same harness,
        and a fresh consumer process loads the committed table with a
        journaled ``tuned_load``."""
        jpath = str(tmp_path / "search_journal.jsonl")
        out = _run_cli(
            ["search", "--table", "tuned.json",
             "--out", "BENCH_autotune.json",
             "--trials", "6", "--budget-s", "240",
             "--kernel-shape", "64x32", "--kernel-iters", "3",
             "--bench-seconds", "0.6", "--clients", "2",
             "--descent-rounds", "1",
             "--arrival",
             os.path.join(REPO, "benchmarks", "arrival_smoke.json")],
            cwd=str(tmp_path), extra_env={"MXNET_TPU_JOURNAL": jpath})
        assert out.returncode == 0, out.stderr[-2000:]
        doc = json.loads(out.stdout.strip().splitlines()[-1])
        assert doc["metric"] == "autotune_search_trials"
        fams = doc["families"]
        assert set(fams) == {"kernel", "serving"}   # ≥ 2 knob families
        for fam in fams.values():
            assert fam["trials"] >= 2
            assert fam["baseline"] is not None      # default was trial 1
            assert fam["tuned_ge_default"]
        assert doc["value"] <= 8                    # trial budget held

        # every trial journaled with config + gate outcome
        trials = _records(jpath, "autotune_trial")
        assert len(trials) == doc["value"]
        assert all("config" in t and "ok" in t for t in trials)

        # committed table: valid, with provenance referencing the trials
        table_path = str(tmp_path / "tuned.json")
        committed, reason = attable.read_table(table_path)
        assert reason is None, reason
        prov = committed["provenance"]
        assert prov["trials"] == len(trials)
        assert prov["journal"] == jpath
        assert set(prov["trial_ids"]) == {"kernel", "serving"}
        assert os.path.exists(str(tmp_path / "BENCH_autotune.json"))

        # a FRESH process (dispatch + Server) loads the tuned values
        check = (
            "import json, numpy as np, jax.numpy as jnp\n"
            "from mxnet_tpu.pallas import registry\n"
            "from mxnet_tpu.serving.server import Server\n"
            "from mxnet_tpu.gluon import nn\n"
            "net = nn.HybridSequential()\n"
            "with net.name_scope():\n"
            "    net.add(nn.Dense(4, in_units=4))\n"
            "net.initialize()\n"
            "s = Server(net)\n"
            "rng = np.random.RandomState(0)\n"
            "y = jnp.asarray(rng.randn(64, 32), np.float32)\n"
            "b = jnp.asarray(rng.randn(1, 32) * 0.1, np.float32)\n"
            "registry.dispatch('matmul_epilogue', y, b, None,\n"
            "                  act_type='relu', interpret=True)\n"
            "print(json.dumps({'window_ms': s.config.window_ms,\n"
            "                  'max_queue': s.config.max_queue}))\n")
        cjournal = str(tmp_path / "consumer_journal.jsonl")
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "PYTHONPATH": REPO + os.pathsep
                    + env.get("PYTHONPATH", ""),
                    "MXNET_TPU_TUNED_TABLE": table_path,
                    "MXNET_TPU_JOURNAL": cjournal})
        env.pop("MXNET_TPU_SERVING_WINDOW_MS", None)
        got = subprocess.run([sys.executable, "-c", check],
                             capture_output=True, text=True,
                             timeout=300, cwd=str(tmp_path), env=env)
        assert got.returncode == 0, got.stderr[-2000:]
        eff = json.loads(got.stdout.strip().splitlines()[-1])
        tuned_serving = committed["knobs"].get("serving", {})
        if "window_ms" in tuned_serving:
            assert eff["window_ms"] == tuned_serving["window_ms"]
        loads = _records(cjournal, "tuned_load")
        sites = {r["site"] for r in loads}
        assert "pallas" in sites     # the kernel family always commits
        if tuned_serving and any(
                tuned_serving.get(k) not in (None, d) for k, d in
                (("window_ms", 5.0), ("max_queue", 128))):
            assert "server" in sites

    def test_apply_validates_then_installs(self, tmp_path):
        src = str(tmp_path / "cand.json")
        dest = str(tmp_path / "active.json")
        attable.commit_table(_table_doc(), src)
        out = _run_cli(["apply", "--src", src, "--dest", dest],
                       cwd=str(tmp_path))
        assert out.returncode == 0, out.stderr[-500:]
        assert json.loads(out.stdout.strip().splitlines()[-1])["ok"]
        assert attable.read_table(dest)[1] is None
        with open(src, "w") as f:
            f.write("{}")
        out2 = _run_cli(["apply", "--src", src, "--dest", dest],
                        cwd=str(tmp_path))
        assert out2.returncode == 1
        assert "invalid_table" in out2.stdout
        assert attable.read_table(dest)[1] is None   # dest untouched


# ---------------------------------------------------------------------------
# serving bench --arrival replay (satellite 2)
# ---------------------------------------------------------------------------
class TestArrivalReplay:
    def test_trace_file_is_valid(self):
        from mxnet_tpu.serving.__main__ import _load_arrival
        events, why = _load_arrival(
            os.path.join(REPO, "benchmarks", "arrival_smoke.json"))
        assert why is None and len(events) >= 40
        assert all(dt >= 0 for dt, _dim in events)

    def test_loader_rejects_malformed(self, tmp_path):
        from mxnet_tpu.serving.__main__ import _load_arrival
        cases = {
            "missing.json": None,
            "garbage.json": "not json",
            "noformat.json": json.dumps({"events": [{"dt_ms": 1}]}),
            "noevents.json": json.dumps(
                {"format": "mxtpu-arrival-v1", "events": []}),
            "baddt.json": json.dumps(
                {"format": "mxtpu-arrival-v1",
                 "events": [{"dt_ms": -4}]}),
        }
        for name, content in cases.items():
            p = str(tmp_path / name)
            if content is not None:
                with open(p, "w") as f:
                    f.write(content)
            events, why = _load_arrival(p)
            assert events is None and why, name

    @pytest.mark.slow
    def test_bench_replay_smoke(self, tmp_path):
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-m", "mxnet_tpu.serving", "bench",
             "--seconds", "1.0", "--clients", "2", "--dim", "8",
             "--arrival",
             os.path.join(REPO, "benchmarks", "arrival_smoke.json"),
             "--out", str(tmp_path / "b.json")],
            capture_output=True, text=True, timeout=300,
            cwd=str(tmp_path), env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        doc = json.loads(out.stdout.strip().splitlines()[-1])
        assert doc["arrival"]["mode"] == "replay"
        assert doc["arrival"]["events"] == 54
        assert doc["completed"] > 0
