"""Trial runner: evaluate one knob configuration against the REAL
objective, in a deadlined subprocess (docs/autotune.md).

The fitness a trial reports is the number the runtime actually cares
about, measured by the harnesses the repo already trusts:

- **kernel trials** drive the Pallas parity harness (``python -m
  mxnet_tpu.autotune _trial``): the candidate block shape runs the
  registered kernel (interpret mode on CPU — the same path as the CI
  parity gate) against its XLA reference; the parity gate is ENFORCED
  (max abs error within the registered tolerance, else the trial is
  gated out) and fitness is element throughput;
- **serving trials** drive ``python -m mxnet_tpu.serving bench`` — the
  existing closed-loop generator (optionally replaying a recorded
  ``--arrival`` trace) — under the candidate ``window_ms``/queue/hedge
  knobs; fitness is −p99 under a shed-rate ceiling (a config that
  sheds its way to a good tail is gated out, not rewarded).

Every trial is a child process under a hard deadline (the bench.py
wedge-proof contract, graftlint G5): the parent parses exactly ONE
JSON metric line from stdout, a wedged/dead child becomes a gated
trial with a structured reason, never a hang.  Trials share one AOT
cache dir (the PR-13 store) so revisited serving configurations
re-evaluate warm, and every trial journals an ``autotune_trial``
record inside a trace span — the provenance the committed table
references.
"""
from __future__ import annotations

import itertools
import json
import os
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field

from ..diagnostics.journal import get_journal
from ..observability import trace as _trace

__all__ = ["TrialResult", "TrialRunner", "KernelObjective",
           "ServingObjective"]

_trial_seq = itertools.count()

# children run ``python -m mxnet_tpu...``: make the import root explicit
# so trials work from any parent cwd (the tree is not pip-installed)
_IMPORT_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _child_env() -> dict:
    env = dict(os.environ)
    pp = env.get("PYTHONPATH")
    env["PYTHONPATH"] = (_IMPORT_ROOT if not pp
                         else _IMPORT_ROOT + os.pathsep + pp)
    return env


@dataclass
class TrialResult:
    """One evaluated configuration.  ``fitness`` is None when the trial
    failed its gate (parity, shed ceiling, deadline, crash) — a gated
    config never competes, whatever its raw numbers said."""

    trial_id: int
    objective: str
    config: dict
    fitness: float | None
    ok: bool
    gate: str | None            # failure reason when not ok
    metrics: dict = field(default_factory=dict)
    cached: bool = False
    resource: float = 1.0
    duration_s: float = 0.0


def _last_json_line(text: str):
    """The artifact contract: children print exactly one JSON object
    line on stdout; scan from the end so stray prints can't break it."""
    for line in reversed((text or "").strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            return json.loads(line)
        except ValueError:
            continue
    return None


class _Objective:
    """Shared child-process machinery for concrete objectives."""

    name = "objective"
    # the objective's gate knobs live on the instance; subclasses
    # implement argv()/score()

    def __init__(self, deadline_s: float = 120.0):
        self.deadline_s = float(deadline_s)

    def argv(self, config: dict, resource: float, workdir: str) -> list:
        raise NotImplementedError

    def env(self, config: dict, workdir: str) -> dict:
        return _child_env()

    def score(self, doc: dict, config: dict, workdir: str):
        """(fitness, gate_reason, metrics) from the child's JSON line."""
        raise NotImplementedError

    def run(self, config: dict, resource: float, workdir: str):
        argv = self.argv(config, resource, workdir)
        try:
            out = subprocess.run(          # hard deadline: G5 — a wedged
                argv, capture_output=True, text=True,   # child is killed,
                timeout=self.deadline_s,                # never waited on
                env=self.env(config, workdir))
        except subprocess.TimeoutExpired:
            return None, f"deadline:{self.deadline_s:g}s", {}
        doc = _last_json_line(out.stdout)
        if doc is None:
            tail = (out.stderr or "").strip()[-300:]
            return None, f"no_metric_line:rc={out.returncode}", \
                {"stderr_tail": tail}
        if doc.get("error"):
            return None, f"child:{doc['error']}", doc
        return self.score(doc, config, workdir)


class KernelObjective(_Objective):
    """Throughput of one registered Pallas kernel at one shape class
    under a candidate block, parity-gated against the XLA reference."""

    name = "kernel"

    def __init__(self, kernel: str = "matmul_epilogue", r: int = 256,
                 c: int = 128, iters: int = 30, deadline_s: float = 120.0,
                 interpret: bool = True):
        super().__init__(deadline_s)
        self.kernel = kernel
        self.r, self.c = int(r), int(c)
        self.iters = int(iters)
        self.interpret = bool(interpret)

    def argv(self, config, resource, workdir):
        iters = max(3, int(round(self.iters * float(resource))))
        argv = [sys.executable, "-m", "mxnet_tpu.autotune", "_trial",
                "--kernel", self.kernel,
                "--shape", f"{self.r}x{self.c}",
                "--iters", str(iters)]
        if config.get("block_r") and config.get("block_c"):
            argv += ["--block",
                     f"{int(config['block_r'])}x{int(config['block_c'])}"]
        if self.interpret:
            argv.append("--interpret")
        return argv

    def env(self, config, workdir):
        env = _child_env()
        env.setdefault("JAX_PLATFORMS", "cpu")
        # the trial must measure the candidate, not an ambient table
        env.pop("MXNET_TPU_TUNED_TABLE", None)
        return env

    def score(self, doc, config, workdir):
        metrics = {k: doc.get(k) for k in
                   ("value", "unit", "max_err", "tolerance", "iters",
                    "compiles")}
        if not doc.get("parity_ok", False):
            return None, f"parity:max_err={doc.get('max_err')}", metrics
        value = doc.get("value")
        if not isinstance(value, (int, float)):
            return None, "no_value", metrics
        return float(value), None, metrics


class ServingObjective(_Objective):
    """p99 (lower is better → fitness is −p99) of the closed-loop
    serving bench under a candidate config, gated on the shed rate."""

    name = "serving"

    def __init__(self, seconds: float = 2.0, clients: int = 4,
                 dim: int = 16, max_batch: int = 8,
                 shed_ceiling: float = 0.2, arrival: str | None = None,
                 deadline_s: float = 180.0, hedge: bool = False):
        super().__init__(deadline_s)
        self.seconds = float(seconds)
        self.clients = int(clients)
        self.dim = int(dim)
        self.max_batch = int(max_batch)
        self.shed_ceiling = float(shed_ceiling)
        self.arrival = arrival
        self.hedge = bool(hedge)

    def argv(self, config, resource, workdir):
        seconds = max(0.3, self.seconds * float(resource))
        out = os.path.join(workdir, "trial_bench.json")
        argv = [sys.executable, "-m", "mxnet_tpu.serving", "bench",
                "--seconds", f"{seconds:g}",
                "--clients", str(self.clients),
                "--dim", str(self.dim),
                "--max-batch", str(self.max_batch),
                "--out", out]
        if "window_ms" in config:
            argv += ["--window-ms", f"{float(config['window_ms']):g}"]
        if "max_queue" in config:
            argv += ["--queue", str(int(config["max_queue"]))]
        if self.hedge and "hedge_ms" in config:
            argv += ["--replicas", "2",
                     "--hedge-ms", f"{float(config['hedge_ms']):g}"]
        if self.arrival:
            argv += ["--arrival", str(self.arrival)]
        return argv

    def env(self, config, workdir):
        env = _child_env()
        env.setdefault("JAX_PLATFORMS", "cpu")
        env.pop("MXNET_TPU_TUNED_TABLE", None)
        # PR-13 store as the trial cache: every trial of this objective
        # shares one AOT dir, so a revisited bucket lattice loads its
        # executables instead of recompiling them
        env.setdefault("MXNET_TPU_AOT_CACHE_DIR",
                       os.path.join(workdir, "aot-trial-cache"))
        return env

    def score(self, doc, config, workdir):
        lat = doc.get("latency_ms") or {}
        completed = doc.get("completed") or 0
        shed = doc.get("client_shed") or 0
        denom = completed + shed
        shed_rate = (shed / denom) if denom else 1.0
        metrics = {"value": doc.get("value"), "p50": lat.get("p50"),
                   "p99": lat.get("p99"), "completed": completed,
                   "client_shed": shed,
                   "shed_rate": round(shed_rate, 4),
                   "compiles": doc.get("compiles"),
                   "compile_bound_ok": doc.get("compile_bound_ok")}
        cp = (doc.get("distributed_trace") or {}).get("critical_path")
        if cp:
            metrics["critical_path"] = cp
        if not completed:
            return None, "no_completions", metrics
        if shed_rate > self.shed_ceiling:
            return None, (f"shed_ceiling:{shed_rate:.3f}"
                          f">{self.shed_ceiling:g}"), metrics
        p99 = lat.get("p99")
        if p99 is None:
            return None, "no_p99", metrics
        return -float(p99), None, metrics


class TrialRunner:
    """Evaluates configurations for one objective: deadline, journal,
    memo.  ``evaluate(config, resource=1.0)`` is the closure handed to
    :mod:`.search`; identical (config, resource) pairs return the
    memoized result (journaled as ``cached`` — coordinate descent
    revisits incumbents freely)."""

    def __init__(self, objective: _Objective, workdir: str | None = None):
        self.objective = objective
        self.workdir = workdir or tempfile.mkdtemp(prefix="mxtpu-autotune-")
        os.makedirs(self.workdir, exist_ok=True)
        self.history: list = []
        self._memo: dict = {}

    @staticmethod
    def _memo_key(config: dict, resource: float):
        return (tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple))
                              else v) for k, v in config.items())),
                round(float(resource), 4))

    def evaluate(self, config: dict, resource: float = 1.0) -> TrialResult:
        key = self._memo_key(config, resource)
        prior = self._memo.get(key)
        tid = next(_trial_seq)
        if prior is not None:
            res = TrialResult(
                trial_id=tid, objective=self.objective.name,
                config=dict(config), fitness=prior.fitness, ok=prior.ok,
                gate=prior.gate, metrics=dict(prior.metrics), cached=True,
                resource=float(resource), duration_s=0.0)
            self._journal(res)
            self.history.append(res)
            return res
        t0 = time.monotonic()
        with _trace.span("autotune_trial", objective=self.objective.name,
                         trial=tid):
            fitness, gate, metrics = self.objective.run(
                config, float(resource), self.workdir)
        res = TrialResult(
            trial_id=tid, objective=self.objective.name,
            config=dict(config), fitness=fitness, ok=gate is None,
            gate=gate, metrics=metrics, cached=False,
            resource=float(resource),
            duration_s=round(time.monotonic() - t0, 3))
        self._memo[key] = res
        self._journal(res)
        self.history.append(res)
        return res

    def _journal(self, res: TrialResult) -> None:
        get_journal().event(
            "autotune_trial", trial=res.trial_id,
            objective=res.objective, config=res.config,
            fitness=res.fitness, ok=res.ok, gate=res.gate,
            cached=res.cached, resource=res.resource,
            duration_s=res.duration_s,
            **{k: v for k, v in res.metrics.items()
               if isinstance(v, (int, float, str, bool))})

    def best(self) -> TrialResult | None:
        scored = [r for r in self.history if r.fitness is not None]
        return max(scored, key=lambda r: r.fitness) if scored else None

    def baseline(self, default_config: dict) -> TrialResult | None:
        """The default configuration's own trial (the A/B anchor)."""
        key_cfg = self._memo_key(default_config, 0.0)[0]
        for r in self.history:
            if self._memo_key(r.config, 0.0)[0] == key_cfg:
                return r
        return None

    def summary(self) -> dict:
        gated = [r for r in self.history if not r.ok]
        return {"objective": self.objective.name,
                "trials": len(self.history),
                "cached": sum(r.cached for r in self.history),
                "gated": len(gated),
                "gate_reasons": sorted({r.gate for r in gated if r.gate}),
                "trial_ids": [r.trial_id for r in self.history]}
