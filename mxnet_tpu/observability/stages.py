"""Set-up by stage: what a process did between its first import and its
first steady step, and which stage each program JAX built belongs to.

:func:`..instrument.setup_stage` opens a stage (``import``,
``initialize``, ``deferred_shapes``, ``place``, ``build_step``,
``first_call{<program>}``, ``inspect``, ``backend_start``); this module
keeps the books. A stage's *inclusive* seconds are its wall time, its
*self* seconds that less the stages opened inside it on the same thread.
Opening the stage that is already innermost is a no-op, so a site may
open it at every level it is reached through
(``ParameterDict.initialize`` → ``Parameter._finish_init``).

One ``jax.monitoring`` listener (installed when a stage opens or closes
with JAX imported; this package still loads without it) books JAX's own
events to the innermost stage open on the thread that raised them, or to
``outside``:

- ``/jax/core/compile/jaxpr_trace_duration`` → phase ``trace``,
  ``.../jaxpr_to_mlir_module_duration`` → ``lower``,
  ``.../backend_compile_duration`` → ``compile``, or ``cache_load`` where
  the persistent cache answered. JAX announces the start of each as a
  scalar event, so an event's seconds are its own less those of the events
  nested in it (a jitted helper traced inside the step's trace, an eager
  constant built while tracing): the phases of a stage add up to wall time.
- ``/jax/compilation_cache/cache_hits`` / ``cache_misses`` (a miss is a
  program that was written to the cache: JAX writes only what took long
  enough to compile) tell the programs apart as ``hit``, ``miss`` or
  ``uncached``; ``compile_time_saved_sec`` is kept as ``saved_s``: what the
  hits would have cost cold. ``cache_retrieval_time_sec`` lies inside the
  ``backend_compile_duration`` of a hit, which is what ``cache_load`` books.

Everything is mirrored into the default registry
(``mxnet_tpu_setup_stage_s{stage,time}``,
``mxnet_tpu_jax_program_s{stage,phase}``,
``mxnet_tpu_jax_programs_total{stage,cache}``); the order of the stages and
the table by ``fun_name`` (at most :data:`MAX_NAMES` rows, the rest under
``other``) live here, and :func:`setup_report` hands out all of it.

Host arithmetic only: no device value is read. Stdlib-only at import.
"""
from __future__ import annotations

import sys
import threading
import time

from .metrics import default_registry

__all__ = ["Books", "CACHE", "MAX_NAMES", "OTHER", "OUTSIDE", "PHASES",
           "PROGRAMS_METRIC", "PROGRAM_S_METRIC", "STAGE_METRIC", "current",
           "reset", "setup_report"]

STAGE_METRIC = "mxnet_tpu_setup_stage_s"
PROGRAM_S_METRIC = "mxnet_tpu_jax_program_s"
PROGRAMS_METRIC = "mxnet_tpu_jax_programs_total"
OUTSIDE = "outside"
OTHER = "other"
MAX_NAMES = 256
PHASES = ("trace", "lower", "compile", "cache_load")
CACHE = ("hit", "miss", "uncached")     # what the persistent cache did

_DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"

_lock = threading.Lock()
_stages: dict = {}      # key -> Books, in the order the stages first opened
_names: dict = {}       # fun_name -> row of the table
_local = threading.local()
_listening = False


class Books:
    """What one stage (or ``outside``) has been charged."""

    __slots__ = ("count", "inclusive_s", "self_s", "jax_s", "programs",
                 "saved_s")

    def __init__(self):
        self.count, self.inclusive_s, self.self_s = 0, 0.0, 0.0
        self.jax_s = dict.fromkeys(PHASES, 0.0)
        self.programs = dict.fromkeys(CACHE, 0)
        self.saved_s = 0.0

    def to_dict(self) -> dict:
        return {"count": self.count, "inclusive_s": self.inclusive_s,
                "self_s": self.self_s, "jax_s": dict(self.jax_s),
                "programs": dict(self.programs), "saved_s": self.saved_s}


def stage_key(stage, attrs) -> str:
    """``first_call`` with ``program="step"`` is ``first_call{step}``."""
    if not attrs:
        return stage
    return f"{stage}{{{','.join(str(v) for v in attrs.values())}}}"


def _frames() -> list:
    try:
        return _local.frames
    except AttributeError:
        _local.frames = []
        return _local.frames


def current():
    """Key of the innermost stage open on this thread, or ``None``."""
    frames = getattr(_local, "frames", None)
    return frames[-1][0] if frames else None


def open_stage(key):
    """Push ``key``; returns the frame :func:`close_stage` takes, or
    ``None`` where ``key`` is already the innermost stage."""
    frames = _frames()
    if frames and frames[-1][0] == key:
        return None
    _install()
    if key not in _stages:
        with _lock:
            _stages.setdefault(key, Books())
    # key, start, seconds of the stages opened inside, JAX's charges
    frame = [key, time.perf_counter(), 0.0, {}]
    frames.append(frame)
    return frame


def close_stage(frame) -> dict:
    """Pop ``frame`` and charge its stage; returns what this opening of the
    stage held, for its span: the stage's key, ``self_s`` and JAX's seconds
    and programs."""
    key, t0, inside, charged = frame
    inclusive = time.perf_counter() - t0
    frames = _frames()
    for at in range(len(frames) - 1, -1, -1):
        if frames[at] is frame:     # and any frame left open further in
            del frames[at:]
            break
    if frames:
        frames[-1][2] += inclusive
    own = max(inclusive - inside, 0.0)
    with _lock:
        books = _stages.get(key)
        if books is None:           # reset() while the stage was open
            books = _stages[key] = Books()
        books.count += 1
        books.inclusive_s += inclusive
        books.self_s += own
    family = default_registry().summary(
        STAGE_METRIC, "set-up stages: wall seconds with (inclusive) and "
        "without (self) the stages opened inside", ("stage", "time"))
    family.labels(stage=key, time="inclusive").observe(inclusive)
    family.labels(stage=key, time="self").observe(own)
    _install()      # the `import` stage opens before JAX is imported
    return dict({k: round(v, 6) for k, v in charged.items()},
                stage=key, self_s=round(own, 6))


# -- JAX's own events ---------------------------------------------------------

def _install():
    """Register the listeners once, as soon as JAX is imported (nothing
    here imports it: a stage may open in a process that never loads it)."""
    global _listening
    if _listening or "jax" not in sys.modules:
        return
    try:
        from jax import monitoring
    except ImportError:     # partially imported: try again at the next stage
        return
    with _lock:
        if _listening:
            return
        _listening = True
    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def _jax_state():
    try:
        return _local.jax
    except AttributeError:
        # open events of this thread, and what the cache said of the
        # program being built
        _local.jax = {"open": [], "cache": None, "saved_s": 0.0}
        return _local.jax


def _on_start(event, value, **kwargs):
    if event in _DURATIONS:
        _jax_state()["open"].append([event, kwargs.get("fun_name"), 0.0])


def _on_event(event, **_):
    if event == _CACHE_HIT:
        _jax_state()["cache"] = "hit"
    elif event == _CACHE_MISS:
        _jax_state()["cache"] = "miss"


def _on_duration(event, seconds, **kwargs):
    if event == _SAVED:
        _jax_state()["saved_s"] = seconds
        return
    phase = _DURATIONS.get(event)
    if phase is None:
        return
    state = _jax_state()
    fun_name = kwargs.get("fun_name")
    inside = 0.0
    stack = state["open"]
    for at in range(len(stack) - 1, -1, -1):
        if stack[at][0] == event and stack[at][1] == fun_name:
            inside = stack[at][2]
            del stack[at:]
            break
    if stack:
        stack[-1][2] += seconds
    cache = None
    saved_s = 0.0
    if phase == "compile":
        cache = state["cache"] or "uncached"
        saved_s = state["saved_s"] if cache == "hit" else 0.0
        state["cache"], state["saved_s"] = None, 0.0
        if cache == "hit":
            phase = "cache_load"
    _charge(current() or OUTSIDE, _plain(fun_name), phase,
            max(seconds - inside, 0.0), cache, saved_s)


def _plain(fun_name) -> str:
    """The traced function's name: lowering and compiling name the
    program (``jit(step)``), tracing the function (``step``)."""
    name = str(fun_name) if fun_name else "?"
    for prefix in ("jit(", "pmap("):
        if name.startswith(prefix) and name.endswith(")"):
            return name[len(prefix):-1]
    return name


def _charge(stage, name, phase, seconds, cache, saved_s):
    frames = getattr(_local, "frames", None)
    if frames:
        charged = frames[-1][3]
        charged[phase + "_s"] = charged.get(phase + "_s", 0.0) + seconds
        if cache is not None:
            charged["programs_" + cache] = \
                charged.get("programs_" + cache, 0) + 1
    with _lock:
        books = _stages.get(stage)
        if books is None:
            books = _stages[stage] = Books()
        books.jax_s[phase] += seconds
        if name not in _names and len(_names) >= MAX_NAMES:
            name = OTHER
        row = _names.get(name)
        if row is None:
            row = _names[name] = dict.fromkeys(
                [p + "_s" for p in PHASES] + ["saved_s"], 0.0)
            row.update(count=0, hits=0, misses=0, stage=stage)
        row[phase + "_s"] += seconds
        if cache is not None:
            books.programs[cache] += 1
            books.saved_s += saved_s
            row["count"] += 1
            row["saved_s"] += saved_s
            row["hits"] += cache == "hit"
            row["misses"] += cache == "miss"
    reg = default_registry()
    reg.counter(PROGRAM_S_METRIC, "seconds JAX spent on the programs built "
                "in a set-up stage, by phase (each event less those nested "
                "in it)", ("stage", "phase")) \
        .labels(stage=stage, phase=phase).inc(seconds)
    if cache is not None:
        reg.counter(PROGRAMS_METRIC, "programs JAX built in a set-up "
                    "stage, by what the persistent cache did",
                    ("stage", "cache")).labels(stage=stage, cache=cache).inc()


# -- read-out -----------------------------------------------------------------

def setup_report(top=20) -> dict:
    """One JSON-able dict: ``stages`` in the order they first opened, each
    with ``count``, ``inclusive_s``, ``self_s``, ``jax_s`` by phase,
    ``programs`` by what the cache did and ``saved_s``; ``outside`` the
    same way for what no stage was open for; ``programs``, the ``top`` rows
    of the table by ``fun_name``, longest first (``stage`` is where the
    name was first built); ``names``, how many the table holds; and
    ``listening``, whether JAX's events are being read."""
    with _lock:
        stages = {key: books.to_dict() for key, books in _stages.items()}
        rows = [dict(row, fun_name=name) for name, row in _names.items()]
    outside = stages.pop(OUTSIDE, None) or Books().to_dict()
    rows.sort(key=lambda r: -sum(r[p + "_s"] for p in PHASES))
    return {"stages": stages, "outside": outside, "programs": rows[:top],
            "names": len(rows), "listening": _listening}


def reset():
    """Forget everything but the ``import`` stage, which a process makes
    once (tests). The listeners stay."""
    with _lock:
        kept = _stages.get("import")
        _stages.clear()
        _names.clear()
        if kept is not None:
            _stages["import"] = kept
