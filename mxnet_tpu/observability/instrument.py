"""Shared instrumentation helpers for the hot paths.

The four trainers, the serving predictor cache and the checkpoint
commit protocol all record the same two shapes of signal:

- **step phases** (data wait / host args / compiled step / guard
  fetch): a monotonic-timed scope observed into the always-on
  ``mxnet_tpu_step_phase_ms{trainer,phase}`` summary (host arithmetic
  only — the per-step cost is two ``perf_counter`` reads and one lock),
  plus a nested trace span when ``MXNET_TPU_TRACE`` is on, inside one
  :func:`call_span` per trainer call. Both also enter a
  ``jax.profiler.TraceAnnotation`` named ``mxnet_tpu.<trainer>.<phase>``
  (``.step`` / ``.run_steps`` for the call), so a profiler session
  shows the phases on the clock of the device trace; with no session an
  annotation is a flag test in C++, so there is no switch for it;
- **set-up stages** (``import``, ``initialize``, ``deferred_shapes``,
  ``place``, ``build_step``, ``first_call{<program>}``, ``inspect``,
  ``backend_start``): :func:`setup_stage`, built like a step phase and
  always observed into ``mxnet_tpu_setup_stage_s{stage,time}``; JAX's own
  trace / lower / compile / cache events are booked to the stage that was
  open (:mod:`.stages`, ``observability.setup_report()``);
- **compile events**: every jit-cache-miss site wraps its build in
  :func:`compile_span`, so XLA trace/lower/compile time lands in
  ``mxnet_tpu_xla_compiles_total{site}`` /
  ``mxnet_tpu_xla_compile_ms{site}`` and, when tracing, as an
  ``xla_compile`` span with the shapes attached.

Zero-device-read contract: nothing here touches a device value —
tests/test_observability.py runs the compiled step paths of all four
trainers under ``jax.transfer_guard_device_to_host("disallow")``.
"""
from __future__ import annotations

import contextlib
import time

from . import stages, trace
from .metrics import default_registry

__all__ = ["aot_load_span", "call_span", "compile_span", "device_scope",
           "maybe_compile_span", "maybe_setup_stage", "setup_stage",
           "step_phase", "ANNOTATION_PREFIX",
           "PHASE_METRIC", "COMPILE_COUNT_METRIC",
           "COMPILE_MS_METRIC", "AOT_LOAD_COUNT_METRIC",
           "AOT_LOAD_MS_METRIC"]

PHASE_METRIC = "mxnet_tpu_step_phase_ms"
COMPILE_COUNT_METRIC = "mxnet_tpu_xla_compiles_total"
COMPILE_MS_METRIC = "mxnet_tpu_xla_compile_ms"
AOT_LOAD_COUNT_METRIC = "mxnet_tpu_aot_loads_total"
AOT_LOAD_MS_METRIC = "mxnet_tpu_aot_load_ms"
ANNOTATION_PREFIX = "mxnet_tpu."

_TraceAnnotation = None


def _annotation(name, **attrs):
    """``jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)``. The
    import waits for the first trainer call: this package loads without
    jax (the journal's exporters must work while everything else is
    wedged)."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(ANNOTATION_PREFIX + name, **attrs)


def device_scope(name):
    """``jax.named_scope(ANNOTATION_PREFIX + name)``: names the device
    operations traced inside it. The name lands in the ``op_name`` metadata
    of every HLO instruction of the compiled program (backward and
    recomputed ones too), which :func:`..scopes.device_scopes` joins
    with a profiler trace; it costs nothing when the program runs."""
    import jax
    return jax.named_scope(ANNOTATION_PREFIX + name)


_phase_cache = None


def _phase_summary():
    # per-registry memo: the family lookup (name validation + registry
    # lock) would otherwise run four times per training step; the cache
    # keys on registry identity so reset_metrics() (tests) invalidates
    global _phase_cache
    reg = default_registry()
    cached = _phase_cache
    if cached is not None and cached[0] is reg:
        return cached[1]
    fam = reg.summary(
        PHASE_METRIC, "per-phase training-step wall time (monotonic), ms",
        ("trainer", "phase"))
    _phase_cache = (reg, fam)
    return fam


@contextlib.contextmanager
def step_phase(trainer, phase, **attrs):
    """One training-step phase: always observed into the phase summary,
    annotated as ``mxnet_tpu.<trainer>.<phase>`` for a profiler session,
    traced as ``<trainer>.<phase>`` when ``MXNET_TPU_TRACE`` is on."""
    name = f"{trainer}.{phase}"
    t0 = time.perf_counter()
    with trace.span(name, **attrs), _annotation(name, **attrs):
        try:
            yield
        finally:
            _phase_summary().labels(trainer=trainer, phase=phase).observe(
                (time.perf_counter() - t0) * 1000.0)


@contextlib.contextmanager
def setup_stage(stage, **attrs):
    """One stage of set-up: always charged to the stage's books
    (:mod:`.stages`: inclusive and self seconds, JAX's events while it is
    the innermost stage open), annotated as ``mxnet_tpu.setup.<stage>`` for
    a profiler session, traced as ``setup.<stage>`` when
    ``MXNET_TPU_TRACE`` is on (the span carries ``self_s`` and what JAX
    built inside). ``attrs`` name the stage's subject and are part of its
    key: ``setup_stage("first_call", program="step")`` is
    ``first_call{step}``. Opening the stage that is already innermost
    does nothing."""
    frame = stages.open_stage(stages.stage_key(stage, attrs))
    if frame is None:
        yield
        return
    name = "setup." + stage
    with trace.span(name, **attrs) as sp, _annotation(name, **attrs):
        try:
            yield
        finally:
            sp.set_attrs(**stages.close_stage(frame))


def maybe_setup_stage(pending, stage, **attrs):
    """``setup_stage`` when ``pending`` (this call is the program's first),
    else a null context."""
    if pending:
        return setup_stage(stage, **attrs)
    return contextlib.nullcontext()


@contextlib.contextmanager
def call_span(trainer, call, **attrs):
    """One trainer call (``step`` / ``run_steps``), the parent of its
    phases by containment: the ``<trainer>.<call>`` trace span and the
    ``mxnet_tpu.<trainer>.<call>`` profiler annotation, both with the
    same host-scalar ``attrs`` (the step number)."""
    name = f"{trainer}.{call}"
    with trace.span(name, **attrs), _annotation(name, **attrs):
        yield


@contextlib.contextmanager
def compile_span(site, **attrs):
    """One compile event (jit cache miss / executable build) at
    ``site``: counted, timed, and traced as ``xla_compile``."""
    reg = default_registry()
    t0 = time.perf_counter()
    with trace.span("xla_compile", site=site, **attrs):
        try:
            yield
        finally:
            ms = (time.perf_counter() - t0) * 1000.0
            reg.counter(COMPILE_COUNT_METRIC,
                        "XLA trace/lower/compile events",
                        ("site",)).labels(site=site).inc()
            reg.summary(COMPILE_MS_METRIC, "XLA compile wall time, ms",
                        ("site",)).labels(site=site).observe(ms)


@contextlib.contextmanager
def aot_load_span(site, **attrs):
    """One deserialized-executable load at ``site``: counted, timed,
    and traced as ``aot_load`` — deliberately a DIFFERENT site family
    from ``xla_compile`` so a warm start's ``compile_stats()`` reads
    zero compiles honestly (docs/observability.md)."""
    reg = default_registry()
    t0 = time.perf_counter()
    with trace.span("aot_load", site=site, **attrs):
        try:
            yield
        finally:
            ms = (time.perf_counter() - t0) * 1000.0
            reg.counter(AOT_LOAD_COUNT_METRIC,
                        "deserialized AOT executable loads",
                        ("site",)).labels(site=site).inc()
            reg.summary(AOT_LOAD_MS_METRIC,
                        "AOT executable load wall time, ms",
                        ("site",)).labels(site=site).observe(ms)


def maybe_compile_span(pending, site, **attrs):
    """``compile_span`` when ``pending`` (this dispatch includes the
    compile), else a null context — the first-call pattern at the
    trainers' jit sites."""
    if pending:
        return compile_span(site, **attrs)
    return contextlib.nullcontext()
