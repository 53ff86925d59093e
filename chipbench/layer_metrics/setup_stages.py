"""Set-up by stage, read from the program's own books.

The program opens a set-up stage where the work happens (``import``,
``initialize``, ``deferred_shapes``, ``place``, ``build_step``,
``first_call{<program>}``, ``inspect``) and books JAX's own trace / lower /
compile / cache events to the stage that was open;
``mxnet_tpu.observability.setup_report()`` hands out the stages in the order
they first opened (``count``, ``inclusive_s``, ``self_s``, ``jax_s`` by
phase, ``programs`` by what the persistent cache did), what fell outside
every stage, and the longest rows of a table by ``fun_name``.

A metric file names its ``stages`` (a name matches the stage itself and the
stage with any subject: ``first_call`` matches ``first_call{step}``;
``without`` takes full keys out again) and its ``quantity``, summed over
them:

- ``inclusive_s``: wall seconds, the stages opened inside included;
- ``self_s``: wall seconds without the stages opened inside;
- ``trace_lower_s``: JAX's ``trace`` + ``lower`` seconds booked to them
  (the Python of ``gluon/`` and ``ops/`` run under ``jit``);
- ``programs``: programs JAX built inside them (hit, miss or too quick to
  be written to the cache);
- ``program_s``: JAX's seconds for those, all four phases.

With the report there, a stage that never opened counts 0. A program without
``setup_report`` (an older commit) gives ``None``. One line, ``chipbench:
setup_stages {...}``, once a run: the whole report, the run's ``setup_s``,
the seconds of it under some stage (``covered_s``: the self seconds of
every stage but ``inspect``, which runs after set-up) and under none
(``uncovered_s``: the runner's own work, the backend's start where the
runner touches the devices first, a traced run's reference and checks), and
the backend's share of the trained programs (``step_backend_s``: ``compile``
+ ``cache_load`` of the ``first_call`` stages but ``evaluate``'s).

The numbers are host clock readings and counts made inside the program: the
contract's word for a sibling reader is ``device_trace``, and the trace is
not read here.
"""
from __future__ import annotations

import json

AFTER_SETUP = ("inspect",)      # opened by readers of a traced run
CHECK = "first_call{evaluate}"  # the runner's forward check, traced runs only
PHASES = ("trace", "lower", "compile", "cache_load")
ROWS = 40                       # of the table by fun_name, in the line

_said = None                    # the run's line, once it is printed


def matches(key, names, without=()):
    return key not in without and any(
        key == name or key.startswith(name + "{") for name in names)


def quantity(stage, name):
    """One stage's number for a metric's ``quantity``."""
    if name in ("inclusive_s", "self_s"):
        return stage[name]
    if name == "trace_lower_s":
        return stage["jax_s"]["trace"] + stage["jax_s"]["lower"]
    if name == "programs":
        return sum(stage["programs"].values())
    if name == "program_s":
        return sum(stage["jax_s"][phase] for phase in PHASES)
    raise ValueError(f"unknown quantity {name!r}")


def metric_of(report, spec):
    """The metric's number out of a report: ``quantity`` summed over the
    stages the file names."""
    return sum(quantity(stage, spec["quantity"])
               for key, stage in report["stages"].items()
               if matches(key, spec["stages"], spec.get("without", ())))


def line_of(report, values) -> dict:
    """What the note line says besides the report."""
    stages = report["stages"]
    covered = sum(stage["self_s"] for key, stage in stages.items()
                  if key not in AFTER_SETUP)
    trained = [stage for key, stage in stages.items()
               if matches(key, ["first_call"], (CHECK,))]
    setup_s = values.get("setup_s")
    return {
        "report": report, "setup_s": setup_s, "covered_s": covered,
        "uncovered_s": None if setup_s is None else setup_s - covered,
        "covered_share": None if not setup_s else covered / setup_s,
        "step_backend_s": sum(stage["jax_s"]["compile"]
                              + stage["jax_s"]["cache_load"]
                              for stage in trained),
        "compile_s": values.get("compile_s"),
    }


def said(values):
    """The run's line, printed at the first call and kept; ``None`` where
    the program has no ``setup_report``."""
    global _said
    if _said is None:
        from mxnet_tpu import observability
        report_of = getattr(observability, "setup_report", None)
        if report_of is None:
            return None
        _said = line_of(report_of(top=ROWS), values)
        print(f"chipbench: setup_stages {json.dumps(_said)}",
              flush=True)
    return _said


def metric(spec, values):
    line = said(values)
    return None if line is None else metric_of(line["report"], spec)


def read(summary, spec, values):
    """The harness executes this file anew for every metric that names it
    (``layer_metrics._sibling_reader``), so the work is handed to the module
    as a normal import gives it, whose state stays."""
    from chipbench.layer_metrics import setup_stages
    return setup_stages.metric(spec, values)
