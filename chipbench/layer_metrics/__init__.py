"""Per-layer metrics, one file each: ``<metric>.json`` says which layer the
metric belongs to, its unit, the end-to-end metric it should move, the
cells it exists in (``"*"`` for all) and where the number comes from:

- ``counter:<name>``: a count or total the runner hands back under
  ``values`` (JAX's compile events, the kernel tier's provenance);
- ``span:<name>``: the summary of a host span under ``spans`` — the
  runner's own, or one of the program's step phases — and ``stat`` says
  which statistic (``p50`` unless given);
- ``trace:<reader>``: a reader of the reduced profiler trace; the built-in
  ones are in ``chipbench.reduce_trace.READERS``, any other is the function
  ``read(summary, spec, values)`` of a sibling ``<metric>.py``;
- ``derived:<formula>``: arithmetic over the names in ``values`` of the
  same run (end-to-end metrics, counters, the peaks of the chip).

A reader that finds nothing to read returns ``None`` and the metric is left
out of the line. Adding a metric is adding a file here and an entry under
``per_layer`` in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import os

from .. import manifest, reduce_trace

HERE = os.path.dirname(os.path.abspath(__file__))


def load_all() -> dict:
    """``{metric name: its file's content}``, from the directory listing."""
    return {f[:-len(".json")]: manifest.load_json(os.path.join(HERE, f))
            for f in sorted(os.listdir(HERE)) if f.endswith(".json")}


def for_cell(cell_name) -> dict:
    return {name: spec for name, spec in load_all().items()
            if spec["cells"] == "*" or cell_name in spec["cells"]}


def _sibling_reader(name):
    spec = importlib.util.spec_from_file_location(
        f"chipbench.layer_metrics.{name}", os.path.join(HERE, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read(spec, values, facts):
    """The metric's number in this run, or ``None``."""
    kind, _, arg = spec["source"].partition(":")
    if kind == "counter":
        return values.get(arg)
    if kind == "span":
        summary = facts["spans"].get(arg)
        return summary[spec.get("stat", "p50")] if summary else None
    if kind == "trace":
        if facts.get("trace") is None:
            return None
        reader = reduce_trace.READERS.get(arg) or _sibling_reader(arg)
        return reader(facts["trace"], spec, values)
    if kind == "derived":
        try:
            return eval(arg, {"__builtins__": {}}, dict(values))
        except (NameError, ZeroDivisionError, TypeError):
            return None
    raise ValueError(f"unknown source kind {kind!r} in {spec['source']!r}")
