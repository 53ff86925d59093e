"""Device time by the program's named scopes, read from the profiler trace
of a traced run.

The program names the operations it traces with ``jax.named_scope``
(``mxnet_tpu.mamba2.ssd``, ``mxnet_tpu.mlp`` ...). A v5e trace names every
``XLA Ops`` event by its HLO instruction and carries no scope, but the
compiled program's text does, on every instruction:
``mxnet_tpu.observability.device_scopes()`` gives ``{program: {"module":
<HLO module name>, "scopes": {instruction: innermost scope}}}`` for the
programs of the live trainers. This reader joins the two: for every run of
such a program on the first device (``XLA Modules``), the time of the leaf
ops inside it goes to the scope of their instruction, or to ``unscoped``.

A metric file names its ``scopes`` (their times are added) and its
``quantity``: ``ms_per_step``, or ``scoped_share`` (per cent of the
device's busy time in the window that the join gave to any scope; ops of
programs the join does not know count as unscoped). A program without
``device_scopes`` (an older commit), or with no live trainer, gives
``None``. One line, ``chipbench: device_scopes {...}``, lists every scope's time in
the window and the unscoped time by op category.

As ``program_phases.py``: the harness hands a reader the reduced summary
only, so this one opens the newest ``.xplane.pb`` itself and trusts it only
if its window is the summary's; the arithmetic works on plain tuples (ns).
"""
from __future__ import annotations

import bisect
import functools
import json
import os

from .. import manifest, reduce_trace

UNSCOPED = "unscoped"


def attributed(ops, window, programs, mapping):
    """``[(scope, category, ns), ...]`` of the leaf ops inside ``window``.
    ``ops``: the first device's ``[(instruction, category, start, end),
    ...]``; ``programs``: its program runs ``[(module, start, end), ...]``;
    ``mapping``: ``{module: {instruction: scope}}``. A leaf op inside a run
    of a mapped module has its instruction's scope; every other leaf op (no
    scope, an unknown module, outside every run) is ``unscoped``."""
    runs = sorted((start, end, module) for module, start, end in programs)
    starts = [r[0] for r in runs]
    out = []
    for name, category, start, end, _, leaf in reduce_trace.self_times(ops):
        lo, hi = max(start, window[0]), min(end, window[1])
        if not leaf or hi <= lo:
            continue
        scope = None
        at = bisect.bisect_right(starts, start) - 1
        if at >= 0 and end <= runs[at][1]:
            scope = mapping.get(runs[at][2], {}).get(name.lstrip("%"))
        out.append((scope or UNSCOPED, category, hi - lo))
    return out


def summed(rows, key) -> dict:
    """``{key(scope, category): ns}`` of :func:`attributed`'s rows."""
    out = {}
    for scope, category, ns in rows:
        k = key(scope, category)
        out[k] = out.get(k, 0) + ns
    return out


def by_scope(ops, window, programs, mapping) -> dict:
    """``{scope: ns}`` of :func:`attributed`."""
    return summed(attributed(ops, window, programs, mapping),
                  lambda scope, category: scope)


def quantities(scope_ns, scopes, steps) -> dict:
    """The numbers a metric file may name, from :func:`by_scope`'s result:
    the listed scopes' time per step, and the share of all the time that
    has a scope."""
    total = sum(scope_ns.values())
    out = {"scoped_share":
           100.0 * (total - scope_ns.get(UNSCOPED, 0)) / total
           if total else None}
    if steps:
        out["ms_per_step"] = sum(scope_ns.get(s, 0) for s in scopes) \
            / 1e6 / steps
    return out


def program_mapping():
    """``{module: {instruction: scope}}`` from the program, or ``None``
    where it cannot say (no ``device_scopes``, no live trainer). Two
    programs of one module name cannot be told apart in a trace: both are
    left out."""
    from mxnet_tpu import observability
    device_scopes = getattr(observability, "device_scopes", None)
    if device_scopes is None:
        return None
    mapping, twice = {}, set()
    for record in device_scopes().values():
        module = record["module"]
        if module in mapping:
            twice.add(module)
        mapping[module] = record["scopes"]
    for module in twice:
        del mapping[module]
    return mapping or None


@functools.lru_cache(maxsize=1)
def kept(path, modified, window_s):
    """``{scope: ns}`` of this run's trace, read once for all the metrics of
    a run; ``None`` unless the program can map its instructions and the
    file's window is ``window_s``, the window of the summary the harness
    reduced."""
    devices, annotations, _, programs = reduce_trace.read_planes(path)
    if not annotations or not devices or not devices[min(devices)]:
        return None
    window = (min(a[1] for a in annotations), max(a[2] for a in annotations))
    if abs((window[1] - window[0]) / 1e9 - window_s) > 1e-9:
        return None
    mapping = program_mapping()
    if mapping is None:
        return None
    rows = attributed(devices[min(devices)], window, programs, mapping)
    scope_ns = summed(rows, lambda scope, category: scope)
    unscoped = summed((r for r in rows if r[0] == UNSCOPED),
                      lambda scope, category: category)
    print("chipbench: device_scopes " + json.dumps(
        {"ms": {s: ns / 1e6 for s, ns in sorted(scope_ns.items())},
         "unscoped_ms_by_category": {c: ns / 1e6
                                     for c, ns in unscoped.items()},
         "modules": {m: len(s) for m, s in mapping.items()}},
        sort_keys=True), flush=True)
    return scope_ns


def metric(summary, spec, values):
    try:
        path = reduce_trace.newest_xplane(
            os.path.join(manifest.ROOT, ".chipbench_trace"))
    except FileNotFoundError:
        return None
    scope_ns = kept(path, os.path.getmtime(path), summary["window_s"])
    if scope_ns is None:
        return None
    return quantities(scope_ns, spec.get("scopes", ()),
                      values.get("steps_traced")).get(spec["quantity"])


def read(summary, spec, values):
    """The harness executes this file anew for every metric that names it
    (``layer_metrics._sibling_reader``), so the work is handed to the module
    as a normal import gives it, whose cache stays."""
    from chipbench.layer_metrics import device_scopes
    return device_scopes.metric(summary, spec, values)
