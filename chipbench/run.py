"""One run of one cell: ``python -m chipbench.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

Loads the cell's configuration and traffic mix by name, hands them to the
runner the traffic names, and prints the contract's one JSON line last
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, in a
traced run, ``breakdown``). With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics. Anything else
worth reading goes on earlier lines that start with ``chipbench:``.

One process, which owns the cell's chips. Without a TPU, or with fewer
chips than the cell asks for, it prints one error line and exits non-zero:
a job is never shrunk to fit a CPU. The program runs as a user gets it: no
``MXNET_*`` variable is read or set here.
"""
from __future__ import annotations

import time

_T0 = time.monotonic()      # set-up is counted from here: before any import

import argparse     # noqa: E402
import importlib    # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402
import traceback    # noqa: E402

if __package__ in (None, ""):           # `python chipbench/run.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import layer_metrics, manifest    # noqa: E402

TRACE_DIR = os.path.join(manifest.ROOT, ".chipbench_trace")


def note(what, **fields):
    print(f"chipbench: {what} {json.dumps(fields, sort_keys=True)}",
          flush=True)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def metric_lines(names, values, units):
    return {n: {"value": values[n], "unit": units[n]}
            for n in names if values.get(n) is not None}


def run_cell(args) -> dict:
    """The result line of one run, as a dict."""
    bench = manifest.load_manifest()
    cell = manifest.by_name(bench["workloads"], args.workload, "workload")
    config = manifest.load_config(bench, cell["config"])
    traffic = manifest.load_traffic(cell["traffic"])

    import jax
    from mxnet_tpu import pallas, runtime
    cache_dir = runtime.enable_compile_cache()
    devices = jax.devices()
    found = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if found["platform"] != "tpu" or len(devices) < cell["chips"]:
        raise NoChip(f"cell {cell['name']} needs {cell['chips']} TPU "
                     f"chip(s) and JAX found {found}")
    devices = devices[:cell["chips"]]
    peaks = manifest.load_peaks(devices[0].device_kind)
    note("start", workload=cell["name"], seed=args.seed,
         seconds=args.seconds, trace=args.trace, cache_dir=cache_dir,
         pallas_mode=pallas.mode())

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(TRACE_DIR, cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    runner = importlib.import_module(
        "chipbench.runners." + traffic["runner"])
    facts = runner.run(config, traffic, devices, args.seed, args.seconds,
                       trace_dir)
    note("pallas", mode=pallas.mode(), provenance=pallas.tier_provenance())
    note("checks", **facts["checks"])
    note("spans", **facts["spans"])

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    return result_line(bench, cell, facts, peaks, device,
                       facts["setup_end"] - _T0, bool(args.trace))


def result_line(bench, cell, facts, peaks, device, setup_s, traced) -> dict:
    """The contract's line from what the runner handed back: the cell's
    end-to-end metrics in a timed run, its per-layer metrics (and the
    trace's busy seconds and breakdown) in a traced one."""
    values = dict(peaks)
    values.update(facts["values"])
    values["chips"] = device["count"]
    values["setup_s"] = setup_s
    note("values", **values)
    device = dict(device, memory_peak_bytes=facts["memory_peak_bytes"])
    line = {"correct": bool(facts["correct"]),
            "attempted": int(facts["attempted"]),
            "failed": int(facts["failed"])}
    if traced:
        summary = facts["trace"]
        note("trace", **summary)
        specs = layer_metrics.for_cell(cell["name"])
        readings = {name: layer_metrics.read(spec, values, facts)
                    for name, spec in specs.items()}
        line["metrics"] = metric_lines(
            sorted(specs), readings,
            {name: spec["unit"] for name, spec in specs.items()})
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["device"] = device
        line["breakdown"] = {"device_ops": summary["device_ops"][:10],
                             "idle_gaps": summary["idle_gaps"][:10]}
    else:
        mine = [m for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
        line["metrics"] = metric_lines(
            [m["name"] for m in mine], values,
            {m["name"]: m["unit"] for m in mine})
        line["device"] = device
    return line


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        line = run_cell(args)
    except NoChip as e:
        print(f"chipbench: error no_accelerator: {e}", flush=True)
        return 3
    except Exception as e:      # the boundary: report and fail, no result
        traceback.print_exc()
        print(f"chipbench: error {type(e).__name__}: {e}", flush=True)
        return 1
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
