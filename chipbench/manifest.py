"""Where the benchmark's files are and how one is found by its name.

``BENCHMARK.json`` (the contract with the driver) sits at the root of the
checkout; everything a cell names sits under ``chipbench/`` in a file of its
own: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``runners/<runner>.py``, ``layer_metrics/<metric>.json``, ``peaks.json``.
"""
from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def by_name(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{sorted(e['name'] for e in entries)}")


def load_config(manifest, name) -> dict:
    return load_json(os.path.join(
        ROOT, by_name(manifest["configs"], name, "configuration")["file"]))


def load_traffic(name) -> dict:
    return load_json(os.path.join(HERE, "traffic", name + ".json"))


def load_peaks(device_kind) -> dict:
    """Published peaks of one chip. A kind the table lacks is an error:
    a share of a guessed peak reads like a measurement and is not one."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r} in chipbench/peaks.json")
    return table[device_kind]


def resolve(dotted):
    """``package.module.attr`` -> the attribute."""
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)
