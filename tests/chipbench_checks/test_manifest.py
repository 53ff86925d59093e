"""BENCHMARK.json and the files it names agree with each other and with the
driver's contract: each cell and each per-layer metric file is a case."""
import ast
import importlib
import os
import re

import pytest

from chipbench import layer_metrics, manifest

BENCH = manifest.load_manifest()
CELLS = BENCH["workloads"]
METRIC_FILES = layer_metrics.load_all()
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# where a metric file's kind of source may appear in the contract's words
CONTRACT_SOURCE = {"counter": {"program_counter", "program_span",
                               "host_clock"},
                   "span": {"host_clock", "program_span"},
                   "trace": {"device_trace"},
                   "derived": {"host_clock"}}


def cells_of(entry):
    return entry.get("workloads", [c["name"] for c in CELLS])


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "-m", "chipbench.run"]
    assert BENCH["paths"] == ["chipbench", "tests/chipbench_checks"]
    assert os.path.getsize(os.path.join(
        manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024
    # a full check with 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert 1 <= BENCH["run_seconds"] <= 51
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= len(CELLS) <= 24 and 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(c["config"], c["traffic"]) for c in CELLS]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


def test_at_most_one_cell_in_four_takes_four_chips():
    four = [c for c in CELLS if c["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    assert all(c["chips"] in (1, 4) for c in CELLS)


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_files_exist_and_import(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    config = manifest.load_config(BENCH, cell["config"])
    traffic = manifest.load_traffic(cell["traffic"])
    runner = importlib.import_module(
        "chipbench.runners." + traffic["runner"])
    assert callable(runner.run)
    for key in ("build", "make_batch", "flops_per_sample", "reference"):
        assert callable(manifest.resolve(config[key])), key
    if "compare" in config:         # optional: chipbench/README.md
        assert callable(manifest.resolve(config["compare"]))
    assert config["flops_per_sample"].startswith("chipbench.models.")
    reports = {n for n, m in END_TO_END.items() if cell["name"] in cells_of(m)}
    assert "setup_s" in reports and len(reports) >= 2
    assert any(cell["name"] in cells_of(m) for m in BENCH["per_layer"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert entry["file"].startswith("chipbench/configs/")
    config = manifest.load_json(os.path.join(manifest.ROOT, entry["file"]))
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    assert 1 <= len(entry["source"]) <= 200 and 1 <= len(entry["why"]) <= 200
    assert any(c["config"] == entry["name"] for c in CELLS)


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert set(cells_of(metric)) <= {c["name"] for c in CELLS}


@pytest.mark.parametrize("name", sorted(METRIC_FILES))
def test_per_layer_metric_file(name):
    spec = METRIC_FILES[name]
    entry = manifest.by_name(BENCH["per_layer"], name, "per-layer metric")
    assert set(entry) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert NAME.match(name) and UNIT.match(spec["unit"])
    for key in ("unit", "better", "layer", "moves"):
        assert entry[key] == spec[key], key
    assert 1 <= len(spec["layer"]) <= 200
    kind = spec["source"].partition(":")[0]
    assert entry["source"] in SOURCES
    assert entry["source"] in CONTRACT_SOURCE[kind]
    if spec["cells"] == "*":
        assert "workloads" not in entry
    else:
        assert entry["workloads"] == spec["cells"]
    # the metric it should move is reported in every cell where this one is
    moved = END_TO_END[spec["moves"]]
    assert set(cells_of(entry)) <= set(cells_of(moved))
    if kind == "trace":
        reader = spec["source"].partition(":")[2]
        from chipbench import reduce_trace
        assert reader in reduce_trace.READERS or os.path.exists(
            os.path.join(layer_metrics.HERE, reader + ".py"))


def test_every_per_layer_entry_has_its_file():
    assert sorted(m["name"] for m in BENCH["per_layer"]) \
        == sorted(METRIC_FILES)
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(spelled) == 1 for spelled in layers.values())


def _benchmark_files():
    for path in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(manifest.ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if not f.endswith(".pyc"):
                    yield os.path.join(folder, f)


def test_file_names_use_the_characters_of_a_name():
    for path in _benchmark_files():
        rel = os.path.relpath(path, manifest.ROOT)
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_chipbench_takes_nothing_from_the_old_scripts_or_the_environment():
    banned = ("bench", "benchmarks", "chip_smoke")
    for path in _benchmark_files():
        rel = os.path.relpath(path, manifest.ROOT)
        if not (rel.startswith("chipbench") and rel.endswith(".py")):
            continue
        source = open(path).read()
        assert "environ" not in source and "getenv" not in source, rel
        for node in ast.walk(ast.parse(source)):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module or ""]
            for n in names:
                assert n.split(".")[0] not in banned, (rel, n)


def test_peaks_are_keyed_by_device_kind():
    assert manifest.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        manifest.load_peaks("TPU v9")
    with pytest.raises(KeyError):
        manifest.load_peaks("source")
