"""The reader of the program's set-up stages
(``chipbench/layer_metrics/setup_stages.py``): each of the six metric files
on a canned report, a program without ``setup_report``, the line printed
once, and the live program's report through the harness's own ``read``."""
import json

import pytest

from chipbench import layer_metrics, manifest
from chipbench.layer_metrics import setup_stages

SPECS = {name: spec for name, spec in layer_metrics.load_all().items()
         if spec["source"] == "trace:setup_stages"}


def stage(inclusive, own, trace=0.0, lower=0.0, compile=0.0, cache_load=0.0,
          hit=0, miss=0, uncached=0):
    return {"count": 1, "inclusive_s": inclusive, "self_s": own,
            "jax_s": {"trace": trace, "lower": lower, "compile": compile,
                      "cache_load": cache_load},
            "programs": {"hit": hit, "miss": miss, "uncached": uncached},
            "saved_s": 0.0}


def canned():
    """A traced run: ``deferred_shapes`` holds two parameters' ``initialize``
    (1 of its 5 s), ``evaluate`` is the runner's check, ``inspect`` a
    reader's, after set-up."""
    return {
        "stages": {
            "import": stage(3.0, 3.0),
            "initialize": stage(4.0, 4.0, trace=0.25, lower=0.5,
                                compile=1.0, uncached=30),
            "backend_start": stage(0.5, 0.5),
            "deferred_shapes": stage(5.0, 4.0, trace=0.5, lower=0.25,
                                     compile=2.0, cache_load=0.125,
                                     hit=1, uncached=60),
            "place": stage(2.0, 2.0, lower=0.125, compile=0.25, uncached=8),
            "build_step": stage(0.001, 0.001),
            "first_call{step}": stage(9.0, 9.0, trace=4.0, lower=2.0,
                                      cache_load=3.0, hit=1),
            "first_call{run_steps(4)}": stage(6.0, 6.0, trace=1.0, lower=0.5,
                                              compile=4.5, miss=1),
            "first_call{evaluate}": stage(7.0, 7.0, trace=3.0, lower=1.0,
                                          compile=3.0, miss=1),
            "inspect": stage(8.0, 8.0, lower=2.0, cache_load=6.0, hit=2),
        },
        "outside": stage(0.0, 0.0, compile=0.5, uncached=12),
        "programs": [], "names": 0, "listening": True,
    }


EXPECTED = {
    "import_s": 3.0,
    "initialize_s": 8.0,
    "place_s": 2.0,
    "step_trace_lower_s": 7.5,
    "eager_programs": 99,
    "eager_program_s": 5.0,
}


def test_the_six_metric_files_are_the_readers():
    """Every cell of the benchmark reads them: the lists name the cells one
    by one, so that the harness's own toy cells (``test_train_runner.py``,
    which expects the metrics that exist in all cells and no other) do not."""
    assert sorted(SPECS) == sorted(EXPECTED)
    cells = [c["name"] for c in manifest.load_manifest()["workloads"]]
    for spec in SPECS.values():
        assert spec["cells"] == cells and spec["moves"] == "setup_s"
        assert spec["better"] == "lower"


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_on_a_canned_report(name):
    assert setup_stages.metric_of(canned(), SPECS[name]) == EXPECTED[name]


def test_a_stage_that_never_opened_counts_nothing():
    report = canned()
    del report["stages"]["deferred_shapes"], report["stages"]["place"]
    assert setup_stages.metric_of(report, SPECS["initialize_s"]) == 4.0
    assert setup_stages.metric_of(report, SPECS["place_s"]) == 0
    assert setup_stages.metric_of(report, SPECS["eager_programs"]) == 30


def test_a_name_matches_the_stage_and_its_subjects_only():
    assert setup_stages.matches("first_call{step}", ["first_call"])
    assert setup_stages.matches("place", ["place"])
    assert not setup_stages.matches("placement", ["place"])
    assert not setup_stages.matches("first_call{evaluate}", ["first_call"],
                                    ("first_call{evaluate}",))
    with pytest.raises(ValueError):
        setup_stages.quantity(stage(1.0, 1.0), "seconds")


def test_the_line_says_what_set_up_is_covered():
    line = setup_stages.line_of(canned(), {"setup_s": 50.0, "compile_s": 16.0})
    # every stage's self seconds but inspect's
    assert line["covered_s"] == pytest.approx(35.501)
    assert line["uncovered_s"] == pytest.approx(14.499)
    assert line["covered_share"] == pytest.approx(0.71002)
    assert line["step_backend_s"] == 7.5       # step 3.0 + run_steps 4.5
    assert line["compile_s"] == 16.0
    json.dumps(line)


def test_without_setup_report_the_reader_gives_none(monkeypatch, capsys):
    from mxnet_tpu import observability
    monkeypatch.setattr(setup_stages, "_said", None)
    monkeypatch.delattr(observability, "setup_report", raising=False)
    read = layer_metrics._sibling_reader("setup_stages")
    for spec in SPECS.values():
        assert read({}, spec, {"setup_s": 50.0}) is None
    assert "setup_stages" not in capsys.readouterr().out


def test_the_line_is_printed_once_a_run(monkeypatch, capsys):
    from mxnet_tpu import observability
    monkeypatch.setattr(setup_stages, "_said", None)
    monkeypatch.setattr(observability, "setup_report",
                        lambda top=20: canned(), raising=False)
    facts = {"trace": {"window_s": 1.0}, "spans": {}}
    values = {"setup_s": 50.0, "compile_s": 16.0}
    readings = {name: layer_metrics.read(spec, values, facts)
                for name, spec in SPECS.items()}
    assert readings == EXPECTED
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("chipbench: setup_stages ")]
    assert len(lines) == 1
    said = json.loads(lines[0].partition("setup_stages ")[2])
    assert said["setup_s"] == 50.0
    # the stages stay in the order they first opened
    assert list(said["report"]["stages"]) == list(canned()["stages"])


def test_the_live_program_gives_every_metric_a_number(monkeypatch):
    """The process that runs this test imported the program and, with the
    other tests of this directory, may have trained with it: whatever it
    did, each of the six reads a number, and ``import`` took time."""
    from mxnet_tpu import observability
    if not hasattr(observability, "setup_report"):
        pytest.skip("this program has no set-up stages (an older commit)")
    monkeypatch.setattr(setup_stages, "_said", None)
    facts = {"trace": {"window_s": 1.0}, "spans": {}}
    readings = {name: layer_metrics.read(spec, {"setup_s": 50.0}, facts)
                for name, spec in SPECS.items()}
    assert all(isinstance(v, (int, float)) for v in readings.values())
    assert readings["import_s"] > 0


def test_a_timed_run_reads_no_trace_metric():
    for spec in SPECS.values():
        assert layer_metrics.read(spec, {"setup_s": 50.0},
                                  {"trace": None, "spans": {}}) is None
