"""The reader of the program's own annotations
(``chipbench/layer_metrics/program_phases.py``): the interval arithmetic on
hand-made tuples, the host side on a CPU trace of this directory's toy, and
a trace recorded on a TPU v5e (``data/``) reduced to the numbers it holds."""
import os

import jax
import pytest

from chipbench import layer_metrics, manifest, reduce_trace
from chipbench.layer_metrics import program_phases as pp
from chipbench.runners import train

HERE = os.path.dirname(os.path.abspath(__file__))
PREFIX = "mxnet_tpu.sharded_trainer."
OUTER = ("step", "run_steps")
US = 1000
DATA = os.path.join(HERE, "data")


def op(name, start, end):
    return (name, "fusion", start * US, end * US)


def span(name, start, end):
    return (name, start * US, end * US)


def one_call():
    """Window 5..200 us. One step() call 10..110 us: data_wait 12..20,
    host_args 20..60, compiled_step 60..90, guard_fetch 95..100, so self
    time 2 + 5 + 10 = 17. On the device: program ``jit_convert`` 30..40 (one
    op, all of it), program ``jit_step`` 70..150 with ops 70..100 and
    120..150 (a bubble of 20 inside the run)."""
    ops = [op("convert.1", 30, 40), op("fusion.1", 70, 100),
           op("fusion.2", 120, 150)]
    annotations = [span("dispatch", 5, 112), span("wait_loss", 112, 200)]
    programs = [span("jit_convert", 30, 40), span("jit_step", 70, 150)]
    spans = [span("step", 10, 110), span("data_wait", 12, 20),
             span("host_args", 20, 60), span("compiled_step", 60, 90),
             span("guard_fetch", 95, 100)]
    calls = [span("convert", 25, 35), span("convert", 26, 34),
             span("step", 62, 88), span("step", 63, 87)]
    return ops, annotations, programs, spans, calls


def test_idle_is_split_into_between_programs_and_inside_a_program():
    r = pp.reduce(*one_call(), OUTER)
    # window 5..200: no program in 5..30, 40..70, 150..200 = 105 us; inside
    # jit_step nothing runs in 100..120
    assert r["window_s"] == pytest.approx(195e-6)
    assert r["idle_between_s"] == pytest.approx(105e-6)
    assert r["idle_in_program_s"] == pytest.approx(20e-6)
    assert r["idle_gaps_between"] == 3
    summary = reduce_trace.summarize({0: one_call()[0]}, one_call()[1])
    assert r["idle_between_s"] + r["idle_in_program_s"] == pytest.approx(
        summary["window_s"] - summary["busy_s_first_device"])
    q = pp.quantities(r, {"steps_traced": 1})
    assert q["idle_between_programs_share"] + q["idle_in_program_share"] \
        == pytest.approx(reduce_trace.idle_share(summary, {}, {}))


def test_idle_between_programs_falls_under_the_phase_the_host_was_in():
    r = pp.reduce(*one_call(), OUTER)
    # 5..30: 5 outside the call, 2 self, 8 data_wait, 10 host_args;
    # 40..70: 20 host_args, 10 compiled_step; 150..200: outside
    assert r["idle_between_by_phase_s"] == {
        "outside": pytest.approx(55e-6), "self": pytest.approx(2e-6),
        "data_wait": pytest.approx(8e-6), "host_args": pytest.approx(30e-6),
        "compiled_step": pytest.approx(10e-6)}
    assert r["idle_in_trainer_s"] == pytest.approx(50e-6)
    assert r["idle_outside_trainer_s"] == pytest.approx(55e-6)
    q = pp.quantities(r, {"steps_traced": 2})
    assert q["idle_in_trainer_ms_per_step"] == pytest.approx(0.025)
    assert q["idle_outside_trainer_ms_per_step"] == pytest.approx(0.0275)
    assert q["idle_in_trainer_ms_per_step"] \
        + q["idle_outside_trainer_ms_per_step"] == pytest.approx(
            q["idle_between_programs_share"] / 100 * r["window_s"] * 1e3 / 2)
    # the longest first, each with its phase and the programs on either side
    assert r["longest_gaps_between"] == [
        {"ms": pytest.approx(0.05), "phase": "outside", "after": "jit_step",
         "before": None},
        {"ms": pytest.approx(0.03), "phase": "host_args",
         "after": "jit_convert", "before": "jit_step"},
        {"ms": pytest.approx(0.025), "phase": "host_args", "after": None,
         "before": "jit_convert"}]


def test_self_time_is_the_call_less_its_phases():
    r = pp.reduce(*one_call(), OUTER)
    assert r["trainer_calls"] == 1
    means = {name: s["mean"] for name, s in r["phases_ms"].items()}
    assert means == {"step": pytest.approx(0.1),
                     "data_wait": pytest.approx(0.008),
                     "host_args": pytest.approx(0.04),
                     "compiled_step": pytest.approx(0.03),
                     "guard_fetch": pytest.approx(0.005),
                     "self": pytest.approx(0.017)}
    assert sum(v for k, v in means.items() if k != "step") \
        == pytest.approx(means["step"])
    q = pp.quantities(r, {})
    assert q["phase_ms.host_args"] == pytest.approx(0.04)
    assert q["phase_ms.self"] == pytest.approx(0.017)
    assert "idle_in_trainer_ms_per_step" not in q       # no steps counted


def test_a_program_is_counted_once_under_the_phase_that_starts_it():
    ops, annotations, programs, spans, calls = one_call()
    calls += [span("gather", 150, 160), span("gather", 151, 159),
              span("step", 300, 310)]                  # the last: no window
    r = pp.reduce(ops, annotations, programs, spans, calls, OUTER)
    assert r["programs_started_per_call"] == {
        "host_args": {"convert": 1.0}, "compiled_step": {"step": 1.0},
        "outside": {"gather": 1.0}}
    assert pp.outermost([("a", 0, 10), ("a", 1, 9), ("a", 5, 8), ("a", 6, 7),
                         ("b", 2, 3), ("a", 12, 14)]) \
        == [("a", 0, 10), ("b", 2, 3), ("a", 12, 14)]


def test_a_program_without_the_annotations_still_splits_the_idle_time():
    ops, annotations, programs, _, calls = one_call()
    r = pp.reduce(ops, annotations, programs, [], calls, OUTER)
    assert set(r) == {"window_s", "idle_between_s", "idle_in_program_s",
                      "idle_gaps_between"}
    assert set(pp.quantities(r, {"steps_traced": 1})) == {
        "idle_between_programs_share", "idle_in_program_share"}


def test_a_gap_in_a_container_op_is_idle_inside_the_program():
    # run_steps: one program 0..100 whose while op holds two fusions
    ops = [("while.1", "while", 0, 100 * US), op("fusion.2", 10, 30),
           op("fusion.3", 40, 90)]
    r = pp.reduce(ops, [span("wait_loss", 0, 120)],
                  [span("jit_multi", 0, 100)], [], [], OUTER)
    assert r["idle_in_program_s"] == pytest.approx(30e-6)
    assert r["idle_between_s"] == pytest.approx(20e-6)


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.syspath_prepend(HERE)       # "tiny_model.build" resolves

    def load(name):
        return manifest.load_json(os.path.join(HERE, "data", name + ".json"))
    return load


def fake_device_plane(monkeypatch):
    """A CPU trace has no device plane: one op and one program run under
    the first half of the first host annotation."""
    real = reduce_trace.read_planes

    def read(path):
        annotations = real(path)[1]
        _, start, end = annotations[0]
        return ({0: [("fusion.1", "fusion", start, (start + end) / 2)]},
                annotations, [], [("jit_step", start, (start + end) / 2)])
    monkeypatch.setattr(reduce_trace, "read_planes", read)


@pytest.mark.parametrize("mix, outer, program, calls", [
    ("tiny_loop", "step", "step", 8), ("tiny_fused", "run_steps", "multi", 2)])
def test_the_metric_files_read_a_cpu_trace_of_the_toy(
        toy, tmp_path, monkeypatch, mix, outer, program, calls):
    fake_device_plane(monkeypatch)
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    traffic = toy(mix)
    facts = train.run(toy("tiny_mlp"), traffic, jax.devices()[:1], 2 ** 31 + 7,
                      0.3, str(tmp_path / ".chipbench_trace" / "toy"))
    specs = {name: spec for name, spec in layer_metrics.load_all().items()
             if spec["source"] == "trace:program_phases"}
    assert len(specs) == 8
    got = {name: layer_metrics.read(spec, facts["values"], facts)
           for name, spec in specs.items()}
    assert all(isinstance(v, float) and v >= 0 for v in got.values()), got
    path = reduce_trace.newest_xplane(str(tmp_path / ".chipbench_trace"))
    r = pp.kept(path, os.path.getmtime(path), PREFIX, OUTER,
                facts["trace"]["window_s"])
    assert pp.kept.cache_info().hits >= 8        # read once for all eight
    assert r["trainer_calls"] == calls
    assert set(r["phases_ms"]) == {outer, "data_wait", "host_args",
                                   "compiled_step", "guard_fetch", "self"}
    phases = r["phases_ms"]
    assert got["host_args_ms"] == phases["host_args"]["mean"]
    assert got["enqueue_ms"] == phases["compiled_step"]["mean"]
    assert got["guard_fetch_ms"] == phases["guard_fetch"]["mean"]
    assert got["trainer_self_ms"] == phases["self"]["mean"]
    assert sum(s["mean"] for name, s in phases.items() if name != outer) \
        == pytest.approx(phases[outer]["mean"])
    assert phases[outer]["mean"] < facts["spans"]["dispatch"]["mean"]
    started = r["programs_started_per_call"]
    assert started["compiled_step"] == {program: 1.0}
    assert "host_args" not in started     # host work only (since PR 30)
    assert "self" not in started and "guard_fetch" not in started
    steps = facts["values"]["steps_traced"]
    assert (got["idle_in_trainer_ms_per_step"]
            + got["idle_outside_trainer_ms_per_step"]) * steps \
        == pytest.approx(got["idle_between_programs_share"] / 100
                         * r["window_s"] * 1e3)
    assert got["idle_between_programs_share"] + got["idle_in_program_share"] \
        == pytest.approx(reduce_trace.idle_share(facts["trace"], {}, {}))
    # a window that is not this file's: nothing is read from it
    stale = dict(facts["trace"], window_s=facts["trace"]["window_s"] + 1e-6)
    assert all(pp.read(stale, spec, facts["values"]) is None
               for spec in specs.values())


def test_no_trace_in_the_checkout_reads_as_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    spec = layer_metrics.load_all()["host_args_ms"]
    assert pp.read({"window_s": 1.0}, spec, {"steps_traced": 1}) is None


def test_a_trace_of_the_parent_commit_gives_the_idle_split_alone():
    # data/tiny_loop_v5e.xplane.pb was recorded before the program had the
    # annotations (PR 24): what needs them is left out, nothing raises
    r = pp.reduce_file(os.path.join(DATA, "tiny_loop_v5e.xplane.pb"),
                       PREFIX, OUTER)
    assert "phases_ms" not in r
    assert r["idle_between_s"] == pytest.approx(0.052483296, rel=1e-9)
    assert r["idle_in_program_s"] == pytest.approx(7.4448e-05, rel=1e-6)
    s = reduce_trace.reduce_file(os.path.join(DATA, "tiny_loop_v5e.xplane.pb"))
    assert r["idle_between_s"] + r["idle_in_program_s"] == pytest.approx(
        s["window_s"] - s["busy_s_first_device"], rel=1e-12)


NINE = {"_threefry_split_foldlike": 1.0, "_unstack": 1.0,
        "convert_element_type": 4.0, "reshape": 2.0, "transpose": 1.0}


def test_the_recorded_v5e_loop_trace_reduces_to_known_numbers():
    # recorded on one TPU v5 lite in PR 25 by runners.train.run in a traced
    # run of this directory's toy (data/tiny_mlp.json under
    # data/tiny_loop.json, bfloat16): eight step() calls, each with a loss
    # read; the numbers are those the chip printed in that call
    path = os.path.join(DATA, "tiny_loop_phases_v5e.xplane.pb")
    r = pp.reduce_file(path, PREFIX, OUTER)
    s = reduce_trace.reduce_file(path)
    assert r["window_s"] == s["window_s"] == pytest.approx(0.058829714,
                                                           rel=1e-9)
    assert r["idle_between_s"] == pytest.approx(0.058696737, rel=1e-8)
    assert r["idle_in_program_s"] == pytest.approx(7.3982e-05, rel=1e-5)
    assert r["idle_between_s"] + r["idle_in_program_s"] == pytest.approx(
        s["window_s"] - s["busy_s_first_device"], rel=1e-12)
    assert r["idle_gaps_between"] == 80 and r["trainer_calls"] == 8
    assert r["idle_in_trainer_s"] == pytest.approx(0.050986124, rel=1e-8)
    assert r["idle_outside_trainer_s"] == pytest.approx(0.007710613, rel=1e-8)
    assert r["idle_between_by_phase_s"] == {
        "host_args": pytest.approx(0.038361244, rel=1e-8),
        "outside": pytest.approx(0.007710613, rel=1e-8),
        "compiled_step": pytest.approx(0.006000659, rel=1e-8),
        "data_wait": pytest.approx(0.00566556, rel=1e-8),
        "self": pytest.approx(0.000853502, rel=1e-8),
        "guard_fetch": pytest.approx(0.000105159, rel=1e-8)}
    assert {name: s["mean"] for name, s in r["phases_ms"].items()} == {
        "step": pytest.approx(6.389413125), "data_wait":
        pytest.approx(0.708766), "host_args": pytest.approx(4.810732125),
        "compiled_step": pytest.approx(0.750082375), "guard_fetch":
        pytest.approx(0.013144875), "self": pytest.approx(0.10668775)}
    assert r["phases_ms"]["host_args"]["p95"] == pytest.approx(5.59027)
    assert r["phases_ms"]["step"]["count"] == 8
    # the turn-around between two steps is the longest wait of the device
    assert r["longest_gaps_between"][0] == {
        "after": "jit_step", "before": "jit_reshape", "phase": "outside",
        "ms": pytest.approx(2.481354)}
    assert [g["phase"] for g in r["longest_gaps_between"]] == [
        "outside"] * 4 + ["compiled_step"]
    # which phase starts which of the ten programs of a step
    assert r["programs_started_per_call"] == {
        "host_args": NINE, "compiled_step": {"step": 1.0}}
    q = pp.quantities(r, {"steps_traced": 8})
    assert q["idle_between_programs_share"] + q["idle_in_program_share"] \
        == pytest.approx(reduce_trace.idle_share(s, {}, {}))
    assert q["idle_in_trainer_ms_per_step"] == pytest.approx(6.3732655)
    assert q["idle_outside_trainer_ms_per_step"] == pytest.approx(0.963826625)
    assert q["phase_ms.host_args"] == pytest.approx(4.810732125)
    assert q["phase_ms.self"] == pytest.approx(0.10668775)


def test_the_recorded_v5e_fused_trace_reduces_to_known_numbers():
    # the same toy under data/tiny_fused.json: two run_steps(3) calls, two
    # in flight. run_steps picks its last loss after its outer span, with
    # three small programs that no phase covers
    path = os.path.join(DATA, "tiny_fused_phases_v5e.xplane.pb")
    r = pp.reduce_file(path, PREFIX, OUTER)
    assert r["window_s"] == pytest.approx(0.016754388, rel=1e-9)
    assert r["idle_between_s"] == pytest.approx(0.016679939, rel=1e-8)
    assert r["idle_in_program_s"] == pytest.approx(3.0787e-05, rel=1e-5)
    assert r["trainer_calls"] == 2 and r["idle_gaps_between"] == 27
    assert {name: s["mean"] for name, s in r["phases_ms"].items()} == {
        "run_steps": pytest.approx(6.528909), "data_wait":
        pytest.approx(0.239805), "host_args": pytest.approx(5.4684295),
        "compiled_step": pytest.approx(0.7321645), "guard_fetch":
        pytest.approx(0.0103), "self": pytest.approx(0.07821)}
    assert r["idle_in_trainer_s"] == pytest.approx(0.013044988, rel=1e-8)
    assert r["longest_gaps_between"][0] == {
        "after": "jit_transpose", "before": "jit_reshape",
        "phase": "host_args", "ms": pytest.approx(1.38498)}
    assert r["programs_started_per_call"] == {
        "host_args": NINE, "compiled_step": {"multi": 1.0},
        "outside": {"convert_element_type": 1.0, "dynamic_slice": 1.0,
                    "squeeze": 1.0}}
    q = pp.quantities(r, {"steps_traced": 6})
    assert q["idle_in_trainer_ms_per_step"] == pytest.approx(2.1741646667)
    assert q["idle_outside_trainer_ms_per_step"] \
        == pytest.approx(0.6058251667)
