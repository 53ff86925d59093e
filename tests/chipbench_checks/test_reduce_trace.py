"""The reduction from a profiler trace to numbers: the interval arithmetic
on a hand-built fixture, and a trace recorded on a TPU v5e (``data/``)
reduced to the numbers it is known to hold."""
import os

import pytest

from chipbench import reduce_trace

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_counts_an_overlap_once():
    assert reduce_trace.union_length([(0, 10), (5, 20), (30, 40)]) == 30
    assert reduce_trace.union_length([(0, 10), (2, 3)]) == 10     # nested
    assert reduce_trace.union_length([]) == 0


def test_gaps_are_what_no_interval_covers():
    assert reduce_trace.gaps([(0, 10), (5, 20), (30, 40)], (0, 50)) \
        == [(20, 30), (40, 50)]
    assert reduce_trace.gaps([(10, 20)], (0, 20)) == [(0, 10)]
    assert reduce_trace.gaps([(-5, 100)], (0, 50)) == []


@pytest.mark.parametrize("text, category", [
    ("%reshape.1 = u32[2,2]{1,0:T(2,128)} reshape(u32[4]{0:T(128)} %args_0_.1)",
     "reshape"),
    ("%slice_reduce_fusion = (u32[2]{0:T(128)S(1)}, u32[2]{0:T(128)S(1)}) "
     "fusion(u32[2,2]{1,0:T(2,128)} %key.1), kind=kLoop, "
     "calls=%fused_computation.35", "fusion:Loop"),
    ("%fusion.7 = bf16[128,768]{1,0:T(8,128)(2,1)} fusion(bf16[128,768] %p), "
     "kind=kOutput, calls=%fused_computation.7", "fusion:Output"),
    ("%convolution_add_fusion.3 = bf16[8,8]{1,0} fusion(bf16[8,8] %p), "
     "kind=kOutput, calls=%fc", "fusion:convolution"),
    ("%copy-start.7 = (f32[4]{0:T(128)S(1)}, f32[4]{0:T(128)}, u32[]{:S(2)}) "
     "copy-start(f32[4]{0:T(128)} %tr_3_.1)", "copy"),
    ("%all-reduce-done.1 = f32[64]{0} all-reduce-done((f32[64], f32[64]) "
     "%all-reduce-start.1)", "all-reduce"),
    ("%custom-call.48 = bf16[256,64,56,56]{3,2,1,0} custom-call(bf16[1] %x), "
     "custom_call_target=\"tpu_custom_call\"", "custom-call"),
    ("%while.2 = (s32[], f32[8]) while((s32[], f32[8]) %tuple), "
     "condition=%cond, body=%body", "while"),
    ("fusion.12", "fusion"), ("all-reduce-start.1", "all-reduce")])
def test_category_is_the_hlo_opcode(text, category):
    assert reduce_trace.category_of(text) == category


def test_instruction_name_is_what_stands_before_the_equals_sign():
    assert reduce_trace.instruction_of(
        "%fusion.7 = bf16[8]{0} fusion(bf16[8] %p), kind=kLoop") == "%fusion.7"
    assert reduce_trace.instruction_of("fusion.12") == "fusion.12"


def test_two_overlapping_ops_and_one_gap():
    # window 0..100 us from the host's spans; on device 0 a fusion 10..40 us
    # and a convolution 30..60 us overlap by 10 us, then nothing until a
    # copy 80..100 us: busy 70 us, one 20 us gap under "wait_loss" and one
    # 10 us gap under "dispatch"
    us = 1000
    devices = {
        0: [("fusion.1", "fusion", 10 * us, 40 * us),
            ("convolution.2", "convolution", 30 * us, 60 * us),
            ("copy.3", "copy", 80 * us, 100 * us)],
        1: [("fusion.1", "fusion", 0, 100 * us)],
    }
    annotations = [("dispatch", 0, 15 * us), ("wait_loss", 15 * us, 100 * us)]
    s = reduce_trace.summarize(devices, annotations)
    assert s["window_s"] == pytest.approx(100e-6)
    assert s["busy_s_first_device"] == pytest.approx(70e-6)
    assert s["busy_s_per_device"] == {0: pytest.approx(70e-6),
                                      1: pytest.approx(100e-6)}
    assert s["busy_s"] == pytest.approx(85e-6)      # mean over the chips
    assert s["categories"] == {"fusion": pytest.approx(30e-6),
                               "convolution": pytest.approx(30e-6),
                               "copy": pytest.approx(20e-6)}
    assert s["idle_gaps"] == [["wait_loss", pytest.approx(20e-6)],
                              ["dispatch", pytest.approx(10e-6)],
                              ["wait_loss:longest", pytest.approx(20e-6)],
                              ["dispatch:longest", pytest.approx(10e-6)]]
    values = {"steps_traced": 2}
    assert reduce_trace.idle_share(s, {}, values) == pytest.approx(30.0)
    assert reduce_trace.busy_ms_per_step(s, {}, values) \
        == pytest.approx(0.035)
    spec = {"categories": ["copy", "conv"]}
    assert reduce_trace.category_ms_per_step(s, spec, values) \
        == pytest.approx(0.025)
    assert reduce_trace.category_ms_per_step(
        s, {"categories": ["all-reduce"]}, values) == 0.0


def test_ops_outside_the_window_are_clipped():
    us = 1000
    devices = {0: [("fusion.1", "fusion", -50 * us, 50 * us),
                   ("copy.2", "copy", 90 * us, 150 * us)]}
    s = reduce_trace.summarize(devices, [("dispatch", 0, 100 * us)])
    assert s["busy_s_first_device"] == pytest.approx(60e-6)
    assert s["idle_gaps"][0] == ["dispatch", pytest.approx(40e-6)]


def test_a_trace_with_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        reduce_trace.summarize({}, [("dispatch", 0, 100)])
    with pytest.raises(ValueError):
        reduce_trace.summarize({0: []}, [("dispatch", 0, 100)])


def test_a_container_is_not_busy_time_and_keeps_only_its_own_time():
    # a while op 0..100 us holds two fusions (10..30, 40..90), as the steps of a
    # multi-step program do: busy is the fusions' 70, the while's own time
    # is the 30 that no child covers, and the gaps inside it are idle
    us = 1000
    devices = {0: [("while.1", "while", 0, 100 * us),
                   ("fusion.2", "fusion", 10 * us, 30 * us),
                   ("fusion.3", "fusion", 40 * us, 90 * us)]}
    s = reduce_trace.summarize(devices, [("wait_loss", 0, 100 * us)])
    assert s["busy_s_first_device"] == pytest.approx(70e-6)
    assert s["categories"] == {"while": pytest.approx(30e-6),
                               "fusion": pytest.approx(70e-6)}
    assert s["idle_gaps"][0] == ["wait_loss", pytest.approx(30e-6)]
    assert [g[1] for g in s["idle_gaps"][1:]] == [pytest.approx(10e-6)] * 3
    assert s["n_idle_gaps"] == 3


def test_the_recorded_v5e_trace_reduces_to_known_numbers():
    # recorded on one TPU v5 lite by runners.train.run in a traced run of
    # this directory's toy (data/tiny_mlp.json under data/tiny_loop.json,
    # bfloat16): eight step() calls, each with a loss read
    s = reduce_trace.reduce_file(
        os.path.join(HERE, "data", "tiny_loop_v5e.xplane.pb"))
    assert s["window_s"] == pytest.approx(0.052617468, rel=1e-9)
    assert s["busy_s"] == s["busy_s_first_device"] \
        == pytest.approx(5.9724e-05, rel=1e-6)
    assert s["n_ops_first_device"] == 392 and s["n_idle_gaps"] == 332
    assert s["categories"] == {
        "reshape": pytest.approx(1.038e-05, rel=1e-6),
        "fusion:Loop": pytest.approx(2.7437e-05, rel=1e-6),
        "copy": pytest.approx(7.618e-06, rel=1e-6),
        "convert": pytest.approx(2.6e-08, rel=1e-6),
        "iota": pytest.approx(2.8e-08, rel=1e-6),
        "fusion:Output": pytest.approx(1.1238e-05, rel=1e-6),
        "custom-call": pytest.approx(1.06e-07, rel=1e-6),
        "fusion:Custom": pytest.approx(2.891e-06, rel=1e-6)}
    assert sum(s["categories"].values()) == pytest.approx(s["busy_s"])
    # a toy leaves the chip idle; the host is under `dispatch` nearly all
    # of that time, and the longest gaps are the turn-arounds between steps
    assert s["idle_gaps"][0] == ["dispatch", pytest.approx(0.051763126)]
    assert s["idle_gaps"][1] == ["wait_loss", pytest.approx(0.000794618)]
    assert s["idle_gaps"][2] == ["dispatch:longest",
                                 pytest.approx(0.002557352)]
    # eight runs of the step, and the small programs step() launches beside
    # it (nine for every step: four dtype conversions, a key split, ...)
    assert s["programs"][0] == ["jit_step", pytest.approx(8.2339e-05)]
    assert s["program_runs"] == 80
    assert reduce_trace.program_runs_per_step(s, {}, {"steps_traced": 8}) == 10
    assert dict(s["overlapped"]) == {"copy": pytest.approx(7.4062e-05)}
    values = {"steps_traced": 8}
    assert reduce_trace.idle_share(s, {}, values) \
        == pytest.approx(99.8865, abs=1e-3)
    assert reduce_trace.busy_ms_per_step(s, {}, values) \
        == pytest.approx(0.0074655)
    assert reduce_trace.category_ms_per_step(
        s, {"categories": ["custom-call"]}, values) \
        == pytest.approx(1.325e-05)
