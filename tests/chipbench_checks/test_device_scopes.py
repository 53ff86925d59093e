"""The reader of device time by named scope
(``chipbench/layer_metrics/device_scopes.py``) and the program's half of the
join (``mxnet_tpu.observability.device_scopes``): the interval arithmetic on
hand-made tuples, the parsing on hand-made HLO text, and both on a toy
trainer of this directory's own."""
import os

import jax
import numpy as np
import pytest

from mxnet_tpu import observability
from mxnet_tpu.observability import scopes

from chipbench import layer_metrics, manifest
from chipbench.layer_metrics import device_scopes as ds

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000
CELL = "granite_4_0_h_micro.fused_bs1_seq4096"


def op(name, start, end, category="fusion"):
    return (name, category, start * US, end * US)


def traced():
    """Window 0..200 us. ``jit_multi`` runs 10..100 and 110..190; its
    ``while.1`` holds three leaves in each run; ``jit_reshape`` runs 102..104
    with one op the mapping does not know."""
    ops = []
    for base in (10, 110):
        ops += [op("%while.1", base, base + 80, "while"),
                op("%fusion.1", base, base + 30),          # ssd
                op("%fusion.2", base + 30, base + 40),     # conv
                op("%copy.3", base + 50, base + 70, "copy")]   # no scope
    ops.append(op("%reshape.1", 102, 104, "reshape"))
    programs = [("jit_multi", 10 * US, 100 * US),
                ("jit_reshape", 102 * US, 104 * US),
                ("jit_multi", 110 * US, 190 * US)]
    mapping = {"jit_multi": {"fusion.1": "mamba2.ssd",
                             "fusion.2": "mamba2.conv",
                             "reshape.1": "mlp"}}
    return ops, (0, 200 * US), programs, mapping


def test_leaf_time_goes_to_the_scope_of_its_instruction():
    got = ds.by_scope(*traced())
    # the while is a container and counts nothing; reshape.1 has a scope in
    # jit_multi only, and ran in another program
    assert got == {"mamba2.ssd": 60 * US, "mamba2.conv": 20 * US,
                   ds.UNSCOPED: (40 + 2) * US}


def test_an_op_is_clipped_to_the_window_and_outside_a_run_is_unscoped():
    ops, _, programs, mapping = traced()
    got = ds.by_scope(ops, (20 * US, 120 * US), programs, mapping)
    assert got["mamba2.ssd"] == (20 + 10) * US
    ops.append(op("%fusion.1", 192, 198))       # after the last run ended
    got = ds.by_scope(ops, (0, 200 * US), programs, mapping)
    assert got["mamba2.ssd"] == 60 * US
    assert got[ds.UNSCOPED] == (42 + 6) * US


@pytest.mark.parametrize("scopes_, quantity, want", [
    (["mamba2.ssd"], "ms_per_step", 0.060 / 8),
    (["mamba2.conv", "mamba2.gate_norm"], "ms_per_step", 0.020 / 8),
    ([], "scoped_share", 100.0 * 80 / 122),
    (["absent"], "ms_per_step", 0.0),
])
def test_quantities(scopes_, quantity, want):
    got = ds.quantities(ds.by_scope(*traced()), scopes_, 8)
    assert got[quantity] == pytest.approx(want)


def test_quantities_without_steps_or_time():
    assert "ms_per_step" not in ds.quantities({"mlp": 5}, ["mlp"], None)
    assert ds.quantities({}, [], 4)["scoped_share"] is None


def test_metric_files_name_what_the_reader_has():
    files = layer_metrics.for_cell(CELL)
    readings = ds.quantities(ds.by_scope(*traced()), [], 8)
    for name in ("ssd_ms_per_step", "mamba_rest_ms_per_step", "scoped_share"):
        spec = files[name]
        assert spec["source"] == "trace:device_scopes"
        assert spec["cells"] == [CELL]
        assert spec["quantity"] in readings
    assert files["ssd_ms_per_step"]["scopes"] == ["mamba2.ssd"]
    assert files["mamba_rest_ms_per_step"]["scopes"] == [
        "mamba2.conv", "mamba2.gate_norm"]


def test_a_program_without_device_scopes_gives_none(monkeypatch, tmp_path):
    """An older commit: the reader returns nothing and does not raise."""
    monkeypatch.delattr(observability, "device_scopes")
    assert ds.program_mapping() is None
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))   # and no trace
    assert ds.metric({"window_s": 1.0}, {"quantity": "scoped_share"},
                     {"steps_traced": 8}) is None


HLO = "\n".join(line for line in (
    ('HloModule jit_multi, is_scheduled=true, entry_computation_la'
     'yout={()->f32[]}'),
    '',
    '%fused_computation.7 (param_0.1: bf16[8,16]) -> bf16[8,16] {',
    '  %param_0.1 = bf16[8,16]{1,0} parameter(0)',
    ('  %exp.3 = bf16[8,16]{1,0} exponential(%param_0.1), metadata'
     '={op_name="jit(multi)/while/body/closed_call/transpose(jvp(m'
     'xnet_tpu.mamba2.ssd))/exp"}'),
    ('  ROOT %multiply.9 = bf16[8,16]{1,0} multiply(%exp.3, %exp.3'
     '), metadata={op_name="jit(multi)/while/body/closed_call/chec'
     'kpoint/rematted_computation/mxnet_tpu.mamba2.conv/mul" stack'
     '_frame_id=4}'),
    '}',
    '',
    ('%fused_computation.8.clone (param_0.2: bf16[8,16]) -> (bf16['
     '8,16], bf16[8,16]) {'),
    '  %param_0.2 = bf16[8,16]{1,0} parameter(0)',
    ('  %negate.1 = bf16[8,16]{1,0} negate(%param_0.2), metadata={'
     'op_name="jit(multi)/mxnet_tpu.mlp/neg"}'),
    ('  ROOT %tuple.4 = (bf16[8,16]{1,0}, bf16[8,16]{1,0}) tuple(%'
     'negate.1, %param_0.2)'),
    '}',
    '',
    'ENTRY %main.44 (Arg_0.1: bf16[8,16]) -> bf16[8,16] {',
    '  %Arg_0.1 = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0)',
    ('  %fusion.12 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%Arg_0.1'
     '), kind=kLoop, calls=%fused_computation.7, metadata={op_name'
     '="jit(multi)/while/body/closed_call/transpose(jvp(mxnet_tpu.'
     'mamba2.ssd))/exp"}'),
    ('  %fusion.13 = (bf16[8,16]{1,0}, bf16[8,16]{1,0}) fusion(%fu'
     'sion.12), kind=kLoop, calls=%fused_computation.8.clone, meta'
     'data={op_name="jit(multi)/mxnet_tpu.optimizer/mxnet_tpu.mlp/'
     'neg"}'),
    '  %copy.2 = bf16[8,16]{1,0} copy(%fusion.12)',
    ('  ROOT %custom-call.5 = bf16[8,16]{1,0} custom-call(%copy.2)'
     ', custom_call_target="tpu_custom_call", metadata={op_name="j'
     'it(multi)/mxnet_tpu.attention/jit(flash)/pallas_call"}'),
    '}',
    '',
))


def test_scopes_are_read_from_the_op_name_of_the_optimized_hlo():
    record = scopes.scopes_of_program(HLO)
    assert record["module"] == "jit_multi"
    got = record["scopes"]
    # a fusion takes its root's scope; one whose root has none, its own
    # innermost; an instruction without a scope is not listed
    assert got["fusion.12"] == "mamba2.conv"
    assert got["fusion.13"] == "mlp"
    assert got["custom-call.5"] == "attention"
    assert got["exp.3"] == "mamba2.ssd" and got["negate.1"] == "mlp"
    assert "copy.2" not in got and "tuple.4" not in got


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp(mxnet_tpu.mlp)/dot_general", "mlp"),
    ("jit(step)/transpose(jvp(mxnet_tpu.mamba2.ssd))/bclgn,bcsgn->bcgls/"
     "dot_general", "mamba2.ssd"),
    ("jit(step)/mxnet_tpu.optimizer/mxnet_tpu.loss/mul", "loss"),
    ("jit(step)/jvp()/convert_element_type", None),
    ("", None), (None, None),
])
def test_the_innermost_scope_is_the_last_in_the_op_name(op_name, want):
    assert scopes.scope_of(op_name) == want


def test_the_join_on_a_toy_trainer(monkeypatch):
    """``device_scopes()`` lists the programs a live trainer has run, by
    the name the trace gives their module; the reader's mapping is keyed by
    that module."""
    monkeypatch.syspath_prepend(HERE)
    import tiny_model
    from mxnet_tpu import parallel
    config = manifest.load_json(os.path.join(HERE, "data", "tiny_mlp.json"))
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    net, trainer = tiny_model.build(config["args"], mesh, 3)
    x, y = tiny_model.make_batch(config["args"], {}, 8,
                                 np.random.default_rng(3))
    assert trainer.program_texts() == {}        # nothing has run yet
    trainer.run_steps(x, y, num_steps=2)
    trainer.step(x, y)
    mine = {name: record
            for name, record in observability.device_scopes().items()
            if record["module"] in ("jit_multi", "jit_step")}
    assert {name.split(":")[-1] for name in mine} >= {"step", "run_steps(2)"}
    record = next(r for n, r in mine.items() if n.endswith("run_steps(2)"))
    assert record["module"] == "jit_multi"
    assert {"loss", "optimizer"} <= set(record["scopes"].values())
    assert trainer.num_update == 3              # asking took no step
    del net, trainer
