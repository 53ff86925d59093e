"""A configuration may bring its own comparison (``compare`` beside
``reference``), and the bound stays the runner's: rehearsed on the CPU with
a routed toy of this directory's own (``routed_model.py``,
``data/routed_*.json``), in bfloat16, with scores made close on purpose."""
import os

import jax
import numpy as np
import pytest

from chipbench import manifest, run as bench_run
from chipbench.runners import train
from test_train_runner import fake_device_plane

HERE = os.path.dirname(os.path.abspath(__file__))
PLAIN_KEYS = {"samples", "max_abs_error", "max_abs_reference", "share",
              "tolerance", "ok"}
SEEDS = [5, 2 ** 31 + 8]


def load(name):
    return manifest.load_json(os.path.join(HERE, "data", name + ".json"))


@pytest.fixture
def routed(monkeypatch):
    monkeypatch.syspath_prepend(HERE)       # "routed_model.build" resolves
    import routed_model
    return routed_model


@pytest.fixture
def device_plane(monkeypatch):
    fake_device_plane(monkeypatch)      # a CPU trace has no device plane


def traced(config, tmp_path, seed, mix="routed_fused"):
    return train.run(load(config) if isinstance(config, str) else config,
                     load(mix), jax.devices()[:1], seed, 0.2,
                     str(tmp_path / "trace"))


@pytest.fixture
def prepared(routed):
    """What the runner has in hand where it calls the check: the toy built,
    what its reference keeps, the trainer prepared, one batch."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    config = load("routed_toy")
    args = config["args"]
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    net, trainer = routed.build(args, mesh, 5)
    x, y = routed.make_batch(args, {}, 128, np.random.default_rng(5))
    net(mx.nd.array(x[:1]))
    kept = train.plain_reference(config, net, x)
    trainer.prepare(x[:1])
    return config, kept, trainer, args, x, y


def altered(monkeypatch, routed, change):
    """``routed_model.compare`` with its dict changed on the way out."""
    real = routed.compare

    def compare(*args):
        said = real(*args)
        change(said)
        return said
    monkeypatch.setattr(routed, "compare", compare)


# --- why the seam is there ------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_a_router_in_bfloat16_fails_the_plain_check(routed, device_plane,
                                                    tmp_path, seed):
    # the twin rows of the router are one row in bfloat16: one token in
    # fourteen goes to another expert than in float32, and its logits move
    # by tenths of the largest
    facts = traced("routed_toy_plain", tmp_path, seed)
    forward = facts["checks"]["forward"]
    assert set(forward) == PLAIN_KEYS
    assert forward["share"] > 3 * train.FORWARD_TOLERANCE
    assert forward["ok"] is False and facts["correct"] is False
    assert facts["checks"]["losses_fell"] and facts["checks"]["losses_finite"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_same_toy_with_its_own_compare_passes(routed, device_plane,
                                                  tmp_path, seed):
    facts = traced("routed_toy", tmp_path, seed)
    forward = facts["checks"]["forward"]
    assert set(forward) == PLAIN_KEYS | {"compared", "conditions"}
    assert forward["samples"] == 128 and forward["compared"] == 128 * 5
    assert forward["tolerance"] == train.FORWARD_TOLERANCE == 0.03
    assert forward["share"] == forward["max_abs_error"] \
        / forward["max_abs_reference"]
    assert 1e-3 < forward["share"] < train.FORWARD_TOLERANCE / 2   # bfloat16
    conditions = forward["conditions"]
    assert set(conditions) == {"routes_differ_outside_margin",
                               "routes_inside_margin"}
    for condition in conditions.values():
        assert set(condition) == set(train.CONDITION_KEYS)
        assert condition["ok"] is True and condition["why"]
    assert conditions["routes_differ_outside_margin"]["value"] == 0.0
    assert 0.05 < conditions["routes_inside_margin"]["value"] \
        < routed.INSIDE_LIMIT
    assert forward["ok"] is True and facts["correct"] is True
    # the line `chipbench: checks` prints the conditions with the rest
    bench_run.note("checks", **facts["checks"])


def test_the_routes_and_the_logits_come_from_one_run(prepared, routed):
    config, kept, trainer, args, x, y = prepared
    outputs = train.system_outputs(trainer, args, x, y)
    assert [o.shape for o in outputs] == [(128, 5), (128, 2)]
    assert outputs[0].dtype.name == "bfloat16"      # each in its own dtype
    assert outputs[1].dtype == np.int32
    params, samples = kept
    own = routed.choose(routed.scores_of(params, samples))
    differ = (np.sort(outputs[1], -1) != np.sort(own, -1)).any(-1)
    assert 0.02 < differ.mean() < 0.2         # the reason for the seam
    # the first entry, cut and in float32, is what the plain check reads
    logits = train.system_logits(trainer, args, x, y, 7)
    assert logits.dtype == np.float32
    assert (logits == outputs[0][:7].astype(np.float32)).all()
    cut = train.system_outputs(trainer, args, x, y, rows=7)
    assert [o.shape for o in cut] == [(7, 5), (7, 2)]


# --- the bound stays the runner's -----------------------------------------

def over_the_tolerance(said):
    said["max_abs_error"] = 0.031 * said["max_abs_reference"]


def claims_its_own_verdict(said):
    over_the_tolerance(said)
    said.update(ok=True, share=0.0, tolerance=1.0)


def fails_a_condition(said):
    said["conditions"]["routes_inside_margin"]["ok"] = False


def not_finite(said):
    said["max_abs_reference"] = float("inf")


@pytest.mark.parametrize("change", [over_the_tolerance,
                                    claims_its_own_verdict,
                                    fails_a_condition, not_finite])
def test_a_compare_cannot_pass_what_the_runner_fails(prepared, routed,
                                                     monkeypatch, change):
    assert train.configured_check(*prepared)["ok"] is True
    altered(monkeypatch, routed, change)
    check = train.configured_check(*prepared)
    assert check["ok"] is False
    assert check["tolerance"] == 0.03
    assert check["share"] == check["max_abs_error"] \
        / check["max_abs_reference"]
    assert set(check) == PLAIN_KEYS | {"compared", "conditions"}


def test_an_error_over_the_tolerance_is_not_correct(routed, device_plane,
                                                    tmp_path, monkeypatch):
    altered(monkeypatch, routed, over_the_tolerance)
    facts = traced("routed_toy", tmp_path, 5)
    assert facts["checks"]["forward"]["ok"] is False
    assert facts["checks"]["losses_fell"] and facts["correct"] is False


def fewer_logits(said):
    said["compared"] -= 5


def fewer_samples(said):
    said["samples"] -= 1
    said["compared"] -= 5


def no_error(said):
    del said["max_abs_error"]


def no_conditions(said):
    del said["conditions"]


def a_condition_without_its_reason(said):
    del said["conditions"]["routes_inside_margin"]["why"]


@pytest.mark.parametrize("change", [fewer_logits, fewer_samples, no_error,
                                    no_conditions,
                                    a_condition_without_its_reason])
def test_a_dict_that_is_short_is_an_error_of_the_run(prepared, routed,
                                                     monkeypatch, change):
    altered(monkeypatch, routed, change)
    with pytest.raises(ValueError, match="routed_model.compare"):
        train.configured_check(*prepared)


def test_an_error_of_the_run_prints_no_result_and_exits_1(
        routed, device_plane, tmp_path, monkeypatch, capsys):
    # the command's boundary: any error of the run is exit code 1, no line
    altered(monkeypatch, routed, fewer_logits)
    monkeypatch.setattr(bench_run, "run_cell",
                        lambda args: traced("routed_toy", tmp_path, 5))
    assert bench_run.main(["--workload", "x", "--seed", "5", "--seconds",
                           "1", "--trace", "1"]) == 1
    out = capsys.readouterr().out
    assert "chipbench: error ValueError" in out and '"correct"' not in out
    assert "all 640 of the first 128 are due" in out


# --- nothing changes for a configuration that names none ------------------

@pytest.mark.parametrize("mix", ["tiny_fused", "tiny_loop"])
def test_without_compare_the_check_is_the_parents(routed, device_plane,
                                                  tmp_path, monkeypatch, mix):
    seen = {}
    real = train.forward_check

    def keep(system, reference):
        seen.update(system=system, reference=reference)
        return real(system, reference)
    monkeypatch.setattr(train, "forward_check", keep)
    monkeypatch.setattr(train, "configured_check", None)    # never called
    facts = traced("tiny_mlp", tmp_path, 2 ** 31 + 11, mix)
    forward = facts["checks"]["forward"]
    assert set(forward) == PLAIN_KEYS
    # the parent's arithmetic, written out, on the arrays the check was given
    system, reference = seen["system"], seen["reference"]
    assert system.dtype == reference.dtype == np.float32
    assert system.shape == reference.shape == (4, 4)
    scale = float(np.max(np.abs(reference)))
    error = float(np.max(np.abs(system - reference)))
    assert forward == {
        "samples": 4, "max_abs_error": error, "max_abs_reference": scale,
        "share": error / scale, "tolerance": 0.03,
        "ok": bool(np.isfinite(system).all() and error <= 0.03 * scale)}
    assert forward["ok"] and forward["share"] < 1e-5        # float32 toy


def test_the_toys_name_what_resolves(routed):
    for name, compares in (("routed_toy", True), ("routed_toy_plain", False),
                           ("tiny_mlp", False)):
        config = load(name)
        assert ("compare" in config) is compares
        for key in ["build", "make_batch", "flops_per_sample", "reference"] \
                + ["compare"] * compares:
            assert callable(manifest.resolve(config[key])), (name, key)
