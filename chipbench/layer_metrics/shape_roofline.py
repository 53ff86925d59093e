"""A layer's share of its roofline from the shapes alone: per cent of the
measured device time of the file's ``scopes`` that the layer's **least** work
needs at the chip's peaks, the larger of operations over
``bf16_flops_per_s`` and bytes over ``hbm_bytes_per_s``.

The metric file names the functions of its ``model`` module that count that
work: ``operations`` and ``bytes``, each called as ``f(args, batch, seq)``
with the live net's configuration (the model module's ``LIVE``) and one
chip's share of the newest batch (its ``BATCH``). For ``mla_attention_roofline``
they are ``attention_operations`` and ``attention_bytes``: the causal
triangle of scores and weighted values for the heads held, backward twice the
forward, the recomputed forward not counted; q, k, v and the output once each
way. The count is the same whatever implements the layer, so a kernel that
pads a head or computes the masked half of the scores reads lower. The time
is ``device_scopes.py``'s for those scopes.

A run without a live net or a batch, or a program without the scopes (an
older commit), gives ``None``.
"""
from __future__ import annotations

import importlib


def roofline(operations, moved, ms_per_step, peaks):
    """Per cent of ``ms_per_step`` that ``operations`` and ``moved`` bytes
    need at the peaks."""
    least_s = max(operations / peaks["bf16_flops_per_s"],
                  moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / ms_per_step


def read(summary, spec, values):
    from chipbench.layer_metrics import device_scopes
    model = importlib.import_module(spec["model"])
    if not getattr(model, "LIVE", None) or not getattr(model, "BATCH", None):
        return None
    ms = device_scopes.metric(summary, dict(spec, quantity="ms_per_step"),
                              values)
    if not ms:
        return None
    args = model.LIVE[0][0].chipbench_args
    batch, seq = model.BATCH[0]
    # the batch is the host's: each chip takes its share of the samples
    batch = batch / values["chips"]
    return roofline(getattr(model, spec["operations"])(args, batch, seq),
                    getattr(model, spec["bytes"])(args, batch, seq), ms,
                    values)
