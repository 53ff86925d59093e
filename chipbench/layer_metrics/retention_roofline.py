"""The retention operator's share of its roofline: per cent of the measured
device time of the file's ``scopes`` (``retention.scan``: the operator,
forward, recomputed and backward) that the operator's **least** work needs at
the chip's peaks: the larger of operations over ``bf16_flops_per_s`` and
bytes over ``hbm_bytes_per_s``.

Operations and bytes come from ``retention_operations`` and
``retention_bytes`` of the file's ``model`` module, from the shapes alone: the
state read once a token a query head and added to once a token a key/value
head at the symmetric second power's size, the causal triangle inside a
chunk, backward twice the forward, the recomputed forward not counted (as
``moe_experts_roofline``); q, k, v, y and the boundary states once each way.
It reads the same work whatever implements the operator, so a program that
expands the second power in full or computes a chunk's scores whole reads
lower, and a fused kernel that keeps the state in fast memory higher. The
time is ``device_scopes.py``'s for those scopes.

The sizes are the live net's (the model module's ``LIVE``) and the newest
batch's (its ``BATCH``). A run without them, or a program without the scope
(an older commit), gives ``None``.
"""
from __future__ import annotations

import importlib


def roofline(model, args, batch, seq, ms_per_step, peaks):
    """Per cent of ``ms_per_step`` that the operator's least work needs at
    the peaks."""
    least_s = max(
        model.retention_operations(args, batch, seq)
        / peaks["bf16_flops_per_s"],
        model.retention_bytes(args, batch, seq) / peaks["hbm_bytes_per_s"])
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
    batch, seq = model.BATCH[0]
    # the batch is the host's: each chip takes its share of the samples
    return roofline(model, model.LIVE[0][0].chipbench_args,
                    batch / values["chips"], seq, ms, values)
