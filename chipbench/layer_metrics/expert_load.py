"""The real load of the expert layers, read from the counters that the layers
carry through the compiled step (``gluon.contrib.nn.expert_load``: the rows
each held expert has received, summed over the training steps, and the count
of those steps), and the grouped products' share of their roofline from it.

A metric file names its ``quantity``:

- ``max_over_mean``: the fullest held expert's rows over the mean of the held
  experts' rows, in the layer where that is largest; rows a step are rows
  over steps, so the ratio is that of the sums.
- ``roofline``: per cent of the measured device time of the file's ``scopes``
  (``moe.experts``: the two grouped products and the activation between
  them, forward, recomputed and backward) that the products' **useful** work
  needs at the chip's peaks: the larger of operations over
  ``bf16_flops_per_s`` and bytes over ``hbm_bytes_per_s``. Operations and
  bytes come from the functions ``expert_product_operations`` and
  ``expert_product_bytes`` of the file's ``model`` module, for the rows really
  routed to the held experts in a step (from the counters); the time is
  ``device_scopes.py``'s for those scopes. It reads the same work whatever
  implements the products.

The counters are found through the model module's ``LIVE`` list, as
``device_scopes.py`` finds the programs. A program without the counters (an
older commit), a run without a live net, or a net that has counted no step
gives ``None``.
"""
from __future__ import annotations

import importlib


def load_of_live_net(model):
    """``[{"rows": [...], "steps": n}, ...]`` of the expert layers of the
    model module's live net, or ``None``."""
    try:
        from mxnet_tpu.gluon.contrib.nn import expert_load
    except ImportError:
        return None
    if not getattr(model, "LIVE", None):
        return None
    net = model.LIVE[0][0]
    load = expert_load()
    prefix = net.prefix
    mine = [said for layer, said in sorted(load.items())
            if layer.startswith(prefix) and said["steps"] > 0]
    return mine or None


def max_over_mean(load):
    """The fullest expert's rows over the mean, in the worst layer."""
    worst = None
    for said in load:
        total = sum(said["rows"])
        if total:
            ratio = max(said["rows"]) * len(said["rows"]) / total
            worst = ratio if worst is None else max(worst, ratio)
    return worst


def roofline(load, model, args, ms_per_step, peaks):
    """Per cent of ``ms_per_step`` that the useful work of the grouped
    products needs at the peaks, and which peak bounds it."""
    rows = sum(sum(said["rows"]) / said["steps"] for said in load)
    operations = model.expert_product_operations(rows, args)
    moved = model.expert_product_bytes(rows, args, len(load))
    least_s = max(operations / peaks["bf16_flops_per_s"],
                  moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / ms_per_step


def read(summary, spec, values):
    from chipbench.layer_metrics import device_scopes
    model = importlib.import_module(spec["model"])
    load = load_of_live_net(model)
    if load is None:
        return None
    if spec["quantity"] == "max_over_mean":
        return max_over_mean(load)
    ms = device_scopes.metric(summary, dict(spec, quantity="ms_per_step"),
                              values)
    if not ms:
        return None
    return roofline(load, model, model.LIVE[0][0].chipbench_args, ms, values)
