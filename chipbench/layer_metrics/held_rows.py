"""The rows that land on the experts held here, over what a uniform router
would send them: the rows the expert layers' counters say the held experts
computed a step (``expert_load.load_of_live_net``), summed over the layers,
over ``layers x tokens x num_experts_per_tok x held / published experts``,
the tokens a step being one chip's share of the newest batch (the model
module's ``BATCH``). The grouped products take time by the row, so where the
router sends more of a step's pairs here the step is longer: 1 is a uniform
router's load.

A program without the counters (an older commit), a run without a live net
or a batch, or a net that has counted no step gives ``None``.
"""
from __future__ import annotations

import importlib


def over_uniform(load, args, tokens):
    """Rows a step on the held experts over a uniform router's."""
    rows = sum(sum(said["rows"]) / said["steps"] for said in load)
    uniform = (len(load) * tokens * args["num_experts_per_tok"]
               * args["n_routed_experts"]
               / args["published_counts"]["n_routed_experts"])
    return rows / uniform


def read(summary, spec, values):
    from chipbench.layer_metrics import expert_load
    model = importlib.import_module(spec["model"])
    load = expert_load.load_of_live_net(model)
    if load is None or not getattr(model, "BATCH", None):
        return None
    batch, seq = model.BATCH[0]
    return over_uniform(load, model.LIVE[0][0].chipbench_args,
                        batch / values["chips"] * seq)
