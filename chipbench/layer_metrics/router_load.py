"""How evenly a router spreads its pairs over **all** its experts, read from
the counters that an expert layer whose balancing bias moves carries through
the compiled step (``gluon.contrib.nn.expert_load``'s ``"router_rows"``: the
pairs the router sent to each of its experts, summed over the training steps
the layer counted): the fullest expert's pairs over the mean, in the layer
where that is largest. It is what the bias's update acts on: 1 is an even
spread, and a bias that balances brings it there.

The counts are those of every step the layers counted in the run, the
warm-up's with the window's. A program without the counters (an older
commit, or a bias that does not move), a run without a live net, or a net
that has counted no step gives ``None``.
"""
from __future__ import annotations

import importlib


def read(summary, spec, values):
    from chipbench.layer_metrics.expert_load import (load_of_live_net,
                                                     max_over_mean)
    load = load_of_live_net(importlib.import_module(spec["model"]))
    routed = [{"rows": said["router_rows"]} for said in load or ()
              if "router_rows" in said]
    return max_over_mean(routed) if routed else None
