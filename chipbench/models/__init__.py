"""Model builders, one module for each configuration: ``build`` (net and
trainer as a user builds them), ``make_batch`` (seeded host batch),
``flops_per_sample`` (from the shapes) and ``reference_logits`` (the plain
float32 forward the system is held to)."""
from __future__ import annotations

import contextlib


def reference_device():
    """Context in which a plain reference computes: the host CPU where JAX
    has that backend beside the chip, else the default device (the
    references ask for ``Precision.HIGHEST`` on every product, which keeps
    float32 there too)."""
    import jax
    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()
