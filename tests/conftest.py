"""Test configuration: force an 8-device virtual CPU mesh so multi-chip
sharding paths are exercised without TPU hardware (the reference's analog:
`tools/launch.py --launcher local` fakes a cluster with local processes,
SURVEY §4 'Distributed/nightly' row).

The variables are set here, at the top of conftest.py: pytest imports
this file before any test module, so before anything initializes a JAX
backend — which is when XLA reads them — and xdist workers inherit them
from the controller. The suite never runs on an accelerator; what runs
there is ``chip_smoke.py``.
"""
import os
import sys

import numpy as np
import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
# entry points place a persistent compile cache (runtime.enable_compile_cache);
# the suite and the scripts it spawns compile afresh, as they always have
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if not f.startswith("--xla_force_host_platform_device_count=")]
os.environ["XLA_FLAGS"] = " ".join(
    _flags + ["--xla_force_host_platform_device_count=8"])
if "jax" in sys.modules:
    # a plugin imported jax before us: its config was read from the old
    # environment (still backend-free — nothing has listed devices yet)
    sys.modules["jax"].config.update("jax_platforms", "cpu")
    sys.modules["jax"].config.update("jax_enable_compilation_cache", False)


@pytest.fixture(autouse=True)
def _seed_rng():
    """ref: tests/python/unittest/common.py @with_seed — reproducible RNG
    per test."""
    np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    yield
