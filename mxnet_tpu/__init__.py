"""mxnet_tpu — a TPU-native deep-learning framework with the capability
surface of Apache MXNet 1.x (reference: yanghaojin/incubator-mxnet).

Built from scratch on JAX/XLA (+Pallas for custom kernels): XLA replaces the
reference's ThreadedEngine/mshadow/cuDNN stack, ``hybridize()`` lowers Gluon
blocks to jitted XLA computations (the reference's CachedOp), and the KVStore
facade maps onto ``jax.lax.psum`` over a device mesh. See SURVEY.md for the
full reference analysis and design-mapping table.

Usage mirrors the reference::

    import mxnet_tpu as mx
    from mxnet_tpu import nd, autograd, gluon

    x = nd.ones((2, 3), ctx=mx.tpu())
    with autograd.record():
        y = (x * 2).sum()
    y.backward()
"""
from __future__ import annotations

__version__ = "0.1.0"

# Set-up stage `import` (docs/observability.md): from here to the last line
# of this file. The observability package is stdlib-only; JAX is imported
# inside the stage, further down.
from .observability import instrument as _instrument

_import_stage = _instrument.setup_stage("import")
_import_stage.__enter__()

# Multi-host: when launched by tools/launch.py (MXTPU_* env protocol), the
# coordination service must be joined BEFORE any jax backend touch — do it
# at package import, the earliest point we control (the kvstore would be
# too late: importing this package already initializes devices).
import os as _os

if _os.environ.get("MXTPU_COORD_ADDR"):
    import jax as _jax
    try:
        _jax.distributed.initialize(
            coordinator_address=_os.environ["MXTPU_COORD_ADDR"],
            num_processes=int(_os.environ["MXTPU_NUM_PROC"]),
            process_id=int(_os.environ["MXTPU_PROC_ID"]))
    except RuntimeError:
        pass          # already joined (re-import / interactive)

# fp32 means fp32: JAX's DEFAULT matmul precision lowers fp32 matmul
# inputs to single-pass bf16 multiplies on TPU (~1e-2 relative error —
# measured FAILing the CPU-oracle parity sweep, benchmarks/hw_parity.py),
# while the reference's fp32 GEMMs are true fp32 (cuBLAS). HIGHEST
# restores fp32 accumulation for fp32 inputs and does not touch the bf16
# AMP fast paths (their operands are already bf16). Override with
# MXNET_MATMUL_PRECISION=default|high|highest.
import jax as _jax_cfg

_prec = _os.environ.get("MXNET_MATMUL_PRECISION") or "highest"
if _prec not in ("default", "high", "highest"):
    raise ImportError(
        f"MXNET_MATMUL_PRECISION={_prec!r} is invalid: expected "
        f"'default', 'high' or 'highest'")
_jax_cfg.config.update("jax_default_matmul_precision", _prec)

from .base import MXNetError
from .context import (Context, cpu, cpu_pinned, cpu_shared, current_context,
                      gpu, gpu_memory_info, num_gpus, num_tpus, tpu)
from . import engine
from . import library
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from . import initializer
from . import initializer as init
from . import optimizer
from . import lr_scheduler
from . import metric
from . import metric_det
# detection mAP lives beside the classification metrics (the reference
# ecosystem ships it in gluoncv.utils.metrics; one registry here)
metric.VOCMApMetric = metric_det.VOCMApMetric
metric.VOC07MApMetric = metric_det.VOC07MApMetric
from . import kvstore
from . import kvstore as kv
from . import gluon
from . import parallel
from . import recordio
from . import io
from . import image
from . import symbol
from . import symbol as sym
from . import model
from . import module
from . import module as mod
from . import monitor
from . import monitor as mon
from . import callback
from . import profiler
from . import contrib
from . import numpy as np
from . import numpy_extension as npx
from . import visualization
from . import visualization as viz
from . import test_utils
from . import operator
from . import runtime
from . import diagnostics
from . import observability    # stdlib-only telemetry substrate
from . import guardrails       # import-light root; fused loads lazily
from . import resilience
from . import serving          # lazy package: submodules load on first use
from . import testing
from . import util
from . import rnn
from . import attribute
from .attribute import AttrScope
from . import name

_import_stage.__exit__(None, None, None)
del _import_stage, _instrument
