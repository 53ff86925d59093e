"""AMP — automatic mixed precision (ref: python/mxnet/contrib/amp/amp.py).

The reference monkey-patches the op namespaces to insert ``amp_cast`` pairs
from fp16 allow/deny lists and wraps the Trainer with a dynamic loss scaler.
TPU-native translation (SURVEY §2.6 #50):

- the natural target dtype is **bfloat16** (MXU-native, fp32 dynamic range
  ⇒ no loss scaling needed);
- casting happens at the compiled-step boundary: ``amp.init()`` sets the
  process-wide compute dtype that ``parallel.ShardedTrainer`` (and bench)
  pick up — one cast into the program, fp32 master weights, fp32 loss math,
  which is exactly where the reference's graph-pass lands after XLA fusion;
- fp16 parity keeps the reference's ``DynamicLossScaler`` (skip-step on
  overflow, ref: amp.py DynamicLossScaler) for scripts that ask for fp16.
"""
from __future__ import annotations

import numpy as np

from ...base import MXNetError

__all__ = ["init", "reset", "init_trainer", "scale_loss", "unscale",
           "convert_hybrid_block", "DynamicLossScaler", "amp_dtype"]

_state = {"initialized": False, "dtype": None, "lists": None}

# Ops that stay fp32 regardless of the blanket compute dtype when the
# per-op policy is active — the reference's FP32_FUNCS core (reductions,
# losses, norms, exp/log families; ref: amp/lists/symbol_fp16.py
# FP32_FUNCS). The policy only engages when init() receives op lists;
# the default TPU path remains the single cast at the step boundary.
_DEFAULT_FP32_OPS = (
    "softmax", "log_softmax", "SoftmaxOutput", "SoftmaxActivation",
    "norm", "mean", "sum", "exp", "log", "log2", "log10", "expm1",
    "log1p", "erf", "erfinv", "logsumexp", "smooth_l1", "MakeLoss",
    "LinearRegressionOutput", "LogisticRegressionOutput",
    "MAERegressionOutput",
)


class _OpCastPolicy:
    """Dispatch-level realization of the reference's amp_cast graph pass
    (ref: python/mxnet/contrib/amp/amp.py _get_fun_to_wrap +
    lists/symbol_fp16.py): inputs of listed ops are recast on the way in.
    Works on eager arrays and tracers (so it holds inside jit programs)."""

    def __init__(self, target_dtype, target_precision_ops,
                 conditional_fp32_ops, fp32_ops):
        import jax.numpy as jnp
        self._target = jnp.dtype(target_dtype)
        self._target_ops = frozenset(target_precision_ops or ())
        self._fp32_ops = frozenset(fp32_ops or ()) | \
            frozenset(_DEFAULT_FP32_OPS)
        # [(op_name, param_name, [values])] → {op: [(param, {values})]}
        cond = {}
        for op_name, param, values in (conditional_fp32_ops or ()):
            vals = values if isinstance(values, (list, tuple, set)) \
                else [values]
            cond.setdefault(op_name, []).append((param, set(vals)))
        self._conditional = cond

    def _cast_all(self, datas, dtype):
        import jax.numpy as jnp
        return [d.astype(dtype)
                if hasattr(d, "dtype") and jnp.issubdtype(d.dtype,
                                                          jnp.floating)
                and d.dtype != dtype else d
                for d in datas]

    def __call__(self, op_name, datas, params):
        import jax.numpy as jnp
        if op_name in self._fp32_ops:
            return self._cast_all(datas, jnp.float32)
        for param, vals in self._conditional.get(op_name, ()):
            if str(params.get(param)) in vals or params.get(param) in vals:
                return self._cast_all(datas, jnp.float32)
        if op_name in self._target_ops:
            return self._cast_all(datas, self._target)
        return datas


def init(target_dtype="bfloat16", target_precision_ops=None,
         conditional_fp32_ops=None, fp32_ops=None):
    """ref: amp.init — enable mixed precision process-wide.

    Without op lists, AMP is one cast at the compiled-step boundary (the
    idiomatic TPU form — XLA keeps fp32 accumulation where it matters).
    With any of ``target_precision_ops`` / ``conditional_fp32_ops`` /
    ``fp32_ops`` given, a per-op cast policy engages at dispatch: listed
    ops force their floating inputs to the listed precision, mirroring
    the reference's allow/deny-list graph pass."""
    target_dtype = str(np.dtype(target_dtype)) if target_dtype != "bfloat16" \
        else "bfloat16"
    if target_dtype not in ("float16", "bfloat16"):
        raise MXNetError("AMP target_dtype must be float16 or bfloat16 "
                         "(bfloat16 recommended on TPU)")
    _state["initialized"] = True
    _state["dtype"] = target_dtype
    from ... import _dispatch
    if target_precision_ops or conditional_fp32_ops or fp32_ops:
        from ...ops.registry import get as get_op
        for name in list(target_precision_ops or []) + \
                [c[0] for c in (conditional_fp32_ops or [])] + \
                list(fp32_ops or []):
            get_op(name)     # unknown op names fail loudly, not silently
        policy = _OpCastPolicy(target_dtype, target_precision_ops,
                               conditional_fp32_ops, fp32_ops)
        _state["lists"] = policy
        _dispatch.set_amp_cast_hook(policy)
    else:
        # re-init without lists must drop any previously installed policy
        # (a stale hook would keep casting to the OLD target dtype)
        _state["lists"] = None
        _dispatch.set_amp_cast_hook(None)


def reset():
    """Disable AMP (test helper; the reference has no uninit)."""
    from ... import _dispatch
    _state.update(initialized=False, dtype=None, lists=None)
    _dispatch.set_amp_cast_hook(None)


def amp_dtype():
    """The active AMP compute dtype, or None (read by ShardedTrainer)."""
    return _state["dtype"] if _state["initialized"] else None


class DynamicLossScaler:
    """ref: amp.py DynamicLossScaler — grow scale on stability, halve and
    skip the step on overflow. bf16 does not need it; kept for fp16.

    The overflow signal now rides the fused guardrail flag
    (docs/guardrails.md): the fused trainers return it as a step output
    (zero extra host reads) and the eager Trainer checks gradients with
    its own fused pass on both step() paths. ``has_overflow`` has no
    in-repo callers anymore — it is kept, on the same fused chokepoint,
    for external/back-compat callers only."""

    def __init__(self, init_scale=2 ** 16, scale_factor=2.0,
                 scale_window=2000, tolerance=0.0):
        self.loss_scale = init_scale
        self._scale_factor = scale_factor
        self._scale_window = scale_window
        self._unskipped = 0

    def has_overflow(self, params):
        """One fused device-side finiteness reduction over every gradient
        of every replica (guardrails.fused.guard_stats), one host sync
        total — not a per-parameter download (each one a blocking
        device→host round trip)."""
        from ...guardrails import fused
        grads = [g._data for p in params
                 for g in (getattr(p, "_grad", None) or ()) if g is not None]
        if not grads:
            return False
        finite, _ = fused.guard_stats(grads)
        return not fused.host_fetch(finite)[0]

    def update_scale(self, overflow):
        if overflow:
            self.loss_scale = max(1.0, self.loss_scale / self._scale_factor)
            self._unskipped = 0
        else:
            self._unskipped += 1
            if self._unskipped >= self._scale_window:
                self.loss_scale *= self._scale_factor
                self._unskipped = 0


def init_trainer(trainer):
    """ref: amp.init_trainer — attach a loss scaler to a gluon Trainer."""
    if not _state["initialized"]:
        raise MXNetError("call amp.init() before amp.init_trainer()")
    trainer._amp_loss_scaler = DynamicLossScaler()
    return trainer


class _ScaledLoss:
    def __init__(self, loss, scaler):
        self._loss = loss
        self._scaler = scaler

    def __enter__(self):
        s = self._scaler.loss_scale
        if isinstance(self._loss, (list, tuple)):
            return [l * s for l in self._loss]
        return self._loss * s

    def __exit__(self, *exc):
        return False


def scale_loss(loss, trainer):
    """``with amp.scale_loss(loss, trainer) as L: L.backward()``
    (ref: amp.scale_loss). The matching unscale happens in unscale()."""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None:
        raise MXNetError("trainer was not passed through amp.init_trainer")
    # Trainer.step uses rescale_grad = _scale / batch_size, so dividing
    # the scale back out happens there (ref: Trainer._amp integration)
    trainer._scale = 1.0 / scaler.loss_scale
    return _ScaledLoss(loss, scaler)


def unscale(trainer):
    """Divide accumulated gradients by the current loss scale."""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None:
        raise MXNetError("trainer was not passed through amp.init_trainer")
    inv = 1.0 / scaler.loss_scale
    for p in trainer._params:
        if p.grad_req != "null" and p._grad is not None:
            for g in p._grad:
                g._rebind((g * inv)._data)
    trainer._scale = 1.0


def convert_hybrid_block(block, target_dtype="bfloat16", ctx=None):
    """Cast a block's parameters for low-precision inference
    (ref: amp.convert_hybrid_block)."""
    block.cast(target_dtype)
    return block
