"""The TPU fast path: ONE jitted, GSPMD-sharded train step.

The reference's training step is five engine-queued phases — forward,
backward, kvstore push (gradient reduce), pull, fused optimizer update
(SURVEY §3.2/§3.3: CachedOp::Forward, Imperative::Backward,
KVStoreDist::PushImpl via src/kvstore/comm.h CommDevice reduce,
src/operator/optimizer_op.cc fused updates). Overlap between them emerges
from the ThreadedEngine's var-dependency scheduling.

On TPU the idiomatic design compiles the WHOLE region into a single XLA
program over a device mesh:

- the batch is sharded on the ``data`` mesh axis; the loss is a global mean,
  so XLA *derives* the gradient all-reduce (psum over ICI) from sharding
  propagation — no explicit collective calls, and the latency-hiding
  scheduler overlaps it with backward compute (subsuming the reference's
  P3 priority scheduling, src/kvstore/p3store_dist.h);
- parameters can be tensor-parallel sharded by regex rules (PartitionSpec on
  the ``model`` axis) — a capability the reference only approximates with
  hand ``ctx_group`` placement (example/model-parallel/);
- optimizer state lives sharded exactly like its parameter; the update runs
  in the same program with donated buffers (true in-place, like the
  reference's mutating ``sgd_mom_update``);
- learning rate and step count enter as *traced scalars* so LR schedules
  never retrace the program.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import _rng, autograd
from .. import ndarray as nd
from ..base import MXNetError
from ..gluon.block import functional_apply  # noqa: F401  (re-export: the
#   primitive moved to gluon.block so serving/cache.py can share it
#   without importing the parallel package; trainers keep this name)
from ..guardrails import fused as _guard
from ..guardrails.monitor import AnomalyMonitor, GuardConfig
from ..guardrails.trainer_mixin import GuardedTrainerMixin
from ..observability import instrument as _obs
from ..observability import scopes as _scopes
from ..ops import optimizer_op as _ops
from . import _ckpt
from .mesh import current_mesh

__all__ = ["ShardedTrainer", "functional_apply",
           "allreduce_across_processes", "project_spec"]


def project_spec(mesh, spec):
    """A PartitionSpec projected onto ``mesh``: axis names the mesh
    doesn't have degrade to replication on that dim.  A dim sharded over
    SEVERAL axes — ``P(("data", "model"), None)`` — keeps exactly the
    axes the mesh still has.  Shared by the trainer's survivor-mesh
    rebuild and the serving shard planner (serving/shardplan.py)."""
    out = []
    for a in spec:
        if isinstance(a, (tuple, list)):
            kept = tuple(x for x in a if x in mesh.axis_names)
            out.append(kept if len(kept) > 1
                       else (kept[0] if kept else None))
        else:
            out.append(a if a is None or a in mesh.axis_names else None)
    return PartitionSpec(*out)


# ---------------------------------------------------------------------------
# Functional optimizer rules: state init + traced-step update per Optimizer
# class. These reuse the SAME fused update kernels as the eager path
# (ops/optimizer_op.py, ref: src/operator/optimizer_op.cc) but thread the
# step count t as a traced value so Adam bias correction / schedules never
# bake into the compiled program.
# ---------------------------------------------------------------------------

def _lr_at(optimizer, t):
    """The lr a single update at step t sees (scheduler-aware) — ONE
    resolution rule shared by both trainers' step and scanned run_steps
    paths."""
    if optimizer.lr_scheduler is not None:
        return float(optimizer.lr_scheduler(t))
    return float(optimizer.learning_rate)


def _scalar_args(lr, t, rescale, lscale):
    """``t``, ``rescale``, ``lscale`` and ``lr`` (one value, or
    ``run_steps``' per-step sequence) as every compiled program of the
    trainer takes them: ONE host float32 array ``[t, rescale, lscale,
    *lr]`` that the compiled call uploads with its other arguments (one
    small upload, not four or five). Traced values, so a new lr or loss
    scale is a new argument and never a new program; not device arrays,
    whose conversion is a device program each."""
    return np.concatenate([(t, rescale, lscale), np.atleast_1d(lr)],
                          dtype=np.float32)


def _zeros_like(w):
    return jnp.zeros(w.shape, w.dtype)


def _as_shapes(arrays):
    return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
            for a in arrays]


def _state_spec(weight_spec, entry):
    """State entries shard like their weight; scalar entries (Nadam's
    schedule product) are replicated. ONE rule for placement and the jit
    in/out shardings — divergence between those produces opaque XLA
    sharding mismatches."""
    return weight_spec if getattr(entry, "ndim", 0) else PartitionSpec()


def _opt_init_state(opt, w):
    name = type(opt).__name__
    if name in ("SGD", "NAG", "Signum"):
        mom = getattr(opt, "momentum", 0.0)
        return (_zeros_like(w),) if mom != 0.0 else ()
    if name in ("Adam", "AdamW", "LAMB", "FTRL", "AdaDelta", "Nadam"):
        state = (_zeros_like(w), _zeros_like(w))
        if name == "Nadam":
            # Nadam's momentum-schedule running product is carried as a
            # scalar state entry (no closed form over a traced t)
            state = state + (jnp.ones((), jnp.float32),)
        return state
    if name in ("RMSProp", "AdaGrad"):
        return (_zeros_like(w),)
    if name == "DCASGD":
        # a real COPY: weights and states are donated separately — the
        # same underlying buffer in both would be donated twice
        prev = jnp.array(w, copy=True)
        if getattr(opt, "momentum", 0.0) != 0.0:
            return (_zeros_like(w), prev)
        return (prev,)
    if name == "FTML":
        return (_zeros_like(w), _zeros_like(w), _zeros_like(w))
    if name == "SGLD":
        return ()
    raise MXNetError(
        f"ShardedTrainer has no functional rule for optimizer "
        f"{name!r}; use the eager gluon.Trainer for it")


def _opt_apply(opt, w, g, state, lr, t, wd, rescale, clip):
    """One traced parameter update; returns (new_w, new_state)."""
    name = type(opt).__name__
    kw = dict(lr=lr, wd=wd, rescale_grad=rescale, clip_gradient=clip)
    if name in ("SGD", "NAG"):
        if not state:
            return _ops._sgd_update(w, g, **kw), ()
        fn = _ops._sgd_mom_update if name == "SGD" else _ops._nag_mom_update
        w2, m2 = fn(w, g, state[0], momentum=opt.momentum, **kw)
        return w2, (m2,)
    if name == "Adam":
        corr = jnp.sqrt(1 - opt.beta2 ** t) / (1 - opt.beta1 ** t)
        w2, m2, v2 = _ops._adam_update(
            w, g, state[0], state[1], beta1=opt.beta1, beta2=opt.beta2,
            epsilon=opt.epsilon, lr=lr * corr, wd=wd, rescale_grad=rescale,
            clip_gradient=clip)
        return w2, (m2, v2)
    if name == "AdamW":
        corr = jnp.sqrt(1 - opt.beta2 ** t) / (1 - opt.beta1 ** t)
        w2, m2, v2 = _ops._adamw_update(
            w, g, state[0], state[1], beta1=opt.beta1, beta2=opt.beta2,
            epsilon=opt.epsilon, lr=lr * corr, wd=wd, rescale_grad=rescale,
            clip_gradient=clip)
        return w2, (m2, v2)
    if name == "LAMB":
        gp, m2, v2 = _ops._lamb_phase1(
            w, g, state[0], state[1], beta1=opt.beta1, beta2=opt.beta2,
            epsilon=opt.epsilon, t=t, bias_correction=opt.bias_correction,
            wd=wd, rescale_grad=rescale, clip_gradient=clip)
        r1 = jnp.linalg.norm(w.astype(jnp.float32))
        r2 = jnp.linalg.norm(gp)
        w2 = _ops._lamb_phase2(
            w, gp, r1, r2, lr=lr,
            lower_bound=opt.lower_bound if opt.lower_bound else -1.0,
            upper_bound=opt.upper_bound if opt.upper_bound else -1.0)
        return w2, (m2, v2)
    if name == "RMSProp":
        w2, n2 = _ops._rmsprop_update(w, g, state[0], gamma1=opt.gamma1,
                                      epsilon=opt.epsilon, **kw)
        return w2, (n2,)
    if name == "AdaGrad":
        w2, h2 = _ops._adagrad_update(w, g, state[0],
                                      epsilon=opt.float_stable_eps, **kw)
        return w2, (h2,)
    if name == "FTRL":
        w2, z2, n2 = _ops._ftrl_update(w, g, state[0], state[1],
                                       lamda1=opt.lamda1, beta=opt.beta, **kw)
        return w2, (z2, n2)
    if name == "Signum":
        if not state:
            return _ops._signsgd_update(w, g, **kw), ()
        g32 = g.astype(jnp.float32) * rescale
        g32 = jnp.where(clip > 0, jnp.clip(g32, -clip, clip), g32)
        m2 = state[0] * opt.momentum - g32 * (1 - opt.momentum)
        w2 = w * (1 - lr * opt.wd_lh) + jnp.sign(m2) * lr
        return w2.astype(w.dtype), (m2,)

    def _g32():
        gg = g.astype(jnp.float32) * rescale
        gg = jnp.where(clip > 0, jnp.clip(gg, -clip, clip), gg)
        return gg + wd * w.astype(jnp.float32)

    if name == "AdaDelta":
        acc_g, acc_d = state
        gg = _g32()
        acc_g2 = opt.rho * acc_g + (1 - opt.rho) * gg * gg
        delta = jnp.sqrt(acc_d + opt.epsilon) / \
            jnp.sqrt(acc_g2 + opt.epsilon) * gg
        acc_d2 = opt.rho * acc_d + (1 - opt.rho) * delta * delta
        return (w.astype(jnp.float32) - delta).astype(w.dtype), \
            (acc_g2, acc_d2)
    if name == "Nadam":
        # note: the eager reference updates its m_schedule product once
        # per update() CALL (i.e. per parameter per step — an upstream
        # quirk); this functional rule keeps the schedule per-parameter,
        # the form the Nadam paper intends. Trajectories differ at the
        # 1e-4 level over a few steps.
        mean, var, msched = state
        gg = _g32()
        d = opt.schedule_decay
        mom_t = opt.beta1 * (1 - 0.5 * 0.96 ** (t * d))
        mom_t1 = opt.beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * d))
        msched2 = msched * mom_t
        msched_next = msched2 * mom_t1
        m2 = opt.beta1 * mean + (1 - opt.beta1) * gg
        v2 = opt.beta2 * var + (1 - opt.beta2) * gg * gg
        g_p = gg / (1 - msched2)
        m_p = m2 / (1 - msched_next)
        v_p = v2 / (1 - opt.beta2 ** t)
        m_bar = (1 - mom_t) * g_p + mom_t1 * m_p
        w2 = w.astype(jnp.float32) - lr * m_bar / (jnp.sqrt(v_p)
                                                   + opt.epsilon)
        return w2.astype(w.dtype), (m2, v2, msched2)
    if name == "DCASGD":
        gg = g.astype(jnp.float32) * rescale
        gg = jnp.where(clip > 0, jnp.clip(gg, -clip, clip), gg)
        prev = state[-1]
        w32 = w.astype(jnp.float32)
        comp = gg + wd * w32 + opt.lamda * gg * gg * (w32 - prev)
        if len(state) == 1:
            return (w32 - lr * comp).astype(w.dtype), (w32,)
        m2 = opt.momentum * state[0] - lr * comp
        return (w32 + m2).astype(w.dtype), (m2, w32)
    if name == "FTML":
        dst, vst, zst = state
        gg = _g32()
        v2 = opt.beta2 * vst + (1 - opt.beta2) * gg * gg
        d2 = (1 - opt.beta1 ** t) / lr * (
            jnp.sqrt(v2 / (1 - opt.beta2 ** t)) + opt.epsilon)
        sigma = d2 - opt.beta1 * dst
        z2 = opt.beta1 * zst + (1 - opt.beta1) * gg - sigma * \
            w.astype(jnp.float32)
        return (-z2 / d2).astype(w.dtype), (d2, v2, z2)
    raise MXNetError(f"no functional update for {name}")


def _collect_aux_losses(block):
    """Sum of weighted auxiliary losses stashed by routed layers during the
    CURRENT trace (gluon.contrib.nn.MoEFFN sets ``_trace_aux_loss`` +
    ``aux_loss_weight`` each forward — the Switch load-balancing term).
    Read-and-clear, so no tracer outlives its trace. Returns None when the
    model has no such layers."""
    total, found = 0.0, False
    stack, seen = [block], set()
    while stack:
        b = stack.pop()
        if id(b) in seen:
            continue
        seen.add(id(b))
        al = getattr(b, "_trace_aux_loss", None)
        if al is not None:
            b._trace_aux_loss = None
            if getattr(b, "aux_loss_weight", 0.0):
                total = total + b.aux_loss_weight * al
                found = True
        stack.extend(getattr(b, "_children", {}).values())
    return total if found else None


class ShardedTrainer(GuardedTrainerMixin):
    """Gluon-level driver for the single-program SPMD step.

    Drop-in upgrade of ``gluon.Trainer`` for mesh execution::

        mesh = parallel.make_mesh({"data": 4, "model": 2})
        trainer = parallel.ShardedTrainer(net, loss_fn, "sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            mesh=mesh,
            param_rules=[(r".*dense\\d+_weight", PartitionSpec(None, "model"))])
        loss = trainer.step(x, y)          # one fused XLA program

    The reference analog is Trainer.step's allreduce+update flow
    (ref: python/mxnet/gluon/trainer.py _allreduce_grads/_update) — here both
    happen inside the compiled program, overlapped by XLA's scheduler.
    """

    _guard_consumer = "sharded_trainer"

    def __init__(self, block, loss_fn, optimizer, optimizer_params=None,
                 mesh: Mesh = None, param_rules=None, batch_axis=0,
                 donate=True, compute_dtype=None, remat=None,
                 master_dtype=None, guard=None):
        from .. import optimizer as opt_mod
        self._block = block
        self._loss = loss_fn
        optimizer_params = optimizer_params or {}
        self._optimizer = (optimizer if isinstance(optimizer, opt_mod.Optimizer)
                           else opt_mod.create(optimizer, **optimizer_params))
        # compute_dtype="bfloat16": forward/backward in bf16 on the MXU with
        # fp32 master weights — the reference's multi-precision (`mp_*`)
        # scheme (ref: src/operator/optimizer_op.cc mp_sgd_update) fused
        # into the step; the optimizer update stays fp32. When unset, the
        # process-wide AMP dtype applies (contrib.amp.init).
        self._explicit_compute_dtype = compute_dtype is not None
        if compute_dtype is None:
            from ..contrib.amp import amp_dtype
            compute_dtype = amp_dtype()
        self._compute_dtype = (jnp.dtype(compute_dtype)
                               if compute_dtype is not None else None)
        # remat: rematerialization policy for the forward pass — the
        # `jax.checkpoint` HBM↔FLOPs trade (MXNET_BACKWARD_DO_MIRROR is the
        # reference's analog, ref: src/executor/graph_executor.cc mirror
        # path). None keeps XLA's default saved-activation schedule;
        # "full" saves nothing (recompute the whole forward in backward);
        # "dots" saves matmul/conv outputs and recomputes elementwise chains;
        # a callable is passed through as a jax.checkpoint policy.
        if remat in (None, "full"):
            self._remat_policy = remat
        elif remat == "dots":
            self._remat_policy = jax.checkpoint_policies.dots_saveable
        elif remat == "dots_no_batch":
            self._remat_policy = \
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        elif callable(remat):
            self._remat_policy = remat
        else:
            raise MXNetError(f"unknown remat policy {remat!r}; expected "
                             "None, 'full', 'dots', 'dots_no_batch' or a "
                             "jax.checkpoint policy callable")
        # master_dtype: storage dtype of weights + optimizer state. Default
        # fp32 masters (the reference's multi-precision mp_* scheme);
        # "bfloat16" halves parameter/state HBM traffic at the cost of
        # update precision — the update math itself stays fp32-internal
        # (ops/optimizer_op.py casts per-kernel).
        self._master_dtype = (jnp.dtype(master_dtype)
                              if master_dtype is not None else None)
        if self._compute_dtype is None and self._master_dtype is not None:
            # low-precision storage without a compute dtype would feed
            # bf16 weights to fp32 inputs — compute in the master dtype
            self._compute_dtype = self._master_dtype
        self._mesh = mesh
        self._param_rules = [(re.compile(pat), spec)
                             for pat, spec in (param_rules or [])]
        self._batch_axis = batch_axis
        self._donate = donate
        self._prepared = False
        self._num_update = self._optimizer.begin_num_update
        self._step_fn = None
        self._eval_fn = None
        self._out_treedef = None
        # the batch each program was first called with, as shapes, by its
        # steps per call (0: step()): what program_texts() lowers it with
        self._program_batches = {}
        _scopes.watch(self)
        # anomaly guardrails (docs/guardrails.md): the fused flag/norm is
        # computed in-program on EVERY step (the reduction is ~free and
        # keeps the program signature stable); the config only decides
        # what the host does with it. fp16 compute always gets a dynamic
        # loss scaler riding the same flag — the parity the eager
        # Trainer's DynamicLossScaler promises, without its host sync.
        self._guard_cfg = GuardConfig.coerce(guard)
        self._monitor = (AnomalyMonitor(self._guard_cfg,
                                        consumer=self._guard_consumer)
                         if self._guard_cfg is not None else None)
        self._scaler = None
        self._resolve_scaler()
        self._guard_state = None
        self._skipped_offset = 0

    def _resolve_scaler(self):
        """(Re)resolve the compute dtype + fp16 loss scaler from the
        LIVE amp state when ``compute_dtype`` wasn't pinned by the
        caller: ``amp.init("float16")`` after construction retraces the
        step with fp16 casts (``_maybe_invalidate_amp``), so the scaler
        — and with it skip-step + scale halving — must follow the
        program's ACTUAL dtype, not a stale ``__init__`` snapshot
        (PipelinedTrainer._resolve_scaler is the same contract)."""
        if not self._explicit_compute_dtype:
            from ..contrib.amp import amp_dtype
            cdt = amp_dtype()
            self._compute_dtype = (jnp.dtype(cdt) if cdt is not None
                                   else self._master_dtype)
        if self._compute_dtype == jnp.float16:
            if self._scaler is None:
                from ..contrib.amp import DynamicLossScaler
                self._scaler = DynamicLossScaler()
        else:
            self._scaler = None
        self._validate_guard_mode()

    # -- sharding layout -----------------------------------------------------
    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = current_mesh()
        return self._mesh

    def _param_spec(self, param):
        # rules match the flat parameter name AND the structural path
        # ('features.3.weight'). Flat names embed process-global counters
        # (dense0 → dense4 in a second net instance), so a rule written
        # against them silently stops matching in a rebuilt net — e.g. on
        # checkpoint resume; structural paths are instance-independent.
        sname = self._struct_name(param)
        for pat, spec in self._param_rules:
            if pat.match(param.name) or pat.match(sname):
                # project onto the live mesh: an axis the mesh doesn't
                # have degrades to replication on that dim, so ONE rule
                # set serves every cohort shape the elastic driver may
                # build (docs/elastic.md) instead of raising at prepare
                return self._spec_on(self.mesh, spec)
        return PartitionSpec()   # replicated (pure data parallel)

    def _batch_spec(self, ndim):
        spec = [None] * ndim
        if "data" in self.mesh.axis_names:
            spec[self._batch_axis] = "data"
        return PartitionSpec(*spec)

    def _shard(self, data, spec):
        return jax.device_put(data, NamedSharding(self.mesh, spec))

    def _shard_batch_arg(self, b):
        """Batch arg → data-sharded device array. Already-placed jax.Arrays
        pass through (device_put with an identical sharding is a no-op), so
        a prefetching input pipeline avoids re-uploads."""
        data = b._data if isinstance(b, nd.NDArray) else b
        if not isinstance(data, jax.Array):
            data = np.asarray(data)
        return self._shard(data, self._batch_spec(np.ndim(data)))

    # -- setup ---------------------------------------------------------------
    def _prepare(self, args):
        if self._prepared:
            return
        from .mesh import use_mesh
        with use_mesh(self.mesh):   # deferred-init pass may hit mesh ops
            self._block._ensure_ready(tuple(
                a if isinstance(a, nd.NDArray) else nd.array(a)
                for a in args))
        with _obs.setup_stage("place"):
            self._place()
        self._prepared = True

    def _place(self):
        """Parameters, aux state, optimizer state and guard counters onto
        the mesh."""
        trainable, aux = self._block._param_split()
        self._trainable, self._aux = trainable, aux
        self._tr_specs = [self._param_spec(p) for p in trainable]
        self._aux_specs = [self._param_spec(p) for p in aux]
        # move parameter + aux arrays onto the mesh with their target layout;
        # the NDArray handles now hold globally-sharded jax.Arrays
        mdt = self._master_dtype
        for p, spec in zip(trainable, self._tr_specs):
            w = p._data[0]._data
            if mdt is not None and jnp.issubdtype(w.dtype, jnp.floating):
                w = w.astype(mdt)
            p._data[0]._rebind(self._shard(w, spec))
        for p, spec in zip(aux, self._aux_specs):
            p._data[0]._rebind(self._shard(p._data[0]._data, spec))
        # optimizer state, sharded like its weight (scalar state entries
        # — e.g. Nadam's momentum-schedule product — are replicated)
        self._states = []
        for p, spec in zip(trainable, self._tr_specs):
            state = _opt_init_state(self._optimizer, p._data[0]._data)
            self._states.append(tuple(
                self._shard(s, _state_spec(spec, s)) for s in state))
        # in-program guard counters (total skips, consecutive skips),
        # replicated — carried through every step/scan for free
        self._guard_state = tuple(
            self._shard(s, PartitionSpec())
            for s in _guard.init_guard_state())

    # -- the compiled step ---------------------------------------------------
    @_obs.setup_stage("build_step")
    def _build_step(self, n_inputs):
        block, loss_block, opt = self._block, self._loss, self._optimizer
        wds = [opt._get_wd(i) for i in range(len(self._trainable))]
        # the multipliers themselves: a ratio of learning rates is 0 / 0
        # while a warm-up starts from 0
        lr_mults = [opt._get_lr_mult(i) for i in range(len(self._trainable))]
        clip = opt.clip_gradient if opt.clip_gradient is not None else -1.0
        guard_clip = (self._guard_cfg.clip_norm
                      if self._guard_cfg is not None else None)

        cdt = self._compute_dtype
        # static at trace time: with no guard AND no fp16 scaler the
        # update applies unconditionally (pre-guardrails behavior) — a
        # silent bitwise skip nobody journals or polls would freeze
        # training invisibly, which is worse than the NaN surfacing
        guarded = self._scaler is not None or self._guard_cfg is not None

        def raw_step(tr, aux, states, gstate, key, lr, t, rescale, lscale,
                     *batch):
            inputs, label = batch[:-1], batch[-1]

            def loss_of(tr_):
                if cdt is not None:
                    tr_ = [w.astype(cdt) if jnp.issubdtype(w.dtype,
                                                           jnp.floating)
                           else w for w in tr_]
                    inputs_c = [i.astype(cdt) if jnp.issubdtype(
                        jnp.asarray(i).dtype, jnp.floating) else i
                        for i in inputs]
                else:
                    inputs_c = inputs
                outs, treedef, aux_new = functional_apply(
                    block, key, tr_, aux, inputs_c, training=True)
                self._out_treedef = treedef
                # loss math in fp32 by default; a loss that does its own
                # fp32-accumulated reductions (amp_safe, e.g. the fused
                # sparse softmax-CE) takes compute-dtype outputs directly —
                # for a [tokens, vocab] MLM head the blanket fp32 cast
                # alone materializes GBs of HBM traffic per step
                if getattr(loss_block, "amp_safe", False):
                    out_nds = [nd.NDArray(o, _skip_device_put=True)
                               for o in outs]
                else:
                    out_nds = [nd.NDArray(
                        o.astype(jnp.float32) if jnp.issubdtype(
                            o.dtype, jnp.floating) else o,
                        _skip_device_put=True) for o in outs]
                label_nd = nd.NDArray(label, _skip_device_put=True)
                with autograd.pause(train_mode=True), \
                        _obs.device_scope("loss"):
                    loss_nd = loss_block(out_nds[0] if len(out_nds) == 1
                                         else out_nds, label_nd)
                    loss_val = jnp.mean(loss_nd._data.astype(jnp.float32))
                aux_pen = _collect_aux_losses(block)
                if aux_pen is not None:     # MoE load-balancing term
                    loss_val = loss_val + jnp.asarray(aux_pen,
                                                      jnp.float32)
                # fp16 loss scaling: the gradient sees the SCALED loss
                # (that is what makes fp16 grads overflow-detectable);
                # the reported loss stays unscaled. lscale is traced, so
                # DynamicLossScaler updates never retrace.
                return loss_val * lscale, (loss_val, outs, aux_new)

            if self._remat_policy is not None:
                loss_of = jax.checkpoint(
                    loss_of,
                    policy=(None if self._remat_policy == "full"
                            else self._remat_policy))
            ((_, (loss_val, outs, aux_new)), grads) = jax.value_and_grad(
                loss_of, has_aux=True)(list(tr))
            aux_new = [a.astype(a0.dtype) for a, a0 in zip(aux_new, aux)]
            # fused guard (docs/guardrails.md): ONE squared-sum reduction
            # over every (scaled) grad doubles as the non-finite flag and
            # the global norm. Grads here are already psum-reduced by
            # GSPMD, so the flag is globally agreed — no rank can branch
            # out of a collective (the skip below is data flow).
            inv = jnp.float32(1.0) / lscale
            with _obs.device_scope("guard"):
                finite, gnorm_scaled = _guard.guard_stats(grads, loss_val)
            gnorm = gnorm_scaled * inv
            rescale_all = rescale * inv
            if guard_clip is not None:
                # global-norm clip off the already-computed norm: folded
                # into rescale_grad, zero extra passes over the grads
                rescale_all = rescale_all * _guard.clip_scale(
                    gnorm * rescale, jnp.float32(guard_clip))
            new_tr, new_states = [], []
            with _obs.device_scope("optimizer"):
                for i, (w, g, s) in enumerate(zip(tr, grads, states)):
                    w2, s2 = _opt_apply(opt, w, g, s, lr * lr_mults[i], t,
                                        wds[i], rescale_all, clip)
                    new_tr.append(w2)
                    new_states.append(s2)
            # skip-step semantics: a non-finite step is a bitwise no-op
            # for params, optimizer state AND aux state (BatchNorm
            # running stats) — jnp.where, so it works under jit/pjit/scan
            if guarded:
                new_tr = _guard.select(finite, new_tr, list(tr))
                new_states = _guard.select(finite, new_states,
                                           list(states))
                aux_new = _guard.select(finite, aux_new, list(aux))
                gstate2 = _guard.update_guard_state(gstate, finite)
            else:
                gstate2 = gstate
            return (new_tr, aux_new, new_states, gstate2, loss_val,
                    (finite, gnorm), tuple(outs))

        def step(tr, aux, states, gstate, root, scalars, *batch):
            # the draw of _rng.split_in_program: the split an eager
            # next_key() makes, inside the program that consumes the subkey
            root, key = jax.random.split(root)
            t, rescale, lscale, lr = scalars
            return raw_step(tr, aux, states, gstate, key, lr, t, rescale,
                            lscale, *batch) + (jax.random.key_data(root),)

        mesh = self.mesh
        ns = lambda spec: NamedSharding(mesh, spec)
        rep = ns(PartitionSpec())
        in_shardings = (
            [ns(s) for s in self._tr_specs],
            [ns(s) for s in self._aux_specs],
            [tuple(ns(_state_spec(s, e)) for e in st)
             for s, st in zip(self._tr_specs, self._states)],
            (rep, rep),                       # guard state
            rep, rep,                         # root key, _scalar_args
        ) + tuple(jax.tree_util.tree_map(
            lambda _: None, tuple(range(n_inputs + 1))))  # batch: auto
        out_shardings = (
            [ns(s) for s in self._tr_specs],
            [ns(s) for s in self._aux_specs],
            [tuple(ns(_state_spec(s, e)) for e in st)
             for s, st in zip(self._tr_specs, self._states)],
            (rep, rep),                       # guard state
            rep, (rep, rep), None,
            rep,                              # the new root key's bits
        )
        donate = (0, 2) if self._donate else ()
        self._raw_step = raw_step
        self._shardings = (in_shardings, out_shardings, donate)
        return jax.jit(step, in_shardings=in_shardings,
                       out_shardings=out_shardings, donate_argnums=donate)

    def step(self, *batch):
        """Run one fused train step; last positional arg is the label.
        Returns the (replicated) scalar loss as an NDArray.

        The call starts one device program, the compiled step: ``t``,
        ``rescale_grad``, the loss scale and ``lr`` go in as one host
        float32 array (``_scalar_args``), and the dropout key is split off
        ``_rng``'s root inside the program, which returns the new root's
        bits for ``_rng`` to keep (the stream is the one
        ``_rng.next_key()`` would give, draw for draw)."""
        args = batch[:-1]
        self._prepare(args)
        self._maybe_invalidate_amp()
        compiling = self._step_fn is None
        if compiling:
            self._step_fn = self._build_step(len(args))
        self._num_update += 1
        t = self._num_update
        # telemetry (docs/observability.md): phases always feed the
        # step-phase summary (host perf_counter only) and a profiler
        # annotation (mxnet_tpu.sharded_trainer.<phase>, a flag test with
        # no profiler session); spans are live only under MXNET_TPU_TRACE
        # — attrs are host scalars, so the deferred-mode zero-device-read
        # contract is untouched
        with _obs.call_span("sharded_trainer", "step", step=t):
            with _obs.step_phase("sharded_trainer", "data_wait"):
                batch_datas = [self._shard_batch_arg(b) for b in batch]
            if 0 not in self._program_batches:
                self._program_batches[0] = _as_shapes(batch_datas)
            # host_args: host work only (the schedule's lr, the parameter
            # lists, four float32 scalars); it starts no device program
            with _obs.step_phase("sharded_trainer", "host_args"):
                self._optimizer.num_update = t
                tr = [p._data[0]._data for p in self._trainable]
                aux = [p._data[0]._data for p in self._aux]
                cshapes = ([list(map(int, np.shape(b))) for b in batch]
                           if compiling else None)
                scalars = _scalar_args(
                    _lr_at(self._optimizer, t), t,
                    self._optimizer.rescale_grad, self._loss_scale())
            from .mesh import use_mesh
            # mesh-aware ops (ring attention) trace under use_mesh
            with _obs.step_phase("sharded_trainer", "compiled_step"), \
                    _obs.maybe_setup_stage(compiling, "first_call",
                                           program="step"), \
                    _obs.maybe_compile_span(compiling,
                                            "sharded_trainer.step",
                                            shapes=cshapes), \
                    use_mesh(self.mesh), _rng.split_in_program() as draw:
                (new_tr, aux_new, new_states, gstate, loss_val,
                 (finite, gnorm), outs, draw.new_root_data) = self._step_fn(
                    tr, aux, self._states, self._guard_state, draw.root,
                    scalars, *batch_datas)
            for p, w in zip(self._trainable, new_tr):
                p._data[0]._rebind(w)
            for p, a in zip(self._aux, aux_new):
                p._data[0]._rebind(a)
            self._states = new_states
            self._guard_state = gstate
            self.last_outputs = [nd.NDArray(o, _skip_device_put=True)
                                 for o in outs]
            with _obs.step_phase("sharded_trainer", "guard_fetch"):
                self._after_step(t, loss_val, finite, gnorm)
        return nd.NDArray(loss_val, _skip_device_put=True)

    def _loss_scale(self):
        return self._scaler.loss_scale if self._scaler is not None else 1.0

    def _lower(self, fn, num_steps, batch):
        """``fn`` (the step, or the program of ``num_steps`` steps) lowered
        with the trainer's arrays as they are now, ``batch`` and arguments
        of the shapes a call passes: a key by its shape only (no draw) and
        ``_scalar_args`` of ones."""
        from .mesh import use_mesh
        key = jax.eval_shape(lambda: jax.random.key(  # graftlint: disable=G2 shape only
            0, impl=_rng._default_impl()))
        lr = np.ones(num_steps) if num_steps else 1.0
        with use_mesh(self.mesh):
            return fn.lower(
                [p._data[0]._data for p in self._trainable],
                [p._data[0]._data for p in self._aux],
                self._states, self._guard_state, key,
                _scalar_args(lr, 1.0, 1.0, 1.0), *batch)

    def step_program_text(self, *batch) -> str:
        """Optimized HLO of the compiled :meth:`step` for this batch — where
        a caller reads which collectives (``all-reduce``) and custom kernels
        (``tpu_custom_call``) the compiler put into the program. Lowers and
        compiles the step again (a persistent compile cache makes that a
        reload) with arguments of the shapes :meth:`step` passes; takes no
        step and draws no key."""
        self._prepare(batch[:-1])
        if self._step_fn is None:
            self._step_fn = self._build_step(len(batch) - 1)
        with _obs.setup_stage("inspect"):
            return self._lower(
                self._step_fn, 0,
                [self._shard_batch_arg(b) for b in batch]
            ).compile().as_text()

    def program_texts(self) -> dict:
        """``{"step": text, "run_steps(<k>)": text}``: optimized HLO of every
        program this trainer has run, each lowered and compiled again as
        :meth:`step_program_text` does, with the shapes of the batch it was
        first called with. Every instruction's ``op_name`` metadata holds
        the ``jax.named_scope`` names it was traced under
        (``observability.device_scopes``). Takes no step, draws no key and
        costs nothing until it is called. What it lowers and compiles is
        booked to the set-up stage ``inspect``, not to a trainer's."""
        texts = {}
        for num_steps, batch in self._program_batches.items():
            if num_steps:
                name = f"run_steps({num_steps})"
                fn = getattr(self, "_multi_fns", {}).get(f"multi{num_steps}")
            else:
                name, fn = "step", self._step_fn
            if fn is not None:      # dropped by an AMP change or a new mesh
                with _obs.setup_stage("inspect"):
                    texts[name] = self._lower(
                        fn, num_steps, batch).compile().as_text()
        return texts

    # -- guard bookkeeping: GuardedTrainerMixin (docs/guardrails.md) ----------
    def _reinit_guard_state(self):
        return tuple(self._shard(s, PartitionSpec())
                     for s in _guard.init_guard_state())

    def _maybe_invalidate_amp(self):
        """Retrace compiled programs when the per-op AMP cast policy
        changes (amp.init with op lists / amp.reset) — a stale program
        would silently keep or miss the casts."""
        from .. import _dispatch
        if getattr(self, "_amp_epoch", None) != _dispatch.amp_epoch():
            self._step_fn = None
            self._eval_fn = None
            self._multi_fns = {}
            self._program_batches = {}
            self._amp_epoch = _dispatch.amp_epoch()
            # the retraced program's dtype may have changed with it —
            # BEFORE the rebuild reads _compute_dtype/_scaler
            self._resolve_scaler()

    @_obs.setup_stage("build_step")
    def _build_multi(self, num_steps):
        """The program of ``num_steps`` steps (``lax.scan`` over the step
        body), jitted with the step's shardings."""
        raw = self._raw_step
        in_sh, out_sh, donate = self._shardings
        rep_sh = out_sh[4]

        def multi(tr, aux, states, gstate, root, scalars, *b):
            root, rng = jax.random.split(root)  # as the step's program
            (t, rescale, lscale), lrs = scalars[:3], scalars[3:]

            # lrs: (num_steps,) host-evaluated schedule — each inner
            # step sees the SAME lr a separate step() call would
            def body(carry, i):
                tr_, aux_, states_, gs_, t_ = carry
                k = jax.random.fold_in(rng, i)
                ntr, naux, nst, gs2, loss, (fin, gn), _ = raw(
                    tr_, aux_, states_, gs_, k, lrs[i], t_, rescale,
                    lscale, *b)
                return (ntr, naux, nst, gs2, t_ + 1.0), (loss, fin, gn)

            (tr, aux, states, gstate, _), (losses, fins, gns) = \
                jax.lax.scan(body, (tr, aux, states, gstate, t),
                             jnp.arange(num_steps))
            return (tr, aux, states, gstate, losses, fins, gns,
                    losses[-1], jax.random.key_data(root))

        return jax.jit(multi, in_shardings=in_sh,
                       out_shardings=out_sh[:4] + (rep_sh,) * 5,
                       donate_argnums=donate)

    def run_steps(self, *batch, num_steps=8):
        """Run ``num_steps`` train steps as ONE compiled program
        (``lax.scan`` over the step body). Amortizes host-dispatch latency
        — the TPU analog of the reference's engine keeping a deep async
        queue ahead of the Python loop (SURVEY §3.2: "the loop
        synchronizes only at metric.update"). The batch is reused each
        inner step; returns the last step's loss.

        Like :meth:`step`, the call starts one device program: the scalars
        and the per-step lr sequence are one host float32 array, the
        program splits ``_rng``'s root once (inner step ``i`` folds ``i``
        into the subkey) and returns the new root's bits and the last loss
        itself."""
        args = batch[:-1]
        self._prepare(args)
        self._maybe_invalidate_amp()
        if self._step_fn is None:
            self._step_fn = self._build_step(len(args))
        key = f"multi{num_steps}"
        if not hasattr(self, "_multi_fns"):
            self._multi_fns = {}
        compiling = key not in self._multi_fns
        if compiling:
            self._multi_fns[key] = self._build_multi(num_steps)
        t = self._num_update + 1
        self._num_update += num_steps
        with _obs.call_span("sharded_trainer", "run_steps", start_step=t,
                            num_steps=num_steps):
            with _obs.step_phase("sharded_trainer", "data_wait"):
                batch_datas = [self._shard_batch_arg(b) for b in batch]
            if compiling:
                self._program_batches[num_steps] = _as_shapes(batch_datas)
            with _obs.step_phase("sharded_trainer", "host_args"):
                self._optimizer.num_update = self._num_update
                tr = [p._data[0]._data for p in self._trainable]
                aux = [p._data[0]._data for p in self._aux]
                cshapes = ([list(map(int, np.shape(b))) for b in batch]
                           if compiling else None)
                # each inner step sees the SAME lr a separate step() call
                # would (a frozen first-step lr silently changes
                # warmup/decay math). fp16 note (docs/guardrails.md): the
                # loss scale is one traced input for the WHOLE window —
                # overflow inside a scanned window skips those steps
                # in-program, and the scaler adjusts once per window from
                # the per-step flags below
                scalars = _scalar_args(
                    [_lr_at(self._optimizer, t + i)
                     for i in range(num_steps)], t,
                    self._optimizer.rescale_grad, self._loss_scale())
            from .mesh import use_mesh
            with _obs.step_phase("sharded_trainer", "compiled_step"), \
                    _obs.maybe_setup_stage(
                        compiling, "first_call",
                        program=f"run_steps({num_steps})"), \
                    _obs.maybe_compile_span(compiling,
                                            "sharded_trainer.run_steps",
                                            num_steps=num_steps,
                                            shapes=cshapes), \
                    use_mesh(self.mesh), _rng.split_in_program() as draw:
                (new_tr, aux_new, new_states, gstate, losses, fins, gns,
                 last_loss, draw.new_root_data) = self._multi_fns[key](
                    tr, aux, self._states, self._guard_state, draw.root,
                    scalars, *batch_datas)
            for p, w in zip(self._trainable, new_tr):
                p._data[0]._rebind(w)
            for p, a in zip(self._aux, aux_new):
                p._data[0]._rebind(a)
            self._states = new_states
            self._guard_state = gstate
            with _obs.step_phase("sharded_trainer", "guard_fetch"):
                self._after_run_steps(t, losses, fins, gns)
        return nd.NDArray(last_loss, _skip_device_put=True)

    def evaluate(self, *batch):
        """Forward + loss under one compiled program (no update)."""
        args = batch[:-1]
        self._prepare(args)
        self._maybe_invalidate_amp()
        compiling = self._eval_fn is None
        if compiling:
            block, loss_block = self._block, self._loss

            def eval_step(tr, aux, key, *b):
                inputs, label = b[:-1], b[-1]
                outs, _, _ = functional_apply(block, key, tr, aux, inputs,
                                              training=False)
                out_nds = [nd.NDArray(o, _skip_device_put=True) for o in outs]
                label_nd = nd.NDArray(label, _skip_device_put=True)
                with autograd.pause(train_mode=False):
                    loss_nd = loss_block(out_nds[0] if len(out_nds) == 1
                                         else out_nds, label_nd)
                return jnp.mean(loss_nd._data.astype(jnp.float32)), \
                    tuple(outs)
            self._eval_fn = jax.jit(eval_step)
        batch_datas = [self._shard_batch_arg(b) for b in batch]
        tr = [p._data[0]._data for p in self._trainable]
        aux = [p._data[0]._data for p in self._aux]
        with _obs.maybe_setup_stage(compiling, "first_call",
                                    program="evaluate"):
            loss_val, outs = self._eval_fn(tr, aux, _rng.next_key(),
                                           *batch_datas)
        self.last_outputs = [nd.NDArray(o, _skip_device_put=True)
                             for o in outs]
        return nd.NDArray(loss_val, _skip_device_put=True)

    # -- checkpoint / resume -------------------------------------------------
    # The flagship path's checkpoint story (ref: python/mxnet/gluon/
    # trainer.py save_states/load_states; SURVEY §5.4). Differences forced
    # by the sharded world: optimizer state lives as GSPMD-sharded
    # jax.Arrays (possibly bf16 masters), and in a multi-host run no single
    # process holds every shard. The layout is therefore per-shard-capable:
    # each process writes only the shards it owns (``<fname>.shard<rank>``)
    # plus one rank-0 meta file; a single-process run collapses to one
    # ordinary .params-format file readable by ``nd.load``. Resume is
    # bit-exact: master weights and state are stored in their storage dtype
    # (no fp32 round trip), and the global RNG key is part of the state so
    # dropout masks continue the same stream (tests/test_sharded_checkpoint).

    def prepare(self, *example_args):
        """Materialize sharded params + optimizer state without running a
        step (the resume entry point: prepare, then ``load_checkpoint``)."""
        self._prepare(example_args)

    def _require_prepared(self, what):
        if not self._prepared:
            raise MXNetError(
                f"ShardedTrainer.{what} needs the sharded state: call "
                "prepare(*example_args) or run a step first")

    def _struct_name(self, param):
        """Structural key ('features.0.weight') — instance-independent, so a
        checkpoint loads into a freshly-constructed net whose auto-generated
        name prefixes differ (same convention as Block.save_parameters)."""
        by_id = getattr(self, "_struct_cache", None)
        if by_id is None:
            by_id = {}
            for key, p in self._block._structural_names().items():
                by_id.setdefault(id(p), key)
            self._struct_cache = by_id
        return by_id.get(id(param), param.name)

    def _state_entries(self):
        """name -> placed jax.Array for every optimizer-state leaf."""
        out = {}
        for p, st in zip(self._trainable, self._states):
            for j, s in enumerate(st):
                out[f"state:{self._struct_name(p)}:{j}"] = s
        return out

    def _param_entries(self):
        out = {}
        for p in self._trainable:
            out[f"arg:{self._struct_name(p)}"] = p._data[0]._data
        for p in self._aux:
            out[f"aux:{self._struct_name(p)}"] = p._data[0]._data
        return out

    def _ckpt_meta(self, per_shard):
        meta = {
            "format": _ckpt.CKPT_FORMAT,
            "optimizer": type(self._optimizer).__name__,
            "num_update": int(self._num_update),
            "master_dtype": (str(self._master_dtype)
                             if self._master_dtype is not None else None),
            "state_arity": [len(st) for st in self._states],
            "per_shard": bool(per_shard),
            "shard_files": _ckpt.group().count(),
        }
        meta.update(_ckpt.rng_meta())
        return meta

    # file machinery shared with PipelinedTrainer — see parallel/_ckpt.py
    def _write_entries(self, fname, entries, meta):
        _ckpt.write_entries(fname, entries, meta)

    def _read_meta(self, fname):
        return _ckpt.read_meta(fname)

    def _read_pieces(self, fname, n_files):
        needed = _ckpt.needed_piece_keys(
            {**self._state_entries(), **self._param_entries()})
        return _ckpt.read_pieces(fname, n_files, needed)

    def _place_like(self, name, cur, loaded, pieces):
        return _ckpt.place_like(name, cur, loaded, pieces)

    def save_states(self, fname, per_shard=None):
        """Checkpoint optimizer state + step count + RNG stream.

        ``per_shard=None`` auto-selects: one plain ``.params``-format file
        in single-process runs, per-process shard files in multi-host runs.
        API parity: gluon.Trainer.save_states (ref: python/mxnet/gluon/
        trainer.py:save_states)."""
        self._require_prepared("save_states")
        if per_shard is None:
            per_shard = _ckpt.group().count() > 1
        self._write_entries(fname, self._state_entries(),
                            self._ckpt_meta(per_shard))

    def _check_states_meta(self, meta):
        """Shared contract checks for a ``.states`` meta (layout-locked
        and resharded loads alike): optimizer class, master storage
        dtype, state arity."""
        if meta["optimizer"] != type(self._optimizer).__name__:
            raise MXNetError(
                f"checkpoint was saved with optimizer {meta['optimizer']!r}, "
                f"trainer has {type(self._optimizer).__name__!r}")
        want_mdt = (str(self._master_dtype)
                    if self._master_dtype is not None else None)
        if meta.get("master_dtype") != want_mdt:
            raise MXNetError(
                f"checkpoint was saved with master_dtype="
                f"{meta.get('master_dtype')!r}, trainer has {want_mdt!r} — "
                "resume with the same storage dtype (a cast would change "
                "the training trajectory)")
        if meta["state_arity"] != [len(st) for st in self._states]:
            raise MXNetError("checkpoint state arity mismatch — different "
                             "optimizer config or parameter set")

    def load_states(self, fname):
        """Restore what ``save_states`` wrote. The trainer must be prepared
        with the same architecture, optimizer class, master_dtype and (for
        per-shard files) mesh layout."""
        self._require_prepared("load_states")
        meta, loaded = self._read_meta(fname)
        self._check_states_meta(meta)
        pieces = (self._read_pieces(fname, int(meta.get("shard_files", 1)))
                  if meta["per_shard"] else None)
        new_states = []
        for p, st in zip(self._trainable, self._states):
            new_states.append(tuple(
                self._place_like(f"state:{self._struct_name(p)}:{j}", s,
                                 loaded, pieces)
                for j, s in enumerate(st)))
        self._states = new_states
        self._num_update = int(meta["num_update"])
        self._optimizer.num_update = self._num_update
        _ckpt.restore_rng(meta)

    def save_checkpoint(self, prefix, per_shard=None):
        """Full resumable snapshot: ``<prefix>.params`` (master weights +
        aux state, exact storage dtype) and ``<prefix>.states`` (optimizer
        state, step count, RNG). Ref: mx.model checkpoint pair
        (python/mxnet/model.py save_checkpoint) lifted to sharded state."""
        self._require_prepared("save_checkpoint")
        if per_shard is None:
            per_shard = _ckpt.group().count() > 1
        self._write_entries(f"{prefix}.params", self._param_entries(),
                            self._ckpt_meta(per_shard))
        self.save_states(f"{prefix}.states", per_shard=per_shard)

    def load_checkpoint(self, prefix):
        """Bit-exact resume of ``save_checkpoint`` output onto a prepared
        trainer: training continues as if never interrupted
        (tests/test_sharded_checkpoint.py asserts bitwise equality)."""
        self._require_prepared("load_checkpoint")
        meta, loaded = self._read_meta(f"{prefix}.params")
        pieces = (self._read_pieces(f"{prefix}.params",
                                    int(meta.get("shard_files", 1)))
                  if meta["per_shard"] else None)
        for p in self._trainable:
            p._data[0]._rebind(self._place_like(
                f"arg:{self._struct_name(p)}", p._data[0]._data, loaded,
                pieces))
        for p in self._aux:
            p._data[0]._rebind(self._place_like(
                f"aux:{self._struct_name(p)}", p._data[0]._data, loaded,
                pieces))
        self.load_states(f"{prefix}.states")

    def checkpoint(self, ckpt_dir, step=None, keep_last=None,
                   per_shard=None):
        """Crash-consistent directory checkpoint (the commit protocol,
        docs/checkpointing.md): params + optimizer state staged under
        ``<ckpt_dir>/step-N.tmp/``, committed behind a rank-0 CRC
        manifest + rename, ``latest`` pointer moved, keep-last-k
        retention applied. ``step`` defaults to the trainer's completed
        update count. Returns the committed step."""
        self._require_prepared("checkpoint")
        step = int(self._num_update if step is None else step)
        return _ckpt.commit_checkpoint(
            ckpt_dir, step,
            lambda prefix: self.save_checkpoint(prefix,
                                                per_shard=per_shard),
            keep_last=keep_last)

    def restore(self, ckpt_dir, step=None, latest=True):
        """Resume from the newest *valid* committed step under
        ``ckpt_dir`` (or a pinned ``step``): a corrupt/torn newest
        checkpoint is skipped with a journaled ``ckpt_fallback`` and
        the next-newest intact one restored. The trainer must be
        prepared (same architecture/optimizer/mesh contract as
        ``load_checkpoint``). Returns the restored step."""
        self._require_prepared("restore")
        if step is None and not latest:
            raise MXNetError("restore needs step=N or latest=True")
        return _ckpt.restore_checkpoint(ckpt_dir, self.load_checkpoint,
                                        step=step)

    # -- elastic: survivor-mesh rebuild + resharded restore ------------------
    # (docs/elastic.md). Two lanes after a cohort shape change: rebuild
    # the mesh in place when this process still holds the state, or build
    # a fresh trainer and pull the newest committed checkpoint back in
    # through the topology-free reader.

    # module-level project_spec, kept as a method name because the
    # elastic lanes (and their tests) reach it through the trainer
    _spec_on = staticmethod(project_spec)

    def rebuild_mesh(self, mesh):
        """Re-place parameters, aux buffers, optimizer state and guard
        counters onto ``mesh`` and drop every compiled program (new
        shard counts invalidate the cached executable — the retrace is
        journaled, never silent). The current arrays must still be
        readable by this process: after losing a *remote* rank, build a
        fresh trainer and :meth:`restore_resharded` instead."""
        self._require_prepared("rebuild_mesh")
        from ..diagnostics.journal import get_journal
        old_n = self._mesh.devices.size if self._mesh is not None else 0
        self._tr_specs = [self._spec_on(mesh, s) for s in self._tr_specs]
        self._aux_specs = [self._spec_on(mesh, s) for s in self._aux_specs]
        self._mesh = mesh
        for p, spec in zip(self._trainable, self._tr_specs):
            p._data[0]._rebind(
                self._shard(_ckpt.gather_host(p._data[0]._data), spec))
        for p, spec in zip(self._aux, self._aux_specs):
            p._data[0]._rebind(
                self._shard(_ckpt.gather_host(p._data[0]._data), spec))
        self._states = [
            tuple(self._shard(_ckpt.gather_host(s), _state_spec(spec, s))
                  for s in st)
            for spec, st in zip(self._tr_specs, self._states)]
        self._guard_state = tuple(
            self._shard(_ckpt.gather_host(s), PartitionSpec())
            for s in self._guard_state)
        self._step_fn = None
        self._eval_fn = None
        self._multi_fns = {}
        self._program_batches = {}
        get_journal().event("elastic_retrace", reason="mesh_rebuild",
                            consumer=self._guard_consumer,
                            old_devices=int(old_n),
                            new_devices=int(mesh.devices.size))

    def load_checkpoint_resharded(self, prefix):
        """Topology-aware twin of :meth:`load_checkpoint`: assemble the
        global tree from however many shard files the SAVING cohort
        wrote (meta's recorded shard set, CRC-verified per piece) and
        re-place it onto THIS trainer's mesh — scale-down and scale-up
        alike. Bit-exact: same storage dtypes, same RNG stream."""
        self._require_prepared("load_checkpoint_resharded")
        from ..elastic import reshard as _reshard
        meta, entries = _reshard.read_global_entries(f"{prefix}.params")
        smeta, sentries = _reshard.read_global_entries(f"{prefix}.states")
        self._check_states_meta(smeta)

        def take(name, cur):
            src = sentries if name.startswith("state:") else entries
            if name not in src:
                raise MXNetError(f"checkpoint is missing entry {name!r}")
            return _reshard.place_global(name, cur, src[name])

        self._place_all(take)
        self._num_update = int(smeta["num_update"])
        self._optimizer.num_update = self._num_update
        _ckpt.restore_rng(smeta)
        _reshard.journal_reshard(prefix, self._num_update, meta,
                                 _ckpt.group().count(),
                                 {**entries, **sentries},
                                 self._guard_consumer)

    def restore_resharded(self, ckpt_dir, step=None):
        """Resume from the newest *valid* committed step under
        ``ckpt_dir`` onto the CURRENT topology, regardless of how many
        ranks wrote it (journaled ``ckpt_fallback`` past corrupt steps,
        ``reshard_restore`` on success). Returns the restored step."""
        self._require_prepared("restore_resharded")
        return _ckpt.restore_checkpoint(
            ckpt_dir, self.load_checkpoint_resharded, step=step)

    def _place_all(self, get):
        """Rebind every leaf — params, aux, optimizer state — through
        ``get(name, current_array)`` (the ONE traversal the resharded
        load and the cohort sync share; names match ``_param_entries``/
        ``_state_entries``)."""
        for p in self._trainable:
            p._data[0]._rebind(get(f"arg:{self._struct_name(p)}",
                                   p._data[0]._data))
        for p in self._aux:
            p._data[0]._rebind(get(f"aux:{self._struct_name(p)}",
                                   p._data[0]._data))
        self._states = [
            tuple(get(f"state:{self._struct_name(p)}:{j}", s)
                  for j, s in enumerate(st))
            for p, st in zip(self._trainable, self._states)]

    def _adopt_host_entries(self, entries):
        """Re-place host arrays over the live tree keeping each leaf's
        current sharding — the elastic driver's cohort sync point.
        Names absent from ``entries`` keep their current value."""
        from ..elastic import reshard as _reshard
        self._place_all(
            lambda name, cur: (_reshard.place_global(name, cur,
                                                     entries[name])
                               if name in entries else cur))

    # -- parity helpers ------------------------------------------------------
    @property
    def num_update(self):
        """Completed optimizer updates (restored by load_checkpoint) —
        the public step counter resume logic should read."""
        return self._num_update

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)


def allreduce_across_processes(arr):
    """Eager sum over worker processes — the kvstore ``dist_sync`` reduce
    (ref: src/kvstore/kvstore_dist.h PushImpl aggregate). Rides DCN via the
    JAX coordination service; identity in single-process runs."""
    import jax
    if jax.process_count() == 1:
        return arr
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(arr._data)
    return nd.NDArray(jnp.sum(gathered, axis=0), ctx=arr.ctx)
