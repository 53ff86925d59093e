"""Gluon Trainer (ref: python/mxnet/gluon/trainer.py).

Bridges Parameters ↔ KVStore ↔ Optimizer: ``step(batch_size)`` does the
gradient allreduce (if multi-replica / multi-host) then the optimizer update,
mirroring the reference's ``_allreduce_grads`` + ``_update`` flow
(SURVEY §3.2). The TPU fast path — gradients reduced by ``psum`` *inside*
the jitted step over ICI — lives in mxnet_tpu.parallel; this Trainer is the
eager/compatibility path and is exactly what the reference's API promises.

Anomaly guardrails (docs/guardrails.md): the finiteness decision is made
from the POST-allreduce gradients with one fused device-side reduction
(``guardrails.fused.guard_stats``) and a single scalar fetch — the old
per-step ``has_overflow`` per-gradient host pull is gone. Multi-process,
the scalar verdict is OR-reduced in one small allgather whose
participation never depends on rank-local state (kvstore type, whether
this rank passed a loss): every rank skips or none does, and no rank
can wedge a peer by sitting out the collective (the hang class an early
return out of a collective could hit).
"""
from __future__ import annotations

import numpy as np

from .. import kvstore as kvs
from .. import optimizer as opt
from ..base import MXNetError
from ..guardrails.monitor import (AnomalyMonitor, GuardConfig,
                                  handle_divergence)
from ..observability import instrument as _obs
from .parameter import Parameter

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None, guard=None):
        if isinstance(params, (dict,)) or hasattr(params, "values"):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise MXNetError("Trainer expects a ParameterDict or list of "
                             "Parameters")
        self._params = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise MXNetError(f"invalid parameter {param!r}")
            self._param2idx[param.name] = i
            self._params.append(param)
        self._compression_params = compression_params
        self._contains_sparse = False
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore_type = kvstore
        self._kvstore = None
        self._kv_initialized = False
        self._update_on_kvstore = update_on_kvstore
        self._optimizer_applied_on_kv = False
        self._guard_cfg = GuardConfig.coerce(guard)
        if self._guard_cfg is not None \
                and self._guard_cfg.mode == "deferred":
            # the fused trainers carry in-program skip counters that a
            # later guard_poll() can read; the eager path decides every
            # step on the host, so deferred's zero-read contract cannot
            # hold here — reject instead of silently running step-mode
            raise MXNetError(
                "GuardConfig(mode='deferred') needs a fused trainer "
                "(parallel.ShardedTrainer / PipelinedTrainer): the "
                "eager Trainer makes its skip decision on the host "
                "every step — use mode='step' (docs/guardrails.md)")
        self._monitor = (AnomalyMonitor(self._guard_cfg,
                                        consumer="gluon_trainer")
                         if self._guard_cfg is not None else None)
        self._step_count = 0
        self._skipped_steps = 0

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: p for i, p in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            if optimizer_params and set(optimizer_params) - {"rescale_grad"}:
                raise MXNetError("optimizer_params must be None when "
                                 "optimizer is an Optimizer instance")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)]

    def _init_kvstore(self):
        if self._kv_initialized:
            return
        if self._kvstore_type is None or self._kvstore_type is False:
            self._kvstore = None
        else:
            kv = self._kvstore_type if isinstance(self._kvstore_type,
                                                  kvs.KVStore) else \
                kvs.create(self._kvstore_type)
            multi_replica = any(len(p.list_ctx()) > 1 for p in self._params
                                if p.grad_req != "null")
            multi_host = kv.num_workers > 1
            if not multi_replica and not multi_host and \
                    not self._update_on_kvstore:
                kv = None  # single device, single host: pure local update
            self._kvstore = kv
            if kv is not None:
                update_on_kv = self._update_on_kvstore
                if update_on_kv is None:
                    update_on_kv = kv.type.startswith("dist")
                if self._compression_params:
                    kv.set_gradient_compression(self._compression_params)
                for i, param in enumerate(self._params):
                    if param.grad_req != "null":
                        kv.init(i, param.data(param.list_ctx()[0]))
                if update_on_kv:
                    kv.set_optimizer(self._optimizer)
                    self._optimizer_applied_on_kv = True
        self._kv_initialized = True

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def _check_grads(self):
        for param in self._params:
            if param.grad_req != "null" and param._grad is None:
                raise MXNetError(
                    f"parameter {param.name} has no gradient buffer — run "
                    f"forward inside autograd.record() and call backward() "
                    f"before step()")

    def _active_scaler(self):
        scaler = getattr(self, "_amp_loss_scaler", None)
        if scaler is not None:
            from ..contrib.amp import amp_dtype
            if amp_dtype() != "float16":
                # bf16 has fp32 exponent range: scale overflow cannot
                # trigger — skip the per-step finiteness check entirely
                scaler = None
        return scaler

    def _grad_arrays(self, first_replica_only=False):
        """Every live gradient AS THE UPDATE WILL CONSUME IT: the dense
        buffer normally, but the retained row-sparse view
        (``RowSparseNDArray``) when one is deposited — the dense buffer
        under a sparse deposit is still zeros, so guarding/clipping it
        would leave the rows ``_update`` actually applies unchecked. A
        consumed (stale) sparse view contributes nothing, matching
        ``_update`` applying nothing.

        ``first_replica_only=True`` is the post-allreduce view: with a
        reducing kvstore every replica holds the identical reduced
        gradient, so summing all of them would inflate the guard's
        global norm by ``sqrt(num_replicas)`` (wrong clip threshold,
        wrong journaled norm) — one replica per parameter is the true
        norm. Finiteness is unaffected either way."""
        out = []
        for p in self._params:
            if p.grad_req == "null":
                continue
            gs = []
            for g in (p._grad or ()):
                if g is None:
                    continue
                rs = getattr(g, "_sparse", None)
                if rs is None:
                    gs.append(g)
                elif not getattr(g, "_sparse_used", False):
                    gs.append(rs)
            if first_replica_only and gs:
                gs = gs[:1]
            out.extend(gs)
        return out

    def _grad_datas(self, first_replica_only=False):
        """`_grad_arrays` as raw arrays (the fused guard's view) —
        row-sparse views contribute their stored rows (host-resident;
        the device put is the cost of not guarding blind there)."""
        from ..ndarray.sparse import RowSparseNDArray
        return [g.data if isinstance(g, RowSparseNDArray) else g._data
                for g in self._grad_arrays(first_replica_only)]

    def step(self, batch_size, ignore_stale_grad=False, loss=None):
        """rescale by 1/batch_size, allreduce, update (ref: Trainer.step).

        With fp16 AMP and/or a :class:`~mxnet_tpu.guardrails.GuardConfig`
        attached, the finiteness decision rides ONE fused device-side
        reduction over the post-allreduce gradients (module docstring):
        a non-finite step skips the update (params/optimizer state
        untouched — ref: amp.py DynamicLossScaler skip-step), journals a
        ``nonfinite_grad`` record, halves the loss scale if one is
        active, and counts against the divergence budget.

        ``loss`` (optional, any loss NDArray — its mean is taken
        device-side) feeds the monitor's sustained-loss-spike divergence
        detection, folded into the guard's single host fetch. The fused
        trainers read the loss in-program; this eager path can only see
        it if the caller passes it — without it, only the
        consecutive-skip budget can trigger divergence here."""
        self._init_kvstore()
        self._check_grads()
        scaler = self._active_scaler()
        cfg = self._guard_cfg
        self._optimizer.rescale_grad = self._scale / batch_size
        if self._optimizer_applied_on_kv:
            self._reject_clip_on_kv(cfg)
            # update-on-kvstore applies the optimizer DURING push, so
            # the decision must precede the reduce: fused check over the
            # local pre-push grads, OR-reduced across processes (the one
            # remaining allgather — the local-update path below has
            # none). Without this the guard would be silently inert on
            # the kv path: a NaN push corrupts the params on the store.
            self._step_count += 1
            with _obs.call_span("gluon_trainer", "step",
                                step=self._step_count, on_kvstore=True):
                if (scaler is not None or cfg is not None) \
                        and not self._prepush_guard_ok(scaler, loss):
                    return
                with _obs.step_phase("gluon_trainer", "allreduce"):
                    self._allreduce_grads()
                if scaler is not None:
                    scaler.update_scale(False)
            return
        self._step_count += 1
        # telemetry (docs/observability.md): always-on phase summaries
        # (host clock only), spans under MXNET_TPU_TRACE
        with _obs.call_span("gluon_trainer", "step", step=self._step_count):
            with _obs.step_phase("gluon_trainer", "allreduce"):
                self._allreduce_grads()
            if scaler is not None or cfg is not None:
                # the flag must be agreed across processes: a non-dist
                # kvstore leaves grads rank-local (one rank skipping while
                # its peers update would silently fork params and
                # loss-scale trajectories), and a caller-passed loss is
                # per-rank local either way (a rank-local spike verdict
                # would roll back one rank alone) — _fetch_guard OR-reduces
                # unconditionally multi-process
                with _obs.step_phase("gluon_trainer", "guard_fetch"):
                    ok, gn, loss_v, gnorm_dev = self._fetch_guard(
                        self._grad_datas(first_replica_only=self._kvstore
                                         is not None),
                        loss)
                if not self._note_guard_outcome(ok, gn, scaler, loss_v):
                    return
                self._apply_guard_clip(gnorm_dev)
            with _obs.step_phase("gluon_trainer", "update"):
                self._update(ignore_stale_grad)
            if scaler is not None:
                scaler.update_scale(False)

    def _apply_guard_clip(self, gnorm_dev):
        """Global-norm clip reusing the guard's already-computed device
        norm: the threshold compares the EFFECTIVE (rescaled) gradient
        norm, and clip_global_norm skips its own reduction pass. Shared
        by step() and the manual update() flow."""
        cfg = self._guard_cfg
        if cfg is None or cfg.clip_norm is None:
            return
        from . import utils as gutils
        gutils.clip_global_norm(
            self._grad_arrays(),
            cfg.clip_norm / max(self._optimizer.rescale_grad, 1e-30),
            check_isfinite=False, global_norm=gnorm_dev)

    @staticmethod
    def _reject_clip_on_kv(cfg):
        if cfg is not None and cfg.clip_norm is not None:
            raise MXNetError(
                "GuardConfig.clip_norm is not supported on the "
                "update-on-kvstore path: the optimizer runs on the "
                "store during push, before a global norm over the "
                "REDUCED gradient exists to clip against — construct "
                "the Trainer with update_on_kvstore=False")

    @staticmethod
    def _loss_scalar(loss):
        """Caller-supplied loss as a traced fp32 mean scalar (None in →
        None out) — joins the guard's existing single host fetch."""
        if loss is None:
            return None
        import jax.numpy as jnp
        return jnp.mean(jnp.asarray(getattr(loss, "_data", loss))
                        .astype(jnp.float32))

    def _fetch_guard(self, grads, loss):
        """One fused reduction + ONE host fetch of this step's guard
        view. Multi-process, the flag is OR-reduced and the loss
        mean-reduced in a single small allgather so every rank reaches
        the same skip AND spike verdicts — participation is
        UNCONDITIONAL (never gated on the kvstore type or on whether
        this rank passed ``loss``): a rank-dependent decision to enter
        the collective is itself the deadlock class the guard exists to
        kill, so ranks may disagree about ``loss`` (a has-loss slot
        scopes the mean to the ranks that sent one) but never about
        participating. Returns ``(ok, global_norm, loss_mean_or_None,
        global_norm_device)`` — the device norm is for clip_global_norm
        reuse."""
        import jax

        from ..guardrails import fused
        loss_dev = self._loss_scalar(loss)
        finite_dev, gnorm_dev = fused.guard_stats(grads, loss=loss_dev)
        if jax.process_count() > 1:
            import jax.numpy as jnp
            from jax.experimental import multihost_utils
            # the gather vector is built DEVICE-side (fetching the
            # scalars first only to re-upload them for the collective
            # would double the per-step host round trips) and carries
            # the norm too, so the gathered result is this step's one
            # host read
            vec = np.asarray(multihost_utils.process_allgather(
                jnp.stack([jnp.where(finite_dev, 0.0, 1.0)
                           .astype(jnp.float32),
                           (loss_dev if loss_dev is not None
                            else jnp.float32(0.0)),
                           jnp.float32(0.0 if loss_dev is None else 1.0),
                           gnorm_dev])
            )).reshape(jax.process_count(), 4)
            ok = not vec[:, 0].any()
            senders = vec[:, 2].sum()
            # EVERY rank adopts the senders' loss mean — including
            # ranks that passed no loss: the spike-divergence verdict
            # is computed per-rank from this value, and a rank whose
            # monitor never sees the loss would keep training while its
            # peers roll back or raise (params fork / hang)
            loss_v = (float(vec[:, 1].sum() / senders) if senders > 0
                      else None)
            return ok, float(vec[jax.process_index(), 3]), loss_v, \
                gnorm_dev
        if loss_dev is not None:
            ok, gn, loss_v = fused.host_fetch(finite_dev, gnorm_dev,
                                              loss_dev)
        else:
            (ok, gn), loss_v = fused.host_fetch(finite_dev,
                                                gnorm_dev), None
        return ok, gn, loss_v, gnorm_dev

    def _note_guard_outcome(self, ok, gn, scaler, loss=None):
        """The skip/ok protocol shared by both step() paths: counters,
        loss-scale feedback, monitor observation, divergence handling.
        Returns True when the update may proceed — False on a skipped
        step OR a spike-triggered rollback (the pending gradients belong
        to the abandoned trajectory either way)."""
        if scaler is not None and gn is not None:
            # journal the UNscaled norm — parity with the fused trainers,
            # and stable across loss-scale halvings. _scale is the live
            # truth about what the grads carry: 1/loss_scale while
            # amp.scale_loss's scaling is still on them, 1.0 once
            # amp.unscale() has divided it back out (dividing by
            # loss_scale here again would understate the norm scale-fold)
            gn = gn * self._scale
        if ok:
            if self._monitor is not None:
                verdict = self._monitor.observe(self._step_count, True,
                                                loss=loss, grad_norm=gn)
                if verdict == "diverged":    # sustained finite-loss spike
                    self._handle_divergence()
                    return False
            return True
        self._skipped_steps += 1
        if scaler is not None:
            scaler.update_scale(True)
        if self._monitor is not None:
            verdict = self._monitor.observe(self._step_count, False,
                                            loss=loss, grad_norm=gn)
            if verdict == "diverged":
                self._handle_divergence()
        else:
            from ..guardrails.monitor import journal_scaler_only_skip
            journal_scaler_only_skip(self._step_count, gn, loss,
                                     "gluon_trainer",
                                     total_skips=self._skipped_steps)
        return False

    def _prepush_guard_ok(self, scaler, loss=None):
        """Pre-push finiteness decision for the update-on-kvstore path:
        one fused reduction over the local grads (all replicas — they
        are NOT yet reduced), flag OR-reduced + loss mean-reduced across
        processes so every rank reaches the same verdicts. Returns True
        when the push may proceed."""
        ok, gn, loss_v, _ = self._fetch_guard(self._grad_datas(), loss)
        return self._note_guard_outcome(ok, gn, scaler, loss_v)

    def allreduce_grads(self):
        self._init_kvstore()
        scaler = self._active_scaler()
        cfg = self._guard_cfg
        if self._optimizer_applied_on_kv \
                and (scaler is not None or cfg is not None):
            # manual flow on the update-on-kvstore path: the optimizer
            # runs on the store DURING this push, so the guard decision
            # must happen here, pre-push, exactly as in step() — a
            # skipped push IS the skip-step (update() applies nothing)
            self._reject_clip_on_kv(cfg)
            self._check_grads()
            self._step_count += 1
            if not self._prepush_guard_ok(scaler):
                return
            self._allreduce_grads()
            if scaler is not None:
                scaler.update_scale(False)
            return
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if getattr(param, "_grad_stype", "default") == "row_sparse" \
                    and any(getattr(g, "_sparse", None) is not None
                            for g in param.list_grad()):
                raise MXNetError(
                    f"parameter {param.name}: row-sparse gradients with a "
                    f"reducing kvstore (multi-replica / update_on_kvstore) "
                    f"are not supported — use kvstore=None (single device) "
                    f"or dense gradients; the dense buffer here would push "
                    f"stale zeros")
            grads = param.list_grad()
            if self._optimizer_applied_on_kv:
                self._kvstore.push(i, grads)
                self._kvstore.pull(i, out=param.list_data())
            else:
                self._kvstore.push(i, grads)
                self._kvstore.pull(i, out=grads)

    def update(self, batch_size, ignore_stale_grad=False):
        """Second half of the manual flow (``allreduce_grads();
        update()`` — gradient accumulation). Guarded identically to
        step(): with fp16 AMP or a GuardConfig attached, a non-finite
        gradient skips the update, journals, feeds the loss scale and
        the divergence budget — the manual flow must not be a silent
        hole in the defense. (On update-on-kvstore the optimizer
        already ran during ``allreduce_grads()``'s push, which carries
        the pre-push guard — nothing is applied here.)"""
        self._init_kvstore()
        self._check_grads()
        scaler = self._active_scaler()
        cfg = self._guard_cfg
        self._optimizer.rescale_grad = self._scale / batch_size
        guarded = scaler is not None or cfg is not None
        # one logical step per update() call — counted here in every
        # combination EXCEPT guarded update-on-kvstore, where the guarded
        # allreduce_grads() push already counted it (the checkpoint()
        # default step rides this counter, so it must track the manual
        # flow too, guarded or not)
        if not (guarded and self._optimizer_applied_on_kv):
            self._step_count += 1
        if not self._optimizer_applied_on_kv and guarded:
            ok, gn, loss_v, gnorm_dev = self._fetch_guard(
                self._grad_datas(first_replica_only=self._kvstore
                                 is not None),
                None)
            # loss_v is non-None only when a PEER rank sent a loss this
            # step (adopted mean) — it must feed the monitor here too or
            # this rank's divergence verdict forks from the senders'
            if not self._note_guard_outcome(ok, gn, scaler, loss_v):
                return
            self._apply_guard_clip(gnorm_dev)
            self._update(ignore_stale_grad)
            if scaler is not None:
                scaler.update_scale(False)
            return
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        if self._optimizer_applied_on_kv:
            return  # weights were updated on the kvstore and pulled back
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            for upd, arr, grad in zip(
                    self._updaters * len(param.list_data()),
                    param.list_data(), param.list_grad()):
                g = grad
                if getattr(param, "_grad_stype", "default") \
                        == "row_sparse":
                    rs = getattr(grad, "_sparse", None)
                    if rs is not None and \
                            not getattr(grad, "_sparse_used", False):
                        g = rs    # touched-rows-only update. The view
                        # stays readable (param.grad()) but is marked
                        # consumed so a step without a fresh backward
                        # doesn't re-apply it (the dense path's stale
                        # grad is the zero buffer).
                        grad._sparse_used = True
                    elif rs is not None:
                        continue  # stale sparse grad: nothing new to apply
                upd(i, g, arr)

    def _handle_divergence(self):
        # optimizer passed as a getter: restore() -> load_states replaces
        # self._optimizer, and the LR backoff must land on the new object
        handle_divergence(
            self._monitor, self._step_count,
            restore_fn=lambda: self.restore(self._guard_cfg.ckpt_root),
            optimizer=lambda: self._optimizer)

    @property
    def skipped_steps(self):
        """Steps skipped on a non-finite gradient so far."""
        return self._skipped_steps

    # -- commit-protocol checkpoint (docs/checkpointing.md) ------------------
    # The sharded trainers own the multi-host story; this is the eager
    # single-process equivalent so divergence rollback (guardrails) and
    # plain crash-consistent training work on the compatibility path too.
    def checkpoint(self, ckpt_dir, step=None, keep_last=None):
        """Stage params + optimizer state under ``<ckpt_dir>/step-N.tmp``
        and publish behind a CRC manifest + rename (resilience.commit).
        ``step`` defaults to the count of completed ``step()`` calls.
        Returns the committed step."""
        self._init_kvstore()
        from ..parallel import _ckpt

        def save_cb(prefix):
            self._save_params_file(f"{prefix}.params")
            self.save_states(f"{prefix}.states")

        step = int(self._step_count if step is None else step)
        return _ckpt.commit_checkpoint(ckpt_dir, step, save_cb,
                                       keep_last=keep_last)

    def restore(self, ckpt_dir, step=None):
        """Restore the newest CRC-valid committed step (corrupt/torn
        candidates journaled as ``ckpt_fallback`` and skipped). Returns
        the restored step."""
        self._init_kvstore()
        from ..parallel import _ckpt

        def load_cb(prefix):
            self._load_params_file(f"{prefix}.params")
            self.load_states(f"{prefix}.states")

        restored = _ckpt.restore_checkpoint(ckpt_dir, load_cb, step=step)
        self._step_count = restored
        if self._kvstore is not None and self._optimizer_applied_on_kv:
            # the kvstore holds the MASTER weights on this path (push
            # applies the optimizer to kv._store, pull copies store →
            # params): without a writeback the next step() would apply
            # grads to the store's un-restored diverged weights and the
            # pull would silently undo the rollback
            for i, param in enumerate(self._params):
                if param.grad_req == "null":
                    continue
                dst = self._kvstore._store.get(str(i))
                if dst is not None:
                    src = param.data(param.list_ctx()[0])
                    dst._rebind(src.as_in_context(dst.ctx)._data)
        return restored

    def _save_params_file(self, fname):
        from .. import ndarray as nd
        nd.save(fname, {p.name: p.data(p.list_ctx()[0])
                        for p in self._params})

    def _load_params_file(self, fname):
        from .. import ndarray as nd
        loaded = nd.load(fname)
        for p in self._params:
            if p.name not in loaded:
                raise MXNetError(f"checkpoint {fname} is missing "
                                 f"parameter {p.name!r}")
            # set_data (not a raw _rebind): per-context placement so
            # multi-replica trainers don't end up with every replica
            # aliasing one load-device array, and the shape check
            # rejects a wrong-shaped checkpoint entry here instead of
            # as an opaque mid-step error
            p.set_data(loaded[p.name])

    def save_states(self, fname):
        """ref: Trainer.save_states — optimizer/updater state checkpoint."""
        self._init_kvstore()
        if self._optimizer_applied_on_kv:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            from ..resilience.atomic import atomic_write
            with atomic_write(fname, "wb") as f:
                f.write(self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        self._init_kvstore()
        if self._optimizer_applied_on_kv:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._updater.optimizer
        else:
            with open(fname, "rb") as f:
                self._updaters[0].set_states(f.read())
            self._optimizer = self._updaters[0].optimizer
