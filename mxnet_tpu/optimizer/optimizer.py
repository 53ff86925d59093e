"""Optimizer classes (ref: python/mxnet/optimizer/optimizer.py).

Same design as the reference: an ``Optimizer`` holds hyperparameters +
per-weight state and calls the *fused update ops* (here
``mxnet_tpu/ops/optimizer_op.py``, jit-fused by XLA with donated buffers);
an ``Updater`` wraps it with a state dict keyed by weight index — the same
object the reference serializes to KVStore servers.

Multi-precision: like the reference's ``mp_*`` path, low-precision weights
(bf16/fp16) automatically keep an fp32 master copy in the state.
"""
from __future__ import annotations

import pickle

import numpy as np

from ..base import MXNetError
from .. import ndarray as nd

__all__ = ["Optimizer", "SGD", "NAG", "Adam", "AdamW", "LAMB", "RMSProp",
           "AdaGrad", "FTRL", "Signum", "SGLD", "AdaDelta", "Nadam",
           "DCASGD", "FTML", "Updater", "create", "register",
           "get_updater"]

_REGISTRY = {}


def register(klass):
    _REGISTRY[klass.__name__.lower()] = klass
    return klass


def create(name, **kwargs):
    if isinstance(name, Optimizer):
        return name
    name = name.lower()
    if name not in _REGISTRY:
        raise MXNetError(f"unknown optimizer {name!r}; "
                         f"known: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


class Optimizer:
    """ref: optimizer.py Optimizer — lr/wd multipliers per param, update
    counting for schedulers, state creation per weight."""

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 multi_precision=False, param_dict=None, begin_num_update=0):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.idx2name = dict(param_idx2name or {})
        self.param_dict = param_dict or {}
        self.lr_mult = {}
        self.wd_mult = {}

    # -- state --------------------------------------------------------------
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and weight.dtype != np.float32:
            master = weight.astype(np.float32)
            return (self.create_state(index, master), master)
        return self.create_state(index, weight)

    # -- bookkeeping ---------------------------------------------------------
    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index):
        lr = (self.lr_scheduler(self.num_update) if self.lr_scheduler
              else self.lr)
        return lr * self._get_lr_mult(index)

    def _get_lr_mult(self, index):
        """What the learning rate of parameter ``index`` is multiplied by."""
        if index in self.param_dict:
            return self.param_dict[index].lr_mult
        if index in self.lr_mult:
            return self.lr_mult[index]
        if index in self.idx2name:
            return self.lr_mult.get(self.idx2name[index], 1.0)
        return 1.0

    def _get_wd(self, index):
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise MXNetError("cannot set lr directly when lr_scheduler is set")
        self.lr = lr

    @property
    def learning_rate(self):
        return (self.lr_scheduler(self.num_update) if self.lr_scheduler
                else self.lr)

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = dict(args_wd_mult)

    def _common(self, index):
        return dict(lr=self._get_lr(index), wd=self._get_wd(index),
                    rescale_grad=self.rescale_grad,
                    clip_gradient=self.clip_gradient
                    if self.clip_gradient is not None else -1.0)

    # -- update --------------------------------------------------------------
    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_row_sparse(self, index, weight, rs_grad, state):
        """Apply this optimizer's own rule to ONLY the touched rows of a
        RowSparseNDArray gradient (the reference's lazy_update sparse
        semantics, ref: optimizer.py sgd/adam sparse paths +
        src/operator/optimizer_op.cc *_update row_sparse kernels):
        weight rows and state rows are gathered, the dense rule runs on
        the gathered slab, and results scatter back — untouched rows see
        no weight decay and no momentum decay."""
        from .. import ndarray as nd
        rows = np.asarray(rs_grad.indices)
        w_rows = nd.NDArray(weight._data[rows], _skip_device_put=True)
        g_rows = nd.NDArray(np.asarray(rs_grad.data), ctx=weight.ctx)

        def gather(s):
            if s is None:
                return None
            if isinstance(s, (tuple, list)):
                return tuple(gather(x) for x in s)
            return nd.NDArray(s._data[rows], _skip_device_put=True)

        def scatter(dst, src):
            if dst is None:
                return
            if isinstance(dst, (tuple, list)):
                for d, s in zip(dst, src):
                    scatter(d, s)
                return
            dst._rebind(dst._data.at[rows].set(src._data))

        state_rows = gather(state)
        self.update(index, w_rows, g_rows, state_rows)
        weight._rebind(weight._data.at[rows].set(w_rows._data))
        scatter(state, state_rows)

    def update_multi_precision(self, index, weight, grad, state):
        from ..ndarray.sparse import RowSparseNDArray
        if isinstance(grad, RowSparseNDArray):
            if self.multi_precision and weight.dtype != np.float32:
                inner_state, master = state
                rs32 = RowSparseNDArray(
                    np.asarray(grad.data, np.float32), grad.indices,
                    grad.shape, dtype=np.float32)
                self.update_row_sparse(index, master, rs32, inner_state)
                # write back only the touched rows — a full-table
                # master.astype() every step would erase the sparse win
                rows = np.asarray(grad.indices)
                weight._rebind(weight._data.at[rows].set(
                    master._data[rows].astype(weight.dtype)))
            else:
                self.update_row_sparse(index, weight, grad, state)
            return
        if self.multi_precision and weight.dtype != np.float32:
            inner_state, master = state
            grad32 = grad.astype(np.float32)
            self.update(index, master, grad32, inner_state)
            weight._rebind(master.astype(weight.dtype)._data)
        else:
            self.update(index, weight, grad, state)


@register
class SGD(Optimizer):
    """SGD with momentum (ref: optimizer.py SGD -> sgd_update/sgd_mom_update)."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx)
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        if state is None:
            nd.sgd_update(weight, grad, out=weight, **kw)
        else:
            w, m = nd.sgd_mom_update(weight, grad, state,
                                     momentum=self.momentum, **kw)
            weight._rebind(w._data)
            state._rebind(m._data)


@register
class NAG(Optimizer):
    """Nesterov SGD (ref: optimizer.py NAG)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx)
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        if state is None:
            nd.sgd_update(weight, grad, out=weight, **kw)
        else:
            w, m = nd.nag_mom_update(weight, grad, state,
                                     momentum=self.momentum, **kw)
            weight._rebind(w._data)
            state._rebind(m._data)


@register
class Adam(Optimizer):
    """Adam with the reference's bias-correction-in-lr formulation
    (ref: optimizer.py Adam)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx),
                nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        t = self._index_update_count[index]
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        kw["lr"] *= np.sqrt(coef2) / coef1
        mean, var = state
        w, m, v = nd.adam_update(weight, grad, mean, var, beta1=self.beta1,
                                 beta2=self.beta2, epsilon=self.epsilon, **kw)
        weight._rebind(w._data)
        mean._rebind(m._data)
        var._rebind(v._data)


@register
class AdamW(Adam):
    """Decoupled weight decay Adam (ref: contrib adamw)."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        t = self._index_update_count[index]
        kw["lr"] *= np.sqrt(1 - self.beta2 ** t) / (1 - self.beta1 ** t)
        mean, var = state
        w, m, v = nd.adamw_update(weight, grad, mean, var, beta1=self.beta1,
                                  beta2=self.beta2, epsilon=self.epsilon, **kw)
        weight._rebind(w._data)
        mean._rebind(m._data)
        var._rebind(v._data)


@register
class LAMB(Optimizer):
    """Layer-wise adaptive large-batch optimizer (ref: optimizer.py LAMB)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, lower_bound=None, upper_bound=None,
                 bias_correction=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lower_bound = lower_bound
        self.upper_bound = upper_bound
        self.bias_correction = bias_correction

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx),
                nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        t = self._index_update_count[index]
        mean, var = state
        g, m, v = nd.lamb_update_phase1(
            weight, grad, mean, var, beta1=self.beta1, beta2=self.beta2,
            epsilon=self.epsilon, t=t, bias_correction=self.bias_correction,
            wd=kw["wd"], rescale_grad=kw["rescale_grad"],
            clip_gradient=kw["clip_gradient"])
        r1 = nd.norm(weight)
        r2 = nd.norm(g)
        w = nd.lamb_update_phase2(
            weight, g, r1, r2, lr=kw["lr"],
            lower_bound=self.lower_bound if self.lower_bound else -1.0,
            upper_bound=self.upper_bound if self.upper_bound else -1.0)
        weight._rebind(w._data)
        mean._rebind(m._data)
        var._rebind(v._data)


@register
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1, self.gamma2, self.epsilon = gamma1, gamma2, epsilon
        self.centered = centered

    def create_state(self, index, weight):
        return nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        w, n = nd.rmsprop_update(weight, grad, state, gamma1=self.gamma1,
                                 epsilon=self.epsilon, **kw)
        weight._rebind(w._data)
        state._rebind(n._data)


@register
class AdaGrad(Optimizer):
    def __init__(self, learning_rate=0.01, eps=1e-7, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        w, h = nd.adagrad_update(weight, grad, state,
                                 epsilon=self.float_stable_eps, **kw)
        weight._rebind(w._data)
        state._rebind(h._data)


@register
class FTRL(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx),
                nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        z, n = state
        w, z2, n2 = nd.ftrl_update(weight, grad, z, n, lamda1=self.lamda1,
                                   beta=self.beta, **kw)
        weight._rebind(w._data)
        z._rebind(z2._data)
        n._rebind(n2._data)


@register
class Signum(Optimizer):
    """signSGD with momentum (ref: optimizer.py Signum)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum != 0.0:
            return nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx)
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        if state is None:
            nd.signsgd_update(weight, grad, out=weight, **kw)
        else:
            # momentum variant: m = beta*m - (1-beta)*grad; w += lr*sign(m)
            g = grad * self.rescale_grad
            if kw["clip_gradient"] > 0:
                g = nd.clip(g, -kw["clip_gradient"], kw["clip_gradient"])
            state._rebind((state * self.momentum - g * (1 - self.momentum))._data)
            weight._rebind((weight * (1 - kw["lr"] * self.wd_lh)
                            + nd.sign(state) * kw["lr"])._data)


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (ref: optimizer.py SGLD)."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        kw = self._common(index)
        g = grad * self.rescale_grad
        if kw["clip_gradient"] > 0:
            g = nd.clip(g, -kw["clip_gradient"], kw["clip_gradient"])
        noise = nd.random.normal(0, np.sqrt(kw["lr"]), shape=weight.shape,
                                 ctx=weight.ctx)
        weight._rebind((weight - kw["lr"] / 2 * (g + kw["wd"] * weight)
                        + noise)._data)


class Updater:
    """State-dict wrapper used by KVStore servers and Module
    (ref: optimizer.py Updater / get_updater)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = \
                self.optimizer.create_state_multi_precision(index, weight)
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.states[index])

    def get_states(self, dump_optimizer=False):
        states_np = {}
        for k, s in self.states.items():
            states_np[k] = _state_to_np(s)
        payload = (states_np, self.optimizer) if dump_optimizer else states_np
        return pickle.dumps(payload)

    def set_states(self, states):
        payload = pickle.loads(states)
        if isinstance(payload, tuple):
            states_np, self.optimizer = payload
        else:
            states_np = payload
        self.states = {k: _state_from_np(v) for k, v in states_np.items()}


def _state_to_np(s):
    if s is None:
        return None
    if isinstance(s, (tuple, list)):
        return tuple(_state_to_np(x) for x in s)
    return s.asnumpy()


def _state_from_np(s):
    if s is None:
        return None
    if isinstance(s, tuple):
        return tuple(_state_from_np(x) for x in s)
    return nd.array(s)


def get_updater(optimizer):
    return Updater(optimizer)


@register
class AdaDelta(Optimizer):
    """ref: optimizer.py AdaDelta (no learning rate in the update)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx),
                nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = nd.clip(g, -self.clip_gradient, self.clip_gradient)
        g = g + wd * weight
        acc_g, acc_delta = state
        acc_g_new = self.rho * acc_g + (1.0 - self.rho) * g * g
        delta = nd.sqrt(acc_delta + self.epsilon) / \
            nd.sqrt(acc_g_new + self.epsilon) * g
        acc_delta_new = self.rho * acc_delta + (1.0 - self.rho) * delta * delta
        acc_g._rebind(acc_g_new._data)
        acc_delta._rebind(acc_delta_new._data)
        weight._rebind((weight - delta)._data)


@register
class Nadam(Optimizer):
    """Adam with Nesterov momentum schedule (ref: optimizer.py Nadam)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx),
                nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = nd.clip(g, -self.clip_gradient, self.clip_gradient)
        g = g + wd * weight
        momentum_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (
            t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * 0.96 ** (
            (t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        mean, var = state
        m_new = self.beta1 * mean + (1.0 - self.beta1) * g
        v_new = self.beta2 * var + (1.0 - self.beta2) * g * g
        g_prime = g / (1.0 - self.m_schedule)
        m_prime = m_new / (1.0 - m_schedule_next)
        v_prime = v_new / (1.0 - self.beta2 ** t)
        m_bar = (1.0 - momentum_t) * g_prime + momentum_t_1 * m_prime
        mean._rebind(m_new._data)
        var._rebind(v_new._data)
        weight._rebind((weight - lr * m_bar /
                        (nd.sqrt(v_prime) + self.epsilon))._data)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (ref: optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lamda = lamda

    def create_state(self, index, weight):
        mom = None
        if self.momentum != 0.0:
            mom = nd.zeros(weight.shape, dtype=weight.dtype, ctx=weight.ctx)
        return (mom, weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = nd.clip(g, -self.clip_gradient, self.clip_gradient)
        mom, prev = state
        comp = g + wd * weight + self.lamda * g * g * (weight - prev)
        if mom is None:
            step = -lr * comp
        else:
            mom._rebind((self.momentum * mom - lr * comp)._data)
            step = mom
        prev._rebind(weight._data)
        weight._rebind((weight + step)._data)


@register
class FTML(Optimizer):
    """Follow the Moving Leader (ref: optimizer.py FTML / ftml_update)."""

    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        return tuple(nd.zeros(weight.shape, dtype=weight.dtype,
                              ctx=weight.ctx) for _ in range(3))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = nd.clip(g, -self.clip_gradient, self.clip_gradient)
        g = g + wd * weight
        d, v, z = state
        v_new = self.beta2 * v + (1.0 - self.beta2) * g * g
        d_new = (1.0 - self.beta1 ** t) / lr * (
            nd.sqrt(v_new / (1.0 - self.beta2 ** t)) + self.epsilon)
        sigma = d_new - self.beta1 * d
        z_new = self.beta1 * z + (1.0 - self.beta1) * g - sigma * weight
        v._rebind(v_new._data)
        d._rebind(d_new._data)
        z._rebind(z_new._data)
        weight._rebind((-z_new / d_new)._data)
