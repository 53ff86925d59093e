"""Gluon basic layers (ref: python/mxnet/gluon/nn/basic_layers.py).

Each layer is a thin HybridBlock over one registered operator, so the same
definition runs eagerly (mx.nd) and inside the jitted program produced by
``hybridize()``.
"""
from __future__ import annotations

import numpy as np

from ...base import MXNetError
from ...ops.contrib import MATMUL_EPILOGUE_ACTS
from ..block import Block, HybridBlock

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "BatchNorm",
           "LayerNorm", "RMSNorm", "GroupNorm", "InstanceNorm", "Embedding",
           "Flatten",
           "Lambda", "HybridLambda", "Activation", "LeakyReLU", "PReLU",
           "ELU", "SELU", "GELU", "Swish", "SyncBatchNorm"]


class Sequential(Block):
    """Stack of Blocks run in order (ref: nn.Sequential)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)(prefix=self._prefix, params=self._params)
            net._empty_prefix = True
            for layer in layers[key]:
                net.add(layer)
            return net
        return layers[key]

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(HybridBlock):
    """Stack of HybridBlocks, hybridizable as one program
    (ref: nn.HybridSequential)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def hybrid_forward(self, F, x):  # pragma: no cover - forward overrides
        raise NotImplementedError

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)(prefix=self._prefix, params=self._params)
            net._empty_prefix = True
            for layer in layers[key]:
                net.add(layer)
            return net
        return layers[key]

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """y = act(x W^T + b) (ref: nn.Dense → FullyConnected op).

    With ``activation`` in relu/tanh/sigmoid/gelu and a bias, the bias +
    activation (+ ``epilogue_dropout``) run as one fused epilogue over
    the matmul output through the guarded ``mxnet_tpu.pallas`` tier —
    one VMEM pass on TPU, the parity-gated XLA reference elsewhere.
    ``epilogue_dropout`` folds an inverted dropout (training only) into
    the same pass — the dropout-in-epilogue lever from
    ROADMAP.md S3/S4."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0, epilogue_dropout=0.0,
                 **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._flatten = flatten
        self._activation = activation
        self._epilogue_dropout = float(epilogue_dropout)
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(units, in_units), init=weight_initializer,
                dtype=dtype, allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(units,), init=bias_initializer,
                    dtype=dtype, allow_deferred_init=True)
            else:
                self.bias = None

    def infer_shape(self, x):
        in_units = int(np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight._set_shape((self._units, in_units))

    def hybrid_forward(self, F, x, weight, bias=None):
        # bias + activation (+ dropout) ride the matmul output in ONE pass
        # (docs/pallas.md) instead of FullyConnected's bias and a separate
        # Activation; gelu is epilogue-only (Activation has no gelu mode)
        fuse = bias is not None and (
            self._activation in MATMUL_EPILOGUE_ACTS
            or (self._activation is None and self._epilogue_dropout > 0))
        if fuse:
            out = F.FullyConnected(x, weight, num_hidden=self._units,
                                   no_bias=True, flatten=self._flatten)
            return F.contrib.matmul_epilogue(
                out, bias, act_type=self._activation or "identity",
                p=self._epilogue_dropout)
        if bias is None:
            out = F.FullyConnected(x, weight, num_hidden=self._units,
                                   no_bias=True, flatten=self._flatten)
        else:
            out = F.FullyConnected(x, weight, bias, num_hidden=self._units,
                                   no_bias=False, flatten=self._flatten)
        if self._activation == "gelu":
            # gelu lives on the LeakyReLU op, not Activation (bias-less
            # Dense can't take the fused-epilogue path above)
            out = F.LeakyReLU(out, act_type="gelu")
        elif self._activation is not None:
            out = F.Activation(out, act_type=self._activation)
        if self._epilogue_dropout > 0:
            out = F.Dropout(out, p=self._epilogue_dropout)
        return out

    def __repr__(self):
        shape = self.weight.shape
        return (f"Dense({shape[1] if shape else None} -> {self._units}, "
                f"{self._activation})")


class Dropout(HybridBlock):
    """Inverted dropout (ref: nn.Dropout)."""

    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        if self._rate <= 0:
            return x
        return F.Dropout(x, p=self._rate, axes=self._axes)

    def __repr__(self):
        return f"Dropout(p = {self._rate}, axes={self._axes})"


class BatchNorm(HybridBlock):
    """Batch normalization with running stats (ref: nn.BatchNorm).

    Running-stat update is functional: the op returns batch mean/var and the
    layer folds them into the aux parameters; under ``hybridize()`` the
    updated stats become extra outputs of the jitted program (see
    gluon/block.py docstring)."""

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0,
                 activation=None, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._momentum = momentum
        self._epsilon = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        # activation applied in the normalize pass (ops/nn.py BatchNorm
        # act_type=): act(scale*x+offset) in fp32, cast back once; no
        # extra params, so checkpoints are interchangeable with a
        # BatchNorm + Activation pair
        self._activation = activation
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True,
                differentiable=scale)
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True, differentiable=center)
            self.running_mean = self.params.get(
                "running_mean", shape=(in_channels,),
                init=running_mean_initializer, allow_deferred_init=True,
                differentiable=False)
            self.running_var = self.params.get(
                "running_var", shape=(in_channels,),
                init=running_variance_initializer, allow_deferred_init=True,
                differentiable=False)

    def infer_shape(self, x):
        channels = x.shape[self._axis]
        for param in (self.gamma, self.beta, self.running_mean,
                      self.running_var):
            param._set_shape((channels,))

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        from ... import autograd
        extra = {}
        if self._activation is not None:
            extra["act_type"] = self._activation
        out, mean, var = F.BatchNorm(
            x, gamma, beta, running_mean, running_var,
            eps=self._epsilon, momentum=self._momentum,
            fix_gamma=not self._scale, axis=self._axis,
            use_global_stats=self._use_global_stats, **extra)
        if autograd.is_training() and not self._use_global_stats:
            import jax.numpy as jnp
            m = self._momentum
            # cold-start: stats exactly at init (mean 0, var 1) adopt the
            # first batch's statistics outright instead of momentum-mixing
            # with the arbitrary init — so the op's running-mean moment
            # shift (ops/nn.py _batch_norm) is near the true mean from
            # step 2 on even for |mean|>>std inputs (torch's
            # num_batches_tracked warmup has the same effect). Tiny,
            # per-channel-vector-only compute; data-dependent via where
            # so it traces into jitted steps.
            cold = jnp.logical_and(jnp.all(running_mean._data == 0),
                                   jnp.all(running_var._data == 1))
            new_mean = jnp.where(
                cold, mean._data,
                running_mean._data * m + mean._data * (1 - m))
            # At COLD start the op's reported batch var can be destroyed
            # by cancellation (the zero-init shift; ops/nn.py) — and
            # adopting it outright would poison eval for many steps. The
            # cancellation test mean² >> var is only meaningful while the
            # shift is the init value, so it gates the COLD adoption
            # alone: suspicious channels keep the init var for one step
            # (the shift warms at step 2 via new_mean, after which the
            # op's var is sound and momentum-mixes normally — gating warm
            # steps on this data property would freeze running_var
            # forever for any |mean|/std > 64 channel).
            susp_cold = jnp.logical_and(
                cold,
                jnp.square(mean._data) > 4096.0 * jnp.maximum(
                    var._data.astype(mean._data.dtype), 1e-30))
            new_var = jnp.where(
                susp_cold, running_var._data,
                jnp.where(cold, var._data,
                          running_var._data * m + var._data * (1 - m)))
            running_mean._rebind(
                new_mean.astype(running_mean._data.dtype))
            running_var._rebind(new_var.astype(running_var._data.dtype))
        return out

    def __repr__(self):
        return (f"BatchNorm(axis={self._axis}, momentum={self._momentum}, "
                f"eps={self._epsilon}, in_channels="
                f"{self.gamma.shape[0] if self.gamma.shape else None})")


class SyncBatchNorm(BatchNorm):
    """Cross-device BatchNorm (ref: contrib.nn.SyncBatchNorm). On TPU the
    mesh-wide statistics come from ``psum`` inside the sharded program when
    run under mxnet_tpu.parallel; single-process semantics equal BatchNorm."""

    def __init__(self, in_channels=0, num_devices=None, momentum=0.9,
                 epsilon=1e-5, center=True, scale=True, **kwargs):
        super().__init__(axis=1, momentum=momentum, epsilon=epsilon,
                         center=center, scale=scale,
                         in_channels=in_channels, **kwargs)


class LayerNorm(HybridBlock):
    """ref: nn.LayerNorm."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True, differentiable=scale)
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True, differentiable=center)

    def infer_shape(self, x):
        channels = x.shape[self._axis]
        self.gamma._set_shape((channels,))
        self.beta._set_shape((channels,))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, axis=self._axis, eps=self._epsilon)


class RMSNorm(HybridBlock):
    """x / rms(x) * gamma over ``axis`` (no reference analog; the op
    computes in float32 and returns x's dtype)."""

    def __init__(self, axis=-1, epsilon=1e-6, gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True)

    def infer_shape(self, x):
        self.gamma._set_shape((x.shape[self._axis],))

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, axis=self._axis, eps=self._epsilon)


class GroupNorm(HybridBlock):
    """ref: nn.GroupNorm."""

    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._num_groups = num_groups
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True, differentiable=scale)
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True, differentiable=center)

    def infer_shape(self, x):
        self.gamma._set_shape((x.shape[1],))
        self.beta._set_shape((x.shape[1],))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.GroupNorm(x, gamma, beta, num_groups=self._num_groups,
                           eps=self._epsilon)


class InstanceNorm(HybridBlock):
    """ref: nn.InstanceNorm."""

    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True, differentiable=scale)
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True, differentiable=center)

    def infer_shape(self, x):
        self.gamma._set_shape((x.shape[1],))
        self.beta._set_shape((x.shape[1],))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.InstanceNorm(x, gamma, beta, eps=self._epsilon)


class Embedding(HybridBlock):
    """Lookup table (ref: nn.Embedding)."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._sparse_grad = sparse_grad
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim),
                init=weight_initializer, dtype=dtype,
                grad_stype="row_sparse" if sparse_grad else "default")

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, input_dim=self._input_dim,
                           output_dim=self._output_dim,
                           sparse_grad=self._sparse_grad)

    def __repr__(self):
        return f"Embedding({self._input_dim} -> {self._output_dim})"


class Flatten(HybridBlock):
    """ref: nn.Flatten."""

    def hybrid_forward(self, F, x):
        return F.Flatten(x)

    def __repr__(self):
        return "Flatten"


class Lambda(Block):
    """Wrap an arbitrary function as a Block (ref: nn.Lambda)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as F
            if not hasattr(F, function):
                raise MXNetError(f"nd has no function {function!r}")
            self._func = getattr(F, function)
            self._name = function
        else:
            self._func = function
            self._name = getattr(function, "__name__", "lambda")

    def forward(self, *args):
        return self._func(*args)

    def __repr__(self):
        return f"Lambda({self._name})"


class HybridLambda(HybridBlock):
    """ref: nn.HybridLambda."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            self._func = lambda F, *args: getattr(F, function)(*args)
            self._name = function
        else:
            self._func = function
            self._name = getattr(function, "__name__", "lambda")

    def hybrid_forward(self, F, x, *args):
        return self._func(F, x, *args)

    def __repr__(self):
        return f"HybridLambda({self._name})"


class Activation(HybridBlock):
    """ref: nn.Activation."""

    def __init__(self, activation, **kwargs):
        super().__init__(**kwargs)
        self._act_type = activation

    def _alias(self):
        return getattr(self, "_act_type", "activation")

    def hybrid_forward(self, F, x):
        return F.Activation(x, act_type=self._act_type)

    def __repr__(self):
        return f"Activation({self._act_type})"


class LeakyReLU(HybridBlock):
    """ref: nn.LeakyReLU."""

    def __init__(self, alpha, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="leaky", slope=self._alpha)

    def __repr__(self):
        return f"LeakyReLU({self._alpha})"


class PReLU(HybridBlock):
    """ref: nn.PReLU — learnable slope."""

    def __init__(self, alpha_initializer=None, in_channels=1, **kwargs):
        super().__init__(**kwargs)
        from ... import initializer
        if alpha_initializer is None:
            alpha_initializer = initializer.Constant(0.25)
        with self.name_scope():
            self.alpha = self.params.get("alpha", shape=(in_channels,),
                                         init=alpha_initializer)

    def hybrid_forward(self, F, x, alpha):
        return F.LeakyReLU(x, alpha, act_type="prelu")


class ELU(HybridBlock):
    """ref: nn.ELU."""

    def __init__(self, alpha=1.0, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    """ref: nn.SELU."""

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="selu")


class GELU(HybridBlock):
    """ref: nn.GELU."""

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="gelu")


class Swish(HybridBlock):
    """ref: nn.Swish."""

    def __init__(self, beta=1.0, **kwargs):
        super().__init__(**kwargs)
        self._beta = beta

    def hybrid_forward(self, F, x):
        return x * F.sigmoid(self._beta * x)
