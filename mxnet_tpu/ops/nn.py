"""Neural-network operators.

TPU-native equivalent of ``src/operator/nn/`` — the reference's cuDNN-backed
Convolution/Pooling/BatchNorm/etc. become ``lax.conv_general_dilated`` /
``lax.reduce_window`` / jnp compositions that XLA tiles onto the MXU. The
fused cuDNN RNN op (ref: src/operator/rnn.cc) becomes a ``lax.scan`` cell;
dropout threads explicit PRNG keys (JAX-idiomatic replacement for the
reference's Resource-managed RNG states, ref: src/resource.cc).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax

from ..base import MXNetError, _as_np_dtype
from .registry import OpParam, register


def _pair(v, n):
    v = tuple(v) if not isinstance(v, int) else (v,) * n
    if len(v) == 1:
        v = v * n
    return v


# ---------------------------------------------------------------------------
# FullyConnected (ref: src/operator/nn/fully_connected.cc)
# ---------------------------------------------------------------------------
@register("FullyConnected", num_inputs=-1,
          params=[OpParam("num_hidden", int, None, required=True),
                  OpParam("no_bias", bool, False),
                  OpParam("flatten", bool, True)],
          doc="y = x W^T + b (ref: src/operator/nn/fully_connected.cc); the "
              "canonical MXU matmul — keep batched and wide")
def _fully_connected(x, weight, *bias, num_hidden=None, no_bias=False, flatten=True):
    if flatten:
        x = x.reshape(x.shape[0], -1)
    y = jnp.matmul(x, weight.T)
    if not no_bias:
        y = y + bias[0]
    return y


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (ref: src/operator/nn/convolution.cc,
# src/operator/nn/cudnn/cudnn_convolution-inl.h — autotune is XLA's job here)
# ---------------------------------------------------------------------------
def _conv_dims(ndim):
    if ndim == 3:
        return ("NCW", "OIW", "NCW")
    if ndim == 4:
        return ("NCHW", "OIHW", "NCHW")
    if ndim == 5:
        return ("NCDHW", "OIDHW", "NCDHW")
    raise MXNetError(f"Convolution: unsupported input ndim {ndim}")


@register("Convolution", num_inputs=-1,
          params=[OpParam("kernel", tuple, None, required=True),
                  OpParam("stride", tuple, None),
                  OpParam("dilate", tuple, None),
                  OpParam("pad", tuple, None),
                  OpParam("num_filter", int, None, required=True),
                  OpParam("num_group", int, 1),
                  OpParam("no_bias", bool, False),
                  OpParam("layout", str, None),
                  OpParam("cudnn_tune", str, None),
                  OpParam("cudnn_off", bool, False),
                  OpParam("workspace", int, 1024)],
          doc="N-D convolution, NCHW/OIHW layouts "
              "(ref: src/operator/nn/convolution.cc ConvolutionCompute)")
def _convolution(x, weight, *bias, kernel=None, stride=None, dilate=None,
                 pad=None, num_filter=None, num_group=1, no_bias=False,
                 layout=None, cudnn_tune=None, cudnn_off=False, workspace=1024):
    nd = len(kernel)
    stride = _pair(stride or 1, nd)
    dilate = _pair(dilate or 1, nd)
    pad = _pair(pad or 0, nd)
    dn = lax.conv_dimension_numbers(x.shape, weight.shape, _conv_dims(x.ndim))
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group)
    if not no_bias:
        out = out + bias[0].reshape((1, -1) + (1,) * nd)
    return out


@register("Deconvolution", num_inputs=-1,
          params=[OpParam("kernel", tuple, None, required=True),
                  OpParam("stride", tuple, None),
                  OpParam("dilate", tuple, None),
                  OpParam("pad", tuple, None),
                  OpParam("adj", tuple, None),
                  OpParam("num_filter", int, None, required=True),
                  OpParam("num_group", int, 1),
                  OpParam("no_bias", bool, True),
                  OpParam("layout", str, None),
                  OpParam("workspace", int, 1024),
                  OpParam("cudnn_tune", str, None),
                  OpParam("cudnn_off", bool, False),
                  OpParam("target_shape", tuple, None)],
          doc="Transposed convolution (ref: src/operator/nn/deconvolution.cc)")
def _deconvolution(x, weight, *bias, kernel=None, stride=None, dilate=None,
                   pad=None, adj=None, num_filter=None, num_group=1,
                   no_bias=True, layout=None, workspace=1024, cudnn_tune=None,
                   cudnn_off=False, target_shape=None):
    nd = len(kernel)
    stride = _pair(stride or 1, nd)
    dilate = _pair(dilate or 1, nd)
    pad = _pair(pad or 0, nd)
    adj = _pair(adj or 0, nd)
    # grad-of-conv formulation: lhs_dilation=stride implements the transpose
    dn = lax.conv_dimension_numbers(x.shape, weight.shape, _conv_dims(x.ndim))
    k_eff = [(kernel[i] - 1) * dilate[i] + 1 for i in range(nd)]
    padding = [(k_eff[i] - 1 - pad[i], k_eff[i] - 1 - pad[i] + adj[i])
               for i in range(nd)]
    # weight layout for deconv in the reference is (in, out/g, *k): swap I/O and
    # flip spatial axes to express as a regular conv
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    if num_group > 1:
        ci = w.shape[0]
        w = w.reshape((num_group, ci // num_group) + w.shape[1:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((w.shape[0] * w.shape[1], ci // num_group) + w.shape[3:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    out = lax.conv_general_dilated(
        x, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if not no_bias and bias:
        out = out + bias[0].reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling (ref: src/operator/nn/pooling.cc)
# ---------------------------------------------------------------------------
@register("Pooling",
          params=[OpParam("kernel", tuple, ()),
                  OpParam("pool_type", str, "max"),
                  OpParam("global_pool", bool, False),
                  OpParam("stride", tuple, None),
                  OpParam("pad", tuple, None),
                  OpParam("pooling_convention", str, "valid"),
                  OpParam("count_include_pad", bool, True),
                  OpParam("cudnn_off", bool, False),
                  OpParam("layout", str, None)],
          doc="Max/avg/sum/lp pooling via lax.reduce_window "
              "(ref: src/operator/nn/pooling.cc)")
def _pooling(x, kernel=(), pool_type="max", global_pool=False, stride=None,
             pad=None, pooling_convention="valid", count_include_pad=True,
             cudnn_off=False, layout=None):
    nd = x.ndim - 2
    if global_pool:
        axes = tuple(range(2, x.ndim))
        if pool_type == "max":
            return jnp.max(x, axis=axes, keepdims=True)
        return jnp.mean(x, axis=axes, keepdims=True)
    kernel = _pair(kernel, nd)
    stride = _pair(stride or 1, nd)
    pad = _pair(pad or 0, nd)
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    if pooling_convention == "full":
        # ceil-mode: add extra right-padding so the last window fits
        extra = []
        for i in range(nd):
            size = x.shape[2 + i] + 2 * pad[i] - kernel[i]
            rem = size % stride[i]
            extra.append((stride[i] - rem) % stride[i] if rem else 0)
        padding = ((0, 0), (0, 0)) + tuple(
            (pad[i], pad[i] + extra[i]) for i in range(nd))
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            # python-level product: kernel is static, and a jnp.prod here
            # becomes a traced op under jit (float() then fails)
            import math
            return summed / float(math.prod(kernel))
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return summed / counts
    if pool_type == "lp":
        p = 2.0
        s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides, padding)
        return s ** (1.0 / p)
    raise MXNetError(f"Pooling: unknown pool_type {pool_type!r}")


# ---------------------------------------------------------------------------
# Activations (ref: src/operator/nn/activation.cc, leaky_relu.cc)
# ---------------------------------------------------------------------------
@register("Activation", params=[OpParam("act_type", str, None, required=True)],
          doc="ref: src/operator/nn/activation.cc")
def _activation(x, act_type=None):
    if act_type == "relu":
        return jnp.maximum(x, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(x)
    if act_type == "tanh":
        return jnp.tanh(x)
    if act_type == "softrelu":
        return jax.nn.softplus(x)
    if act_type == "softsign":
        return jax.nn.soft_sign(x)
    if act_type == "relu6":
        return jnp.clip(x, 0, 6)
    raise MXNetError(f"Activation: unknown act_type {act_type!r}")


@register("LeakyReLU", num_inputs=-1,
          params=[OpParam("act_type", str, "leaky"),
                  OpParam("slope", float, 0.25),
                  OpParam("lower_bound", float, 0.125),
                  OpParam("upper_bound", float, 0.334)],
          doc="leaky/prelu/elu/selu/gelu family (ref: src/operator/leaky_relu.cc)")
def _leaky_relu(x, *gamma, act_type="leaky", slope=0.25, lower_bound=0.125,
                upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(x >= 0, x, slope * x)
    if act_type == "prelu":
        g = gamma[0]
        if g.ndim == 1 and x.ndim > 1:
            g = g.reshape((1, -1) + (1,) * (x.ndim - 2))
        return jnp.where(x >= 0, x, g * x)
    if act_type == "elu":
        return jnp.where(x >= 0, x, slope * jnp.expm1(x))
    if act_type == "selu":
        return jax.nn.selu(x)
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(x >= 0, x, mid * x)
    raise MXNetError(f"LeakyReLU: unknown act_type {act_type!r}")


def _epilogue_act(out, act_type, dtype):
    """``act(out)`` cast to ``dtype``: the tail BatchNorm's ``act_type=``
    and ``contrib.conv_epilogue`` share. ``out`` is the float32 value the
    caller folded; the one cast back comes after the activation."""
    if act_type == "gelu":
        out = _leaky_relu(out, act_type="gelu")
    elif act_type in ("relu", "tanh", "sigmoid"):
        out = _activation(out, act_type=act_type)
    elif act_type not in (None, "identity"):
        from ..pallas import EPILOGUE_ACTS
        raise MXNetError(f"epilogue: unknown act_type {act_type!r}; one of "
                         f"{', '.join(EPILOGUE_ACTS)}")
    return out.astype(dtype)


@register("softmax", params=[OpParam("axis", int, -1),
                             OpParam("temperature", float, None),
                             OpParam("length", tuple, None),
                             OpParam("dtype", str, None)],
          doc="ref: src/operator/nn/softmax.cc")
def _softmax(x, axis=-1, temperature=None, length=None, dtype=None):
    if temperature:
        x = x / temperature
    out = jax.nn.softmax(x, axis=axis)
    return out.astype(_as_np_dtype(dtype)) if dtype else out


@register("log_softmax", params=[OpParam("axis", int, -1),
                                 OpParam("temperature", float, None)],
          doc="ref: src/operator/nn/softmax.cc log_softmax")
def _log_softmax(x, axis=-1, temperature=None):
    if temperature:
        x = x / temperature
    # max-shifted with fp32-accumulated row sums: under bf16 AMP this is one
    # fused read of x with no fp32 materialization of the full tensor (the
    # [tokens, vocab] MLM-head case is HBM-dominant otherwise)
    from .tensor import shifted_expsum
    _, shifted, se32 = shifted_expsum(x, axis=axis)
    return shifted - jnp.log(se32).astype(x.dtype)


@register("softmin", params=[OpParam("axis", int, -1)])
def _softmin(x, axis=-1):
    return jax.nn.softmax(-x, axis=axis)


@register("SoftmaxActivation", params=[OpParam("mode", str, "instance")])
def _softmax_activation(x, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(x, axis=1)
    # explicit product, not -1 (ambiguous on zero-size inputs)
    return jax.nn.softmax(x.reshape(x.shape[0], math.prod(x.shape[1:])),
                          axis=-1).reshape(x.shape)


# ---------------------------------------------------------------------------
# Normalization (ref: src/operator/nn/batch_norm.cc, layer_norm.cc,
# group_norm.cc, instance_norm.cc, l2_normalization.cc)
# ---------------------------------------------------------------------------
@register("BatchNorm", num_inputs=5, num_outputs=3, needs_mode=True,
          params=[OpParam("eps", float, 1e-3),
                  OpParam("momentum", float, 0.9),
                  OpParam("fix_gamma", bool, True),
                  OpParam("use_global_stats", bool, False),
                  OpParam("output_mean_var", bool, False),
                  OpParam("axis", int, 1),
                  OpParam("cudnn_off", bool, False),
                  OpParam("act_type", str, None,
                          doc="apply an activation (identity, relu, gelu, "
                              "tanh, sigmoid) in the normalize pass: "
                              "act(x*scale + offset) computed in float32 "
                              "on the array as it is and cast back once, "
                              "plain jax.numpy that XLA fuses into one "
                              "elementwise pass on every backend")],
          doc="Batch normalization. Inputs: data, gamma, beta, moving_mean, "
              "moving_var. Outputs: (out, batch_mean, batch_var) — like the "
              "reference's three NNVM outputs; running-stat update is done "
              "functionally by the caller (ref: src/operator/nn/batch_norm.cc)")
def _batch_norm(x, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9,
                fix_gamma=True, use_global_stats=False, output_mean_var=False,
                axis=1, cudnn_off=False, act_type=None, training=False):
    axes = tuple(i for i in range(x.ndim) if i != axis % x.ndim)

    def per_channel(v):
        # (C,) -> x's rank with C on `axis`: a broadcast, never a reshape
        return lax.expand_dims(v, axes)

    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    if training and not use_global_stats:
        # one-pass batch stats accumulated in fp32: a single fused read of x
        # instead of jnp.var's mean-then-centered-moments passes — this
        # keeps the op HBM-minimal under bf16 AMP, where the step is
        # bandwidth-bound (see docs/perf_notes.md). The raw E[x^2]-E[x]^2
        # form cancels catastrophically when |mean| >> std, so moments are
        # shifted by the running mean — the only shift that is FREE: any
        # same-pass data-derived shift (measured round 3: even one element
        # per channel) breaks XLA's reduce+normalize fusion and costs
        # 11-25% of RN50 throughput, and a lax.cond exact-recompute branch
        # fails to compile inside the differentiated scanned step. Safety
        # instead comes from two sides: (a) the gluon layer adopts the
        # first batch's stats outright at cold start (basic_layers.py), so
        # the shift is within O(std) of the true mean from step 2 on; (b)
        # in-op, channels where cancellation provably destroyed var
        # ((mean-c)² > 4095·var ⇒ >12 bits lost) fall back to e2 = the
        # second moment about c — a bounded, already-computed normalizer
        # (output std ≤ 1) instead of rsqrt(garbage) (the round-2 advisor
        # measured output std 158 at mean=1e4 on zero-init stats).
        c = lax.stop_gradient(moving_mean.astype(jnp.float32))
        xc = x.astype(jnp.float32) - per_channel(c)
        mean_c = jnp.mean(xc, axis=axes)
        e2 = jnp.mean(jnp.square(xc), axis=axes)
        var_raw = jnp.maximum(e2 - jnp.square(mean_c), 0.0)
        mean = mean_c + c
        suspicious = e2 > 4096.0 * jnp.maximum(var_raw, 1e-30)
        # normalize with the bounded fallback, but REPORT var_raw: the
        # layer detects the cancelled case as mean² >> reported var and
        # refuses to put it into the running stats (reporting e2 would
        # defeat that test — e2 ≈ mean² exactly when suspicious)
        var_norm = jnp.where(suspicious, e2, var_raw)
        var = var_raw
    else:
        mean = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
        var_norm = var
    # fold (mean, var, gamma, beta) into per-channel scale/offset in fp32,
    # cast once to the compute dtype: the normalize pass over x is then a
    # single fused multiply-add in x's dtype (no fp32 upcast of the tensor)
    inv = lax.rsqrt(var_norm + eps)
    scale = inv * gamma.astype(jnp.float32)
    offset = beta.astype(jnp.float32) - mean * scale
    if act_type is None:
        out = x * per_channel(scale.astype(x.dtype)) \
            + per_channel(offset.astype(x.dtype))
    else:
        # BN+activation: the multiply-add stays in fp32 up to the
        # activation and is cast back once, on x's own layout, so XLA
        # fuses it into one elementwise pass next to the convolutions
        out = _epilogue_act(
            x.astype(jnp.float32) * per_channel(scale)
            + per_channel(offset), act_type, x.dtype)
    return out, mean.astype(moving_mean.dtype), var.astype(moving_var.dtype)


def _moments_acc(x, axes):
    """Centered two-pass moments with accumulation in at least fp32
    (fp64 stays fp64): safe for |mean| >> std inputs — the raw one-pass
    E[x^2]-E[x]^2 form cancels catastrophically there, and bf16
    accumulation (x's own dtype) loses the variance of wide rows.
    BatchNorm keeps its one-pass form because its running mean provides
    a stable shift (see _batch_norm)."""
    acc = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(acc)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    return mean, var


@register("LayerNorm", num_inputs=3,
          params=[OpParam("axis", int, -1), OpParam("eps", float, 1e-5),
                  OpParam("output_mean_var", bool, False)],
          doc="ref: src/operator/nn/layer_norm.cc")

def _layer_norm(x, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    mean, var = _moments_acc(x, axis)
    inv = lax.rsqrt(var + eps)
    bshape = [1] * x.ndim
    bshape[axis % x.ndim] = x.shape[axis % x.ndim]
    out = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("GroupNorm", num_inputs=3,
          params=[OpParam("num_groups", int, 1), OpParam("eps", float, 1e-5)],
          doc="ref: src/operator/nn/group_norm.cc")
def _group_norm(x, gamma, beta, num_groups=1, eps=1e-5):
    n, c = x.shape[0], x.shape[1]
    g = num_groups
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean, var = _moments_acc(xg, axes)
    xg = (xg - mean.astype(xg.dtype)) \
        * lax.rsqrt(var + eps).astype(xg.dtype)
    out = xg.reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("InstanceNorm", num_inputs=3, params=[OpParam("eps", float, 1e-3)],
          doc="ref: src/operator/instance_norm.cc")
def _instance_norm(x, gamma, beta, eps=1e-3):
    axes = tuple(range(2, x.ndim))
    mean, var = _moments_acc(x, axes)
    out = (x - mean.astype(x.dtype)) \
        * lax.rsqrt(var + eps).astype(x.dtype)
    bshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("L2Normalization",
          params=[OpParam("eps", float, 1e-10), OpParam("mode", str, "instance")],
          doc="ref: src/operator/l2_normalization.cc")
def _l2_normalization(x, eps=1e-10, mode="instance"):
    if mode == "instance":
        norm = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1) + eps)
        return x / norm.reshape((-1,) + (1,) * (x.ndim - 1))
    if mode == "channel":
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + eps)
        return x / norm
    if mode == "spatial":
        axes = tuple(range(2, x.ndim))
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
        return x / norm
    raise MXNetError(f"L2Normalization: unknown mode {mode!r}")


@register("RMSNorm", num_inputs=2,
          params=[OpParam("axis", int, -1), OpParam("eps", float, 1e-6)],
          doc="RMSNorm (new op — modern LLM parity; no reference analog)")
def _rms_norm(x, gamma, axis=-1, eps=1e-6):
    # the mean of squares and the scaling in float32 whatever x's dtype (a
    # bfloat16 sum over thousands of channels loses the norm), one cast back
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=axis, keepdims=True)
    return (x32 * lax.rsqrt(ms + eps)
            * gamma.astype(jnp.float32)).astype(x.dtype)


def yarn_mscale(factor, mscale):
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1`` for a
    factor over 1, else 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inverse_frequencies(dim, theta, factor, original_length, beta_fast,
                             beta_slow):
    """The ``dim / 2`` inverse frequencies of YaRN (Peng et al., 2023, as
    DeepSeek-V2 applies it), float64 numpy: ``theta^(-2i/dim)`` where a
    coordinate turns more than ``beta_fast`` times over ``original_length``
    positions, that divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, and a linear ramp over the coordinates between, whose
    ends are the floor and the ceiling of ``d(n) = dim ln(original_length /
    (2 pi n)) / (2 ln theta)``."""
    def turns(n):
        return dim * math.log(original_length / (n * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(turns(beta_fast)), 0)
    hi = min(math.ceil(turns(beta_slow)), dim - 1)
    if hi == lo:
        hi += 0.001
    extra = theta ** (-_np.arange(0, dim, 2, dtype=_np.float64) / dim)
    ramp = _np.clip((_np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    return extra * (1.0 - ramp) + extra / factor * ramp


@register("_contrib_rotary_embedding", num_inputs=1,
          params=[OpParam("theta", float, 10000.0),
                  OpParam("scaling_factor", float, None),
                  OpParam("original_max_position_embeddings", int, 4096),
                  OpParam("beta_fast", float, 32.0),
                  OpParam("beta_slow", float, 1.0),
                  OpParam("mscale", float, 1.0),
                  OpParam("mscale_all_dim", float, 0.0)],
          doc="Rotary position embedding over the whole last axis of x (B, "
              "S, heads, D), D even, rotate-half convention: coordinates i "
              "and i + D/2 of row s turn by the angle s * theta^(-2i/D), s = "
              "0 .. S-1. With scaling_factor, YaRN's frequencies "
              "(yarn_inverse_frequencies: original_max_position_embeddings, "
              "beta_fast, beta_slow) and cos and sin times yarn_mscale("
              "factor, mscale) / yarn_mscale(factor, mscale_all_dim). Angles, "
              "sines and the rotation in float32, returned in x's dtype "
              "(new op; no reference analog)")
def _rotary_embedding(x, theta=10000.0, scaling_factor=None,
                      original_max_position_embeddings=4096, beta_fast=32.0,
                      beta_slow=1.0, mscale=1.0, mscale_all_dim=0.0):
    if x.ndim != 4 or x.shape[-1] % 2:
        raise MXNetError(f"rotary_embedding: x (B, S, heads, D) with D even "
                         f"expected, got {x.shape}")
    half = x.shape[-1] // 2
    if scaling_factor is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        inv_freq = jnp.asarray(yarn_inverse_frequencies(
            x.shape[-1], theta, scaling_factor,
            original_max_position_embeddings, beta_fast, beta_slow),
            jnp.float32)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angles)[:, None, :]               # one angle for all heads
    sin = jnp.sin(angles)[:, None, :]
    if scaling_factor is not None:
        ratio = yarn_mscale(scaling_factor, mscale) \
            / yarn_mscale(scaling_factor, mscale_all_dim)
        if ratio != 1.0:
            cos, sin = cos * ratio, sin * ratio
    lo, hi = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Dropout (ref: src/operator/nn/dropout.cc) — explicit PRNG key threading
# ---------------------------------------------------------------------------
@register("Dropout", needs_rng=True, needs_mode=True,
          params=[OpParam("p", float, 0.5),
                  OpParam("mode", str, "training"),
                  OpParam("axes", tuple, ())],
          doc="Inverted dropout; rng key threaded explicitly "
              "(ref: src/operator/nn/dropout.cc)")
def _dropout(x, rng=None, p=0.5, mode="training", axes=(), training=False):
    if p <= 0 or (not training and mode != "always"):
        return x
    shape = list(x.shape)
    for a in axes:
        shape[a] = 1
    # one random BYTE per element, not bernoulli's uint32+float compare:
    # 4x less generator work and mask traffic — dropout-mask generation
    # measured 24% of a BERT step before the rbg+bits treatment
    # (docs/perf_notes.md round 3). Keep-probability granularity is
    # 1/256, immaterial for dropout rates.
    bits = jax.random.bits(rng, tuple(shape), dtype=jnp.uint8)
    # ONE definition of the keep threshold (pallas.keep_threshold): the
    # fused matmul-epilogue's bit-identical-mask contract depends on it
    from ..pallas.kernels import keep_threshold
    keep = bits >= jnp.uint8(keep_threshold(p))
    return jnp.where(keep, x / (1.0 - p), jnp.zeros_like(x))


# ---------------------------------------------------------------------------
# Embedding (ref: src/operator/tensor/indexing_op.cc EmbeddingOpForward)
# ---------------------------------------------------------------------------
@register("Embedding", num_inputs=2,
          params=[OpParam("input_dim", int, None, required=True),
                  OpParam("output_dim", int, None, required=True),
                  OpParam("dtype", str, "float32"),
                  OpParam("sparse_grad", bool, False)],
          doc="Lookup table (ref: indexing_op.cc Embedding)")
def _embedding(indices, weight, input_dim=None, output_dim=None,
               dtype="float32", sparse_grad=False):
    ids = indices.astype(jnp.int32)
    grouped = _grouped_table_gradient(ids, weight)
    if isinstance(ids, jax.core.Tracer):
        _count_traced_embedding(weight, ids, grouped)
    if grouped:
        return _take_rows(weight.shape[0], weight, ids)
    return jnp.take(weight, ids, axis=0)


EMBEDDING_GRAD_COUNT_METRIC = "mxnet_tpu_embedding_grads_traced_total"


def _grouped_table_gradient(ids, weight):
    """Whether the lookup's backward is the tier's ``embedding_grad``: where
    the tier would take it (on a TPU, not partitioned by the compiler, not
    switched off). Asked in the forward, so everywhere else the lookup is
    the plain ``jnp.take`` and its backward autodiff's, to the instruction.
    On a v5e the kernel is no slower than autodiff's scatter-add at any of
    the tables the benchmark's cells run, 768 to 5120 units wide, and 14
    times faster from 4232 units up, where XLA gathers the sorted cotangent
    in a fusion of its own first (PERF.md sec. 6, PR 39)."""
    from ..pallas.registry import declines
    cotangent = jax.ShapeDtypeStruct(ids.shape + weight.shape[1:],
                                     weight.dtype)
    return declines("embedding_grad", ids, cotangent,
                    rows=weight.shape[0]) is None


def _count_traced_embedding(weight, ids, grouped):
    """One lookup traced into a program, by the table's rows and units, the
    ids looked up and the form its table's gradient takes (``grouped``: the
    tier's kernel where the program is lowered for a TPU; ``scatter``:
    autodiff's): trace-time only, so a compiled step never counts."""
    from ..observability.metrics import default_registry
    default_registry().counter(
        EMBEDDING_GRAD_COUNT_METRIC,
        "embedding lookups traced into a program, by their table "
        "gradient's form", ("rows", "units", "tokens", "path")).labels(
            rows=str(weight.shape[0]), units=str(weight.shape[-1]),
            tokens=str(ids.size),
            path="grouped" if grouped else "scatter").inc()


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _take_rows(rows, weight, ids):
    return jnp.take(weight, ids, axis=0)


def _take_rows_fwd(rows, weight, ids):
    return jnp.take(weight, ids, axis=0), ids


def _take_rows_bwd(rows, ids, cotangent):
    from ..pallas import dispatch
    return dispatch("embedding_grad", ids, cotangent, rows=rows), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


# ---------------------------------------------------------------------------
# SoftmaxOutput — softmax forward + CE gradient in backward, the Module-era
# classification head (ref: src/operator/softmax_output.cc)
# ---------------------------------------------------------------------------
def _softmax_output_fwd(data, label, grad_scale, ignore_label, multi_output,
                        use_ignore, normalization, out_grad, smooth_alpha):
    return jax.nn.softmax(data, axis=-1)


@jax.custom_vjp
def _softmax_output_core(data, label, grad_scale, ignore_label, use_ignore):
    return jax.nn.softmax(data, axis=-1)


def _softmax_output_core_fwd(data, label, grad_scale, ignore_label, use_ignore):
    out = jax.nn.softmax(data, axis=-1)
    return out, (out, label, grad_scale, ignore_label, use_ignore)


def _softmax_output_core_bwd(res, g):
    out, label, grad_scale, ignore_label, use_ignore = res
    num_classes = out.shape[-1]
    onehot = jax.nn.one_hot(label.astype(jnp.int32), num_classes, dtype=out.dtype)
    grad = (out - onehot) * grad_scale
    if use_ignore:
        mask = (label != ignore_label).astype(out.dtype)
        grad = grad * mask[..., None]
    # reference ignores incoming head gradient (it's a terminal loss op)
    return grad, jnp.zeros_like(label, dtype=out.dtype), None, None, None


_softmax_output_core.defvjp(_softmax_output_core_fwd, _softmax_output_core_bwd)


@register("SoftmaxOutput", num_inputs=2,
          params=[OpParam("grad_scale", float, 1.0),
                  OpParam("ignore_label", float, -1.0),
                  OpParam("multi_output", bool, False),
                  OpParam("use_ignore", bool, False),
                  OpParam("preserve_shape", bool, False),
                  OpParam("normalization", str, "null"),
                  OpParam("out_grad", bool, False),
                  OpParam("smooth_alpha", float, 0.0)],
          doc="Softmax with cross-entropy backward "
              "(ref: src/operator/softmax_output.cc)")
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    orig_shape = data.shape
    if multi_output and data.ndim > 2:
        # (N, C, d...) -> softmax over C per spatial position
        data2 = jnp.moveaxis(data, 1, -1)
        out = _softmax_output_core(data2.reshape(-1, data2.shape[-1]),
                                   label.reshape(-1).astype(data.dtype),
                                   grad_scale, ignore_label, use_ignore)
        out = out.reshape(data2.shape)
        return jnp.moveaxis(out, -1, 1)
    if data.ndim > 2 and not preserve_shape:
        data = data.reshape(data.shape[0], -1)
    return _softmax_output_core(data, label.astype(data.dtype), grad_scale,
                                ignore_label, use_ignore).reshape(orig_shape)


def _regression_core(link, grad_fn):
    @jax.custom_vjp
    def core(data, label, grad_scale):
        return link(data)

    def fwd(data, label, grad_scale):
        return link(data), (link(data), label, grad_scale)

    def bwd(res, g):
        out, label, grad_scale = res
        n = out.shape[1] if out.ndim > 1 else 1
        grad = grad_fn(out, label.reshape(out.shape)) * grad_scale / n
        return grad, jnp.zeros_like(out), None

    core.defvjp(fwd, bwd)
    return core


_linear_reg = _regression_core(lambda x: x, lambda o, l: o - l)
_mae_reg = _regression_core(lambda x: x, lambda o, l: jnp.sign(o - l))
_logistic_reg = _regression_core(lambda x: jax.nn.sigmoid(x),
                                 lambda o, l: o - l)


@register("LinearRegressionOutput", num_inputs=2,
          params=[OpParam("grad_scale", float, 1.0)],
          doc="Identity forward, (pred-label) backward "
              "(ref: src/operator/regression_output.cc)")
def _linear_regression_output(data, label, grad_scale=1.0):
    return _linear_reg(data, label.astype(data.dtype), grad_scale)


@register("MAERegressionOutput", num_inputs=2,
          params=[OpParam("grad_scale", float, 1.0)],
          doc="ref: src/operator/regression_output.cc (MAE head)")
def _mae_regression_output(data, label, grad_scale=1.0):
    return _mae_reg(data, label.astype(data.dtype), grad_scale)


@register("LogisticRegressionOutput", num_inputs=2,
          params=[OpParam("grad_scale", float, 1.0)],
          doc="Sigmoid forward, (sigmoid-label) backward "
              "(ref: src/operator/regression_output.cc)")
def _logistic_regression_output(data, label, grad_scale=1.0):
    return _logistic_reg(data, label.astype(data.dtype), grad_scale)


@register("MakeLoss", params=[OpParam("grad_scale", float, 1.0),
                              OpParam("valid_thresh", float, 0.0),
                              OpParam("normalization", str, "null")],
          doc="Marks a symbol as a loss: forward=identity, backward=grad_scale "
              "(ref: src/operator/make_loss.cc)")
def _make_loss(x, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    @jax.custom_vjp
    def core(v):
        return v

    def fwd(v):
        return v, v.shape

    def bwd(shape, g):
        return (jnp.full(shape, grad_scale),)

    core.defvjp(fwd, bwd)
    return core(x)


@register("smooth_l1", params=[OpParam("scalar", float, 1.0)],
          doc="Huber-like loss elementwise (ref: src/operator/tensor/"
              "elemwise_binary_scalar_op_extended.cc smooth_l1)")
def _smooth_l1(x, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * jnp.square(x),
                     jnp.abs(x) - 0.5 / s2)


# ---------------------------------------------------------------------------
# Fused RNN (ref: src/operator/rnn.cc — cuDNN fused multi-layer RNN).
# Parameters arrive as ONE flat vector in cuDNN layout order, exactly like the
# reference, so checkpoints/scripts port directly. Compute is lax.scan over
# time — XLA compiles to a tight TPU loop.
# ---------------------------------------------------------------------------
def _rnn_gate_count(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def _rnn_unpack(params, mode, num_layers, input_size, state_size, bidirectional,
                projection_size=None):
    """Slice the flat param vector into per-layer (Wx, Wh, bx, bh[, Wr])
    in the reference's layout: all weights first (layer-major, i2h then
    h2h then the LSTMP projection when present, directions interleaved),
    then all biases (ref: rnn-inl.h GetRnnParamSize incl. LSTMP)."""
    g = _rnn_gate_count(mode)
    d = 2 if bidirectional else 1
    proj = projection_size
    h_out = proj if proj else state_size      # recurrent/output width
    off = 0
    sizes = []
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else h_out * d
        for _dir in range(d):
            sizes.append(("wx", g * state_size, in_sz))
            sizes.append(("wh", g * state_size, h_out))
            if proj:
                sizes.append(("wr", proj, state_size))
    mats = []
    for kind, r, c in sizes:
        mats.append(params[off:off + r * c].reshape(r, c))
        off += r * c
    biases = []
    for layer in range(num_layers):
        for _dir in range(d):
            biases.append(params[off:off + g * state_size]); off += g * state_size
            biases.append(params[off:off + g * state_size]); off += g * state_size
    out = []
    mi = 0
    bi = 0
    per_dir = 3 if proj else 2
    for layer in range(num_layers):
        dirs = []
        for _dir in range(d):
            wx, wh = mats[mi], mats[mi + 1]
            wr = mats[mi + 2] if proj else None
            mi += per_dir
            bx, bh = biases[bi], biases[bi + 1]; bi += 2
            dirs.append((wx, wh, bx, bh, wr))
        out.append(dirs)
    return out


def _rnn_cell_step(mode, carry, x_t, wx, wh, bx, bh, state_size,
                   wr=None):
    if mode == "lstm":
        h, c = carry
        gates = x_t @ wx.T + bx + h @ wh.T + bh
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c = f * c + i * g
        h = o * jnp.tanh(c)
        if wr is not None:           # LSTMP: project the hidden state
            h = h @ wr.T
        return (h, c), h
    if mode == "gru":
        h = carry[0]
        gx = x_t @ wx.T + bx
        gh = h @ wh.T + bh
        rx, zx, nx = jnp.split(gx, 3, axis=-1)
        rh, zh, nh = jnp.split(gh, 3, axis=-1)
        r = jax.nn.sigmoid(rx + rh)
        z = jax.nn.sigmoid(zx + zh)
        n = jnp.tanh(nx + r * nh)
        h = (1 - z) * n + z * h
        return (h,), h
    act = jnp.tanh if mode == "rnn_tanh" else (lambda v: jnp.maximum(v, 0))
    h = carry[0]
    h = act(x_t @ wx.T + bx + h @ wh.T + bh)
    return (h,), h


def _rnn_layer_scan(mode, x, h0, c0, weights, state_size, reverse=False,
                    seq_len=None):
    wx, wh, bx, bh, wr = weights
    carry0 = (h0, c0) if mode == "lstm" else (h0,)
    T = x.shape[0]

    def step(carry, inp):
        x_t, t = inp
        new_carry, y = _rnn_cell_step(mode, carry, x_t, wx, wh, bx, bh,
                                      state_size, wr=wr)
        if seq_len is not None:
            # cuDNN varlen semantics: beyond a sequence's length the
            # state holds and outputs are zero (ref: rnn.cc
            # use_sequence_length; works for the reverse direction too —
            # the held initial state enters at t = len-1)
            valid = (t < seq_len)[:, None]
            new_carry = tuple(jnp.where(valid, nc, oc)
                              for nc, oc in zip(new_carry, carry))
            y = jnp.where(valid, y, jnp.zeros_like(y))
        return new_carry, y

    carry, ys = lax.scan(step, carry0,
                         (x, jnp.arange(T)), reverse=reverse)
    return carry, ys


def _rnn_outputs(params):
    mode = params.get("mode", "lstm")
    if not params.get("state_outputs", False):
        return 1
    return 3 if mode == "lstm" else 2


@register("RNN", num_inputs=-1, num_outputs=_rnn_outputs, needs_rng=True,
          needs_mode=True,
          params=[OpParam("state_size", int, None, required=True),
                  OpParam("num_layers", int, None, required=True),
                  OpParam("mode", str, "lstm"),
                  OpParam("bidirectional", bool, False),
                  OpParam("p", float, 0.0, doc="dropout between layers"),
                  OpParam("state_outputs", bool, False),
                  OpParam("projection_size", int, None),
                  OpParam("use_sequence_length", bool, False)],
          doc="Fused multi-layer RNN/LSTM/GRU over time via lax.scan "
              "(ref: src/operator/rnn.cc, rnn-inl.h; cuDNN-layout flat params). "
              "Inputs: data (T,N,C), params(flat), state, [state_cell].")
def _rnn(data, params, state, *rest, rng=None, state_size=None, num_layers=None,
         mode="lstm", bidirectional=False, p=0.0, state_outputs=False,
         projection_size=None, use_sequence_length=False, training=False):
    if projection_size is not None and mode != "lstm":
        raise MXNetError("RNN: projection_size is an LSTM(P) feature")
    rest = list(rest)
    state_cell = rest.pop(0) if (mode == "lstm" and rest) else None
    seq_len = None
    if use_sequence_length:
        if not rest:
            raise MXNetError("RNN: use_sequence_length=True needs a "
                             "sequence_length input (N,)")
        seq_len = rest.pop(0).astype(jnp.int32)
    d = 2 if bidirectional else 1
    layers = _rnn_unpack(params, mode, num_layers, data.shape[-1], state_size,
                         bidirectional, projection_size=projection_size)
    x = data
    hs, cs = [], []
    for li, dirs in enumerate(layers):
        outs = []
        for di, weights in enumerate(dirs):
            idx = li * d + di
            h0 = state[idx]
            c0 = state_cell[idx] if state_cell is not None else None
            carry, ys = _rnn_layer_scan(mode, x, h0, c0, weights, state_size,
                                        reverse=(di == 1), seq_len=seq_len)
            if di == 1:
                pass  # lax.scan(reverse=True) already emits outputs in orig order
            outs.append(ys)
            hs.append(carry[0])
            if mode == "lstm":
                cs.append(carry[1])
        x = outs[0] if d == 1 else jnp.concatenate(outs, axis=-1)
        if p > 0 and training and li < len(layers) - 1 and rng is not None:
            keep = jax.random.bernoulli(jax.random.fold_in(rng, li), 1.0 - p, x.shape)
            x = jnp.where(keep, x / (1.0 - p), jnp.zeros_like(x))
    hy = jnp.stack(hs, axis=0)
    if not state_outputs:
        return x
    if mode == "lstm":
        return x, hy, jnp.stack(cs, axis=0)
    return x, hy


# ---------------------------------------------------------------------------
# correlation / upsampling / misc layers used by zoos
# ---------------------------------------------------------------------------
@register("UpSampling", num_inputs=-1,
          params=[OpParam("scale", int, 1, required=True),
                  OpParam("sample_type", str, "nearest"),
                  OpParam("num_args", int, 1),
                  OpParam("num_filter", int, 0),
                  OpParam("multi_input_mode", str, "concat"),
                  OpParam("workspace", int, 512)],
          doc="ref: src/operator/upsampling.cc (nearest mode)")
def _upsampling(*args, scale=1, sample_type="nearest", num_args=1, num_filter=0,
                multi_input_mode="concat", workspace=512):
    x = args[0]
    if sample_type != "nearest":
        raise MXNetError("UpSampling: only nearest supported; use "
                         "contrib.BilinearResize2D for bilinear")
    out = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
    return out


# ---------------------------------------------------------------------------
# CTC loss (ref: src/operator/contrib/ctc_loss.cc / 3rdparty warp-ctc).
# TPU-native design: the alpha recursion is a lax.scan over time — static
# shapes, log-space accumulation, fully fused by XLA.
# ---------------------------------------------------------------------------
def _ctc_alpha_scan(logp, ext_labels, T_mask, S_len):
    """logp: (T, N, C) log-probs; ext_labels: (N, S) blank-interleaved labels;
    T_mask: (T, N) bool valid-time mask; S_len: (N,) valid ext length."""
    T, N, C = logp.shape
    S = ext_labels.shape[1]
    neg_inf = jnp.asarray(-1e30, logp.dtype)
    # emission log-probs per extended label position: (T, N, S)
    emit = jnp.take_along_axis(
        logp, jnp.broadcast_to(ext_labels[None], (T, N, S)), axis=2)

    # allow skip from s-2 when current label != label at s-2 and != blank
    can_skip = jnp.concatenate(
        [jnp.zeros((N, 2), bool),
         (ext_labels[:, 2:] != ext_labels[:, :-2]) &
         (ext_labels[:, 2:] != C - 1)],
        axis=1)

    alpha0 = jnp.full((N, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(emit[0, :, 0])
    alpha0 = alpha0.at[:, 1].set(jnp.where(S_len > 1, emit[0, :, 1], neg_inf))

    def step(alpha, inputs):
        emit_t, valid_t = inputs
        shift1 = jnp.concatenate([jnp.full((N, 1), neg_inf), alpha[:, :-1]],
                                 axis=1)
        shift2 = jnp.concatenate([jnp.full((N, 2), neg_inf), alpha[:, :-2]],
                                 axis=1)
        shift2 = jnp.where(can_skip, shift2, neg_inf)
        merged = jnp.logaddexp(jnp.logaddexp(alpha, shift1), shift2) + emit_t
        new = jnp.where(valid_t[:, None], merged, alpha)
        return new, None

    alpha, _ = lax.scan(step, alpha0, (emit[1:], T_mask[1:]))
    last = jnp.take_along_axis(alpha, (S_len - 1)[:, None], axis=1)[:, 0]
    last2 = jnp.take_along_axis(
        alpha, jnp.maximum(S_len - 2, 0)[:, None], axis=1)[:, 0]
    return -jnp.logaddexp(last, jnp.where(S_len > 1, last2, neg_inf))


@register("CTCLoss", num_inputs=-1, aliases=["ctc_loss", "_contrib_CTCLoss"],
          params=[OpParam("use_data_lengths", bool, False),
                  OpParam("use_label_lengths", bool, False),
                  OpParam("blank_label", str, "last"),
                  OpParam("data_lengths", None, None),
                  OpParam("label_lengths", None, None)],
          doc="CTC loss, alpha recursion as lax.scan "
              "(ref: src/operator/contrib/ctc_loss.cc). Input (T, N, C) "
              "activations (softmax applied internally), labels (N, L).")
def _ctc_loss(data, labels, *lens, use_data_lengths=False,
              use_label_lengths=False, blank_label="last", data_lengths=None,
              label_lengths=None):
    li = list(lens)
    if use_data_lengths and data_lengths is None:
        data_lengths = li.pop(0)
    if use_label_lengths and label_lengths is None:
        label_lengths = li.pop(0)
    # lengths may arrive as kwargs carrying NDArrays (the reference's calling
    # convention) — unwrap to jax arrays
    if data_lengths is not None:
        data_lengths = jnp.asarray(getattr(data_lengths, "_data", data_lengths))
    if label_lengths is not None:
        label_lengths = jnp.asarray(getattr(label_lengths, "_data",
                                            label_lengths))
    T, N, C = data.shape
    if labels.shape[1] == 0:
        # no labels: the only path is all blanks
        logp0 = jax.nn.log_softmax(data, axis=2)
        blank0 = C - 1 if blank_label == "last" else 0
        t_mask = jnp.arange(T)[:, None] < (
            data_lengths.astype(jnp.int32)[None, :] if data_lengths is not None
            else jnp.full((1, N), T))
        return -jnp.sum(jnp.where(t_mask, logp0[:, :, blank0], 0.0), axis=0)
    logp = jax.nn.log_softmax(data, axis=2)
    labels = labels.astype(jnp.int32)
    L = labels.shape[1]
    if blank_label == "last":
        blank = C - 1
    else:  # 'first': class 0 is blank; shift labels down like the reference
        blank = C - 1
        logp = jnp.concatenate([logp[:, :, 1:], logp[:, :, :1]], axis=2)
        labels = labels - 1
    if label_lengths is None:
        # labels padded with values < 0 (or == blank) don't count
        label_len = jnp.sum((labels >= 0) & (labels < C - 1), axis=1)
    else:
        label_len = label_lengths.astype(jnp.int32)
    if data_lengths is None:
        t_len = jnp.full((N,), T, jnp.int32)
    else:
        t_len = data_lengths.astype(jnp.int32)

    # blank-interleaved extended labels: (N, 2L+1)
    S = 2 * L + 1
    ext = jnp.full((N, S), blank, jnp.int32)
    safe_labels = jnp.clip(labels, 0, C - 1)
    ext = ext.at[:, 1::2].set(safe_labels)
    S_len = 2 * label_len + 1
    T_mask = (jnp.arange(T)[:, None] < t_len[None, :])
    return _ctc_alpha_scan(logp, ext, T_mask, S_len)
