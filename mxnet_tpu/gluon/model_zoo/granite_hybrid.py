"""Granite 4.0-H style hybrid decoders: Mamba-2 state-space layers with a
grouped-query attention layer among every few, a shared SiLU-gated MLP in
every layer, pre-norm residuals with a multiplier, a tied head
(``model_type`` ``granitemoehybrid`` without experts; docs/granite_hybrid.md
has the equations, the initialisation and what was assumed).

The blocks are ordinary HybridBlocks over registered ops (``ops/ssm.py``,
``_contrib_flash_attention``, ``RMSNorm``), so the model trains through
``ShardedTrainer`` like any other; each decoder layer can ask for its
activations to be recomputed in the backward pass (``recompute=True`` ->
``HybridBlock.recompute``). Device time is named by ``jax.named_scope``
(``mxnet_tpu.mamba2.ssd`` ...; ``observability.device_scopes``).
"""
from __future__ import annotations

import functools

import numpy as np

from ... import initializer as init
from ...base import MXNetError
from ...observability.instrument import device_scope
from .. import nn
from ..block import HybridBlock

__all__ = ["GatedMLP", "GroupedQueryAttention", "Mamba2Mixer",
           "HybridDecoderLayer", "GraniteHybridModel", "granite_hybrid",
           "granite_4_0_h_micro", "GRANITE_4_0_H_MICRO"]


class _Sampled(init.Initializer):
    """An initializer that ignores the name-suffix dispatch (``dt_bias``
    ends in ``bias``): every element comes from ``sample(shape)``."""

    def __call__(self, desc, arr):
        self._set(arr, self.sample(arr.shape))


class _UniformAll(_Sampled):
    def __init__(self, scale):
        super().__init__(scale=scale)
        self.scale = scale

    def sample(self, shape):
        return np.random.uniform(-self.scale, self.scale, shape)


class _LogOfUniform(_Sampled):
    """``A_log = log(uniform(low, high))`` (Mamba-2's ``A_init_range``)."""

    def __init__(self, low=1.0, high=16.0):
        super().__init__(low=low, high=high)
        self.low, self.high = low, high

    def sample(self, shape):
        return np.log(np.random.uniform(self.low, self.high, shape))


class _InverseSoftplusOfLogUniform(_Sampled):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in
    ``[low, high]`` (Mamba-2's ``dt_min`` / ``dt_max``, floor 1e-4)."""

    def __init__(self, low=1e-3, high=1e-1, floor=1e-4):
        super().__init__(low=low, high=high, floor=floor)
        self.low, self.high, self.floor = low, high, floor

    def sample(self, shape):
        dt = np.exp(np.random.uniform(np.log(self.low), np.log(self.high),
                                      shape))
        dt = np.maximum(dt, self.floor)
        return dt + np.log(-np.expm1(-dt))


def _linear(units, in_units, prefix):
    return nn.Dense(units, flatten=False, use_bias=False, in_units=in_units,
                    prefix=prefix)


def embed_tokens(F, tokens, weight, vocab, units, multiplier=1.0):
    """``weight[tokens] * multiplier``: (B, S) ids -> (B, S, units),
    ``weight`` (vocab, units)."""
    with device_scope("embed"):
        h = F.Embedding(tokens, weight, input_dim=vocab, output_dim=units)
        return h if multiplier == 1.0 else h * multiplier


def project_logits(F, h, final_norm, weight, vocab, scaling=1.0):
    """``final_norm(h) weight^T / scaling``: the head over ``weight`` (vocab,
    units), the embedding's where the head is tied."""
    with device_scope("norm"):
        h = final_norm(h)
    with device_scope("lm_head"):
        logits = F.FullyConnected(h, weight, num_hidden=vocab, no_bias=True,
                                  flatten=False)
        return logits if scaling == 1.0 else logits * (1.0 / scaling)


class GatedMLP(HybridBlock):
    """``W_out(silu(g) * u)`` with ``[g, u] = split(W_in x)``, no bias."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.w_in = _linear(2 * hidden_size, units, "in_")
            self.w_out = _linear(units, hidden_size, "out_")

    def hybrid_forward(self, F, x):
        with device_scope("mlp"):
            return self.w_out(F.contrib.swiglu(self.w_in(x)))


class GroupedQueryAttention(HybridBlock):
    """Causal self-attention with ``num_kv_heads`` key/value heads, each
    serving ``num_heads // num_kv_heads`` query heads, over
    ``_contrib_flash_attention``. No bias; ``sm_scale`` multiplies the
    scores (``None``: ``head_dim ** -0.5``). By default no position
    embedding and no norm; ``qk_norm``: q and k each through an RMSNorm
    over each head (a weight of ``head_dim``, eps 1e-5), and
    ``rope_theta``: then rotary over the whole head (rotate-half, that
    base)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim=None,
                 sm_scale=None, qk_norm=False, rope_theta=None, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise MXNetError(f"num_heads {num_heads} is no multiple of "
                             f"num_kv_heads {num_kv_heads}")
        head_dim = head_dim or units // num_heads
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._sm_scale, self._theta = sm_scale, rope_theta
        with self.name_scope():
            self.q_proj = _linear(num_heads * head_dim, units, "q_")
            self.k_proj = _linear(num_kv_heads * head_dim, units, "k_")
            self.v_proj = _linear(num_kv_heads * head_dim, units, "v_")
            self.o_proj = _linear(units, num_heads * head_dim, "o_")
            self.q_norm = nn.RMSNorm(epsilon=1e-5, in_channels=head_dim,
                                     prefix="q_norm_") if qk_norm else None
            self.k_norm = nn.RMSNorm(epsilon=1e-5, in_channels=head_dim,
                                     prefix="k_norm_") if qk_norm else None

    def hybrid_forward(self, F, x):
        def heads(t, n, norm=None, turn=False):
            t = F.reshape(t, (0, 0, n, -1))     # (B, S, n * D) -> (B, S, n, D)
            if norm is not None:
                t = norm(t)
            if turn and self._theta is not None:
                t = F.contrib.rotary_embedding(t, theta=self._theta)
            return F.transpose(t, axes=(0, 2, 1, 3))    # -> (B, n, S, D)

        with device_scope("attention"):
            q = heads(self.q_proj(x), self._heads, self.q_norm, turn=True)
            k = heads(self.k_proj(x), self._kv_heads, self.k_norm, turn=True)
            v = heads(self.v_proj(x), self._kv_heads)
            # query head h reads key/value head h // (heads // kv_heads)
            out = F.contrib.flash_attention(q, k, v, causal=True,
                                            sm_scale=self._sm_scale)
            out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)), (0, 0, -1))
            return self.o_proj(out)


class Mamba2Mixer(HybridBlock):
    """Mamba-2 mixer: ``[z | xBC | dt] = W_in x``; ``xBC`` through a
    depthwise causal convolution and SiLU; the selective scan over ``x``
    (``num_heads`` x ``head_dim``) with ``B`` and ``C`` (``n_groups`` x
    ``state_size``: a group serves ``num_heads // n_groups`` consecutive
    heads); ``RMSNorm(y * silu(z))``, the mean square taken over each of
    the ``n_groups`` runs of channels apart; ``W_out``."""

    def __init__(self, units, num_heads, head_dim, state_size, n_groups=1,
                 conv_kernel=4, chunk_size=256, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._head_dim = num_heads, head_dim
        self._groups, self._state = n_groups, state_size
        self._chunk, self._epsilon = chunk_size, epsilon
        inner = self._inner = num_heads * head_dim
        conv_dim = self._conv_dim = inner + 2 * n_groups * state_size
        bound = conv_kernel ** -0.5     # torch's Conv1d default, fan-in K
        with self.name_scope():
            self.in_proj = _linear(inner + conv_dim + num_heads, units, "in_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(conv_dim, conv_kernel),
                init=_UniformAll(bound))
            self.conv_bias = self.params.get(
                "conv_bias", shape=(conv_dim,), init=_UniformAll(bound))
            self.dt_bias = self.params.get(
                "dt_bias", shape=(num_heads,),
                init=_InverseSoftplusOfLogUniform())
            self.A_log = self.params.get(
                "A_log", shape=(num_heads,), init=_LogOfUniform())
            self.D = self.params.get("D", shape=(num_heads,), init="ones")
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(inner,), init="ones")
            self.out_proj = _linear(units, inner, "out_")

    def hybrid_forward(self, F, x, conv_weight, conv_bias, dt_bias, A_log, D,
                       norm_gamma):
        inner, conv_dim = self._inner, self._conv_dim
        bc = self._groups * self._state

        def cut(t, begin, end):
            return F.slice_axis(t, axis=-1, begin=begin, end=end)

        with device_scope("mamba2.in_proj"):
            zxbcdt = self.in_proj(x)
        with device_scope("mamba2.conv"):
            xbc = F.contrib.causal_conv1d(
                cut(zxbcdt, inner, inner + conv_dim), conv_weight, conv_bias,
                act_type="silu")
        with device_scope("mamba2.ssd"):
            y = F.contrib.mamba2_ssd(
                F.reshape(cut(xbc, 0, inner), (0, 0, self._heads, -1)),
                cut(zxbcdt, inner + conv_dim, None), A_log,
                F.reshape(cut(xbc, inner, inner + bc),
                          (0, 0, self._groups, -1)),
                F.reshape(cut(xbc, inner + bc, None),
                          (0, 0, self._groups, -1)),
                D, dt_bias, chunk_size=self._chunk)
        with device_scope("mamba2.gate_norm"):
            y = F.contrib.gated_rms_norm(
                F.reshape(y, (0, 0, -1)), cut(zxbcdt, 0, inner), norm_gamma,
                eps=self._epsilon, groups=self._groups)
        with device_scope("mamba2.out_proj"):
            return self.out_proj(y)


class HybridDecoderLayer(HybridBlock):
    """``h + m * mixer(RMSNorm(h))`` then ``h + m * MLP(RMSNorm(h))``:
    pre-norm, residual multiplier ``m``; ``make_mixer(prefix=...)`` builds
    the mixer (a Mamba-2 mixer, grouped-query attention ...) under this
    layer's names, and ``make_mlp(prefix=...)`` the MLP (default a
    :class:`GatedMLP` of ``hidden_size``). An MLP that returns ``(y,
    routes, rows, scores)`` (routed experts) makes the layer return ``(h,
    routes, rows, scores)``."""

    def __init__(self, make_mixer, units, hidden_size,
                 residual_multiplier=1.0, epsilon=1e-5, make_mlp=None,
                 **kwargs):
        super().__init__(**kwargs)
        self._multiplier = residual_multiplier
        make_mlp = make_mlp or functools.partial(GatedMLP, units, hidden_size)
        with self.name_scope():
            self.input_norm = nn.RMSNorm(epsilon=epsilon, in_channels=units,
                                         prefix="input_norm_")
            self.mixer = make_mixer(prefix="mixer_")
            self.mlp_norm = nn.RMSNorm(epsilon=epsilon, in_channels=units,
                                       prefix="mlp_norm_")
            self.mlp = make_mlp(prefix="mlp_")

    def hybrid_forward(self, F, h):
        with device_scope("norm"):
            x = self.input_norm(h)
        h = h + self._multiplier * self.mixer(x)
        with device_scope("norm"):
            x = self.mlp_norm(h)
        out = self.mlp(x)
        if isinstance(out, (tuple, list)):
            return (h + self._multiplier * out[0],) + tuple(out[1:])
        return h + self._multiplier * out


class GraniteHybridModel(HybridBlock):
    """Tokens (B, S) -> logits (B, S, vocab): ``E[tokens] *
    embedding_multiplier``, the layers of ``layer_types`` (``"mamba"`` /
    ``"attention"``), a final RMSNorm, the head tied to the embedding,
    logits divided by ``logits_scaling``."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 layer_types, num_attention_heads, num_key_value_heads,
                 mamba_n_heads, mamba_d_head, mamba_d_state, mamba_n_groups=1,
                 mamba_d_conv=4, mamba_chunk_size=256, rms_norm_eps=1e-5,
                 attention_multiplier=None, embedding_multiplier=1.0,
                 residual_multiplier=1.0, logits_scaling=1.0,
                 recompute=False, **kwargs):
        super().__init__(**kwargs)
        self._vocab, self._units = vocab_size, hidden_size
        self._embedding_multiplier = embedding_multiplier
        self._logits_scaling = logits_scaling
        mixers = {
            "mamba": functools.partial(
                Mamba2Mixer, hidden_size, mamba_n_heads, mamba_d_head,
                mamba_d_state, n_groups=mamba_n_groups,
                conv_kernel=mamba_d_conv, chunk_size=mamba_chunk_size,
                epsilon=rms_norm_eps),
            "attention": functools.partial(
                GroupedQueryAttention, hidden_size, num_attention_heads,
                num_key_value_heads, sm_scale=attention_multiplier)}
        unknown = sorted(set(layer_types) - set(mixers))
        if unknown:
            raise MXNetError(f"unknown layer types {unknown}; one of "
                             f"{sorted(mixers)}")
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, hidden_size))
            self.layers = nn.HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for kind in layer_types:
                    self.layers.add(HybridDecoderLayer(
                        mixers[kind], hidden_size, intermediate_size,
                        residual_multiplier=residual_multiplier,
                        epsilon=rms_norm_eps).recompute(recompute))
            self.final_norm = nn.RMSNorm(epsilon=rms_norm_eps,
                                         in_channels=hidden_size,
                                         prefix="final_norm_")

    def hybrid_forward(self, F, tokens, embed_weight):
        h = embed_tokens(F, tokens, embed_weight, self._vocab, self._units,
                         self._embedding_multiplier)
        h = self.layers(h)
        return project_logits(F, h, self.final_norm, embed_weight,
                              self._vocab, self._logits_scaling)


# https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json
GRANITE_4_0_H_MICRO = dict(
    vocab_size=100352, hidden_size=2048, intermediate_size=8192,
    layer_types=["attention" if i % 10 == 5 else "mamba" for i in range(40)],
    num_attention_heads=32, num_key_value_heads=8, mamba_n_heads=64,
    mamba_d_head=64, mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4,
    mamba_chunk_size=256, rms_norm_eps=1e-5, attention_multiplier=0.015625,
    embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8)


def granite_hybrid(recompute=False, **config):
    """A :class:`GraniteHybridModel` from the keys of a ``granitemoehybrid``
    ``config.json`` that shape a model without experts (the keys of
    ``GRANITE_4_0_H_MICRO``)."""
    return GraniteHybridModel(recompute=recompute, **config)


def granite_4_0_h_micro(recompute=False, **overrides):
    """ibm-granite/granite-4.0-h-micro (40 layers, attention at 5, 15, 25,
    35); ``overrides`` replace keys of its configuration, e.g.
    ``layer_types=GRANITE_4_0_H_MICRO["layer_types"][:10]``."""
    return granite_hybrid(recompute=recompute,
                          **dict(GRANITE_4_0_H_MICRO, **overrides))
