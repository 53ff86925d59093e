"""Brumby style decoders (``model_type`` ``brumby``): a pre-norm decoder
whose mixer is power retention, gated linear attention of degree 2, where a
transformer has softmax attention; a SiLU-gated MLP in every layer, a final
RMSNorm, an untied head (docs/brumby.md has the equations and what was
assumed: ``config.json`` carries none of the retention's own keys).

The layer is granite_hybrid's two-part ``HybridDecoderLayer`` and its
``GatedMLP``; the mixer, :class:`PowerRetention`, is an ordinary HybridBlock
over registered ops (``_contrib_power_retention``,
``_contrib_rotary_embedding``, ``RMSNorm``), so the model trains through
``ShardedTrainer`` like any other, and each layer can ask for its
activations to be recomputed in the backward pass (``recompute``). Device
time is named ``retention`` (projections, head norms, rotary, the gate) and
``retention.scan`` (the operator).
"""
from __future__ import annotations

import functools

from ...base import MXNetError
from ...observability.instrument import device_scope
from .. import nn
from ..block import HybridBlock
from .granite_hybrid import (HybridDecoderLayer, _linear, embed_tokens,
                             project_logits)

__all__ = ["PowerRetention", "BrumbyModel", "brumby", "brumby_14b_base",
           "BRUMBY_14B_BASE"]


class PowerRetention(HybridBlock):
    """``W_o retention(rope(RMSNorm_d(W_q x)), rope(RMSNorm_d(W_k x)), W_v x,
    log sigmoid(W_g x))``: ``num_heads`` query heads and ``num_kv_heads``
    key/value heads of ``head_dim``, one gate a key/value head; query head
    ``h`` reads key/value head and gate ``h // (num_heads // num_kv_heads)``.
    No bias anywhere. ``chunk_size`` rows are retained in the ``a[t, s]``
    form, the rest through the state; ``retention_eps`` stands beside the
    normaliser."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 rope_theta=1e6, epsilon=1e-6, chunk_size=1024,
                 retention_eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise MXNetError(f"num_heads {num_heads} is no multiple of "
                             f"num_kv_heads {num_kv_heads}")
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._theta, self._chunk = rope_theta, chunk_size
        self._retention_eps = retention_eps
        with self.name_scope():
            self.q_proj = _linear(num_heads * head_dim, units, "q_")
            self.k_proj = _linear(num_kv_heads * head_dim, units, "k_")
            self.v_proj = _linear(num_kv_heads * head_dim, units, "v_")
            self.g_proj = _linear(num_kv_heads, units, "g_")
            self.o_proj = _linear(units, num_heads * head_dim, "o_")
            self.q_norm = nn.RMSNorm(epsilon=epsilon, in_channels=head_dim,
                                     prefix="q_norm_")
            self.k_norm = nn.RMSNorm(epsilon=epsilon, in_channels=head_dim,
                                     prefix="k_norm_")

    def hybrid_forward(self, F, x):
        def heads(t, n):                # (B, S, n * D) -> (B, S, n, D)
            return F.reshape(t, (0, 0, n, -1))

        with device_scope("retention"):
            q = F.contrib.rotary_embedding(
                self.q_norm(heads(self.q_proj(x), self._heads)),
                theta=self._theta)
            k = F.contrib.rotary_embedding(
                self.k_norm(heads(self.k_proj(x), self._kv_heads)),
                theta=self._theta)
            v = heads(self.v_proj(x), self._kv_heads)
            # log sigmoid(gamma) = -softplus(-gamma), in float32
            log_g = -F.Activation(-F.cast(self.g_proj(x), dtype="float32"),
                                  act_type="softrelu")
            with device_scope("retention.scan"):
                y = F.contrib.power_retention(
                    q, k, v, log_g, chunk_size=self._chunk,
                    eps=self._retention_eps)
            return self.o_proj(F.reshape(y, (0, 0, -1)))


class BrumbyModel(HybridBlock):
    """Tokens (B, S) -> logits (B, S, vocab): ``E[tokens]``,
    ``num_hidden_layers`` of ``h + retention(RMSNorm(h))`` then ``h +
    MLP(RMSNorm(h))``, a final RMSNorm, an untied head. With
    ``output_hidden_states`` the outputs are the logits and, after them, the
    residual stream (B, S, hidden) as each layer leaves it, first layer
    first (a loss then takes the first output: ``FirstOutputLoss``)."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 num_hidden_layers, num_attention_heads, num_key_value_heads,
                 head_dim, rms_norm_eps=1e-6, rope_theta=1e6,
                 chunk_size=1024, retention_eps=1e-6, recompute=False,
                 output_hidden_states=False, **kwargs):
        super().__init__(**kwargs)
        self._vocab, self._units = vocab_size, hidden_size
        self._output_hidden_states = output_hidden_states
        mixer = functools.partial(
            PowerRetention, hidden_size, num_attention_heads,
            num_key_value_heads, head_dim, rope_theta=rope_theta,
            epsilon=rms_norm_eps, chunk_size=chunk_size,
            retention_eps=retention_eps)
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, hidden_size))
            self.layers = nn.HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for _ in range(num_hidden_layers):
                    self.layers.add(HybridDecoderLayer(
                        mixer, hidden_size, intermediate_size,
                        epsilon=rms_norm_eps).recompute(recompute))
            self.final_norm = nn.RMSNorm(epsilon=rms_norm_eps,
                                         in_channels=hidden_size,
                                         prefix="final_norm_")
            self.head_weight = self.params.get(
                "head_weight", shape=(vocab_size, hidden_size))

    def hybrid_forward(self, F, tokens, embed_weight, head_weight):
        h = embed_tokens(F, tokens, embed_weight, self._vocab, self._units)
        hidden = []
        for layer in self.layers:
            h = layer(h)
            hidden.append(h)
        logits = project_logits(F, h, self.final_norm, head_weight,
                                self._vocab)
        if not self._output_hidden_states:
            return logits
        return [logits] + hidden


# https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json
BRUMBY_14B_BASE = dict(
    vocab_size=151936, hidden_size=5120, intermediate_size=17408,
    num_hidden_layers=40, num_attention_heads=40, num_key_value_heads=8,
    head_dim=128, rms_norm_eps=1e-6, rope_theta=1000000)

# keys of a brumby config.json that shape nothing here: the bias that is
# false, the activation that is SiLU, windows the retention has none of,
# what belongs to generation
UNUSED_KEYS = frozenset((
    "attention_bias", "hidden_act", "max_position_embeddings",
    "max_window_layers", "model_type", "rope_scaling", "sliding_window",
    "tie_word_embeddings", "use_sliding_window"))


def brumby(recompute=False, output_hidden_states=False, **config):
    """A :class:`BrumbyModel` from the keys of a ``brumby`` ``config.json``
    (those of ``BRUMBY_14B_BASE``; the file's other keys, ``UNUSED_KEYS``,
    are taken and shape nothing) and the retention's own, which the file
    does not carry: ``chunk_size``, ``retention_eps``."""
    if config.get("hidden_act", "silu") != "silu" \
            or config.get("attention_bias") or config.get("rope_scaling") \
            or config.get("tie_word_embeddings"):
        raise MXNetError(
            "brumby: hidden_act 'silu', no attention bias, no rope scaling "
            "and an untied head expected, got "
            + repr({key: config.get(key) for key in (
                "hidden_act", "attention_bias", "rope_scaling",
                "tie_word_embeddings")}))
    shaping = {key: value for key, value in config.items()
               if key not in UNUSED_KEYS}
    return BrumbyModel(recompute=recompute,
                       output_hidden_states=output_hidden_states, **shaping)


def brumby_14b_base(recompute=False, **overrides):
    """manifestai/Brumby-14B-Base (40 layers of power retention and a gated
    MLP); ``overrides`` replace keys of its configuration, e.g.
    ``num_hidden_layers=4``."""
    return brumby(recompute=recompute, **dict(BRUMBY_14B_BASE, **overrides))
