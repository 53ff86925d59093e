"""Nemotron-H style hybrid decoders (``model_type`` ``nemotron_h``): every
layer is one part, ``h + mixer(RMSNorm(h))``, and ``hybrid_override_pattern``
says which mixer each layer has: ``M`` a Mamba-2 mixer, ``*`` grouped-query
attention without any position embedding, ``E`` routed experts with a shared
expert; then a final RMSNorm and an untied head (docs/nemotron_h.md has the
equations, the initialisation and what was assumed). A pattern with the
format's fourth letter, ``-`` (a plain feed-forward layer), is refused: no
published configuration built here has one.

The mixers are granite_hybrid's (``Mamba2Mixer``, ``GroupedQueryAttention``)
and ``gluon.contrib.nn``'s ``RoutedExperts``: ordinary HybridBlocks over
registered ops, so the model trains through
``ShardedTrainer`` like any other. An expert layer can be told which experts
it holds (``experts_held``: one chip's share under expert parallelism); it
routes over all ``n_routed_experts`` and computes its own. Each layer can ask
for its activations to be recomputed in the backward pass (``recompute``).
"""
from __future__ import annotations

import functools

from ...base import MXNetError
from ...observability.instrument import device_scope
from .. import nn
from ..block import HybridBlock
from ..contrib.nn import RoutedExperts
from .granite_hybrid import (GroupedQueryAttention, Mamba2Mixer,
                             embed_tokens, project_logits)

__all__ = ["NemotronHLayer", "NemotronHModel", "FirstOutputLoss",
           "nemotron_h", "nemotron_3_nano_30b_a3b",
           "NEMOTRON_3_NANO_30B_A3B"]


class NemotronHLayer(HybridBlock):
    """``h + mixer(RMSNorm(h))``; ``make_mixer(prefix=...)`` builds the mixer
    under this layer's names. A mixer that returns ``(y, routes, rows,
    scores)`` makes the layer return ``(h, routes, rows, scores)``."""

    def __init__(self, make_mixer, units, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm = nn.RMSNorm(epsilon=epsilon, in_channels=units,
                                   prefix="norm_")
            self.mixer = make_mixer(prefix="mixer_")

    def hybrid_forward(self, F, h):
        with device_scope("norm"):
            x = self.norm(h)
        out = self.mixer(x)
        if isinstance(out, (tuple, list)):
            return (h + out[0],) + tuple(out[1:])
        return h + out


class NemotronHModel(HybridBlock):
    """Tokens (B, S) -> logits (B, S, vocab): ``E[tokens]``, the layers of
    ``hybrid_override_pattern``, a final RMSNorm, an untied head. With
    ``return_routes`` the outputs are ``[logits, routes of the first expert
    layer, of the second, ..., scores of the first, of the second, ...,
    rows]``: the routes each (B, S, experts a token) int32, the routers'
    scores each (B, S, experts) float32, and last the rows each held expert
    computed, (expert layers, experts held) int32."""

    def __init__(self, vocab_size, hidden_size, hybrid_override_pattern,
                 num_attention_heads, num_key_value_heads, head_dim,
                 mamba_num_heads, mamba_head_dim, ssm_state_size, n_groups,
                 conv_kernel, chunk_size, n_routed_experts,
                 num_experts_per_tok, moe_intermediate_size,
                 moe_shared_expert_intermediate_size,
                 n_shared_experts=1, norm_topk_prob=True,
                 routed_scaling_factor=1.0, mlp_hidden_act="relu2",
                 layer_norm_epsilon=1e-5, experts_held=None,
                 return_routes=False, recompute=False, **kwargs):
        super().__init__(**kwargs)
        if mlp_hidden_act != "relu2":
            raise MXNetError(
                f"mlp_hidden_act {mlp_hidden_act!r}: the experts are "
                f"W2 relu(W1 x)^2, 'relu2' expected")
        self._vocab, self._units = vocab_size, hidden_size
        self._return_routes = bool(return_routes)
        first, held = experts_held or (0, n_routed_experts)
        mixers = {
            "M": functools.partial(
                Mamba2Mixer, hidden_size, mamba_num_heads, mamba_head_dim,
                ssm_state_size, n_groups=n_groups, conv_kernel=conv_kernel,
                chunk_size=chunk_size, epsilon=layer_norm_epsilon),
            "*": functools.partial(
                GroupedQueryAttention, hidden_size, num_attention_heads,
                num_key_value_heads, head_dim=head_dim),
            "E": functools.partial(
                RoutedExperts, hidden_size, moe_intermediate_size,
                n_routed_experts, k=num_experts_per_tok, first_expert=first,
                experts_held=held, norm_topk_prob=norm_topk_prob,
                scaling_factor=routed_scaling_factor,
                shared_hidden_size=n_shared_experts
                * moe_shared_expert_intermediate_size,
                return_routes=return_routes)}
        unknown = sorted(set(hybrid_override_pattern) - set(mixers))
        if unknown or not hybrid_override_pattern:
            raise MXNetError(
                f"hybrid_override_pattern {hybrid_override_pattern!r}: "
                f"letters among {sorted(mixers)} expected")
        self._pattern = hybrid_override_pattern
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, hidden_size))
            self.layers = nn.HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for kind in hybrid_override_pattern:
                    self.layers.add(NemotronHLayer(
                        mixers[kind], hidden_size,
                        epsilon=layer_norm_epsilon).recompute(recompute))
            self.final_norm = nn.RMSNorm(epsilon=layer_norm_epsilon,
                                         in_channels=hidden_size,
                                         prefix="final_norm_")
            self.head_weight = self.params.get(
                "head_weight", shape=(vocab_size, hidden_size))

    def hybrid_forward(self, F, tokens, embed_weight, head_weight):
        h = embed_tokens(F, tokens, embed_weight, self._vocab, self._units)
        routes, rows, scores = [], [], []
        for kind, layer in zip(self._pattern, self.layers._children.values()):
            if kind == "E" and self._return_routes:
                h, chosen, computed, scored = layer(h)
                routes.append(chosen)
                rows.append(computed)
                scores.append(scored)
            else:
                h = layer(h)
        logits = project_logits(F, h, self.final_norm, head_weight,
                                self._vocab)
        if not self._return_routes:
            return logits
        return [logits] + routes + scores \
            + ([F.stack(*rows, axis=0)] if rows else [])


class FirstOutputLoss(HybridBlock):
    """``loss(outputs[0], label)``: a trainer hands a loss the list of a
    net's outputs, and a net built with ``return_routes`` has the logits
    first."""

    def __init__(self, loss, **kwargs):
        super().__init__(**kwargs)
        self.loss = loss

    @property
    def amp_safe(self):
        return getattr(self.loss, "amp_safe", False)

    def hybrid_forward(self, F, outputs, label):
        first = outputs[0] if isinstance(outputs, (list, tuple)) else outputs
        return self.loss(first, label)


# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json
NEMOTRON_3_NANO_30B_A3B = dict(
    vocab_size=131072, hidden_size=2688,
    hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                            "EMEMEMEME",
    num_attention_heads=32, num_key_value_heads=2, head_dim=128,
    mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128, n_groups=8,
    conv_kernel=4, chunk_size=128, n_routed_experts=128,
    num_experts_per_tok=6, moe_intermediate_size=1856,
    moe_shared_expert_intermediate_size=3712, n_shared_experts=1,
    norm_topk_prob=True, routed_scaling_factor=2.5, mlp_hidden_act="relu2",
    layer_norm_epsilon=1e-5)

# keys of a nemotron_h config.json that shape nothing here: biases that are
# all false but the convolution's, rotary keys the model does not apply,
# the width of the ``-`` layers no pattern taken here has, kernels'
# switches, what belongs to generation
UNUSED_KEYS = frozenset((
    "attention_bias", "expand", "intermediate_size", "mamba_hidden_act",
    "mamba_proj_bias", "max_position_embeddings", "mlp_bias", "model_type",
    "n_group", "norm_eps", "num_hidden_layers", "num_logits_to_keep",
    "partial_rotary_factor", "rescale_prenorm_residual", "residual_in_fp32",
    "rope_theta", "sliding_window", "tie_word_embeddings", "time_step_floor",
    "time_step_max", "time_step_min", "topk_group", "use_bias",
    "use_conv_bias", "use_mamba_kernels"))


def nemotron_h(experts_held=None, return_routes=False, recompute=False,
               **config):
    """A :class:`NemotronHModel` from the keys of a ``nemotron_h``
    ``config.json`` (those of ``NEMOTRON_3_NANO_30B_A3B``; the file's other
    keys, ``UNUSED_KEYS``, are taken and shape nothing). ``experts_held``:
    ``(first, count)`` of the ``n_routed_experts`` that each expert layer
    computes here (``None``: all)."""
    if config.get("num_hidden_layers") not in (
            None, len(config.get("hybrid_override_pattern", ""))):
        raise MXNetError(
            f"num_hidden_layers {config['num_hidden_layers']} is not the "
            f"length of hybrid_override_pattern "
            f"{config.get('hybrid_override_pattern')!r}")
    shaping = {key: value for key, value in config.items()
               if key not in UNUSED_KEYS}
    return NemotronHModel(experts_held=experts_held,
                          return_routes=return_routes, recompute=recompute,
                          **shaping)


def nemotron_3_nano_30b_a3b(experts_held=None, return_routes=False,
                            recompute=False, **overrides):
    """nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B (52 layers: 23 Mamba-2, 23
    routed-expert, 6 attention); ``overrides`` replace keys of its
    configuration, e.g. ``hybrid_override_pattern="MEMEM*EME"``."""
    return nemotron_h(experts_held=experts_held, return_routes=return_routes,
                      recompute=recompute,
                      **dict(NEMOTRON_3_NANO_30B_A3B, **overrides))
