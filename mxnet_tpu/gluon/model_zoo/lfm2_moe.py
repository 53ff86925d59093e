"""LFM2-MoE style decoders (``model_type`` ``lfm2_moe``): a pre-norm decoder
whose operator is, by ``layer_types``, a **gated short convolution** or
grouped-query attention with head norms and rotary; a dense SiLU-gated MLP
in the first ``num_dense_layers`` layers and routed experts after them (a
sigmoid router whose choice bias moves, gated experts, no shared expert);
a final RMSNorm and a head tied to the embedding (docs/lfm2_moe.md has the
equations, the initialisation and what was assumed).

The layer is granite_hybrid's two-part ``HybridDecoderLayer`` (its mixer the
operator, its MLP the dense ``GatedMLP`` or routed experts); the blocks are
ordinary HybridBlocks over registered ops (``_contrib_causal_conv1d``,
``_contrib_flash_attention``, ``_contrib_rotary_embedding``, ``RMSNorm``) and
``gluon.contrib.nn``'s ``RoutedExperts``, so the model trains through
``ShardedTrainer`` like any other. An expert layer can be told which experts
it holds (``experts_held``: one chip's share under expert parallelism); it
routes over all ``num_experts`` and computes its own. Each layer can ask for
its activations to be recomputed in the backward pass (``recompute``).
Device time is named ``shortconv`` (the two projections and the split),
``shortconv.conv`` (the gate, the three-tap convolution and the gate),
``attention``, ``mlp`` and the expert layer's ``moe.*``.
"""
from __future__ import annotations

import functools

from ...base import MXNetError
from ...observability.instrument import device_scope
from .. import nn
from ..block import HybridBlock
from ..contrib.nn import RoutedExperts
from .granite_hybrid import (GroupedQueryAttention, HybridDecoderLayer,
                             _linear, _UniformAll, embed_tokens,
                             project_logits)

__all__ = ["ShortConv", "Lfm2MoeModel", "lfm2_moe", "lfm2_24b_a2b",
           "LFM2_24B_A2B"]


class ShortConv(HybridBlock):
    """``W_out(C * conv(B * x))`` with ``[B | C | x] = W_in h``: a gate on
    each side of a depthwise causal convolution of ``kernel`` taps, no bias
    anywhere and no activation (LFM2's ``Lfm2ShortConv``)."""

    def __init__(self, units, kernel=3, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        with self.name_scope():
            self.in_proj = _linear(3 * units, units, "in_")
            # torch's Conv1d default: fan-in of a depthwise filter is K
            self.conv_weight = self.params.get(
                "conv_weight", shape=(units, kernel),
                init=_UniformAll(kernel ** -0.5))
            self.out_proj = _linear(units, units, "out_")

    def hybrid_forward(self, F, x, conv_weight):
        u = self._units

        def cut(t, i):
            return F.slice_axis(t, axis=-1, begin=i * u, end=(i + 1) * u)

        with device_scope("shortconv"):
            bcx = self.in_proj(x)
            with device_scope("shortconv.conv"):
                y = cut(bcx, 1) * F.contrib.causal_conv1d(
                    cut(bcx, 0) * cut(bcx, 2), conv_weight, no_bias=True,
                    act_type="identity")
            return self.out_proj(y)


class Lfm2MoeModel(HybridBlock):
    """Tokens (B, S) -> logits (B, S, vocab): ``E[tokens]``, the layers of
    ``layer_types`` (``"conv"`` / ``"full_attention"``), the first
    ``num_dense_layers`` with a dense gated MLP and the others with routed
    experts, a final RMSNorm, the head tied to the embedding. With
    ``return_routes`` the outputs are ``[logits, routes of the first expert
    layer, of the second, ..., scores of the first, of the second, ...,
    rows]``: the routes each (B, S, experts a token) int32, the routers'
    scores each (B, S, experts) float32, and last the rows each held expert
    computed, (expert layers, experts held) int32."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 moe_intermediate_size, layer_types, num_dense_layers,
                 num_attention_heads, num_key_value_heads, num_experts,
                 num_experts_per_tok, conv_L_cache=3, norm_eps=1e-5,
                 rope_theta=1e6, norm_topk_prob=True,
                 routed_scaling_factor=1.0, use_expert_bias=True,
                 expert_bias_update_rate=0.0, experts_held=None,
                 return_routes=False, recompute=False, **kwargs):
        super().__init__(**kwargs)
        self._vocab, self._units = vocab_size, hidden_size
        self._return_routes = bool(return_routes)
        first, held = experts_held or (0, num_experts)
        ops = {
            "conv": functools.partial(ShortConv, hidden_size,
                                      kernel=conv_L_cache),
            "full_attention": functools.partial(
                GroupedQueryAttention, hidden_size, num_attention_heads,
                num_key_value_heads, qk_norm=True, rope_theta=rope_theta)}
        unknown = sorted(set(layer_types) - set(ops))
        if unknown or not layer_types:
            raise MXNetError(f"layer_types {layer_types!r}: kinds among "
                             f"{sorted(ops)} expected")
        if not 0 <= num_dense_layers <= len(layer_types):
            raise MXNetError(f"num_dense_layers {num_dense_layers} of "
                             f"{len(layer_types)} layers")
        routed = functools.partial(
            RoutedExperts, hidden_size, moe_intermediate_size, num_experts,
            k=num_experts_per_tok, first_expert=first, experts_held=held,
            norm_topk_prob=norm_topk_prob, norm_eps=1e-6,
            scaling_factor=routed_scaling_factor, gated=True,
            bias_update_rate=expert_bias_update_rate if use_expert_bias
            else 0.0, return_routes=return_routes)
        self._routed = [i >= num_dense_layers for i in range(len(layer_types))]
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, hidden_size))
            self.layers = nn.HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for kind, is_routed in zip(layer_types, self._routed):
                    self.layers.add(HybridDecoderLayer(
                        ops[kind], hidden_size, intermediate_size,
                        epsilon=norm_eps,
                        make_mlp=routed if is_routed else None).recompute(
                            recompute))
            self.final_norm = nn.RMSNorm(epsilon=norm_eps,
                                         in_channels=hidden_size,
                                         prefix="final_norm_")

    def hybrid_forward(self, F, tokens, embed_weight):
        h = embed_tokens(F, tokens, embed_weight, self._vocab, self._units)
        routes, rows, scores = [], [], []
        for is_routed, layer in zip(self._routed,
                                    self.layers._children.values()):
            if is_routed and self._return_routes:
                h, chosen, computed, scored = layer(h)
                routes.append(chosen)
                rows.append(computed)
                scores.append(scored)
            else:
                h = layer(h)
        logits = project_logits(F, h, self.final_norm, embed_weight,
                                self._vocab)
        if not self._return_routes:
            return logits
        return [logits] + routes + scores \
            + ([F.stack(*rows, axis=0)] if rows else [])


# https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json
LFM2_24B_A2B = dict(
    vocab_size=65536, hidden_size=2048, intermediate_size=11776,
    moe_intermediate_size=1536,
    layer_types=["full_attention" if i % 4 == 2 else "conv"
                 for i in range(40)],
    num_dense_layers=2, num_attention_heads=32, num_key_value_heads=8,
    num_experts=64, num_experts_per_tok=4, conv_L_cache=3, norm_eps=1e-5,
    rope_theta=1000000, norm_topk_prob=True, routed_scaling_factor=1,
    use_expert_bias=True)

# keys of an lfm2_moe config.json that shape nothing here, or that lfm2_moe()
# checks and drops: the bias that is false, the rotary key group (whose theta
# is passed as rope_theta), the depth that layer_types gives, what belongs to
# generation
UNUSED_KEYS = frozenset((
    "conv_bias", "max_position_embeddings", "model_type", "num_hidden_layers",
    "rope_parameters"))


def lfm2_moe(experts_held=None, return_routes=False, recompute=False,
             expert_bias_update_rate=0.0, **config):
    """A :class:`Lfm2MoeModel` from the keys of an ``lfm2_moe``
    ``config.json`` (those of ``LFM2_24B_A2B``; ``rope_parameters`` gives
    ``rope_theta``; the file's other keys, ``UNUSED_KEYS``, are taken and
    shape nothing). ``experts_held``: ``(first, count)`` of the
    ``num_experts`` that each expert layer computes here (``None``: all);
    ``expert_bias_update_rate``: the step of the balancing bias, which the
    file does not carry (0: frozen)."""
    rope = config.get("rope_parameters") or {}
    if config.get("conv_bias") or rope.get("rope_type", "default") \
            != "default":
        raise MXNetError(
            "lfm2_moe: a convolution without bias and default rotary "
            "expected, got " + repr({key: config.get(key) for key in (
                "conv_bias", "rope_parameters")}))
    if config.get("num_hidden_layers") not in (
            None, len(config.get("layer_types", ()))):
        raise MXNetError(
            f"num_hidden_layers {config['num_hidden_layers']} is not the "
            f"length of layer_types {config.get('layer_types')!r}")
    shaping = {key: value for key, value in config.items()
               if key not in UNUSED_KEYS}
    if "rope_theta" in rope:
        shaping.setdefault("rope_theta", rope["rope_theta"])
    return Lfm2MoeModel(experts_held=experts_held, return_routes=return_routes,
                        recompute=recompute,
                        expert_bias_update_rate=expert_bias_update_rate,
                        **shaping)


def lfm2_24b_a2b(experts_held=None, return_routes=False, recompute=False,
                 expert_bias_update_rate=0.0, **overrides):
    """LiquidAI/LFM2-24B-A2B (40 layers: 30 gated short convolutions, 10
    attention at 2, 6, ..., 38; 2 dense, then 64 experts, 4 a token);
    ``overrides`` replace keys of its configuration, e.g.
    ``layer_types=LFM2_24B_A2B["layer_types"][1:10], num_dense_layers=1``."""
    return lfm2_moe(experts_held=experts_held, return_routes=return_routes,
                    recompute=recompute,
                    expert_bias_update_rate=expert_bias_update_rate,
                    **dict(LFM2_24B_A2B, **overrides))
