"""DeepSeek-V2 style decoders (``model_type`` ``deepseek_v2``): pre-norm
two-part layers whose attention is multi-head latent attention (MLA: queries
and keys/values through low-rank latents, a rotary part of the key shared by
all heads, YaRN-scaled), whose feed-forward part is a gated MLP in the first
``first_k_dense_replace`` layers and routed experts after them (softmax
scores, group-limited choice, gated experts, gated shared experts); a final
RMSNorm, an untied head (docs/deepseek_v2.md has the equations, the cut and
what was assumed).

The blocks are ordinary HybridBlocks over registered ops
(``_contrib_flash_attention`` with a value head smaller than the query's,
``_contrib_rotary_embedding`` with YaRN's frequencies, ``RMSNorm``, the
expert ops behind ``gluon.contrib.nn.RoutedExperts``), so the model trains
through ``ShardedTrainer`` like any other, and each layer can ask for its
activations to be recomputed in the backward pass (``recompute``). One
chip's share of a layer: an attention block is told which heads it holds
(``heads_held``), an expert layer which experts (``experts_held``); what the
absent heads and experts would add is left out. Device time is named
``mla`` (projections, latent norms, rotary, the concatenation),
``mla.attention`` (the attention core), ``mlp``, ``moe.*``.
"""
from __future__ import annotations

import functools

from ...base import MXNetError
from ...observability.instrument import device_scope
from ...ops.nn import yarn_mscale
from .. import nn
from ..block import HybridBlock
from ..contrib.nn import RoutedExperts
from .granite_hybrid import GatedMLP, _linear, embed_tokens, project_logits

__all__ = ["LatentAttention", "DeepseekV2Layer", "DeepseekV2Model",
           "deepseek_v2", "DEEPSEEK_V2", "MLA_COUNT_METRIC"]

MLA_COUNT_METRIC = "mxnet_tpu_latent_attention_layers_traced_total"


def _count_traced_layer(heads, held, qk, v, path):
    """One latent-attention layer traced into a program, by the heads it
    holds, the query/key and value head sizes and the attention branch its
    core takes (``ops.contrib.attention_branch``): trace-time only, so a
    compiled step never counts."""
    from ...observability.metrics import default_registry
    default_registry().counter(
        MLA_COUNT_METRIC, "latent-attention layers traced into a program",
        ("heads", "held", "qk", "v", "path")).labels(
            heads=str(heads), held=str(held), qk=str(qk), v=str(v),
            path=path).inc()


class LatentAttention(HybridBlock):
    """Multi-head latent attention, causal, no bias anywhere.

    ``c_q = RMSNorm(W_DQ x)`` (``q_lora_rank``), ``[q_nope | q_pe]_h = (W_UQ
    c_q)_h``; ``[c_kv | k_pe] = W_DKV x`` (``kv_lora_rank`` | ``rope``),
    ``[k_nope | v]_h = (W_UKV RMSNorm(c_kv))_h``; ``q_pe`` and ``k_pe`` (one
    vector for all heads) turn by rotary at positions 0..S-1 (rotate-half,
    ``rope_theta``, YaRN where ``rope_scaling`` says so); ``score_h =
    [q_nope | q_pe]_h . [k_nope | k_pe]_h * (nope + rope)^-1/2 * m^2`` with
    ``m = yarn_mscale(factor, mscale_all_dim)``; causal softmax times
    ``v_h``; the heads held concatenated, then ``W_O``.

    ``heads_held``: ``(first, count)`` of the ``num_heads`` this block holds
    (``None``: all); the per-head projections are the held heads' rows and
    columns, and the block's output is their part of the layer's."""

    def __init__(self, units, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta=10000.0, rope_scaling=None, epsilon=1e-6,
                 heads_held=None, **kwargs):
        super().__init__(**kwargs)
        first, held = heads_held or (0, num_heads)
        if not (0 <= first and 0 < held and first + held <= num_heads):
            raise MXNetError(f"LatentAttention: heads {first}..."
                             f"{first + held - 1} are not among {num_heads}")
        self._heads, self._held = num_heads, held
        self._nope, self._rope, self._v = (qk_nope_head_dim, qk_rope_head_dim,
                                           v_head_dim)
        self._kv_rank = kv_lora_rank
        self._rotary = {"theta": float(rope_theta)}
        self._scale = (qk_nope_head_dim + qk_rope_head_dim) ** -0.5
        if rope_scaling:
            factor = float(rope_scaling["factor"])
            self._rotary.update(
                scaling_factor=factor,
                original_max_position_embeddings=int(
                    rope_scaling["original_max_position_embeddings"]),
                beta_fast=float(rope_scaling["beta_fast"]),
                beta_slow=float(rope_scaling["beta_slow"]),
                mscale=float(rope_scaling["mscale"]),
                mscale_all_dim=float(rope_scaling["mscale_all_dim"]))
            self._scale *= yarn_mscale(
                factor, float(rope_scaling["mscale_all_dim"])) ** 2
        qk_dim = qk_nope_head_dim + qk_rope_head_dim
        with self.name_scope():
            self.q_a_proj = _linear(q_lora_rank, units, "q_a_")
            self.q_a_norm = nn.RMSNorm(epsilon=epsilon,
                                       in_channels=q_lora_rank,
                                       prefix="q_a_norm_")
            self.q_b_proj = _linear(held * qk_dim, q_lora_rank, "q_b_")
            self.kv_a_proj = _linear(kv_lora_rank + qk_rope_head_dim, units,
                                     "kv_a_")
            self.kv_a_norm = nn.RMSNorm(epsilon=epsilon,
                                        in_channels=kv_lora_rank,
                                        prefix="kv_a_norm_")
            self.kv_b_proj = _linear(held * (qk_nope_head_dim + v_head_dim),
                                     kv_lora_rank, "kv_b_")
            self.o_proj = _linear(units, held * v_head_dim, "o_")

    @property
    def scale(self):
        """What the scores are multiplied by."""
        return self._scale

    def hybrid_forward(self, F, x):
        import jax
        from ...ops.contrib import attention_branch
        nope, rope, held = self._nope, self._rope, self._held

        def cut(t, begin, end):
            return F.slice_axis(t, axis=-1, begin=begin, end=end)

        def by_head(t):                 # (B, S, H, D) -> (B, H, S, D)
            return F.transpose(t, axes=(0, 2, 1, 3))

        with device_scope("mla"):
            q = F.reshape(self.q_b_proj(self.q_a_norm(self.q_a_proj(x))),
                          (0, 0, held, -1))
            kv = self.kv_a_proj(x)
            k_pe = F.reshape(cut(kv, self._kv_rank, None), (0, 0, 1, -1))
            kv = F.reshape(self.kv_b_proj(self.kv_a_norm(
                cut(kv, 0, self._kv_rank))), (0, 0, held, -1))
            q_pe = F.contrib.rotary_embedding(cut(q, nope, None),
                                              **self._rotary)
            k_pe = F.contrib.rotary_embedding(k_pe, **self._rotary)
            q = by_head(F.concat(cut(q, 0, nope), q_pe, dim=-1))
            k = by_head(F.concat(cut(kv, 0, nope),
                                 F.repeat(k_pe, repeats=held, axis=2),
                                 dim=-1))
            v = by_head(cut(kv, nope, None))
            if isinstance(x._data, jax.core.Tracer):
                _count_traced_layer(self._heads, held, nope + rope, self._v,
                                    attention_branch(q._data, k._data,
                                                     v._data))
            with device_scope("mla.attention"):
                out = F.contrib.flash_attention(q, k, v, causal=True,
                                                sm_scale=self._scale)
            return self.o_proj(F.reshape(by_head(out), (0, 0, -1)))


class DeepseekV2Layer(HybridBlock):
    """``h + attention(RMSNorm(h))`` then ``h + ffn(RMSNorm(h))``;
    ``make_attention(prefix=...)`` and ``make_ffn(prefix=...)`` build the
    two parts under this layer's names. An ``ffn`` that returns ``(y,
    routes, rows, scores)`` makes the layer return ``(h, routes, rows,
    scores)``."""

    def __init__(self, make_attention, make_ffn, units, epsilon=1e-6,
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.input_norm = nn.RMSNorm(epsilon=epsilon, in_channels=units,
                                         prefix="input_norm_")
            self.attention = make_attention(prefix="attention_")
            self.mlp_norm = nn.RMSNorm(epsilon=epsilon, in_channels=units,
                                       prefix="mlp_norm_")
            self.mlp = make_ffn(prefix="mlp_")

    def hybrid_forward(self, F, h):
        with device_scope("norm"):
            x = self.input_norm(h)
        h = h + self.attention(x)
        with device_scope("norm"):
            x = self.mlp_norm(h)
        out = self.mlp(x)
        if isinstance(out, (tuple, list)):
            return (h + out[0],) + tuple(out[1:])
        return h + out


def _is_expert_layer(i, first_k_dense_replace, moe_layer_freq):
    return i >= first_k_dense_replace and i % moe_layer_freq == 0


def balance_loss(routes, scores, n_group, topk_group, alphas):
    """DeepSeek-V2's three balance losses of one expert layer
    (arXiv:2405.04434 sec. 2.2.3), each over a sequence and their sum
    averaged over the batch: ``alphas[0] sum_i f_i P_i`` over the experts,
    ``alphas[1] sum_d f'_d P'_d`` over the devices and ``alphas[2] sum_d
    f''_d P'_d``, the devices being the router's ``n_group`` groups and a
    token sent to at most ``topk_group`` of them. ``f_i = N / (K S)`` times
    the tokens that chose expert ``i``, ``P_i`` the mean score of expert
    ``i``; ``f'_d`` the mean ``f_i`` and ``P'_d`` the summed ``P_i`` of a
    device's experts; ``f''_d = n_group / (topk_group S)`` times the tokens
    sent to device ``d``. ``routes`` (B, S, K) int ids among the N experts
    (a dropped pair's less N), ``scores`` (B, S, N) float32; only the scores
    carry a gradient."""
    import jax
    import jax.numpy as jnp
    seq, k = routes.shape[-2:]
    experts = scores.shape[-1]
    # a pair dropped past a device's budget was chosen all the same
    chosen = jnp.sum(jax.nn.one_hot(jnp.mod(routes, experts), experts,
                                    dtype=jnp.float32), -2)
    f = experts / (k * seq) * jnp.sum(chosen, axis=-2)
    p = jnp.mean(scores.astype(jnp.float32), axis=-2)
    by_device = f.shape[:-1] + (n_group, experts // n_group)
    f_device = jnp.mean(f.reshape(by_device), -1)
    p_device = jnp.sum(p.reshape(by_device), -1)
    sent = jnp.max(chosen.reshape(chosen.shape[:-1] + by_device[-2:]), -1)
    f_sent = n_group / (topk_group * seq) * jnp.sum(sent, axis=-2)
    per_sequence = (alphas[0] * jnp.sum(f * p, -1)
                    + alphas[1] * jnp.sum(f_device * p_device, -1)
                    + alphas[2] * jnp.sum(f_sent * p_device, -1))
    return jnp.mean(per_sequence)


class DeepseekV2Model(HybridBlock):
    """Tokens (B, S) -> logits (B, S, vocab): ``E[tokens]``,
    ``num_hidden_layers`` two-part layers (the first
    ``first_k_dense_replace`` with a gated MLP of ``intermediate_size``,
    then every ``moe_layer_freq``-th with routed experts), a final RMSNorm,
    an untied head. With ``return_routes`` the outputs are ``[logits,
    routes of the first expert layer, of the second, ..., scores of the
    first, of the second, ..., rows]``, as ``nemotron_h``'s.
    ``balance_alphas``: the coefficients of :func:`balance_loss`, summed
    over the expert layers into the trainer's objective (``None``: no
    balance loss). ``capacity_factor``: ``RoutedExperts``' (0: nothing is
    dropped)."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 moe_intermediate_size, num_hidden_layers, num_attention_heads,
                 q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                 v_head_dim, n_routed_experts, n_shared_experts,
                 num_experts_per_tok, first_k_dense_replace=1,
                 moe_layer_freq=1, n_group=1, topk_group=1,
                 norm_topk_prob=False, routed_scaling_factor=1.0,
                 scoring_func="softmax", rms_norm_eps=1e-6, rope_theta=10000.0,
                 rope_scaling=None, experts_held=None, heads_held=None,
                 balance_alphas=None, capacity_factor=0.0,
                 return_routes=False, recompute=False, **kwargs):
        super().__init__(**kwargs)
        self._vocab, self._units = vocab_size, hidden_size
        self._return_routes = bool(return_routes)
        self._balance = (tuple(float(a) for a in balance_alphas)
                         if balance_alphas else None)
        self._groups = (n_group, topk_group)
        # the trainer adds _trace_aux_loss times this to its objective
        self.aux_loss_weight = 1.0
        self._trace_aux_loss = None
        first, held = experts_held or (0, n_routed_experts)
        attention = functools.partial(
            LatentAttention, hidden_size, num_attention_heads, q_lora_rank,
            kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
            rope_theta=rope_theta, rope_scaling=rope_scaling,
            epsilon=rms_norm_eps, heads_held=heads_held)
        dense = functools.partial(GatedMLP, hidden_size, intermediate_size)
        experts = functools.partial(
            RoutedExperts, hidden_size, moe_intermediate_size,
            n_routed_experts, k=num_experts_per_tok, first_expert=first,
            experts_held=held, norm_topk_prob=norm_topk_prob,
            scaling_factor=routed_scaling_factor,
            shared_hidden_size=n_shared_experts * moe_intermediate_size,
            return_routes=return_routes or self._balance is not None,
            scoring=scoring_func,
            n_group=n_group, topk_group=topk_group, gated=True,
            capacity_factor=capacity_factor)
        self._routed = [_is_expert_layer(i, first_k_dense_replace,
                                         moe_layer_freq)
                        for i in range(num_hidden_layers)]
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, hidden_size))
            self.layers = nn.HybridSequential(prefix="layers_")
            with self.layers.name_scope():
                for routed in self._routed:
                    self.layers.add(DeepseekV2Layer(
                        attention, experts if routed else dense, hidden_size,
                        epsilon=rms_norm_eps).recompute(recompute))
            self.final_norm = nn.RMSNorm(epsilon=rms_norm_eps,
                                         in_channels=hidden_size,
                                         prefix="final_norm_")
            self.head_weight = self.params.get(
                "head_weight", shape=(vocab_size, hidden_size))

    def hybrid_forward(self, F, tokens, embed_weight, head_weight):
        h = embed_tokens(F, tokens, embed_weight, self._vocab, self._units)
        routes, rows, scores = [], [], []
        for routed, layer in zip(self._routed, self.layers):
            if routed and (self._return_routes or self._balance):
                h, chosen, computed, scored = layer(h)
                routes.append(chosen)
                rows.append(computed)
                scores.append(scored)
            else:
                h = layer(h)
        if self._balance and routes:
            with device_scope("moe.balance"):
                self._trace_aux_loss = sum(
                    balance_loss(chosen._data, scored._data, *self._groups,
                                 self._balance)
                    for chosen, scored in zip(routes, scores))
        logits = project_logits(F, h, self.final_norm, head_weight,
                                self._vocab)
        if not self._return_routes:
            return logits
        return [logits] + routes + scores \
            + ([F.stack(*rows, axis=0)] if rows else [])


# https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json
DEEPSEEK_V2 = dict(
    vocab_size=102400, hidden_size=5120, intermediate_size=12288,
    moe_intermediate_size=1536, num_hidden_layers=60, num_attention_heads=128,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=160,
    n_shared_experts=2, num_experts_per_tok=6, first_k_dense_replace=1,
    moe_layer_freq=1, n_group=8, topk_group=3, norm_topk_prob=False,
    routed_scaling_factor=16.0, scoring_func="softmax", rms_norm_eps=1e-6,
    rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=40, beta_fast=32, beta_slow=1,
                      mscale=0.707, mscale_all_dim=0.707,
                      original_max_position_embeddings=4096))

# keys of a deepseek_v2 config.json that shape nothing here: the bias that
# is false, the activation that is SiLU, the choice's method (checked), the
# key/value heads that latent attention does not have apart from the query
# heads, the switch of per-sequence balance losses (``balance_alphas`` gives
# their coefficients; they are always per sequence here), what belongs to
# generation
UNUSED_KEYS = frozenset((
    "attention_bias", "hidden_act", "max_position_embeddings", "model_type",
    "num_key_value_heads", "seq_aux", "tie_word_embeddings", "topk_method"))


def deepseek_v2(experts_held=None, heads_held=None, balance_alphas=None,
                capacity_factor=0.0, return_routes=False, recompute=False,
                **config):
    """A :class:`DeepseekV2Model` from the keys of a ``deepseek_v2``
    ``config.json`` (those of ``DEEPSEEK_V2``; the file's other keys,
    ``UNUSED_KEYS``, are taken and shape nothing). ``experts_held`` /
    ``heads_held``: ``(first, count)`` of the ``n_routed_experts`` and of the
    ``num_attention_heads`` that each layer computes here (``None``: all);
    ``balance_alphas``, ``capacity_factor``: :class:`DeepseekV2Model`'s.
    Refused: a choice other than ``group_limited_greedy``, a rope scaling
    other than ``yarn``, an activation other than SiLU, an attention bias, a
    tied head."""
    scaling = config.get("rope_scaling")
    if config.get("topk_method", "group_limited_greedy") \
            != "group_limited_greedy" \
            or scaling and scaling.get("type") != "yarn" \
            or config.get("hidden_act", "silu") != "silu" \
            or config.get("attention_bias") \
            or config.get("tie_word_embeddings"):
        raise MXNetError(
            "deepseek_v2: topk_method 'group_limited_greedy', rope_scaling "
            "of type 'yarn' or none, hidden_act 'silu', no attention bias "
            "and an untied head expected, got "
            + repr({key: config.get(key) for key in (
                "topk_method", "rope_scaling", "hidden_act",
                "attention_bias", "tie_word_embeddings")}))
    shaping = {key: value for key, value in config.items()
               if key not in UNUSED_KEYS}
    return DeepseekV2Model(experts_held=experts_held, heads_held=heads_held,
                           balance_alphas=balance_alphas,
                           capacity_factor=capacity_factor,
                           return_routes=return_routes, recompute=recompute,
                           **shaping)
