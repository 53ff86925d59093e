"""BERT model family (GluonNLP parity: the reference ecosystem's
gluonnlp.model.bert — BERTEncoder/BERTModel and the bert_12_768_12 /
bert_24_1024_16 configurations that drive the driver's config #3).

TPU-first choices: attention runs through the blockwise flash-attention op
(ops/contrib.py _contrib_flash_attention) so long sequences stream through
VMEM; under a mesh the same model trains sequence-parallel via
mxnet_tpu.parallel.ring_attention; GELU/LayerNorm/Dense all lower to fused
XLA ops on the MXU.
"""
from __future__ import annotations


from ...base import MXNetError
from ...ops.contrib import MATMUL_EPILOGUE_ACTS
from .. import nn
from ..block import HybridBlock

__all__ = ["MultiHeadAttention", "PositionwiseFFN", "TransformerEncoderCell",
           "BERTEncoder", "BERTModel", "bert_12_768_12", "bert_24_1024_16",
           "get_bert_model"]


class MultiHeadAttention(HybridBlock):
    """Self-attention with fused QKV projection (the reference ecosystem
    fuses via _contrib_interleaved_matmul_selfatt_*; here one Dense + the
    flash-attention op)."""

    def __init__(self, units, num_heads, dropout=0.0, use_bias=True,
                 causal=False, attention_block_size=512, seq_parallel=False,
                 **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError(f"units {units} not divisible by num_heads "
                             f"{num_heads}")
        self._units = units
        self._num_heads = num_heads
        self._causal = causal
        self._block = attention_block_size
        if seq_parallel not in (False, True, "ring", "ulysses"):
            raise MXNetError(
                f"seq_parallel must be False, True/'ring', or 'ulysses'; "
                f"got {seq_parallel!r}")
        self._seq_parallel = seq_parallel
        with self.name_scope():
            self.qkv = nn.Dense(3 * units, flatten=False, use_bias=use_bias,
                                prefix="qkv_")
            self.proj = nn.Dense(units, flatten=False, use_bias=use_bias,
                                 prefix="proj_")
            self.dropout = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x, mask=None):
        # x: (B, S, C)
        h = self._num_heads
        d = self._units // h
        qkv = self.qkv(x)                                  # (B, S, 3C)
        if not self._seq_parallel:
            # single-program path: attention straight off the fused QKV in
            # (B, S, H, D) einsum layout — no permute copies (the
            # (3,B,H,S,D) chain cost ~6 GB/step, docs/perf_notes.md).
            # Shape-free (the op clamps block_size to the concrete S at
            # trace time) so the block exports symbolically.
            out = F.contrib.fused_self_attention(
                qkv, heads=h, causal=self._causal, block_size=self._block)
            out = self.proj(out)
            if self.dropout is not None:
                out = self.dropout(out)
            return out
        b, s, c = x.shape
        qkv = F.reshape(qkv, (b, s, 3, h, d))
        qkv = F.transpose(qkv, axes=(2, 0, 3, 1, 4))       # (3, B, H, S, D)
        q, k, v = qkv[0], qkv[1], qkv[2]
        # seq_parallel=True/'ring' → ring attention; 'ulysses' → the
        # all-to-all head-scatter variant (better when heads ≥ shards)
        if self._seq_parallel == "ulysses":
            out = F.contrib.ulysses_attention(q, k, v,
                                              causal=self._causal)
        else:
            out = F.contrib.ring_attention(q, k, v, causal=self._causal)
        out = F.transpose(out, axes=(0, 2, 1, 3))          # (B, S, H, D)
        out = F.reshape(out, (b, s, self._units))
        out = self.proj(out)
        if self.dropout is not None:
            out = self.dropout(out)
        return out


class PositionwiseFFN(HybridBlock):
    """ref ecosystem: gluonnlp PositionwiseFFN (GELU for BERT).

    Both halves ride the guarded pallas matmul-epilogue tier
    (docs/pallas.md): ffn_1's bias+gelu and ffn_2's bias+dropout each run
    as ONE pass over the matmul output (dropout-in-epilogue — the BERT
    MFU lever, ROADMAP.md S3/S4) instead of separate bias /
    activation / mask ops. Same params, same math; non-fusable
    activations keep the classic layout."""

    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu",
                 **kwargs):
        super().__init__(**kwargs)
        fused_act = activation if activation in MATMUL_EPILOGUE_ACTS \
            else None
        with self.name_scope():
            self.ffn_1 = nn.Dense(hidden_size, flatten=False, prefix="ffn1_",
                                  activation=fused_act)
            if fused_act is not None:
                self.activation = None
            else:
                self.activation = nn.GELU() if activation == "gelu" else \
                    nn.Activation(activation)
            self.ffn_2 = nn.Dense(units, flatten=False, prefix="ffn2_",
                                  epilogue_dropout=dropout)

    def hybrid_forward(self, F, x):
        out = self.ffn_1(x)
        if self.activation is not None:
            out = self.activation(out)
        # dropout is folded into ffn_2's epilogue (epilogue_dropout=)
        return self.ffn_2(out)


class TransformerEncoderCell(HybridBlock):
    """Post-LayerNorm transformer cell (BERT arrangement)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 causal=False, seq_parallel=False, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attention = MultiHeadAttention(units, num_heads,
                                                dropout=dropout,
                                                causal=causal,
                                                seq_parallel=seq_parallel,
                                                prefix="attn_")
            self.ln1 = nn.LayerNorm(epsilon=1e-12, prefix="ln1_")
            self.ffn = PositionwiseFFN(units, hidden_size, dropout=dropout,
                                       prefix="ffn_")
            self.ln2 = nn.LayerNorm(epsilon=1e-12, prefix="ln2_")
            self.dropout = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        att = self.attention(x)
        if self.dropout is not None:
            att = self.dropout(att)
        x = self.ln1(x + att)
        x = self.ln2(x + self.ffn(x))
        return x


class BERTEncoder(HybridBlock):
    """Stack of transformer cells (gluonnlp BERTEncoder parity)."""

    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, seq_parallel=False, **kwargs):
        super().__init__(**kwargs)
        self._num_layers = num_layers
        with self.name_scope():
            self.transformer_cells = nn.HybridSequential(prefix="cells_")
            with self.transformer_cells.name_scope():
                for _ in range(num_layers):
                    self.transformer_cells.add(TransformerEncoderCell(
                        units, hidden_size, num_heads, dropout=dropout,
                        seq_parallel=seq_parallel))

    def hybrid_forward(self, F, x):
        return self.transformer_cells(x)


class BERTModel(HybridBlock):
    """gluonnlp BERTModel parity: embeddings → encoder → (pooler, MLM,
    NSP) heads. forward(inputs, token_types) → (sequence_out, pooled_out)
    or with masked_positions → MLM scores."""

    def __init__(self, num_layers=12, units=768, hidden_size=3072,
                 num_heads=12, max_length=512, vocab_size=30522,
                 token_type_vocab_size=2, dropout=0.1,
                 use_pooler=True, use_decoder=True, use_classifier=True,
                 seq_parallel=False, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._use_pooler = use_pooler
        self._use_decoder = use_decoder
        self._use_classifier = use_classifier
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units,
                                           prefix="word_embed_")
            self.token_type_embed = nn.Embedding(token_type_vocab_size,
                                                 units,
                                                 prefix="token_type_embed_")
            self.position_weight = self.params.get(
                "position_embed", shape=(max_length, units))
            self.embed_layer_norm = nn.LayerNorm(epsilon=1e-12,
                                                 prefix="embed_ln_")
            self.embed_dropout = nn.Dropout(dropout) if dropout else None
            self.encoder = BERTEncoder(num_layers, units, hidden_size,
                                       num_heads, dropout=dropout,
                                       seq_parallel=seq_parallel,
                                       prefix="encoder_")
            if use_pooler:
                self.pooler = nn.Dense(units, activation="tanh",
                                       flatten=False, prefix="pooler_")
            if use_decoder:
                self.decoder = nn.HybridSequential(prefix="decoder_")
                with self.decoder.name_scope():
                    self.decoder.add(nn.Dense(units, flatten=False,
                                              activation=None))
                    self.decoder.add(nn.GELU())
                    self.decoder.add(nn.LayerNorm(epsilon=1e-12))
                    self.decoder.add(nn.Dense(vocab_size, flatten=False))
            if use_classifier:
                self.classifier = nn.Dense(2, prefix="nsp_")

    def hybrid_forward(self, F, inputs, token_types=None,
                       masked_positions=None, position_weight=None):
        x = self.word_embed(inputs)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        # shape-free position add (exports symbolically): slice the
        # (1, max_len, U) table along the sequence axis like x (B, S, U)
        pos = F.slice_like(F.expand_dims(position_weight, axis=0), x,
                           axes=(1,))
        x = F.broadcast_add(x, pos)
        x = self.embed_layer_norm(x)
        if self.embed_dropout is not None:
            x = self.embed_dropout(x)
        seq_out = self.encoder(x)
        outputs = [seq_out]
        if self._use_pooler:
            cls = F.squeeze(F.slice(seq_out, begin=(None, 0, None),
                                    end=(None, 1, None)), axis=1)
            pooled = self.pooler(cls)
            outputs.append(pooled)
            if self._use_classifier:
                outputs.append(self.classifier(pooled))
        if self._use_decoder:
            if masked_positions is not None:
                # per-row gather: picked[b, m] = seq_out[b, pos[b, m]];
                # batch indices built shape-free via arange_like so the
                # masked path also exports symbolically
                batch_idx = F.broadcast_like(
                    F.reshape(F.arange_like(masked_positions, axis=0),
                              (-1, 1)),
                    masked_positions)
                idx = F.stack(batch_idx, masked_positions, axis=0)
                picked = F.gather_nd(seq_out, idx)
                outputs.append(self.decoder(picked))
            else:
                outputs.append(self.decoder(seq_out))
        return tuple(outputs) if len(outputs) > 1 else outputs[0]


_bert_configs = {
    "bert_12_768_12": dict(num_layers=12, units=768, hidden_size=3072,
                           num_heads=12),
    "bert_24_1024_16": dict(num_layers=24, units=1024, hidden_size=4096,
                            num_heads=16),
}


def get_bert_model(model_name="bert_12_768_12", vocab_size=30522,
                   max_length=512, dropout=0.1, **kwargs):
    if model_name not in _bert_configs:
        raise MXNetError(f"unknown BERT config {model_name!r}; "
                         f"options: {sorted(_bert_configs)}")
    cfg = dict(_bert_configs[model_name])
    cfg.update(kwargs)
    return BERTModel(vocab_size=vocab_size, max_length=max_length,
                     dropout=dropout, **cfg)


def bert_12_768_12(**kwargs):
    return get_bert_model("bert_12_768_12", **kwargs)


def bert_24_1024_16(**kwargs):
    return get_bert_model("bert_24_1024_16", **kwargs)
