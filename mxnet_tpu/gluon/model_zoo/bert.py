"""BERT model family (transformer encoder).

The reference repo has no in-tree BERT model; its BERT story is the
transformer attention helper kernels (src/operator/contrib/transformer.cc)
plus the GluonNLP model zoo built on Gluon. This module provides the same
surface the GluonNLP BERT zoo exposed (bert_12_768_12 / bert_24_1024_16,
masked-LM + next-sentence heads) built TPU-first:

  * attention runs through npx.multi_head_attention -> the pallas flash
    attention kernel (ops/attention.py) — fused QKV projection keeps one big
    MXU matmul instead of three;
  * everything is HybridBlock, so ``hybridize()`` jits the whole encoder;
  * the MLM decoder is weight-tied to the word embedding (standard BERT).
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from ... import numpy_extension as npx
from .. import nn
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["BERTEncoder", "BERTModel", "BERTForPretrain",
           "MultiHeadAttentionCell", "PositionwiseFFN",
           "TransformerEncoderCell", "get_bert", "bert_12_768_12",
           "bert_24_1024_16"]


class MultiHeadAttentionCell(HybridBlock):
    """Self-attention with fused QKV projection + flash attention."""

    def __init__(self, units, num_heads, dropout=0.0, use_bias=True, **kw):
        super().__init__(**kw)
        if units % num_heads:
            raise ValueError(f"units {units} not divisible by heads {num_heads}")
        self._units = units
        self._num_heads = num_heads
        self.qkv = nn.Dense(3 * units, use_bias=use_bias, flatten=False,
                            in_units=units)
        self.proj = nn.Dense(units, use_bias=use_bias, flatten=False,
                             in_units=units)
        self.dropout = nn.Dropout(dropout) if dropout else None

    def forward(self, x, mask=None, valid_length=None):
        from ... import numpy as mnp
        qkv = self.qkv(x)                      # (B, T, 3U)
        q, k, v = mnp.split(qkv, 3, axis=-1)
        out = npx.multi_head_attention(q, k, v, num_heads=self._num_heads,
                                       mask=mask, valid_length=valid_length)
        out = self.proj(out)
        if self.dropout is not None:
            out = self.dropout(out)
        return out


class PositionwiseFFN(HybridBlock):
    """Transformer FFN block: Dense -> act -> Dense (+dropout)."""

    def __init__(self, units, hidden_size, activation="erf_gelu", dropout=0.0,
                 **kw):
        super().__init__(**kw)
        self.ffn1 = nn.Dense(hidden_size, flatten=False, in_units=units)
        self.ffn2 = nn.Dense(units, flatten=False, in_units=hidden_size)
        self._act = activation
        self.dropout = nn.Dropout(dropout) if dropout else None

    def forward(self, x):
        h = npx.activation(self.ffn1(x), act_type=self._act)
        h = self.ffn2(h)
        if self.dropout is not None:
            h = self.dropout(h)
        return h


class TransformerEncoderCell(HybridBlock):
    """Post-norm transformer encoder layer (BERT style)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 layer_norm_eps=1e-12, **kw):
        super().__init__(**kw)
        self.attention = MultiHeadAttentionCell(units, num_heads,
                                                dropout=dropout)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout=dropout)
        self.layer_norm_att = nn.LayerNorm(epsilon=layer_norm_eps,
                                           in_channels=units)
        self.layer_norm_ffn = nn.LayerNorm(epsilon=layer_norm_eps,
                                           in_channels=units)

    def forward(self, x, mask=None, valid_length=None):
        x = self.layer_norm_att(x + self.attention(x, mask, valid_length))
        x = self.layer_norm_ffn(x + self.ffn(x))
        return x


class BERTEncoder(HybridBlock):
    """Stack of transformer encoder cells."""

    def __init__(self, num_layers=12, units=768, hidden_size=3072,
                 num_heads=12, dropout=0.1, max_length=512,
                 layer_norm_eps=1e-12, **kw):
        super().__init__(**kw)
        self._units = units
        self._max_length = max_length
        self.layers = nn.HybridSequential()
        for _ in range(num_layers):
            self.layers.add(TransformerEncoderCell(
                units, hidden_size, num_heads, dropout=dropout,
                layer_norm_eps=layer_norm_eps))

    def forward(self, x, mask=None, valid_length=None):
        for cell in self.layers:
            x = cell(x, mask, valid_length)
        return x


class BERTModel(HybridBlock):
    """BERT backbone: embeddings + encoder + pooler.

    forward(inputs, token_types, valid_length=None) ->
        (sequence_output (B,T,U), pooled_output (B,U))
    """

    def __init__(self, vocab_size=30522, token_type_vocab_size=2,
                 units=768, hidden_size=3072, num_layers=12, num_heads=12,
                 max_length=512, dropout=0.1, layer_norm_eps=1e-12,
                 dtype=jnp.float32, **kw):
        super().__init__(**kw)
        self._units = units
        self._max_length = max_length
        self.word_embed = nn.Embedding(vocab_size, units, dtype=dtype)
        self.token_type_embed = nn.Embedding(token_type_vocab_size, units,
                                             dtype=dtype)
        self.position_weight = Parameter(shape=(max_length, units),
                                         dtype=dtype, name="position_weight")
        self.embed_layer_norm = nn.LayerNorm(epsilon=layer_norm_eps,
                                             in_channels=units)
        self.embed_dropout = nn.Dropout(dropout) if dropout else None
        self.encoder = BERTEncoder(num_layers=num_layers, units=units,
                                   hidden_size=hidden_size,
                                   num_heads=num_heads, dropout=dropout,
                                   max_length=max_length,
                                   layer_norm_eps=layer_norm_eps)
        self.pooler = nn.Dense(units, activation="tanh", flatten=False,
                               in_units=units)

    def forward(self, inputs, token_types=None, valid_length=None):
        seq_len = inputs.shape[1]
        if seq_len > self._max_length:
            raise ValueError(
                f"sequence length {seq_len} exceeds max_length "
                f"{self._max_length} this BERTModel was built with")
        emb = self.word_embed(inputs)
        if token_types is not None:
            emb = emb + self.token_type_embed(token_types)
        pos = self.position_weight.data()[:seq_len]
        emb = emb + pos.reshape(1, seq_len, self._units)
        emb = self.embed_layer_norm(emb)
        if self.embed_dropout is not None:
            emb = self.embed_dropout(emb)

        # per-row key lengths ride the pallas kernel's SMEM length input
        # (a boolean mask would force the O(T^2) reference fallback)
        out = self.encoder(emb, None, valid_length)
        pooled = self.pooler(out[:, 0])
        return out, pooled


class BERTForPretrain(HybridBlock):
    """Masked-LM + next-sentence-prediction heads over BERTModel.

    forward(inputs, token_types, valid_length, masked_positions) ->
        (mlm_scores (B,P,V), nsp_scores (B,2))
    The MLM decoder is tied to the word-embedding matrix.
    """

    def __init__(self, bert: BERTModel, vocab_size=None, **kw):
        super().__init__(**kw)
        self.bert = bert
        self._vocab_size = vocab_size or bert.word_embed._input_dim
        units = bert._units
        # exact erf GELU — BERT semantics (and weight-porting parity); the
        # tanh-approximate "gelu" diverges ~1e-3/layer over 12-24 layers
        self.mlm_transform = nn.Dense(units, activation="erf_gelu",
                                      flatten=False, in_units=units)
        self.mlm_layer_norm = nn.LayerNorm(epsilon=1e-12, in_channels=units)
        self.mlm_bias = Parameter(shape=(self._vocab_size,), init="zeros",
                                  name="mlm_bias")
        self.nsp = nn.Dense(2, flatten=False, in_units=units)

    def forward(self, inputs, token_types=None, valid_length=None,
                masked_positions=None):
        seq_out, pooled = self.bert(inputs, token_types, valid_length)
        nsp_scores = self.nsp(pooled)
        if masked_positions is None:
            hidden = seq_out
        else:
            # gather the masked positions: (B, P, U)
            from ... import numpy as mnp
            idx = masked_positions.reshape(
                masked_positions.shape[0], -1, 1).astype(jnp.int32)
            hidden = mnp.take_along_axis(seq_out, idx, axis=1)
        h = self.mlm_transform(hidden)
        h = self.mlm_layer_norm(h)
        embed_w = self.bert.word_embed.weight.data()     # (V, U)
        scores = npx.fully_connected(h, embed_w, self.mlm_bias.data(),
                                     num_hidden=self._vocab_size,
                                     flatten=False)
        return scores, nsp_scores


_BERT_SPECS = {
    "bert_12_768_12": dict(num_layers=12, units=768, hidden_size=3072,
                           num_heads=12),
    "bert_24_1024_16": dict(num_layers=24, units=1024, hidden_size=4096,
                            num_heads=16),
}


def get_bert(name="bert_12_768_12", vocab_size=30522, max_length=512,
             dropout=0.1, **kwargs):
    spec = dict(_BERT_SPECS[name])
    spec.update(kwargs)
    return BERTModel(vocab_size=vocab_size, max_length=max_length,
                     dropout=dropout, **spec)


def bert_12_768_12(**kwargs):
    """BERT-base."""
    return get_bert("bert_12_768_12", **kwargs)


def bert_24_1024_16(**kwargs):
    """BERT-large."""
    return get_bert("bert_24_1024_16", **kwargs)
