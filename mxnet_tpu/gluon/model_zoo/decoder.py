"""Autoregressive LM carriers for the decode path (docs/serving.md).

The reference repo's generative story was GluonNLP's language models
(AWD-LSTM, transformer decoders) built on Gluon RNN cells and the
transformer attention helpers (src/operator/contrib/transformer.cc).
This module grows the same two families from the in-tree pieces — the
BERT transformer cells (bert.py) and LSTMCell (rnn/rnn_cell.py) — into
decode-ready blocks with *functional* cache state, the shape the serve
decode loop (serve/decode.py) needs:

    logits, new_cache = lm(tokens, cache, cache_len, n_tokens)

One hybridized signature serves both prefill (T = padded prompt chunk)
and the decode step (T = 1); only the shapes differ, so ShapeBucketer
grids over (T, C) and the whole thing AOT-warms at registration.

Signature contract (both carriers):

  * ``tokens``    — ``(B, T)`` int32 token ids.
  * ``cache``     — tuple of per-layer leaf tuples.  A leaf is of one
    of three kinds: ``"paged"`` — per-position pages, 4-D with the
    bucketed capacity C on axis 2 — ``"window"`` — per-position pages
    on a RING, 4-D with the ring's rows on axis 2 whatever the capacity
    (a window layer: the row of position ``p`` is ``p mod R``) — or
    ``"state"`` — constant in the context, whatever its rank.
    ``begin_cache`` at two capacities says which leaves follow the
    capacity (``serve.decode.cache_spec``); a block whose tree holds a
    leaf that does not names its kinds in ``cache_kinds()``, and the
    spec checks one against the other; one that names a window leaf
    says in ``attention_window`` how many positions its layers see.
    The serve tier's grower, mover,
    warm-up grid and prefix guard go by the kind; the page copy and the
    prefix trie take paged leaves only.  The transformer and the LSTM
    below are the two pure cases, ``kimi_linear.py`` holds paged and
    state leaves in one tree, ``mellum.py`` paged and window leaves.
    Transformer: ``((kv0,), ...)``, ONE
    payload leaf ``(B, H, C, 2*dh)`` per layer holding K in
    ``[..., :dh]`` and V in ``[..., dh:]`` of every position — at head
    size 64 the last axis fills a whole 128-lane tile, so XLA's
    in-place append and the decode kernel agree on the leaf's layout
    in HBM and no step re-lays it out (PERF.md section 5).  With
    ``cache_dtype="int8"`` the per-layer tuple is
    ``(kv_q, k_scale, v_scale)`` — the int8 payload leaf plus
    per-position f32 scales ``(B, H, C, 1)``, ~4x less HBM per page
    (docs/precision.md).  LSTM: ``((h0, c0), ...)`` each ``(B, U)`` —
    capacity-independent, the recurrent state IS the whole history.
  * ``cache_len`` — ``(B,)`` int32, the PRE-call valid length per row.
    Transformer attention lets local query ``i`` see cache positions
    ``<= cache_len + i``, so garbage keys appended past a row's true
    prompt length are never attended (they get overwritten by later
    appends once the host advances the valid length by the TRUE token
    count, not the padded T).
  * ``n_tokens``  — ``(B,)`` int32, how many of the T tokens are real
    this call.  The LSTM gates its state update per step on
    ``step < n_tokens`` (a sequential model cannot "mask out" padding
    after the fact); the transformer ignores it (masking is positional).
  * returns ``(logits (B, T, V), new_cache)`` — same tree structure as
    ``cache``, donation-friendly (serve hybridizes with
    ``donate_args=(1,)`` so XLA aliases the old cache buffers into the
    new ones; xla_lint X004 verifies).

``begin_cache(batch_size, capacity)`` builds the zeroed state tree; a
row with ``cache_len == 0`` is inert (attends at most its own fresh
token) so empty serve slots decode garbage harmlessly instead of NaN.
"""
from __future__ import annotations

import jax.numpy as jnp

from ... import numpy_extension as npx
from .. import nn
from ..block import HybridBlock
from ..parameter import Parameter
from ..rnn import LSTMCell
from .bert import PositionwiseFFN

__all__ = ["CausalSelfAttentionCell", "TransformerDecoderCell",
           "TransformerLM", "LSTMLM", "transformer_lm", "lstm_lm",
           "CACHE_PAGED", "CACHE_WINDOW", "CACHE_STATE"]

# the three kinds of cache leaf (serve/decode.py:cache_spec)
CACHE_PAGED, CACHE_WINDOW, CACHE_STATE = "paged", "window", "state"


class CausalSelfAttentionCell(HybridBlock):
    """Self-attention against a fixed-capacity KV cache.

    Fused QKV projection (one MXU matmul, same as
    :class:`~.bert.MultiHeadAttentionCell`), then the new tokens' K‖V
    rows are appended into the layer's one cache leaf at ``cache_len``
    (one append a layer) and attention runs
    through ``npx.flash_attention_decode`` — the cache-aware kernel with
    the block-skip over never-attended capacity (ops/attention.py).
    """

    def __init__(self, units, num_heads, use_bias=True, **kw):
        super().__init__(**kw)
        if units % num_heads:
            raise ValueError(f"units {units} not divisible by heads {num_heads}")
        self._units = units
        self._num_heads = num_heads
        self._head_dim = units // num_heads
        self.qkv = nn.Dense(3 * units, use_bias=use_bias, flatten=False,
                            in_units=units)
        self.proj = nn.Dense(units, use_bias=use_bias, flatten=False,
                             in_units=units)

    def forward(self, x, kv_cache, cache_len, k_scale=None, v_scale=None):
        from ... import numpy as mnp
        b, t = x.shape[0], x.shape[1]
        h, dh = self._num_heads, self._head_dim
        q, kv = mnp.split(self.qkv(x), [self._units], axis=-1)
        qh = q.reshape(b, t, h, dh).transpose(0, 2, 1, 3)   # (B, H, T, dh)
        # the new rows as the cache stores them, K‖V on the last axis:
        # (B, T, [k|v], H, dh) -> (B, H, T, [k|v], dh), reshape and
        # transpose only (no concatenate: the X003 budgets)
        kvh = kv.reshape(b, t, 2, h, dh).transpose(0, 3, 1, 2, 4)
        if k_scale is not None:
            # int8 cache: quantize BEFORE the append — cache_append casts
            # payloads to the cache dtype and a raw float->int8 astype
            # TRUNCATES instead of rounding to scale (ops/attention.py).
            # K and V rows each by their own amax over dh
            kvq, sc = npx.quantize_kv(kvh)                  # (B, H, T, 2, .)
            kv_new = npx.cache_append(
                kv_cache, kvq.reshape(b, h, t, 2 * dh), cache_len)
            ks_new = npx.cache_append(k_scale, sc[:, :, :, 0], cache_len)
            vs_new = npx.cache_append(v_scale, sc[:, :, :, 1], cache_len)
            out = npx.flash_attention_decode(qh, kv_new, cache_len,
                                             k_scale=ks_new, v_scale=vs_new)
            out = out.transpose(0, 2, 1, 3).reshape(b, t, self._units)
            return self.proj(out), kv_new, ks_new, vs_new
        kv_new = npx.cache_append(
            kv_cache, kvh.reshape(b, h, t, 2 * dh), cache_len)
        out = npx.flash_attention_decode(qh, kv_new, cache_len)
        out = out.transpose(0, 2, 1, 3).reshape(b, t, self._units)
        return self.proj(out), kv_new


class TransformerDecoderCell(HybridBlock):
    """Pre-norm decoder layer: x + attn(ln(x)); x + ffn(ln(x)).

    Pre-norm (GPT-style) rather than BERT's post-norm: decode-depth
    stacks train/propagate more stably and the residual stream stays
    the identity path, which matters when the same weights run both
    T=prompt and T=1 signatures.
    """

    def __init__(self, units, hidden_size, num_heads, layer_norm_eps=1e-5,
                 **kw):
        super().__init__(**kw)
        self.attention = CausalSelfAttentionCell(units, num_heads)
        self.ffn = PositionwiseFFN(units, hidden_size)
        self.ln_att = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.ln_ffn = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)

    def forward(self, x, kv_cache, cache_len, k_scale=None, v_scale=None):
        a, *leaves = self.attention(self.ln_att(x), kv_cache, cache_len,
                                    k_scale, v_scale)
        x = x + a
        x = x + self.ffn(self.ln_ffn(x))
        return (x, *leaves)


class TransformerLM(HybridBlock):
    """Causal transformer LM with functional KV-cache state.

    ``forward(tokens, cache, cache_len, n_tokens) -> (logits, new_cache)``
    — see the module docstring for the contract.  The output head is
    weight-tied to the word embedding (BERTForPretrain idiom).
    """

    def __init__(self, vocab_size=256, units=128, hidden_size=None,
                 num_layers=2, num_heads=4, max_length=2048,
                 layer_norm_eps=1e-5, dtype=jnp.float32,
                 cache_dtype=None, **kw):
        super().__init__(**kw)
        if cache_dtype not in (None, "int8"):
            raise ValueError(
                f"cache_dtype={cache_dtype!r} unsupported; None (cache in "
                "the model dtype) or 'int8' (quantized KV pages with "
                "per-position scales, docs/precision.md)")
        self._cache_dtype = cache_dtype
        self._vocab_size = vocab_size
        self._units = units
        self._num_layers = num_layers
        self._num_heads = num_heads
        self._head_dim = units // num_heads
        self._max_length = max_length
        self._dtype = dtype
        self.word_embed = nn.Embedding(vocab_size, units, dtype=dtype)
        self.position_weight = Parameter(shape=(max_length, units),
                                         dtype=dtype, name="position_weight")
        self.layers = nn.HybridSequential()       # container only; iterated
        for _ in range(num_layers):
            self.layers.add(TransformerDecoderCell(
                units, hidden_size or 4 * units, num_heads,
                layer_norm_eps=layer_norm_eps))
        self.ln_f = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.out_bias = Parameter(shape=(vocab_size,), init="zeros",
                                  name="out_bias")

    def begin_cache(self, batch_size, capacity):
        from ... import numpy as mnp
        shape = (batch_size, self._num_heads, capacity, 2 * self._head_dim)
        if self._cache_dtype == "int8":
            # (kv_q, k_scale, v_scale) per layer: the int8 payload leaf
            # plus per-position f32 scales (B, H, C, 1) — every leaf is
            # a 4-D capacity-axis page layout, so the serve tier's
            # grower/mover/prefix-trie treat scales as (thin) pages
            sshape = shape[:3] + (1,)
            return tuple((mnp.zeros(shape, dtype=jnp.int8),
                          mnp.zeros(sshape, dtype=jnp.float32),
                          mnp.zeros(sshape, dtype=jnp.float32))
                         for _ in range(self._num_layers))
        return tuple((mnp.zeros(shape, dtype=self._dtype),)
                     for _ in range(self._num_layers))

    def forward(self, tokens, cache, cache_len, n_tokens):
        from ... import numpy as mnp
        t = tokens.shape[1]
        emb = self.word_embed(tokens)                       # (B, T, U)
        # absolute position = cache_len + local offset; clip keeps padded
        # garbage rows in-table (their outputs are never read)
        pos = cache_len.reshape(-1, 1).astype(jnp.int32) \
            + mnp.arange(t, dtype=jnp.int32).reshape(1, -1)
        pos = mnp.clip(pos, 0, self._max_length - 1)
        emb = emb + mnp.take(self.position_weight.data(), pos, axis=0)
        x = emb
        new_cache = []
        for cell, leaves in zip(self.layers, cache):
            # (kv,) or, int8, (kv_q, k_scale, v_scale)
            x, *new_leaves = cell(x, leaves[0], cache_len, *leaves[1:])
            new_cache.append(tuple(new_leaves))
        hid = self.ln_f(x)
        logits = npx.fully_connected(hid, self.word_embed.weight.data(),
                                     self.out_bias.data(),
                                     num_hidden=self._vocab_size,
                                     flatten=False)
        return logits, tuple(new_cache)


class LSTMLM(HybridBlock):
    """Stacked-LSTM LM — the second decode carrier.

    Same signature as :class:`TransformerLM`; the cache is the per-layer
    ``(h, c)`` recurrent state, capacity-independent (``begin_cache``
    ignores ``capacity``), so the serve tier's cache-growth path is a
    no-op for this family.  The unroll gates every state update on
    ``step < n_tokens`` — a sequential model must FREEZE at the true
    prompt length or padded garbage tokens would corrupt the state.
    """

    def __init__(self, vocab_size=256, units=128, num_layers=2,
                 dtype=jnp.float32, **kw):
        super().__init__(**kw)
        self._vocab_size = vocab_size
        self._units = units
        self._num_layers = num_layers
        self._dtype = dtype
        self.word_embed = nn.Embedding(vocab_size, units, dtype=dtype)
        self.cells = nn.HybridSequential()        # container only; iterated
        for _ in range(num_layers):
            self.cells.add(LSTMCell(units, input_size=units))
        self.out_bias = Parameter(shape=(vocab_size,), init="zeros",
                                  name="out_bias")

    def begin_cache(self, batch_size, capacity=0):
        from ... import numpy as mnp
        return tuple((mnp.zeros((batch_size, self._units), dtype=self._dtype),
                      mnp.zeros((batch_size, self._units), dtype=self._dtype))
                     for _ in range(self._num_layers))

    def forward(self, tokens, cache, cache_len, n_tokens):
        from ... import numpy as mnp
        t = tokens.shape[1]
        emb = self.word_embed(tokens)                       # (B, T, U)
        states = [[pair[0], pair[1]] for pair in cache]
        outs = []
        for step in range(t):
            x = emb[:, step]                                # (B, U)
            upd = (n_tokens > step).reshape(-1, 1)          # (B, 1)
            for li, cell in enumerate(self.cells):
                h_old, c_old = states[li]
                out, (h_new, c_new) = cell(x, [h_old, c_old])
                h_kept = mnp.where(upd, h_new, h_old)
                c_kept = mnp.where(upd, c_new, c_old)
                states[li] = [h_kept, c_kept]
                x = h_kept
            outs.append(x)
        hid = mnp.stack(outs, axis=1)                       # (B, T, U)
        logits = npx.fully_connected(hid, self.word_embed.weight.data(),
                                     self.out_bias.data(),
                                     num_hidden=self._vocab_size,
                                     flatten=False)
        return logits, tuple((s[0], s[1]) for s in states)


def transformer_lm(**kwargs):
    """Small causal transformer LM (decode-path carrier)."""
    return TransformerLM(**kwargs)


def lstm_lm(**kwargs):
    """Small stacked-LSTM LM (decode-path carrier)."""
    return LSTMLM(**kwargs)
