"""Multi-head latent attention (MLA) without rotary, in its two forms.

A token's cache entry is ONE row for all heads: the RMS-normalised latent
``c`` (``kv_lora_rank`` wide) beside a plain shared key part ``k_pe``
(``qk_rope_head_dim`` wide; with ``mla_use_nope`` no rotary is applied to
it).  Per head ``[k_nope | v] = W_kvb c`` and ``k = [k_nope | k_pe]``.

* :func:`mla_expanded` -- keys and values of the NEW rows expanded per
  head, causal softmax among them: the prefill of a prompt from an empty
  cache (positions before the call are not attended);
* :func:`mla_absorbed` -- ``W_kvb``'s key half folded into the query and its
  value half applied after the softmax, so the queries of all heads run
  over the latent rows themselves: scores ``q~ . [c | k_pe]``, values the
  first ``rank`` lanes of the same row.  General in T and in ``cache_len``;
  the decode step (T = 1) uses it.

Same mathematics (tests/test_kimi_linear.py holds the two together).
Scores and softmax are float32 whatever the inputs; the products after
it come back in the inputs' dtype (they feed a matrix product next).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["mla_expanded", "mla_absorbed"]

_NEG = -1e30


def _split_kvb(w_kvb, heads, nope, dv):
    """``(H * (nope + dv), rank)`` -> key half (H, nope, rank), value half
    (H, dv, rank)."""
    w = w_kvb.reshape(heads, nope + dv, w_kvb.shape[-1])
    return w[:, :nope], w[:, nope:]


def mla_expanded(q, c, k_pe, w_kvb, nope, dv):
    """q: (B, T, H, nope + rope); c: (B, T, rank) normalised latents of
    the same T rows; k_pe: (B, T, rope); w_kvb: (H * (nope + dv), rank).
    Causal among the T rows.  Returns (B, T, H * dv) in q's dtype."""
    b, t, h, d = q.shape
    f32 = jnp.float32
    with jax.named_scope("mla_prefill"):
        w_k, w_v = _split_kvb(w_kvb, h, nope, dv)
        k_nope = jnp.einsum("btr,hnr->bthn", c, w_k)
        v = jnp.einsum("btr,hvr->bthv", c, w_v)
        s = (jnp.einsum("bqhn,bkhn->bhqk", q[..., :nope], k_nope,
                        preferred_element_type=f32)
             + jnp.einsum("bqhp,bkp->bhqk", q[..., nope:], k_pe,
                          preferred_element_type=f32)) * d ** -0.5
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, _NEG), axis=-1)
        o = jnp.einsum("bhqk,bkhv->bqhv", p.astype(v.dtype), v)
        return o.reshape(b, t, h * dv)


def mla_absorbed(q, latent, cache_len, w_kvb, nope, dv):
    """q: (B, T, H, nope + rope); latent: (B, 1, C, W), the cache leaf WITH
    the call's own rows already appended at ``cache_len``: lanes
    ``[0, rank)`` the latent, ``[rank, rank + rope)`` the key part, any
    further lanes padding that is never read;
    cache_len: (B,) valid rows before the call.  Local query ``i`` attends
    positions ``<= cache_len + i``.  Returns (B, T, H * dv)."""
    b, t, h, d = q.shape
    f32 = jnp.float32
    rows = latent[:, 0]                               # (B, C, >= rank + rope)
    rank = w_kvb.shape[-1]
    with jax.named_scope("mla_decode"):
        w_k, w_v = _split_kvb(w_kvb, h, nope, dv)
        q_lat = jnp.einsum("bthn,hnr->bthr", q[..., :nope], w_k)
        s = (jnp.einsum("bthr,bcr->bhtc", q_lat, rows[..., :rank],
                        preferred_element_type=f32)
             + jnp.einsum("bthp,bcp->bhtc", q[..., nope:],
                          rows[..., rank:rank + d - nope],
                          preferred_element_type=f32)) * d ** -0.5
        qpos = cache_len.astype(jnp.int32)[:, None] + jnp.arange(t)[None, :]
        seen = jnp.arange(rows.shape[1])[None, None, :] <= qpos[:, :, None]
        p = jax.nn.softmax(jnp.where(seen[:, None], s, _NEG), axis=-1)
        ctx = jnp.einsum("bhtc,bcr->bthr", p.astype(rows.dtype),
                         rows[..., :rank])
        o = jnp.einsum("bthr,hvr->bthv", ctx, w_v)
        return o.reshape(b, t, h * dv)
