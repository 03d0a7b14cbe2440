"""A third decoder family: the Mellum block (JetBrains Mellum 2; the keys
of its published ``config.json``), a setting of the shared skeleton
(``mixer_lm.py``: norm, gated FFN, held-expert layer, cell, layer loop, head
and cache assembly) with another mixer, another router and another position
scheme than ``kimi_linear.py``'s.

Per layer: **grouped-query attention** (``num_attention_heads`` query heads
on ``num_key_value_heads`` K/V heads of ``head_dim``, no bias, RMSNorm over
each head of q and k when ``assumed.qk_norm``) with **rotary positions**,
and a routed expert layer with a **softmax** router (top-k, renormalised
when ``norm_topk_prob``, no shared expert; ``parallel/moe.py``) over the
experts this device holds (``mlp_layer_types``: ``"sparse"``; ``"dense"``
takes the gated FFN at ``intermediate_size``).  ``layer_types`` picks the
attention of each layer:

* ``"full_attention"`` sees every position; its leaf is ``"paged"``,
  ``(B, Hkv, C, 2*dh)`` K‖V rows (keys rotated) following the capacity;
* ``"sliding_attention"`` sees the last ``sliding_window`` positions; its
  leaf is ``"window"``, a RING of ``sliding_window + assumed.prefill_chunk``
  rows whatever the capacity (a chunk of that many queries appended to the
  ring must not overwrite what its first query still sees), the row of
  position ``p`` at ``p mod R``.

``rope_parameters`` gives each kind its frequencies (:func:`rope_inv_freq`:
``"default"``, or ``"yarn"`` with its ramp between the two correction
dimensions and ``attention_factor`` on cosine and sine alike); the tables of
a call are worked out once, from ``cache_len``, not once a layer
(:meth:`MellumLM.positions`).  Both forms of ``flash_decode`` serve both
kinds (``ops/attention.py``: ``window=``), and every mixer takes a non-empty
cache at ``T > 1``, so a prompt past the largest prompt bucket is forwarded
in chunks (serve/decode.py).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as onp

from ...ops import attention as _att
from ...ops.dispatch import call as _call
from ..block import HybridBlock
from .decoder import CACHE_PAGED, CACHE_WINDOW
from .mixer_lm import (GatedFFN, HeldMoE, MixerLM, RMSNorm, _branch, _dense,
                       _mm, _rms, _scoped)

__all__ = ["MellumLM", "mellum", "rope_inv_freq", "rope_tables",
           "rope_positions", "attend"]

FULL, SLIDING = "full_attention", "sliding_attention"


def rope_inv_freq(params, head_dim):
    """``(inv_freq (head_dim / 2,) float32, gain)`` of one entry of
    ``rope_parameters``: the angle of channel pair ``i`` at position ``p`` is
    ``p * inv_freq[i]``, and cosine and sine are both multiplied by ``gain``.

    ``"default"``: ``f_i = theta ** (-2i / d)``, gain 1.  ``"yarn"`` (Peng et
    al. 2023, as HF's ``_compute_yarn_parameters``): with ``c(n) = d ln(L /
    (2 pi n)) / (2 ln theta)``, ``low = floor(c(beta_fast))``, ``high =
    ceil(c(beta_slow))`` (clipped to ``[0, d - 1]``) and ``ramp_i = clip((i -
    low) / (high - low), 0, 1)``: ``(1 - ramp_i) f_i + ramp_i f_i / factor``;
    gain ``attention_factor`` (``0.1 ln(factor) + 1`` when not given)."""
    d, theta = head_dim, float(params["rope_theta"])
    f = theta ** (-onp.arange(0, d, 2, dtype=onp.float64) / d)
    kind = params.get("rope_type", "default")
    if kind == "default":
        return f.astype(onp.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"unknown rope_type {kind!r}")
    factor = float(params["factor"])
    span = params["original_max_position_embeddings"]

    def correction(rotations):
        return d * math.log(span / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(params.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction(params.get("beta_slow", 1))), d - 1)
    ramp = onp.clip((onp.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    gain = params.get("attention_factor")
    if gain is None:
        gain = 0.1 * math.log(factor) + 1.0
    return ((1 - ramp) * f + ramp * f / factor).astype(onp.float32), \
        float(gain)


def _rotate(x, cos, sin):
    """Rotary positions in the half-split form on ``x`` (B, T, H, d), ``cos``
    and ``sin`` (B, T, d / 2): ``x * cos + (-x2 ‖ x1) * sin``."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[:, :, None], sin[:, :, None]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def rope_tables(rope, cache_len, t):
    """``(cos, sin)``, each (B, t, head_dim / 2) float32, of positions
    ``cache_len + 0 .. t-1`` a row for one layer type's ``rope =
    (inv_freq, gain)`` (:func:`rope_inv_freq`)."""
    inv, gain = rope
    pos = (cache_len.astype(jnp.int32)[:, None]
           + jnp.arange(t, dtype=jnp.int32)[None]).astype(jnp.float32)
    angle = pos[..., None] * jnp.asarray(inv)
    return gain * jnp.cos(angle), gain * jnp.sin(angle)


def rope_positions(ropes, cache_len, t):
    """``{layer type: (cos, sin)}``, each (B, t, head_dim / 2) float32 for
    positions ``cache_len + 0 .. t-1``, once a call, from ``ropes = {layer
    type: rope_inv_freq's pair}``: what a family of :class:`GQAMixer`
    layers answers from ``positions``."""
    kinds = list(ropes)

    def tables(cache_len):
        return sum((rope_tables(ropes[kind], cache_len, t)
                    for kind in kinds), ())

    flat = _call(tables, (cache_len,), {}, name="rope_tables")
    return {kind: (flat[2 * i], flat[2 * i + 1])
            for i, kind in enumerate(kinds)}


def attend(q, k, v, kv, cache_len, cos, sin, window):
    """What a layer makes of its heads once projected (and normalised): q
    (B, T, Hq, d), k and v (B, T, Hkv, d) float32 at positions ``cache_len +
    0 .. T-1`` -> ``(o (B, Hq, T, d), kv)``.  Rotary on q and k, the rows
    appended to the K‖V leaf ``kv`` -- a ring under a ``window`` -- and the
    decode kernel over what the queries may see.  :class:`GQAMixer` calls
    this and nothing else between its projections, so a reference that puts
    its own heads through it holds the served path's positions, window and
    precision (``chipbench/references/mellum.py``: ``ATTN_RTOL``)."""
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    rows = jnp.concatenate([k, v], -1).transpose(0, 2, 1, 3)
    # the append's ops carry this scope AND cache_append's
    with jax.named_scope("attn_full" if window is None else "attn_window"):
        kv = _att.cache_append(kv, rows, cache_len, ring=window is not None)
        o = _att.flash_attention_decode(
            q.transpose(0, 2, 1, 3).astype(kv.dtype), kv, cache_len,
            window=window)
    return o, kv


class GQAMixer(HybridBlock):
    """Grouped-query attention with rotary positions against one K‖V leaf:
    every position's when ``window`` is None, a ring of ``ring`` rows seen
    through a window of ``window`` otherwise."""

    # the name a looped stack gives the two matrix products in the trace
    # (MixerLM sets it on its cells' blocks; None: no name)
    dense_scope = None

    def __init__(self, units, heads, kv_heads, head_dim, kind, window, ring,
                 qk_norm, eps, dtype, **kw):
        super().__init__(**kw)
        self._hq, self._hkv, self._dh = heads, kv_heads, head_dim
        self._eps, self.kind = eps, kind
        self._window, self._ring = window, ring
        self.cache_kinds = (CACHE_PAGED if window is None else CACHE_WINDOW,)
        self.qkv = _dense((heads + 2 * kv_heads) * head_dim, units, dtype)
        self.q_norm = RMSNorm(head_dim, dtype) if qk_norm else None
        self.k_norm = RMSNorm(head_dim, dtype) if qk_norm else None
        self.o_proj = _dense(units, heads * head_dim, dtype)

    def begin_cache(self, batch_size, capacity, dtype):
        from ... import numpy as mnp
        rows = capacity if self._window is None else self._ring
        return (mnp.zeros((batch_size, self._hkv, rows, 2 * self._dh),
                          dtype=dtype),)

    def forward(self, x, gamma, leaves, step, post=None):
        """``x + mixer(RMSNorm(x))`` -> ``(x, (kv,))``; with ``post`` (a
        sandwich cell's gamma), ``x + RMSNorm(mixer(RMSNorm(x)))``."""
        hq, hkv, dh, eps = self._hq, self._hkv, self._dh, self._eps
        window, normed = self._window, self.q_norm is not None
        sandwich, scope = post is not None, self.dense_scope

        def mix(x, gamma, w_qkv, w_o, kv, cache_len, cos, sin, *norms):
            b, t = x.shape[:2]
            h = _rms(x, gamma, eps)
            with _scoped(scope):
                qkv = _mm(h, w_qkv).reshape(b, t, hq + 2 * hkv, dh)
            q, k, v = qkv[:, :, :hq], qkv[:, :, hq:hq + hkv], \
                qkv[:, :, hq + hkv:]
            if normed:
                q, k = _rms(q, norms[0], eps), _rms(k, norms[1], eps)
            o, kv = attend(q, k, v, kv, cache_len, cos, sin, window)
            o = o.transpose(0, 2, 1, 3).reshape(b, t, hq * dh)
            with _scoped(scope):
                y = _mm(o, w_o)
            return _branch(x, y, norms[-1] if sandwich else None, eps), kv

        norms = (self.q_norm.gamma.data(), self.k_norm.gamma.data()) \
            if normed else ()
        x, kv = _call(
            mix, (x, gamma, self.qkv.weight.data(), self.o_proj.weight.data(),
                  leaves[0], step[0]) + step[2][self.kind] + norms
            + ((post,) if sandwich else ()), {}, name="gqa_mixer")
        return x, (kv,)


class MellumLM(MixerLM):
    """Causal LM of the Mellum family from a configuration under its
    published keys (``chipbench/configs/mellum2-12b-a2.5b.json``;
    ``tests/test_mellum.py`` has a tiny one).  Beside them:
    ``published.num_experts`` (the router's width when ``num_experts`` is
    what this device holds), ``deployment.held_start``, and under
    ``assumed``: ``qk_norm``, ``prefill_chunk`` (the most queries one call
    appends: a window layer's ring holds ``sliding_window`` + that many
    rows; 512) and ``routed_out_gain``."""

    def __init__(self, config, dtype=jnp.bfloat16, **kw):
        c = config
        units, eps, dh = c["hidden_size"], c["rms_norm_eps"], c["head_dim"]
        assumed = c.get("assumed", {})
        window = c["sliding_window"] if c.get("use_sliding_window", True) \
            else None
        ring = None if window is None \
            else window + assumed.get("prefill_chunk", 512)
        n_routed = c.get("published", {}).get("num_experts", c["num_experts"])
        held_start = c.get("deployment", {}).get("held_start", 0)
        cells = []
        for kind, mlp in zip(c["layer_types"], c["mlp_layer_types"]):
            if kind not in (FULL, SLIDING):
                raise ValueError(f"unknown layer type {kind!r}")
            mixer = GQAMixer(units, c["num_attention_heads"],
                             c["num_key_value_heads"], dh, kind,
                             window if kind == SLIDING else None, ring,
                             assumed.get("qk_norm", False), eps, dtype)
            if mlp == "sparse":
                ffn = HeldMoE(units, c["moe_intermediate_size"], n_routed,
                              c["num_experts"], held_start,
                              c["num_experts_per_tok"], c["norm_topk_prob"],
                              dtype, assumed.get("routed_out_gain", 1.0),
                              router="softmax", shared=False)
            else:
                ffn = GatedFFN(units, c["intermediate_size"], dtype)
            cells.append((mixer, ffn))
        if len(cells) != c["num_hidden_layers"]:
            raise ValueError(
                f"layer_types names {len(cells)} layers, num_hidden_layers "
                f"{c['num_hidden_layers']}")
        super().__init__(c["vocab_size"], units, eps, dtype, cells, **kw)
        self.attention_window = window if SLIDING in c["layer_types"] \
            else None
        self._rope = {kind: rope_inv_freq(c["rope_parameters"][kind], dh)
                      for kind in sorted(set(c["layer_types"]))}

    def positions(self, cache_len, t):
        return rope_positions(self._rope, cache_len, t)


def mellum(**kwargs):
    """Mellum decoder LM (grouped-query attention on full and window layers,
    rotary positions, held-expert MoE with a softmax router)."""
    return MellumLM(**kwargs)
