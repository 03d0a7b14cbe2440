"""A second decoder family beside ``decoder.TransformerLM``: the
Kimi-Linear block (Kimi Linear tech report, arXiv:2510.26692), a setting of
the shared skeleton (``mixer_lm.py``: norm, gated FFN, held-expert layer,
cell, layer loop, head and cache assembly).

Pre-norm RMSNorm, no position encoding anywhere, SiLU-gated FFNs, an
untied head, and per layer a mixer and an FFN chosen from the
configuration: layer ``i`` is **KDA** (gated delta-rule linear attention,
ops/kda.py) when ``i + 1`` is in ``linear_attn_config.kda_layers`` and
**MLA** (latent attention without rotary, ops/mla.py) when it is in
``full_attn_layers``; its FFN is dense for ``i < first_k_dense_replace``
and a routed expert layer with one shared expert after
(parallel/moe.py: sigmoid scores over ALL ``published.num_experts``, top-k
renormalised and scaled, dropless, computing the part of the
``num_experts`` experts this device HOLDS from ``deployment.held_start``).

Same decode contract as ``decoder.py``
(``forward(tokens, cache, cache_len, n_tokens)``, ``begin_cache``), with
two additions the serve tier reads (serve/decode.py):

* **the cache tree holds two kinds of leaf** (``serve.decode.cache_spec``
  reads them from the mixers' ``cache_kinds`` and checks them against
  ``begin_cache`` at two capacities): a KDA layer
  keeps ``(state (B, H, dk, dv) in ops/kda.py's STATE_DTYPE, tail
  (B, K-1, 3*H*dk))`` -- constant in the context, ``"state"`` -- and an
  MLA layer one ``"paged"`` leaf ``(B, 1, C, W)``: a token's normalised
  latent beside its shared key part, one row for all heads, ``W`` =
  ``rank + rope`` rounded up to whole 128-lane tiles (576 -> 640);
* ``forward`` returns a third value, the ``(moe layers, held experts)``
  int32 count of token-expert pairs computed in this call
  (``step_counters`` turns it into telemetry increments), so routing is
  counted without per-token work on the host.

A T = 1 call takes the one-token forms (``kda_step``, ``mla_absorbed``);
T > 1 the chunk-parallel KDA and the expanded MLA, which **presumes an
empty cache** (``cache_len == 0``: a prompt's prefill; the prefix cache
refuses this tree and ``prefill_needs_empty_cache`` makes the serve tier
refuse a prompt past its largest bucket instead of chunking it, so serving
never asks otherwise).  ``n_tokens`` is
honoured by both kinds of state: a row's positions at or past it leave
the KDA state and conv tail as they were and route to no expert.

Every parameter is created in ``dtype`` and initialised there leaf by
leaf: no float32 copy of the model ever exists (at the published widths
the chip's share is 4.3 G parameters).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...ops import attention as _att
from ...ops import kda as _kda
from ...ops import mla as _mla
from ...ops.dispatch import call as _call
from ..block import HybridBlock
from ..parameter import Parameter
from .decoder import CACHE_PAGED, CACHE_STATE
from .mixer_lm import (GatedFFN, HeldMoE, MixerLM, RMSNorm, _Seeded, _dense,
                       _mm, _normal, _rms)

__all__ = ["KimiLinearLM", "kimi_linear"]

L2_EPS = 1e-6


class KDAMixer(HybridBlock):
    """Kimi Delta Attention with its short convolution, decay and output
    gates (ops/kda.py holds the recurrence)."""

    cache_kinds = (CACHE_STATE, CACHE_STATE)

    def __init__(self, units, heads, head_dim, conv_kernel, low_rank, eps,
                 dtype, **kw):
        super().__init__(**kw)
        n = heads * head_dim
        self._heads, self._dk, self._eps = heads, head_dim, eps
        self._kernel = conv_kernel
        self.qkv = _dense(3 * n, units, dtype)
        self.conv_weight = Parameter(shape=(3 * n, conv_kernel), dtype=dtype,
                                     init=_normal(conv_kernel ** -0.5),
                                     name="conv_weight")
        self.f_a = _dense(low_rank, units, dtype)
        self.f_b = _dense(n, low_rank, dtype, sigma=0.5 * low_rank ** -0.5)
        # fla's initialisation: A in [1, 16), a time step in [1e-3, 1e-1)
        # through the inverse of softplus
        self.A_log = Parameter(
            shape=(heads,), dtype=jnp.float32, name="A_log",
            init=_Seeded(lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, minval=1.0, maxval=16.0))))

        def dt_bias(key, shape):
            dt = jnp.exp(jax.random.uniform(
                key, shape, minval=math.log(1e-3), maxval=math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))

        self.dt_bias = Parameter(shape=(n,), dtype=jnp.float32,
                                 init=_Seeded(dt_bias), name="dt_bias")
        self.b_proj = _dense(heads, units, dtype)
        self.g_a = _dense(low_rank, units, dtype)
        self.g_b = _dense(n, low_rank, dtype)
        self.o_norm = RMSNorm(head_dim, dtype)
        self.o_proj = _dense(units, n, dtype)

    def begin_cache(self, batch_size, capacity, dtype):
        from ... import numpy as mnp
        heads, dk = self._heads, self._dk
        return (mnp.zeros((batch_size, heads, dk, dk),
                          dtype=_kda.STATE_DTYPE),
                mnp.zeros((batch_size, self._kernel - 1, 3 * heads * dk),
                          dtype=dtype))

    def forward(self, x, gamma, leaves, step):
        """``x + mixer(RMSNorm(x))`` -> ``(x, (state, tail))``."""
        heads, dk, eps = self._heads, self._dk, self._eps
        (state, tail), n_tokens = leaves, step[1]

        def mix(x, gamma, w_qkv, conv_w, w_fa, w_fb, a_log, dt_bias, w_b,
                w_ga, w_gb, o_gamma, w_o, state, tail, n_tokens):
            b, t = x.shape[:2]
            h = _rms(x, gamma, eps)
            y, tail = _kda.short_conv(_mm(h, w_qkv),
                                      conv_w.astype(jnp.float32), tail,
                                      n_tokens)
            y = jax.nn.silu(y)
            q, k, v = (a.reshape(b, t, heads, dk) for a in jnp.split(y, 3, -1))
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True)
                                  + L2_EPS) * dk ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                _mm(_mm(h, w_fa), w_fb) + dt_bias).reshape(b, t, heads, dk)
            beta = jax.nn.sigmoid(_mm(h, w_b))
            if t == 1:
                g, beta = _kda.mask_rows(g, beta, n_tokens)
                state, o = _kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                         beta[:, 0], state)
                o = o[:, None]
            else:
                o, state = _kda.kda_chunk(q, k, v, g, beta, state, n_tokens)
            o = _rms(o, o_gamma, eps).reshape(b, t, heads * dk)
            gate = jax.nn.sigmoid(_mm(_mm(h, w_ga), w_gb))
            return x + _mm(gate * o, w_o), state, tail

        w = lambda d: d.weight.data()
        x, *leaves = _call(
            mix, (x, gamma, w(self.qkv), self.conv_weight.data(),
                  w(self.f_a), w(self.f_b), self.A_log.data(),
                  self.dt_bias.data(), w(self.b_proj), w(self.g_a),
                  w(self.g_b), self.o_norm.gamma.data(), w(self.o_proj),
                  state, tail, n_tokens), {}, name="kda_mixer")
        return x, leaves


class MLAMixer(HybridBlock):
    """Latent attention, NoPE: the cache row is ``[RMSNorm(c) | k_pe |
    zeros up to whole lane tiles]``."""

    cache_kinds = (CACHE_PAGED,)

    def __init__(self, units, heads, nope, rope, v_dim, rank, eps, dtype,
                 **kw):
        super().__init__(**kw)
        self._heads, self._nope, self._rope = heads, nope, rope
        self._dv, self._rank, self._eps = v_dim, rank, eps
        # a latent row padded to whole 128-lane tiles: a leaf whose last
        # axis is 576 wide has two layouts in HBM (capacity-minor for the
        # in-place append, row-major for the attention) and is copied
        # between them twice a layer and step (PERF.md section 6, PR 29; what PR 28
        # found for K/V at head size 64); 640 lanes have one
        self._lanes = -(-(rank + rope) // 128) * 128
        self.q_proj = _dense(heads * (nope + rope), units, dtype)
        self.kv_a = _dense(rank + rope, units, dtype)
        self.kv_norm = RMSNorm(rank, dtype)
        self.kv_b = _dense(heads * (nope + v_dim), rank, dtype)
        self.o_proj = _dense(units, heads * v_dim, dtype)

    def begin_cache(self, batch_size, capacity, dtype):
        from ... import numpy as mnp
        return (mnp.zeros((batch_size, 1, capacity, self._lanes),
                          dtype=dtype),)

    def forward(self, x, gamma, leaves, step):
        """``x + mixer(RMSNorm(x))`` -> ``(x, (latent,))``."""
        heads, nope, rope = self._heads, self._nope, self._rope
        dv, rank, eps = self._dv, self._rank, self._eps
        (latent,), cache_len = leaves, step[0]

        def mix(x, gamma, w_q, w_kva, kv_gamma, w_kvb, w_o, latent,
                cache_len):
            b, t = x.shape[:2]
            h = _rms(x, gamma, eps)
            dt = latent.dtype
            q = _mm(h, w_q).reshape(b, t, heads, nope + rope).astype(dt)
            ckv = _mm(h, w_kva)
            pad = jnp.zeros((b, t, latent.shape[-1] - rank - rope), ckv.dtype)
            row = jnp.concatenate([_rms(ckv[..., :rank], kv_gamma, eps),
                                   ckv[..., rank:], pad], -1).astype(dt)
            latent = _att.cache_append(latent, row[:, None], cache_len)
            if t == 1:
                o = _mla.mla_absorbed(q, latent, cache_len, w_kvb, nope, dv)
            else:                        # a prompt's prefill, cache empty
                o = _mla.mla_expanded(q, row[..., :rank],
                                      row[..., rank:rank + rope], w_kvb,
                                      nope, dv)
            return x + _mm(o, w_o), latent

        w = lambda d: d.weight.data()
        x, latent = _call(
            mix, (x, gamma, w(self.q_proj), w(self.kv_a),
                  self.kv_norm.gamma.data(), w(self.kv_b), w(self.o_proj),
                  latent, cache_len), {}, name="mla_mixer")
        return x, (latent,)


class KimiLinearLM(MixerLM):
    """Causal LM of the Kimi-Linear family from a configuration under its
    published keys (``chipbench/configs/kimi-linear-48b-a3b.json``;
    ``tests/test_kimi_linear.py`` has a tiny one)."""

    prefill_needs_empty_cache = True     # kda_chunk and mla_expanded

    def __init__(self, config, dtype=jnp.bfloat16, **kw):
        c = config
        lin = c["linear_attn_config"]
        units, eps = c["hidden_size"], c["rms_norm_eps"]
        n_routed = c.get("published", {}).get("num_experts", c["num_experts"])
        held_start = c.get("deployment", {}).get("held_start", 0)
        assumed = c.get("assumed", {})
        low_rank = assumed.get("gate_low_rank", lin["head_dim"])
        cells = []
        for i in range(c["num_hidden_layers"]):
            if i + 1 in lin["kda_layers"]:
                mixer = KDAMixer(units, lin["num_heads"], lin["head_dim"],
                                 lin["short_conv_kernel_size"], low_rank, eps,
                                 dtype)
            elif i + 1 in lin["full_attn_layers"]:
                mixer = MLAMixer(units, c["num_attention_heads"],
                                 c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                                 c["v_head_dim"], c["kv_lora_rank"], eps,
                                 dtype)
            else:
                raise ValueError(f"layer {i + 1} is in neither kda_layers "
                                 "nor full_attn_layers")
            if i < c["first_k_dense_replace"]:
                ffn = GatedFFN(units, c["intermediate_size"], dtype)
            else:
                ffn = HeldMoE(units, c["moe_intermediate_size"], n_routed,
                              c["num_experts"], held_start,
                              c["num_experts_per_token"],
                              c["moe_renormalize"], dtype,
                              assumed.get("routed_out_gain", 1.0),
                              router="sigmoid",
                              scale=c["routed_scaling_factor"])
            cells.append((mixer, ffn))
        super().__init__(c["vocab_size"], units, eps, dtype, cells, **kw)


def kimi_linear(**kwargs):
    """Kimi-Linear decoder LM (KDA + MLA mixers, held-expert MoE)."""
    return KimiLinearLM(**kwargs)
