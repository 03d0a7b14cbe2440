"""A second decoder family beside ``decoder.TransformerLM``: the
Kimi-Linear block (Kimi Linear tech report, arXiv:2510.26692).

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
  tells them apart from ``begin_cache`` at two capacities): a KDA layer
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
refuses this tree, so serving never asks otherwise).  ``n_tokens`` is
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

from ... import initializer as _init
from ...ops import attention as _att
from ...ops import kda as _kda
from ...ops import mla as _mla
from ...ops.dispatch import call as _call
from ...parallel import moe as _moe
from ...random import next_key
from .. import nn
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["KimiLinearLM", "kimi_linear"]

L2_EPS = 1e-6
# Seeded weights are N(0, 1/fan_in): every branch adds about unit variance
# to the residual stream.  A configuration may state another gain for the
# routed experts' output projection (``assumed.routed_out_gain``).


class _Seeded(_init.Initializer):
    """``fn(key, shape) -> float32 array`` whatever the parameter's name
    (the base class zeroes every ``*bias`` and sets every ``*gamma`` to
    one, which ``dt_bias`` must escape)."""

    def __init__(self, fn):
        super().__init__()
        self._fn = fn

    def init(self, name, arr):
        self._fill(arr, self._fn(next_key(), arr.shape))


def _normal(sigma):
    return _Seeded(lambda key, shape: sigma * jax.random.normal(key, shape))


def _dense(units, in_units, dtype, sigma=None):
    """A bias-free projection with N(0, 1/in) weights unless told.  The
    layers below read ``.weight`` and multiply through :func:`_mm`."""
    return nn.Dense(units, use_bias=False, flatten=False, dtype=dtype,
                    in_units=in_units,
                    weight_initializer=_normal(sigma or in_units ** -0.5))


def _mm(x, w):
    """``x @ w.T`` with ``x`` rounded to the weight's dtype and the result
    accumulated and returned in float32."""
    return jnp.einsum("...i,oi->...o", x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _rms(x, gamma, eps):
    """RMSNorm in float32, returned in float32."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return y * gamma.astype(jnp.float32)


def _gated(h, w_gate, w_up, w_down):
    """``W_down(SiLU(W_gate h) * W_up h)`` in float32 out."""
    return _mm(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up), w_down)


class RMSNorm(HybridBlock):
    """Holds the scale; the layers apply :func:`_rms` themselves."""

    def __init__(self, units, dtype, **kw):
        super().__init__(**kw)
        self.gamma = Parameter(shape=(units,), dtype=dtype, init="ones",
                               name="gamma")


class GatedFFN(HybridBlock):
    """``W_down(SiLU(W_gate x) * W_up x)``: the dense FFN of the leading
    layers and the shared expert (holds the weights; :func:`_gated`)."""

    def __init__(self, units, hidden, dtype, **kw):
        super().__init__(**kw)
        self.gate = _dense(hidden, units, dtype)
        self.up = _dense(hidden, units, dtype)
        self.down = _dense(units, hidden, dtype)

    def weights(self):
        return (self.gate.weight.data(), self.up.weight.data(),
                self.down.weight.data())

    def forward(self, x, gamma, eps):
        """``x + ffn(RMSNorm(x))`` on the float32 residual stream."""
        return _call(lambda x, g, *w: x + _gated(_rms(x, g, eps), *w),
                     (x, gamma) + self.weights(), {}, name="gated_ffn")


class KDAMixer(HybridBlock):
    """Kimi Delta Attention with its short convolution, decay and output
    gates (ops/kda.py holds the recurrence)."""

    def __init__(self, units, heads, head_dim, conv_kernel, low_rank, eps,
                 dtype, **kw):
        super().__init__(**kw)
        n = heads * head_dim
        self._heads, self._dk, self._eps = heads, head_dim, eps
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

    def forward(self, x, gamma, state, tail, n_tokens):
        """``x + mixer(RMSNorm(x))`` -> ``(x, state, tail)``."""
        heads, dk, eps = self._heads, self._dk, self._eps

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
        return _call(
            mix, (x, gamma, w(self.qkv), self.conv_weight.data(),
                  w(self.f_a), w(self.f_b), self.A_log.data(),
                  self.dt_bias.data(), w(self.b_proj), w(self.g_a),
                  w(self.g_b), self.o_norm.gamma.data(), w(self.o_proj),
                  state, tail, n_tokens), {}, name="kda_mixer")


class MLAMixer(HybridBlock):
    """Latent attention, NoPE: the cache row is ``[RMSNorm(c) | k_pe |
    zeros up to whole lane tiles]``."""

    def __init__(self, units, heads, nope, rope, v_dim, rank, eps, dtype,
                 **kw):
        super().__init__(**kw)
        self._heads, self._nope, self._rope = heads, nope, rope
        self._dv, self._rank, self._eps = v_dim, rank, eps
        self.q_proj = _dense(heads * (nope + rope), units, dtype)
        self.kv_a = _dense(rank + rope, units, dtype)
        self.kv_norm = RMSNorm(rank, dtype)
        self.kv_b = _dense(heads * (nope + v_dim), rank, dtype)
        self.o_proj = _dense(units, heads * v_dim, dtype)

    def forward(self, x, gamma, latent, cache_len):
        """``x + mixer(RMSNorm(x))`` -> ``(x, latent)``."""
        heads, nope, rope = self._heads, self._nope, self._rope
        dv, rank, eps = self._dv, self._rank, self._eps

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
        return _call(
            mix, (x, gamma, w(self.q_proj), w(self.kv_a),
                  self.kv_norm.gamma.data(), w(self.kv_b), w(self.o_proj),
                  latent, cache_len), {}, name="mla_mixer")


class HeldMoE(HybridBlock):
    """The routed expert layer as ONE device of an expert-parallel
    deployment sees it: the router scores all ``n_routed`` experts, this
    device holds ``n_held`` of them from ``held_start`` and computes their
    part, plus the shared expert that every device computes alike."""

    def __init__(self, units, hidden, n_routed, n_held, held_start, top_k,
                 scale, renormalize, dtype, out_gain=1.0, **kw):
        super().__init__(**kw)
        self._k, self._scale, self._renorm = top_k, scale, renormalize
        self._held_start = held_start
        self.router = _dense(n_routed, units, jnp.float32)
        # used for the choice only; seeded small and non-zero so that the
        # path is worked
        self.e_score_correction = Parameter(
            shape=(n_routed,), dtype=jnp.float32, init=_normal(0.02),
            name="e_score_correction")
        stack = lambda i, o, name, gain=1.0: Parameter(
            shape=(n_held, i, o), dtype=dtype, init=_normal(gain * i ** -0.5),
            name=name)
        self.experts_gate = stack(units, hidden, "experts_gate")
        self.experts_up = stack(units, hidden, "experts_up")
        self.experts_down = stack(hidden, units, "experts_down", out_gain)
        self.shared = GatedFFN(units, hidden, dtype)

    def forward(self, x, gamma, eps, n_tokens):
        """``x + moe(RMSNorm(x))`` -> ``(x, counts (n_held,) int32)``."""
        k, scale, renorm = self._k, self._scale, self._renorm
        start = self._held_start

        def routed(x, gamma, w_r, corr, w_g, w_u, w_d, s_g, s_u, s_d,
                   n_tokens):
            b, t, d = x.shape
            h = _rms(x, gamma, eps).reshape(b * t, d)
            weights, idx = _moe.route_sigmoid_topk(h, w_r, corr, k, scale,
                                                   renorm)
            real = (jnp.arange(t)[None, :] < n_tokens[:, None]).reshape(b * t)
            y, counts = _moe.held_experts_ffn(
                h.astype(w_g.dtype), weights, idx, w_g, w_u, w_d, start, real)
            with jax.named_scope("shared_expert"):
                y = y + _gated(h, s_g, s_u, s_d)
            return x + y.reshape(b, t, d), counts

        return _call(
            routed, (x, gamma, self.router.weight.data(),
                     self.e_score_correction.data(),
                     self.experts_gate.data(), self.experts_up.data(),
                     self.experts_down.data()) + self.shared.weights()
            + (n_tokens,), {}, name="held_moe")


class KimiLinearCell(HybridBlock):
    """``x + mixer(RMSNorm(x))``; ``x + ffn(RMSNorm(x))`` on a float32
    residual stream (matrix products take bf16 operands and accumulate in
    float32; norms, gates, softmax, the router and the recurrence are
    float32)."""

    def __init__(self, kind, mixer, ffn, units, eps, dtype, **kw):
        super().__init__(**kw)
        self.kind, self._eps = kind, eps
        self.ln_mixer = RMSNorm(units, dtype)
        self.mixer = mixer
        self.ln_ffn = RMSNorm(units, dtype)
        self.ffn = ffn

    def forward(self, x, leaves, cache_len, n_tokens):
        gamma = self.ln_mixer.gamma.data()
        if self.kind == "kda":
            x, *leaves = self.mixer(x, gamma, leaves[0], leaves[1], n_tokens)
        else:
            x, *leaves = self.mixer(x, gamma, leaves[0], cache_len)
        gamma = self.ln_ffn.gamma.data()
        if isinstance(self.ffn, HeldMoE):
            x, counts = self.ffn(x, gamma, self._eps, n_tokens)
            return x, tuple(leaves), counts
        return self.ffn(x, gamma, self._eps), tuple(leaves), None


class KimiLinearLM(HybridBlock):
    """Causal LM of the Kimi-Linear family from a configuration under its
    published keys (``chipbench/configs/kimi-linear-48b-a3b.json``;
    ``tests/test_kimi_linear.py`` has a tiny one)."""

    def __init__(self, config, dtype=jnp.bfloat16, **kw):
        super().__init__(**kw)
        c = config
        lin = c["linear_attn_config"]
        units, eps = c["hidden_size"], c["rms_norm_eps"]
        self._vocab_size = c["vocab_size"]
        self._dtype = dtype
        self._kda = (lin["num_heads"], lin["head_dim"],
                     lin["short_conv_kernel_size"])
        # a latent row padded to whole 128-lane tiles: a leaf whose last
        # axis is 576 wide has two layouts in HBM (capacity-minor for the
        # in-place append, row-major for the attention) and is copied
        # between them twice a layer and step (PERF.md section 6, PR 29; what PR 28
        # found for K/V at head size 64); 640 lanes have one
        self._latent = -(-(c["kv_lora_rank"] + c["qk_rope_head_dim"]) // 128) \
            * 128
        n_routed = c.get("published", {}).get("num_experts", c["num_experts"])
        held_start = c.get("deployment", {}).get("held_start", 0)
        assumed = c.get("assumed", {})
        low_rank = assumed.get("gate_low_rank", lin["head_dim"])
        self.word_embed = nn.Embedding(c["vocab_size"], units, dtype=dtype,
                                       weight_initializer=_normal(1.0))
        self.layers = nn.HybridSequential()       # container only; iterated
        for i in range(c["num_hidden_layers"]):
            if i + 1 in lin["kda_layers"]:
                kind = "kda"
                mixer = KDAMixer(units, *self._kda, low_rank, eps, dtype)
            elif i + 1 in lin["full_attn_layers"]:
                kind = "mla"
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
                              c["routed_scaling_factor"],
                              c["moe_renormalize"], dtype,
                              assumed.get("routed_out_gain", 1.0))
            self.layers.add(KimiLinearCell(kind, mixer, ffn, units, eps,
                                           dtype))
        self.ln_f = RMSNorm(units, dtype)
        self._eps = eps
        self.head = _dense(c["vocab_size"], units, dtype)
        # inference only (the KDA forms and the routed layer have no
        # backward yet): without this every parameter is initialised WITH a
        # gradient buffer of its own size, 8.6 GB more at the published
        # widths
        for p in self.collect_params().values():
            p.grad_req = "null"

    # ------------------------------------------------------------ cache
    def begin_cache(self, batch_size, capacity):
        from ... import numpy as mnp
        heads, dk, kernel = self._kda
        out = []
        for cell in self.layers:
            if cell.kind == "kda":
                out.append((
                    mnp.zeros((batch_size, heads, dk, dk),
                              dtype=_kda.STATE_DTYPE),
                    mnp.zeros((batch_size, kernel - 1, 3 * heads * dk),
                              dtype=self._dtype)))
            else:
                out.append((mnp.zeros((batch_size, 1, capacity, self._latent),
                                      dtype=self._dtype),))
        return tuple(out)

    @staticmethod
    def step_counters(counts):
        """Telemetry increments for the host-side ``counts`` of one call:
        token-expert pairs computed here, and held experts that saw a
        token, both summed over layers."""
        return {"serve.moe_held_picks": int(counts.sum()),
                "serve.moe_experts_hit": int((counts > 0).sum())}

    def forward(self, tokens, cache, cache_len, n_tokens):
        from ... import numpy as mnp
        x = self.word_embed(tokens).astype(jnp.float32)     # (B, T, U)
        new_cache, counts = [], []
        for cell, leaves in zip(self.layers, cache):
            x, leaves, n = cell(x, leaves, cache_len, n_tokens)
            new_cache.append(leaves)
            if n is not None:
                counts.append(n)
        eps = self._eps
        logits = _call(lambda x, g, w: _mm(_rms(x, g, eps), w),
                       (x, self.ln_f.gamma.data(), self.head.weight.data()),
                       {}, name="lm_head")
        return logits, tuple(new_cache), mnp.stack(counts, axis=0)


def kimi_linear(**kwargs):
    """Kimi-Linear decoder LM (KDA + MLA mixers, held-expert MoE)."""
    return KimiLinearLM(**kwargs)
