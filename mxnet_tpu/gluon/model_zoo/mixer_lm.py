"""The decoder skeleton the configured families share: a pre-norm block
on a float32 residual stream whose per-layer MIXER, FFN and position scheme
come from the configuration (``kimi_linear.py``: KDA and MLA mixers, a
sigmoid router, no positions; ``mellum.py``: grouped-query attention on full
and window layers, a softmax router, rotary positions; ``ouro.py``: the
layer list run several times over the same weights, a norm on each branch's
output too, a norm that ends a pass).

What is here once: the seeded initialisers, RMSNorm, the SiLU-gated FFN,
the held-expert MoE layer with its two routers, the cell
(``x + mixer(RMSNorm(x))``; ``x + ffn(RMSNorm(x))``, each branch through a
second norm before it is added in a sandwich cell), and :class:`MixerLM` --
embedding, the layer loop and the passes over it, the untied head, the
cache tree's assembly and its kinds, and the routing counts.
``decoder.TransformerLM`` (LayerNorm, learned positions, a bf16 stream, a
tied head) is not yet a setting of it (ROADMAP.md D12).

**A mixer** is a HybridBlock with

* ``cache_kinds`` -- the kind of each leaf it keeps
  (``decoder.CACHE_PAGED`` / ``CACHE_WINDOW`` / ``CACHE_STATE``;
  ``serve.decode.cache_spec`` reads them through
  :meth:`MixerLM.cache_kinds` and checks them against ``begin_cache``),
* ``begin_cache(batch, capacity, dtype)`` -- its zeroed leaves,
* ``forward(x, gamma, leaves, step)`` -> ``(x, leaves)`` with ``step =
  (cache_len, n_tokens, positions)``, ``positions`` being whatever
  :meth:`MixerLM.positions` made of this call (None here).  A mixer that
  may sit in a sandwich cell takes a fifth argument, ``post``, the gamma
  of the norm on its branch's output.

Same decode contract as ``decoder.py`` (``forward(tokens, cache, cache_len,
n_tokens)``, ``begin_cache``); with routed layers ``forward`` returns a
third value, the ``(moe layers, held experts)`` int32 count of token-expert
pairs computed in this call (``step_counters`` turns it into telemetry
increments), so routing is counted without per-token work on the host.

Every parameter is created in ``dtype`` and initialised there leaf by leaf,
without a gradient buffer: no float32 copy of a model ever exists.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from ... import initializer as _init
from ...ops.dispatch import call as _call
from ...parallel import moe as _moe
from ...random import next_key
from .. import nn
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["MixerLM", "MixerCell", "HeldMoE", "GatedFFN", "RMSNorm",
           "route_rows"]

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


def _scoped(name):
    """``jax.named_scope(name)``; nothing where there is no name."""
    return jax.named_scope(name) if name else contextlib.nullcontext()


def _branch(x, y, post, eps):
    """The residual stream with a branch's output added: as it is, or
    through the sandwich cell's norm on it first (``post``: its gamma)."""
    return x + (y if post is None else _rms(y, post, eps))


def route_rows(h, w_r, corr, k, scale, renorm):
    """The routed layer's whole path from its normed rows ``h`` (n, d) to
    the router's ``(weights, idx)``: sigmoid scoring with the selection
    bias ``corr``, softmax when ``corr`` is None (``parallel/moe.py``).
    :class:`HeldMoE` calls this and does nothing else to the rows before
    the choice, so a reference that puts its own rows through it holds what
    the served layer does to them, casts included
    (``chipbench/references/mellum.py``: ``ROUTE_RTOL``)."""
    if corr is None:
        return _moe.route_softmax_topk(h, w_r, k, renorm)
    return _moe.route_sigmoid_topk(h, w_r, corr, k, scale, renorm)


class RMSNorm(HybridBlock):
    """Holds the scale; the layers apply :func:`_rms` themselves."""

    def __init__(self, units, dtype, **kw):
        super().__init__(**kw)
        self.gamma = Parameter(shape=(units,), dtype=dtype, init="ones",
                               name="gamma")


class GatedFFN(HybridBlock):
    """``W_down(SiLU(W_gate x) * W_up x)``: the dense FFN of the leading
    layers and the shared expert (holds the weights; :func:`_gated`)."""

    # the name a looped stack gives the three matrix products, with the
    # norm before them and the gate between them, in the trace
    # (MixerLM sets it on its cells' blocks; None: no name)
    dense_scope = None

    def __init__(self, units, hidden, dtype, **kw):
        super().__init__(**kw)
        self.gate = _dense(hidden, units, dtype)
        self.up = _dense(hidden, units, dtype)
        self.down = _dense(units, hidden, dtype)

    def weights(self):
        return (self.gate.weight.data(), self.up.weight.data(),
                self.down.weight.data())

    def forward(self, x, gamma, eps, post=None):
        """``x + ffn(RMSNorm(x))`` on the float32 residual stream; with
        ``post`` (a sandwich cell's gamma), ``x + RMSNorm(ffn(RMSNorm(x)))``."""
        sandwich, scope = post is not None, self.dense_scope

        def ffn(x, g, w_gate, w_up, w_down, *post):
            with _scoped(scope):
                y = _gated(_rms(x, g, eps), w_gate, w_up, w_down)
            return _branch(x, y, post[0] if sandwich else None, eps)

        return _call(ffn, (x, gamma) + self.weights()
                     + ((post,) if sandwich else ()), {}, name="gated_ffn")


class HeldMoE(HybridBlock):
    """The routed expert layer as ONE device of an expert-parallel
    deployment sees it: the router scores all ``n_routed`` experts, this
    device holds ``n_held`` of them from ``held_start`` and computes their
    part, plus -- where the family has one -- the shared expert that every
    device computes alike.  ``router``: ``"sigmoid"`` (a selection bias,
    ``scale``) or ``"softmax"`` (``parallel/moe.py``)."""

    def __init__(self, units, hidden, n_routed, n_held, held_start, top_k,
                 renormalize, dtype, out_gain=1.0, router="sigmoid",
                 scale=1.0, shared=True, **kw):
        super().__init__(**kw)
        if router not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown router {router!r}")
        self._k, self._scale, self._renorm = top_k, scale, renormalize
        self._held_start, self._router = held_start, router
        self._routed = n_routed
        self.router = _dense(n_routed, units, jnp.float32)
        if router == "sigmoid":
            # used for the choice only; seeded small and non-zero so that
            # the path is worked
            self.e_score_correction = Parameter(
                shape=(n_routed,), dtype=jnp.float32, init=_normal(0.02),
                name="e_score_correction")
        stack = lambda i, o, name, gain=1.0: Parameter(
            shape=(n_held, i, o), dtype=dtype, init=_normal(gain * i ** -0.5),
            name=name)
        self.experts_gate = stack(units, hidden, "experts_gate")
        self.experts_up = stack(units, hidden, "experts_up")
        self.experts_down = stack(hidden, units, "experts_down", out_gain)
        self.shared = GatedFFN(units, hidden, dtype) if shared else None

    def forward(self, x, gamma, eps, n_tokens):
        """``x + moe(RMSNorm(x))`` -> ``(x, counts (n_held,) int32)``."""
        k, scale, renorm = self._k, self._scale, self._renorm
        start, sigmoid = self._held_start, self._router == "sigmoid"
        n_routed = self._routed

        def routed(x, gamma, w_r, *rest):
            rest = list(rest)
            corr = rest.pop(0) if sigmoid else None
            w_g, w_u, w_d, *shared, n_tokens = rest
            b, t, d = x.shape
            h = _rms(x, gamma, eps).reshape(b * t, d)
            weights, idx = route_rows(h, w_r, corr, k, scale, renorm)
            real = (jnp.arange(t)[None, :] < n_tokens[:, None]).reshape(b * t)
            y, counts = _moe.held_experts_ffn(
                h.astype(w_g.dtype), weights, idx, w_g, w_u, w_d, start, real,
                routed=n_routed)
            if shared:
                with jax.named_scope("shared_expert"):
                    y = y + _gated(h, *shared)
            return x + y.reshape(b, t, d), counts

        return _call(
            routed, (x, gamma, self.router.weight.data())
            + ((self.e_score_correction.data(),) if sigmoid else ())
            + (self.experts_gate.data(), self.experts_up.data(),
               self.experts_down.data())
            + (self.shared.weights() if self.shared is not None else ())
            + (n_tokens,), {}, name="held_moe")


class MixerCell(HybridBlock):
    """``x + mixer(RMSNorm(x))``; ``x + ffn(RMSNorm(x))`` on a float32
    residual stream (matrix products take bf16 operands and accumulate in
    float32; norms, gates, softmax, the router and a recurrence are
    float32).  ``sandwich``: a norm on each branch's OUTPUT too, ``x +
    RMSNorm(mixer(RMSNorm(x)))``, whose gammas the cell holds and hands to
    the mixer's and the FFN's own calls (the branch is added inside them)."""

    def __init__(self, mixer, ffn, units, eps, dtype, sandwich=False, **kw):
        super().__init__(**kw)
        self._eps = eps
        self.ln_mixer = RMSNorm(units, dtype)
        self.mixer = mixer
        self.ln_ffn = RMSNorm(units, dtype)
        self.ffn = ffn
        if sandwich and isinstance(ffn, HeldMoE):
            raise ValueError("a sandwich cell's FFN is a GatedFFN: the "
                             "held-expert layer takes no norm on its output")
        self.post_mixer = RMSNorm(units, dtype) if sandwich else None
        self.post_ffn = RMSNorm(units, dtype) if sandwich else None

    def forward(self, x, leaves, step):
        post = () if self.post_mixer is None \
            else (self.post_mixer.gamma.data(),)
        x, leaves = self.mixer(x, self.ln_mixer.gamma.data(), leaves, step,
                               *post)
        gamma = self.ln_ffn.gamma.data()
        if isinstance(self.ffn, HeldMoE):
            x, counts = self.ffn(x, gamma, self._eps, step[1])
            return x, tuple(leaves), counts
        post = () if self.post_ffn is None else (self.post_ffn.gamma.data(),)
        return self.ffn(x, gamma, self._eps, *post), tuple(leaves), None


class MixerLM(HybridBlock):
    """Causal LM over ``cells``, a list of ``(mixer, ffn)`` a layer that
    the family's constructor builds from its configuration.

    ``loops = R > 1`` runs the layer list ``R`` times over the SAME
    parameters, a pass after a pass: pass ``r`` of layer ``l`` reads and
    writes cache entry ``r * L + l`` and no other, so the cache tree has
    ``R x L`` entries of the mixers' own leaves (the serve tier sees plain
    leaves and more of them).  ``pass_norm``: the final norm also ends every
    pass before the last and the next pass starts from its output (the last
    pass's is the head's own).  ``sandwich``: :class:`MixerCell`'s."""

    # True for a family one of whose mixers presumes an empty cache at
    # T > 1: the serve tier then refuses a prompt past its largest bucket
    # instead of forwarding it in chunks (serve/decode.py)
    prefill_needs_empty_cache = False
    # the window of the family's window layers, if it has any (the serve
    # tier counts serve.step_window_positions with it)
    attention_window = None

    def __init__(self, vocab_size, units, eps, dtype, cells, loops=1,
                 sandwich=False, pass_norm=False, **kw):
        super().__init__(**kw)
        self._vocab_size, self._dtype, self._eps = vocab_size, dtype, eps
        if loops < 1:
            raise ValueError(f"a stack runs at least once, not {loops} times")
        # passes a forward makes over the layer list (the serve tier counts
        # serve.stack_passes with it)
        self.loops, self._pass_norm = loops, pass_norm
        self.word_embed = nn.Embedding(vocab_size, units, dtype=dtype,
                                       weight_initializer=_normal(1.0))
        self.layers = nn.HybridSequential()       # container only; iterated
        for mixer, ffn in cells:
            if loops > 1:       # a looped stack names its matrix products
                mixer.dense_scope = ffn.dense_scope = "loop_dense"
            self.layers.add(MixerCell(mixer, ffn, units, eps, dtype,
                                      sandwich=sandwich))
        self.ln_f = RMSNorm(units, dtype)
        self.head = _dense(vocab_size, units, dtype)
        # inference only (the routed layer has no backward yet): without
        # this every parameter is initialised WITH a gradient buffer of its
        # own size, 8.6 GB more at Kimi-Linear's published widths
        for p in self.collect_params().values():
            p.grad_req = "null"

    # ------------------------------------------------------------ cache
    def begin_cache(self, batch_size, capacity):
        """An entry a layer AND a pass, pass-major: entry ``r * L + l`` is
        pass ``r``'s cache of layer ``l``."""
        return tuple(tuple(cell.mixer.begin_cache(batch_size, capacity,
                                                  self._dtype))
                     for _ in range(self.loops) for cell in self.layers)

    def cache_kinds(self):
        """The kind of every leaf of :meth:`begin_cache`'s tree, as the
        mixers declare them (``serve.decode.cache_spec``)."""
        return tuple(tuple(cell.mixer.cache_kinds)
                     for _ in range(self.loops) for cell in self.layers)

    @staticmethod
    def step_counters(counts):
        """Telemetry increments for the host-side ``counts`` of one call:
        token-expert pairs computed here, and held experts that saw a
        token, both summed over layers."""
        return {"serve.moe_held_picks": int(counts.sum()),
                "serve.moe_experts_hit": int((counts > 0).sum())}

    def positions(self, cache_len, t):
        """What the mixers need of this call's positions (``cache_len +
        0 .. t-1`` a row); nothing for a family without positions."""
        return None

    def stack(self, tokens, cache, cache_len, n_tokens):
        """The embedding through every pass of the layer list: ``(ends,
        new_cache, counts)``.  ``ends[r]`` is the residual stream as pass
        ``r`` leaves it, BEFORE the final norm -- the last is what the head
        norms and reads; under ``pass_norm`` each earlier one goes through
        the final norm into the next pass.  ``counts``: the routed layers'
        ``(n_held,)`` counts, every pass's, as a list."""
        x = self.word_embed(tokens).astype(jnp.float32)     # (B, T, U)
        step = (cache_len, n_tokens, self.positions(cache_len,
                                                    tokens.shape[1]))
        eps, n = self._eps, len(self.layers)
        if len(cache) != self.loops * n:
            raise ValueError(
                f"a cache of {len(cache)} entries for {self.loops} pass(es) "
                f"over {n} layers; begin_cache gives {self.loops * n}")
        ends, new_cache, counts = [], [], []
        for r in range(self.loops):
            if ends:                          # a pass after the first
                x = ends[-1] if not self._pass_norm else _call(
                    lambda x, g: _rms(x, g, eps),
                    (ends[-1], self.ln_f.gamma.data()), {}, name="pass_norm")
            with _scoped("loop_pass" if self.loops > 1 else None):
                for cell, leaves in zip(self.layers,
                                        cache[r * n:(r + 1) * n]):
                    x, leaves, c = cell(x, leaves, step)
                    new_cache.append(leaves)
                    if c is not None:
                        counts.append(c)
            ends.append(x)
        return ends, tuple(new_cache), counts

    def forward(self, tokens, cache, cache_len, n_tokens):
        from ... import numpy as mnp
        ends, new_cache, counts = self.stack(tokens, cache, cache_len,
                                             n_tokens)
        eps = self._eps
        logits = _call(lambda x, g, w: _mm(_rms(x, g, eps), w),
                       (ends[-1], self.ln_f.gamma.data(),
                        self.head.weight.data()),
                       {}, name="lm_head")
        # a Python list, one entry a routed layer and pass: static
        if not counts:  # mxlint: disable=H003
            return logits, new_cache
        return logits, new_cache, mnp.stack(counts, axis=0)
