"""A looped decoder family: the Ouro block (ByteDance Ouro 1.4B / 2.6B
"LoopLM"; the keys of its published ``config.json``), a third setting of
the shared skeleton (``mixer_lm.py``) -- ONE stack of layers run
``total_ut_steps`` times over the same weights.

``x = E[tokens]``; for pass ``r = 1..R``, for layer ``l = 1..L`` (the same
``theta_l`` in every pass):

* ``q, k, v = W (RMS(x; g1_l))``, ``num_attention_heads`` on
  ``num_key_value_heads`` heads of ``head_dim``, no bias; rotary on q and k
  at the token's absolute position, the same in every pass
  (``rope_theta``; ``mellum.GQAMixer``, ``mellum.attend``);
* k and v are appended to THIS pass's cache of THIS layer (entry ``r * L +
  l`` of the cache tree: ``R x L`` K‖V leaves, paged) and the queries
  attend to it alone;
* ``x <- x + RMS(W_o a; g2_l)``; ``x <- x + RMS(ffn(RMS(x; g3_l)); g4_l)``
  with the SiLU-gated FFN: a norm on each branch's output too
  (``assumed.sandwich_norm``);

and the pass ends with the final norm, ``h_r = RMS(x; g_f)``, from which the
next pass starts (``assumed.pass_norm``).  ``logits = W_head h_R``.

**The exit gate** (``assumed.exit_gate``): ``lambda_r = sigmoid(w_g . h_r +
b_g)`` a position and pass; ``p_r = lambda_r prod_{j<r} (1 - lambda_j)`` for
``r < R`` and ``p_R`` the rest, and a row leaves the loop at the first ``r``
whose cumulative ``p`` reaches ``early_exit_threshold`` (:meth:`OuroLM.
exit_pdf`).  At the published threshold 1 that is ``R`` for every row, so
the served programs run every pass and do not evaluate the gate.  A
threshold below 1 is refused at construction: rows of one batch leaving the
loop at different passes need a scheduler and a cache that know of it
(ROADMAP.md queue 2).

Every mixer takes a non-empty cache at ``T > 1``, so a prompt past the
largest prompt bucket is forwarded in chunks, and every leaf is paged, so
the serve tier's prefix cache takes the tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops.dispatch import call as _call
from .. import nn
from .mellum import FULL, GQAMixer, rope_inv_freq, rope_positions
from .mixer_lm import GatedFFN, MixerLM, _mm, _normal, _rms

__all__ = ["OuroLM", "ouro"]


class OuroLM(MixerLM):
    """Causal LM of the Ouro family from a configuration under its
    published keys (``chipbench/configs/ouro-2.6b.json``;
    ``tests/fixtures/tiny-ouro.json`` is a tiny one).  Beside them, under
    ``assumed``, what ``config.json`` does not state: ``sandwich_norm``,
    ``pass_norm``, ``exit_gate``, ``attention_bias`` and ``qk_norm``."""

    def __init__(self, config, dtype=jnp.bfloat16, **kw):
        c = config
        assumed = c.get("assumed", {})
        units, eps, dh = c["hidden_size"], c["rms_norm_eps"], c["head_dim"]
        if c["early_exit_threshold"] < 1:
            raise ValueError(
                f"early_exit_threshold {c['early_exit_threshold']} < 1: rows "
                "of one batch would leave the loop at different passes, which "
                "the serve tier's scheduler and cache do not know of; only "
                "the published threshold 1 (every row runs total_ut_steps "
                "passes) is built")
        if set(c["layer_types"]) != {FULL} or c.get("use_sliding_window"):
            raise ValueError("an Ouro stack is full_attention layers only, "
                             f"not {sorted(set(c['layer_types']))}")
        if len(c["layer_types"]) != c["num_hidden_layers"]:
            raise ValueError(
                f"layer_types names {len(c['layer_types'])} layers, "
                f"num_hidden_layers {c['num_hidden_layers']}")
        if c.get("rope_scaling") is not None:
            raise ValueError(f"unknown rope_scaling {c['rope_scaling']!r}")
        if assumed.get("attention_bias", False):
            raise ValueError("assumed.attention_bias: the grouped-query "
                             "mixer has no bias on its projections")
        cells = [(GQAMixer(units, c["num_attention_heads"],
                           c["num_key_value_heads"], dh, FULL, None, None,
                           assumed.get("qk_norm", False), eps, dtype),
                  GatedFFN(units, c["intermediate_size"], dtype))
                 for _ in c["layer_types"]]
        super().__init__(c["vocab_size"], units, eps, dtype, cells,
                         loops=c["total_ut_steps"],
                         sandwich=assumed.get("sandwich_norm", False),
                         pass_norm=assumed.get("pass_norm", False), **kw)
        self._rope = {FULL: rope_inv_freq({"rope_theta": c["rope_theta"]}, dh)}
        # seeded like every projection, the bias non-zero so that it is
        # worked
        self.exit_gate = nn.Dense(
            1, use_bias=True, flatten=False, dtype=dtype, in_units=units,
            weight_initializer=_normal(units ** -0.5),
            bias_initializer=_normal(0.5)) \
            if assumed.get("exit_gate", False) else None
        if self.exit_gate is not None:
            for p in self.exit_gate.collect_params().values():
                p.grad_req = "null"

    def positions(self, cache_len, t):
        return rope_positions(self._rope, cache_len, t)

    def exit_pdf(self, tokens):
        """``(B, T, R)`` float32: the probability that a position's row
        leaves the loop after pass ``r``, from the gate on every pass's
        ``h_r`` over one whole forward of ``tokens`` (B, T) from an empty
        cache.  Sums to one over ``R``."""
        from ... import numpy as mnp

        if self.exit_gate is None:
            raise ValueError("this configuration has no exit gate "
                             "(assumed.exit_gate)")
        b, t = tokens.shape
        ends, _, _ = self.stack(
            tokens, self.begin_cache(b, t), mnp.zeros((b,), dtype="int32"),
            mnp.full((b,), t, dtype="int32"))
        eps = self._eps

        def pdf(g_f, w_g, b_g, *ends):
            with jax.named_scope("exit_gate"):
                lam = [jax.nn.sigmoid(_mm(_rms(x, g_f, eps), w_g)[..., 0]
                                      + b_g.astype(jnp.float32))
                       for x in ends]
                stay, out = jnp.ones_like(lam[0]), []
                for gate in lam[:-1]:
                    out.append(gate * stay)
                    stay = stay * (1.0 - gate)
                return jnp.stack(out + [stay], axis=-1)

        return _call(pdf, (self.ln_f.gamma.data(),
                           self.exit_gate.weight.data(),
                           self.exit_gate.bias.data()) + tuple(ends), {},
                     name="exit_pdf")


def ouro(**kwargs):
    """Ouro looped decoder LM (one stack of grouped-query attention and
    gated-FFN layers run several times over shared weights, sandwich norms,
    a cache a pass, an exit gate)."""
    return OuroLM(**kwargs)
