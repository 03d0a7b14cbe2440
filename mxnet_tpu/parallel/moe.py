"""Mixture-of-Experts: two routed expert layers.

No reference counterpart (the reference's distributed story is kvstore
data parallelism only); built per the framework charter -- expert
parallelism is a first-class sharding dimension next to dp/fsdp/tp/sp.

**Capacity-bounded dispatch over an 'ep' mesh axis** (``moe_ffn``,
``moe_reference``; the Mesh-TensorFlow/Switch algorithm, for training):

  1. gate: token -> top-k experts (softmax over E logits)
  2. capacity-bounded dispatch tensor (tokens, E, C) built from a
     position-in-expert cumsum -- static shapes, jit-safe; a token past an
     expert's capacity is DROPPED
  3. lax.all_to_all over 'ep' routes each expert's token slots to the
     device that owns it (E = ep_size * experts_per_device)
  4. local experts run their FFN on (E_local, ep*C, d)
  5. reverse all_to_all + combine weights scatter results back to tokens

``moe_ffn`` is valid inside shard_map/pjit with an 'ep' axis;
``moe_reference`` is the dense single-device semantics used by tests and
the eager fallback.  The auxiliary load-balancing loss follows the
Switch-Transformer formula (mean gate prob x mean dispatch fraction x E).

**Dropless routing over the experts HELD here** (``route_sigmoid_topk``,
``route_softmax_topk``, ``held_experts_ffn``; for serving,
gluon/model_zoo/mixer_lm.py:HeldMoE): the
router scores ALL experts of the model (sigmoid with a selection bias used
for the choice only, or softmax; top-k renormalised), and a device that holds
experts ``[held_start, held_start + E_held)`` computes exactly their part
of the result, as one batch of products over the held experts: by the
call's static shapes every row goes through every held expert (a decode
step's few rows), or the token-expert pairs are sorted by expert and each
held expert's rows gathered into a slot (a prefill chunk; an overflowing
slot sends the call to the dense form) -- so no capacity exists and no
token is dropped.  What the other devices' experts
add is theirs to compute; on one chip the layer runs without an exchange.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .collectives import axis_size as _axis_size

__all__ = ["moe_ffn", "moe_reference", "gate_topk", "aux_load_balance",
           "route_sigmoid_topk", "route_softmax_topk", "held_experts_ffn"]


def gate_topk(logits, k: int):
    """Top-k gating: returns (weights (n, k), indices (n, k)) with the
    selected probabilities renormalized to sum to 1 per token."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = lax.top_k(probs, k)
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    return vals, idx


def aux_load_balance(probs, dispatch_frac):
    """Switch aux loss: E * mean_e(gate prob) . mean_e(token fraction)."""
    e = probs.shape[-1]
    return e * jnp.sum(probs.mean(0) * dispatch_frac)


def _dispatch_tensors(logits, num_experts: int, capacity: int, k: int):
    """Build (dispatch (n,E,C) bool, combine (n,E,C) f32, aux scalar)."""
    n = logits.shape[0]
    weights, idx = gate_topk(logits, k)             # (n,k)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    dispatch = jnp.zeros((n, num_experts, capacity), jnp.bool_)
    combine = jnp.zeros((n, num_experts, capacity), jnp.float32)
    # experts fill slots in token order, k-th choices after (k-1)-th:
    # running per-expert counts thread through the selection loop
    counts = jnp.zeros((num_experts,), jnp.int32)
    frac = jnp.zeros((num_experts,), jnp.float32)
    for j in range(k):
        sel = jax.nn.one_hot(idx[:, j], num_experts, dtype=jnp.int32)  # (n,E)
        pos = counts[None, :] + jnp.cumsum(sel, axis=0) - sel          # (n,E)
        keep = sel.astype(bool) & (pos < capacity)
        slot = jax.nn.one_hot(jnp.where(keep.any(-1), pos[jnp.arange(n),
                                                         idx[:, j]], 0),
                              capacity, dtype=jnp.float32)             # (n,C)
        token_keep = keep[jnp.arange(n), idx[:, j]]                    # (n,)
        d_j = (sel.astype(jnp.float32)[:, :, None] * slot[:, None, :]
               * token_keep[:, None, None])
        dispatch = dispatch | d_j.astype(bool)
        combine = combine + d_j * weights[:, j][:, None, None]
        counts = counts + (sel * token_keep[:, None]).sum(0)
        frac = frac + sel.astype(jnp.float32).mean(0)
    aux = aux_load_balance(probs, frac / k)
    return dispatch, combine, aux


def moe_reference(x, gate_w, w_up, w_down, k: int = 2,
                  capacity_factor: float = 1.5,
                  activation=jax.nn.gelu):
    """Dense single-device MoE semantics (all experts local).

    x: (n, d); gate_w: (d, E); w_up: (E, d, h); w_down: (E, h, d).
    Returns (out (n, d), aux_loss scalar)."""
    n, d = x.shape
    e = gate_w.shape[1]
    capacity = max(1, math.ceil(n * k * capacity_factor / e))
    logits = x @ gate_w.astype(x.dtype)
    dispatch, combine, aux = _dispatch_tensors(logits, e, capacity, k)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), x)
    h = activation(jnp.einsum("ecd,edh->ech", expert_in, w_up))
    expert_out = jnp.einsum("ech,ehd->ecd", h, w_down)
    out = jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), expert_out)
    return out.astype(x.dtype), aux


def moe_ffn(x, gate_w, w_up_local, w_down_local, axis_name: str = "ep",
            k: int = 2, capacity_factor: float = 1.5,
            activation=jax.nn.gelu):
    """Expert-parallel MoE FFN — call inside shard_map over 'ep'.

    Per-device views:
      x:            (n_local, d)  token shard
      gate_w:       (d, E)        replicated gate, E = ep * E_local
      w_up_local:   (E_local, d, h)  this device's experts
      w_down_local: (E_local, h, d)
    Returns (out (n_local, d), aux_loss scalar — psum-mean over the axis).
    """
    ep = _axis_size(axis_name)
    n, d = x.shape
    e_local = w_up_local.shape[0]
    e = ep * e_local
    capacity = max(1, math.ceil(n * k * capacity_factor / e))

    logits = x @ gate_w.astype(x.dtype)
    dispatch, combine, aux = _dispatch_tensors(logits, e, capacity, k)

    # (n, E, C) -> (E, C, d) token slots, grouped by owning device:
    # axis 0 of the (ep, e_local, C, d) view indexes the DESTINATION
    expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), x)
    expert_in = expert_in.reshape(ep, e_local, capacity, d)
    # after the exchange axis 0 indexes the SOURCE device; each device
    # now holds every peer's slots for ITS local experts
    routed = lax.all_to_all(expert_in, axis_name, split_axis=0,
                            concat_axis=0)          # (ep_src, e_local, C, d)
    routed = routed.transpose(1, 0, 2, 3).reshape(e_local,
                                                  ep * capacity, d)

    h = activation(jnp.einsum("ecd,edh->ech", routed, w_up_local))
    out_slots = jnp.einsum("ech,ehd->ecd", h, w_down_local)

    # reverse route: regroup by source device and send each slice home
    out_slots = out_slots.reshape(e_local, ep, capacity, d)
    out_slots = out_slots.transpose(1, 0, 2, 3)     # (ep_dst, e_local, C, d)
    returned = lax.all_to_all(out_slots, axis_name, split_axis=0,
                              concat_axis=0)        # (ep_owner, e_local, C, d)
    returned = returned.reshape(e, capacity, d)     # expert-major, as sent
    out = jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), returned)
    aux = lax.pmean(aux, axis_name)
    return out.astype(x.dtype), aux


# ------------------------------------------------- dropless, held experts
def route_sigmoid_topk(x, router_w, correction, k: int, scale: float = 1.0,
                       renormalize: bool = True):
    """Sigmoid scoring with a selection bias (``noaux_tc`` with one expert
    group): ``s = sigmoid(x W_r^T)`` over ALL experts, the ``k`` largest of
    ``s + correction`` chosen, weights ``scale * s_e / (sum_chosen s +
    1e-20)`` (the bias takes part in the choice only).

    x: (n, d); router_w: (E, d); correction: (E,).  Scores in float32 at
    ``highest`` precision whatever the inputs: the choice is discrete, and
    a rounding that flips it swaps a whole expert.  Returns
    ``(weights (n, k) f32, indices (n, k) int32)``."""
    with jax.named_scope("moe_route"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32).T,
            precision=lax.Precision.HIGHEST))
        _, idx = lax.top_k(s + correction.astype(jnp.float32), k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if renormalize:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return w * scale, idx.astype(jnp.int32)


def route_softmax_topk(x, router_w, k: int, renormalize: bool = True):
    """Softmax scoring: ``p = softmax(x W_r^T)`` over ALL experts, the ``k``
    largest chosen, weights ``p_e``, divided by the sum over the chosen
    when ``renormalize`` (``norm_topk_prob``).

    x: (n, d); router_w: (E, d).  Float32 at ``highest`` precision whatever
    the inputs, for :func:`route_sigmoid_topk`'s reason.  Returns
    ``(weights (n, k) f32, indices (n, k) int32)``."""
    with jax.named_scope("moe_route"):
        p = jax.nn.softmax(jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32).T,
            precision=lax.Precision.HIGHEST), axis=-1)
        w, idx = lax.top_k(p, k)
        if renormalize:
            w = w / w.sum(-1, keepdims=True)
        return w, idx.astype(jnp.int32)


def _slot_rows(n: int, k: int, routed: int) -> Optional[int]:
    """Rows of a held expert's slot for ``n`` token rows of ``k`` picks over
    ``routed`` experts: twice the rows it expects, in whole sublane tiles of
    8 -- or None where an expert expects less than one tile, and
    ``held_experts_ffn`` takes its dense form."""
    if n * k < 8 * routed:
        return None
    return -(-2 * n * k // (8 * routed)) * 8


def held_experts_ffn(x, weights, idx, w_gate, w_up, w_down,
                     held_start: int = 0, real=None, *, routed: int):
    """``sum over chosen e in held of w_e E_e(x)`` with ``E(x) =
    (SiLU(x W_gate) * x W_up) W_down`` -- the held experts' part of a routed
    layer, dropless.

    x: (n, d); weights, idx: (n, k) from the router, over all experts;
    w_gate, w_up: (E_held, d, h); w_down: (E_held, h, d), the stacks of
    experts ``held_start .. held_start + E_held - 1``; real: optional (n,)
    bool, False for padding rows (they route nowhere); routed: how many
    experts the router scores (the model's, not the held).

    Both forms read the held experts' weights whole and multiply them as
    one batch over the experts; they differ in the rows (``_slot_rows``, by
    the static shapes):

    * **slotted**, where a held expert expects a sublane tile of rows or
      more (a prefill chunk): the token-expert pairs are sorted by expert
      and each held expert's rows gathered into a slot of twice what it
      expects.  Should an expert overflow its slot, the call takes the
      dense form instead (``lax.cond``), so nothing is dropped.
    * **dense** otherwise (a decode step's few rows): every row through
      every held expert, no sort.  The weights' streaming bounds it: 16
      rows on 16 experts of 2304 x 896 read 205 us a layer on a v5e, where
      the weights alone are 242 us at 819 GB/s (my chip runs, PR 33).

    Returns ``(y (n, d) float32, counts (E_held,) int32)``: the
    token-expert pairs of each held expert.  Products take operands in x's
    dtype and accumulate in float32."""
    n, k = idx.shape
    e = w_gate.shape[0]
    slot = _slot_rows(n, k, routed)
    product = functools.partial(jnp.einsum,
                                preferred_element_type=jnp.float32)

    def dense(held, local):
        chosen = held[..., None] & (
            local[..., None] == jnp.arange(e, dtype=local.dtype))
        share = jnp.einsum("nk,nke->en", weights,
                           chosen.astype(weights.dtype))
        hid = jax.nn.silu(product("nd,edh->enh", x, w_gate)) \
            * product("nd,edh->enh", x, w_up)
        out = product("enh,ehd->end", hid.astype(x.dtype), w_down)
        return jnp.einsum("en,end->nd", share, out)

    with jax.named_scope("moe_experts"):
        local = idx - held_start
        held = (local >= 0) & (local < e)
        if real is not None:
            held = held & real[:, None]
        key = jnp.where(held, local, e).reshape(n * k)
        counts = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
        if slot is None:
            return dense(held, local), counts
        order = jnp.argsort(key, stable=True)      # pairs sorted by expert
        back = jnp.zeros((n * k,), jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32))

        def slotted(held, local):
            first = jnp.cumsum(counts) - counts    # an expert's first pair
            at = first[:, None] + jnp.arange(slot)[None]        # (e, slot)
            rows = x[order[jnp.minimum(at, n * k - 1)] // k]    # (e, slot, d)
            hid = jax.nn.silu(product("esd,edh->esh", rows, w_gate)) \
                * product("esd,edh->esh", rows, w_up)
            out = product("esh,ehd->esd", hid.astype(x.dtype), w_down)
            # sorted pair p of expert e lies in slot row e * slot + p - first
            where = jnp.minimum(key[order], e - 1)
            row = where * slot + jnp.arange(n * k) - first[where]
            pairs = out.reshape(e * slot, -1)[
                jnp.clip(row, 0, e * slot - 1)][back].reshape(n, k, -1)
            # rows of pairs held elsewhere hold anything
            return jnp.sum(jnp.where(held[..., None],
                                     pairs * weights[..., None], 0.0), axis=1)

        return lax.cond(jnp.max(counts) <= slot, slotted, dense,
                        held, local), counts
