"""Ring attention — sequence/context parallelism over the 'sp' mesh axis.

No reference counterpart (SURVEY.md §5: long-context parallelism absent);
built per the framework charter as first-class. Blockwise-safe softmax
attention where K/V blocks rotate around the ring via lax.ppermute, each
device holding one sequence shard — memory O(seq/sp_size) per chip, compute
fully overlapped with ICI neighbor exchange by XLA's latency-hiding
scheduler.

Use inside shard_map over a mesh with an 'sp' axis; eager single-device
fallback computes plain attention.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "blockwise_attention", "attention_reference"]


def attention_reference(q, k, v, mask=None, scale: Optional[float] = None):
    """Plain softmax attention (B, H, T, D) — the single-chip baseline."""
    if scale is None:
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def _block_accumulate(q, k, v, scale, carry, kv_index, q_index, causal,
                      block_len):
    """One ring step: online-softmax accumulate q·k_block."""
    acc, row_max, row_sum = carry
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        # global positions: q in [q_index*L, ...), k in [kv_index*L, ...)
        qpos = q_index * block_len + jnp.arange(q.shape[2])
        kpos = kv_index * block_len + jnp.arange(k.shape[2])
        cmask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(cmask[None, None], logits, -jnp.inf)
    new_max = jnp.maximum(row_max, logits.max(axis=-1))
    correction = jnp.exp(row_max - new_max)
    p = jnp.exp(logits - new_max[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    new_sum = row_sum * correction + p.sum(axis=-1)
    new_acc = acc * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    return new_acc, new_max, new_sum


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None):
    """Sequence-sharded attention: each rank holds (B,H,T/sp,D) shards.

    K/V rotate around the ring; lax.fori_loop over sp_size steps with
    ppermute neighbor exchange. Must be called inside shard_map with
    ``axis_name`` bound."""
    sp = lax.psum(1, axis_name)
    rank = lax.axis_index(axis_name)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    block_len = q.shape[2]
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    acc0 = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    max0 = jnp.full(q.shape[:3], -jnp.inf, jnp.float32)
    sum0 = jnp.zeros(q.shape[:3], jnp.float32)
    # the scan carry must be marked varying over the manual axis
    acc0, max0, sum0 = (lax.pcast(a, (axis_name,), to="varying")
                        for a in (acc0, max0, sum0))

    def body(i, state):
        k_blk, v_blk, carry = state
        kv_index = (rank - i) % sp
        carry = _block_accumulate(q, k_blk, v_blk, scale, carry, kv_index,
                                  rank, causal, block_len)
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, carry)

    _, _, (acc, _, row_sum) = lax.fori_loop(
        0, sp, body, (k, v, (acc0, max0, sum0)))
    out = acc / row_sum[..., None]
    return out.astype(q.dtype)


def blockwise_attention(q, k, v, block_size: int = 512, causal: bool = False,
                        scale: Optional[float] = None):
    """Single-device blockwise (flash-style) attention via lax.scan over KV
    blocks — the memory-efficient kernel ring_attention runs per-shard; also
    useful alone for long sequences on one chip."""
    b, h, t, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    nblk = max(1, (t + block_size - 1) // block_size)
    pad = nblk * block_size - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, h, nblk, -1, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nblk, -1, d).transpose(2, 0, 1, 3, 4)

    acc0 = jnp.zeros((b, h, t, d), jnp.float32)
    max0 = jnp.full((b, h, t), -jnp.inf, jnp.float32)
    sum0 = jnp.zeros((b, h, t), jnp.float32)

    def step(carry, blk):
        i, (kb_i, vb_i) = blk
        acc, row_max, row_sum = carry
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, kb_i).astype(jnp.float32) * scale
        kpos = i * block_size + jnp.arange(kb_i.shape[2])
        valid = kpos < t
        if causal:
            qpos = jnp.arange(t)
            valid = valid[None, :] & (qpos[:, None] >= kpos[None, :])
            logits = jnp.where(valid[None, None], logits, -jnp.inf)
        else:
            logits = jnp.where(valid[None, None, None, :], logits, -jnp.inf)
        new_max = jnp.maximum(row_max, logits.max(-1))
        corr = jnp.exp(row_max - new_max)
        p = jnp.exp(logits - new_max[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        new_sum = row_sum * corr + p.sum(-1)
        new_acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vb_i.dtype), vb_i).astype(jnp.float32)
        return (new_acc, new_max, new_sum), None

    (acc, _, row_sum), _ = lax.scan(step, (acc0, max0, sum0),
                                    (jnp.arange(nblk), (kb, vb)))
    return (acc / row_sum[..., None]).astype(q.dtype)
