"""Flash-attention backward — blockwise Pallas kernels (dq, then dk/dv).

Completes the Pallas forward in ``ops/attention.py``: with this, BERT
*training* keeps the whole attention gradient on-chip instead of falling
back to the O(T^2) reference VJP ("Operator Fusion in XLA", PAPERS.md —
attention without materializing the score matrix is exactly the fusion
XLA will not find on its own).

Standard flash recipe over the forward's saved row ``lse``:

    delta_i = sum(g_i * out_i)                       (jnp, O(T*D))
    p_ij    = exp(s_ij - lse_i)
    ds      = p * (g @ v^T - delta)
    dq_i    = sum_j ds @ k_j * scale                 (dq kernel)
    dk_j    = sum_i ds^T @ q_i * scale               (dk/dv kernel)
    dv_j    = sum_i p^T @ g_i

Two kernels because the reduction axes differ: dq accumulates over kv
blocks (grid ``(BH, nq, nk)``, kv innermost/arbitrary), dk/dv over q
blocks (grid ``(BH, nk, nq)``).  Only (block, d)-sized tiles live in
VMEM; no (Tq, Tk) tensor exists in either pass.  Same skip rules as the
forward: causal upper-triangle blocks and blocks past the row's
``kv_len`` never run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import registry as _registry

__all__ = ["flash_attention_bwd_pallas"]

_NEG_INF = float("-inf")


def _masked_p_ds_t(q, k, v, g, lse, delta, *, scale, causal, cur_len, i, j,
                   bq, bk):
    """Shared block math in the TRANSPOSED (bk, bq) domain: returns
    ``(p^T, ds^T)`` for the (i, j) block pair.  Keys ride the sublanes
    and queries the lanes so that the per-query ``lse``/``delta`` vectors
    are consumed as the lane-major ``(1, bq)`` rows they are stored as
    (a ``(bq, 1)`` column would need a lane-to-sublane move Mosaic
    does not do)."""
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    if causal:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        s = jnp.where(qpos >= kpos, s, _NEG_INF)
    if cur_len is not None:
        s = jnp.where(kpos < cur_len, s, _NEG_INF)
    # fully-masked rows saved lse = -inf; exp(s - lse) must stay 0 not nan
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - lse_safe), 0.0)
    dp = jax.lax.dot_general(v, g, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    return p, ds


def _dq_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
               dq_ref, acc_ref, *, scale: float, causal: bool,
               has_len: bool, bq: int, bk: int, nk: int):
    import jax.experimental.pallas as pl

    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)
    cur_len = len_ref[pl.program_id(0)] if has_len else None

    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        _, ds_t = _masked_p_ds_t(
            q, k, v_ref[0].astype(jnp.float32),
            g_ref[0].astype(jnp.float32), lse_ref[0], delta_ref[0],
            scale=scale, causal=causal, cur_len=cur_len, i=i, j=j,
            bq=bq, bk=bk)
        acc_ref[...] += jax.lax.dot_general(
            ds_t, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, j * bk <= i * bq + (bq - 1))
    if has_len:
        run = jnp.logical_and(run, j * bk < cur_len)
    pl.when(run)(_step)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0, ...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                causal: bool, has_len: bool, bq: int, bk: int, nq: int):
    import jax.experimental.pallas as pl

    i = pl.program_id(2)
    j = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    cur_len = len_ref[pl.program_id(0)] if has_len else None

    def _step():
        q = q_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        p_t, ds_t = _masked_p_ds_t(
            q, k_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32), g, lse_ref[0], delta_ref[0],
            scale=scale, causal=causal, cur_len=cur_len, i=i, j=j,
            bq=bq, bk=bk)
        dk_acc[...] += jax.lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        dv_acc[...] += jax.lax.dot_general(
            p_t, g, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, i * bq + (bq - 1) >= j * bk)
    if has_len:
        run = jnp.logical_and(run, j * bk < cur_len)
    pl.when(run)(_step)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0, ...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd_pallas(q, k, v, g, out, lse, kv_len, causal: bool,
                               scale: float, bq: int, bk: int,
                               interpret: bool = False):
    """(dq, dk, dv) for (B, H, T, D) inputs via the two backward kernels.

    ``lse`` is the forward's (B, H, Tq) row log-sum-exp (f32); ``kv_len``
    an optional (B,) int32 valid-key-length vector (same contract as the
    forward).  ``bq``/``bk`` are the block sizes the caller validated."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk = k.shape[2]
    nq, nk = tq // bq, tk // bk
    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    gr = g.reshape(b * h, tq, d)
    # per-query rows travel as (BH, 1, Tq) with (1, 1, bq) blocks: a
    # (1, bq) block over (BH, Tq) breaks Mosaic's (8, 128) block rule
    lser = lse.reshape(b * h, 1, tq)
    # delta = rowsum(g * out): O(T*D) elementwise — jnp, fused by XLA
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    deltar = delta.reshape(b * h, 1, tq)
    has_len = kv_len is not None
    if has_len:
        lens = jnp.broadcast_to(kv_len.astype(jnp.int32)[:, None],
                                (b, h)).reshape(b * h)
    else:
        lens = jnp.full((b * h,), tk, jnp.int32)

    len_spec = pl.BlockSpec((b * h,), lambda b_, x, y: (0,),
                            memory_space=pltpu.SMEM)
    q_at_i = pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0))
    k_at_j = pl.BlockSpec((1, bk, d), lambda b_, i, j: (b_, j, 0))
    row_at_i = pl.BlockSpec((1, 1, bq), lambda b_, i, j: (b_, 0, i))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          has_len=has_len, bq=bq, bk=bk, nk=nk),
        grid=(b * h, nq, nk),
        in_specs=[len_spec, q_at_i, k_at_j, k_at_j, q_at_i, row_at_i,
                  row_at_i],
        out_specs=pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_registry.tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(lens, qr, kr, vr, gr, lser, deltar)

    # dk/dv grid: kv block is the middle (parallel) axis, q innermost
    q_at_i2 = pl.BlockSpec((1, bq, d), lambda b_, j, i: (b_, i, 0))
    k_at_j2 = pl.BlockSpec((1, bk, d), lambda b_, j, i: (b_, j, 0))
    row_at_i2 = pl.BlockSpec((1, 1, bq), lambda b_, j, i: (b_, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          has_len=has_len, bq=bq, bk=bk, nq=nq),
        grid=(b * h, nk, nq),
        in_specs=[len_spec, q_at_i2, k_at_j2, k_at_j2, q_at_i2, row_at_i2,
                  row_at_i2],
        out_specs=[pl.BlockSpec((1, bk, d), lambda b_, j, i: (b_, j, 0)),
                   pl.BlockSpec((1, bk, d), lambda b_, j, i: (b_, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((b * h, tk, d), jnp.float32),
                   jax.ShapeDtypeStruct((b * h, tk, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_registry.tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(lens, qr, kr, vr, gr, lser, deltar)

    return (dq.reshape(b, h, tq, d).astype(q.dtype),
            dk.reshape(b, h, tk, d).astype(k.dtype),
            dv.reshape(b, h, tk, d).astype(v.dtype))
