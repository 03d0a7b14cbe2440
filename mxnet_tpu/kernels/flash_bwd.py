"""Flash-attention backward — blockwise Pallas kernels (dq, then dk/dv).

Completes the Pallas forward in ``ops/attention.py``: with this, BERT
*training* keeps the whole attention gradient on-chip instead of falling
back to the O(T^2) reference VJP ("Operator Fusion in XLA", PAPERS.md —
attention without materializing the score matrix is exactly the fusion
XLA will not find on its own).

Standard flash recipe over the forward's saved row ``lse``:

    delta_i = sum(g_i * out_i)                       (in the kernels)
    p_ij    = exp(s_ij - lse_i)
    ds      = p * (g @ v^T - delta)
    dq_i    = sum_j ds @ k_j * scale                 (dq kernel)
    dk_j    = sum_i ds^T @ q_i * scale               (dk/dv kernel)
    dv_j    = sum_i p^T @ g_i

Two kernels because the reduction axes differ: dq accumulates over kv
blocks (grid ``(B, H // hg, nq, nk)``, kv innermost/arbitrary), dk/dv
over q blocks (grid ``(B, H // hg, nk, nq)``).  Where one block is the
sequence there is nothing to reduce over, and the dk/dv kernel computes
dq too: ONE program, s, p, dp and ds once, five products for seven and
one read of every operand (BERT at 128 tokens).  Only (block, d)-sized
tiles live in VMEM; no (Tq, Tk) tensor exists in either pass.  Same skip
rules as the forward: causal upper-triangle blocks and blocks past the
row's ``kv_len`` never run.

The program form is the forward's (``ops/attention.py:_train_form``): the
arrays come as ``(B, T, H*d)``, a program holds ``hg`` heads of one batch
row and walks them one after another; operands go to the MXU in the
arrays' own dtype with f32 accumulation, ``p`` and ``ds`` cast to it for
the gradient products; dq, dk and dv leave the kernels in their inputs'
dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import registry as _registry

__all__ = ["flash_attention_bwd_lanes", "flash_attention_bwd_pallas"]

_NEG_INF = float("-inf")


def _seen_t(*, causal, cur_len, i, j, bq, bk):
    """The ``(bk, bq)`` mask of block pair (i, j), shared by a program's
    heads (None = everything attends)."""
    if not causal and cur_len is None:
        return None
    seen = None
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    if causal:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        seen = qpos >= kpos
    if cur_len is not None:
        live = kpos < cur_len
        seen = live if seen is None else seen & live
    return seen


def _masked_p_ds_t(q, k, v, g, lse, delta, seen, scale):
    """Shared block math of one head in the TRANSPOSED (bk, bq) domain:
    returns ``(p^T, ds^T)`` in f32 for the (i, j) block pair.  Keys ride
    the sublanes and queries the lanes so that the per-query
    ``lse``/``delta`` vectors are consumed as the lane-major ``(1, bq)``
    rows they are stored as (a ``(bq, 1)`` column would need a
    lane-to-sublane move Mosaic does not do)."""
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if seen is not None:
        s = jnp.where(seen, s, _NEG_INF)
    # fully-masked rows saved lse = -inf; exp(s - lse) must stay 0 not
    # nan (a masked logit is -inf less a finite number: p = 0)
    p = jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, 0.0))
    dp = jax.lax.dot_general(v, g, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta)


def _head_blocks(h, d, q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref, operand):
    """Head ``h`` of a program's blocks — lanes ``[h*d, (h+1)*d)`` of the
    ``(1, block, hg*d)`` operand blocks, row ``h`` of the saved lse — and
    its ``delta = rowsum(g * out)`` as the lane-major ``(1, bq)`` row the
    transposed domain consumes: a product of ones with ``(g * out)^T`` on
    the MXU, in full f32, so the column of row sums never has to turn."""
    lanes = slice(h * d, (h + 1) * d)
    g = g_ref[0, :, lanes]
    go = g.astype(jnp.float32) * o_ref[0, :, lanes].astype(jnp.float32)
    delta = jax.lax.dot_general(
        jnp.ones((8, d), jnp.float32), go, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[:1]
    return (q_ref[0, :, lanes].astype(operand),
            k_ref[0, :, lanes].astype(operand),
            v_ref[0, :, lanes].astype(operand), g.astype(operand),
            lse_ref[0, h], delta)


def _side_by_side(heads, ref):
    """Store the heads' ``(block, d)`` results as the ``(block, hg*d)``
    block they are lanes of: one store of whole lane tiles."""
    ref[0] = jnp.concatenate(heads, axis=-1).astype(ref.dtype)


def _dq_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
               dq_ref, acc_ref, *, d: int, scale: float, causal: bool,
               has_len: bool, bq: int, bk: int, nk: int):
    import jax.experimental.pallas as pl

    hg = acc_ref.shape[0]
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cur_len = len_ref[pl.program_id(0)] if has_len else None
    operand = _registry.operand_dtype(q_ref, k_ref, v_ref, g_ref)

    def _step():
        seen = _seen_t(causal=causal, cur_len=cur_len, i=i, j=j, bq=bq,
                       bk=bk)
        for h in range(hg):
            q, k, v, g, lse, delta = _head_blocks(
                h, d, q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref, operand)
            _, ds_t = _masked_p_ds_t(q, k, v, g, lse, delta, seen, scale)
            acc_ref[h] += jax.lax.dot_general(
                ds_t.astype(operand), k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, j * bk <= i * bq + (bq - 1))
    if has_len:
        run = jnp.logical_and(run, j * bk < cur_len)
    pl.when(run)(_step)

    @pl.when(j == nk - 1)
    def _finish():
        _side_by_side([acc_ref[h] for h in range(hg)], dq_ref)


def _dkv_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                *rest, d: int, scale: float, causal: bool, has_len: bool,
                bq: int, bk: int, nq: int, with_dq: bool):
    """dk and dv of a kv block, summed over the q blocks (the innermost
    grid axis).  ``with_dq``: the one block IS the sequence (``nq == nk
    == 1``), so ``ds`` is whole here and ``dq`` leaves this program too —
    s, p, dp, ds once and three gradient products, nothing accumulated and
    nothing skipped (a fully-masked row's p is 0)."""
    import jax.experimental.pallas as pl

    hg = q_ref.shape[2] // d
    j, i = pl.program_id(2), pl.program_id(3)
    cur_len = len_ref[pl.program_id(0)] if has_len else None
    operand = _registry.operand_dtype(q_ref, k_ref, v_ref, g_ref)

    def _grads(h, seen):
        q, k, v, g, lse, delta = _head_blocks(
            h, d, q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref, operand)
        p_t, ds_t = _masked_p_ds_t(q, k, v, g, lse, delta, seen, scale)
        ds_t = ds_t.astype(operand)
        dk = jax.lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        dv = jax.lax.dot_general(
            p_t.astype(operand), g, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv, ds_t, k

    if with_dq:
        dq_ref, dk_ref, dv_ref = rest
        seen = _seen_t(causal=causal, cur_len=cur_len, i=0, j=0, bq=bq,
                       bk=bk)
        dqs, dks, dvs = [], [], []
        for h in range(hg):
            dk, dv, ds_t, k = _grads(h, seen)
            dqs.append(jax.lax.dot_general(
                ds_t, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale)
            dks.append(dk)
            dvs.append(dv)
        _side_by_side(dqs, dq_ref)
        _side_by_side(dks, dk_ref)
        _side_by_side(dvs, dv_ref)
        return

    dk_ref, dv_ref, dk_acc, dv_acc = rest

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _step():
        seen = _seen_t(causal=causal, cur_len=cur_len, i=i, j=j, bq=bq,
                       bk=bk)
        for h in range(hg):
            dk, dv, _, _ = _grads(h, seen)
            dk_acc[h] += dk
            dv_acc[h] += dv

    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, i * bq + (bq - 1) >= j * bk)
    if has_len:
        run = jnp.logical_and(run, j * bk < cur_len)
    pl.when(run)(_step)

    @pl.when(i == nq - 1)
    def _finish():
        _side_by_side([dk_acc[h] for h in range(hg)], dk_ref)
        _side_by_side([dv_acc[h] for h in range(hg)], dv_ref)


# jitted on its own, as the forward: traced and lowered once a step program
@functools.partial(jax.jit, static_argnames=(
    "heads", "causal", "scale", "hg", "bq", "bk", "interpret", "one_program"))
def flash_attention_bwd_lanes(q, k, v, g, out, lse, kv_len, heads: int,
                              causal: bool, scale: float, hg: int, bq: int,
                              bk: int, interpret: bool = False,
                              one_program: Optional[bool] = None):
    """(dq, dk, dv) for ``(B, T, H*d)`` arrays (the layout of
    ``ops/attention.py:_to_lanes``), each in its input's dtype.

    ``lse`` is the forward's (B, H, Tq) row log-sum-exp (f32); ``kv_len``
    an optional (B,) int32 valid-key-length vector (same contract as the
    forward).  ``hg``/``bq``/``bk`` are the form the caller chose
    (``ops/attention.py:_train_form``): heads a program and the block
    sizes.  Where one block is the sequence (``nq == nk == 1``) the
    backward is ONE program a head group, run under the dk/dv kernel's
    name; ``one_program=False`` keeps the two kernels there (tests and
    measurements compare the two)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, e = q.shape
    tk, d = k.shape[1], e // heads
    nq, nk = tq // bq, tk // bk
    if one_program is None:
        one_program = nq == nk == 1
    elif one_program and not nq == nk == 1:
        raise ValueError("flash_attention_bwd: one program needs one block "
                         f"a sequence, got {nq} x {nk}")
    # per-query rows travel as (B, H, 1, Tq) with (1, hg, 1, bq) blocks: a
    # (1, bq) block over (H, Tq) breaks Mosaic's (8, 128) block rule
    lser = lse.reshape(b, heads, 1, tq)
    has_len = kv_len is not None
    lens = (kv_len.astype(jnp.int32) if has_len
            else jnp.full((b,), tk, jnp.int32))
    params = _registry.tpu_compiler_params(
        ("parallel", "parallel", "parallel", "arbitrary"))
    static = dict(d=d, scale=scale, causal=causal, has_len=has_len, bq=bq,
                  bk=bk)

    len_spec = pl.BlockSpec((b,), lambda b_, g_, x, y: (0,),
                            memory_space=pltpu.SMEM)

    def specs(order):
        """(q block, kv block, per-query row) specs for a grid whose two
        last axes are (i, j) or (j, i)."""
        def at(block, pick):
            return pl.BlockSpec(block, lambda b_, g_, x, y: pick(
                b_, g_, *((x, y) if order == "ij" else (y, x))))

        return (at((1, bq, hg * d), lambda b_, g_, i, j: (b_, i, g_)),
                at((1, bk, hg * d), lambda b_, g_, i, j: (b_, j, g_)),
                at((1, hg, 1, bq), lambda b_, g_, i, j: (b_, g_, 0, i)))

    dq = None
    if not one_program:
        q_at_i, k_at_j, row_at_i = specs("ij")
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, nk=nk, **static),
            grid=(b, heads // hg, nq, nk),
            in_specs=[len_spec, q_at_i, k_at_j, k_at_j, q_at_i, q_at_i,
                      row_at_i],
            out_specs=q_at_i,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((hg, bq, d), jnp.float32)],
            compiler_params=params,
            interpret=interpret,
            name="flash_bwd_dq",
        )(lens, q, k, v, g, out, lser)

    # dk/dv grid: kv block before the q block, q innermost
    q_at_i, k_at_j, row_at_i = specs("ji")
    dkv_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if one_program:
        out_specs = [q_at_i, k_at_j, k_at_j]
        out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)] + dkv_shape
        scratch = []
    else:
        out_specs, out_shape = [k_at_j, k_at_j], dkv_shape
        scratch = [pltpu.VMEM((hg, bk, d), jnp.float32)] * 2
    grads = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, with_dq=one_program, **static),
        grid=(b, heads // hg, nk, nq),
        in_specs=[len_spec, q_at_i, k_at_j, k_at_j, q_at_i, q_at_i,
                  row_at_i],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(lens, q, k, v, g, out, lser)
    return (dq, *grads) if dq is not None else tuple(grads)


def flash_attention_bwd_pallas(q, k, v, g, out, lse, kv_len, causal: bool,
                               scale: float, bq: int, bk: int,
                               interpret: bool = False,
                               hg: Optional[int] = None,
                               one_program: Optional[bool] = None):
    """``flash_attention_bwd_lanes`` for (B, H, T, D) arrays (tests; the
    custom VJP calls the lanes form itself, where its transposes cancel
    against the model's own).  ``hg``: ``_train_form``'s when None."""
    from ..ops.attention import _from_lanes, _to_lanes, _train_form

    heads = q.shape[1]
    if hg is None:
        hg = _train_form(heads, q.shape[2], k.shape[2], q.shape[3],
                         _registry.operand_dtype(q, k, v))[0]
    grads = flash_attention_bwd_lanes(
        *(_to_lanes(x) for x in (q, k, v, g, out)), lse, kv_len, heads,
        causal, scale, hg, bq, bk, interpret=interpret,
        one_program=one_program)
    return tuple(_from_lanes(x, heads) for x in grads)
