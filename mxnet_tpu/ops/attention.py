"""Fused attention: Pallas TPU flash-attention kernel + jnp fallback.

Reference counterpart: the interleaved-matmul self-attention helper kernels
in src/operator/contrib/transformer.cc (which fuse QKV projections and
softmax(QK^T)V on GPU). TPU-native redesign: a single blockwise
online-softmax kernel (flash attention) written in Pallas so the whole
score/softmax/weighted-sum pipeline stays in VMEM — O(T) memory instead of
the O(T^2) score matrix, MXU-friendly (bq x d) x (d x bk) tiles.  The
training kernels read and write (B, T, H*d) — the layout a projection
leaves — in a program form chosen from the static shapes (_train_form).

Dispatch rules (mx.kernels registry, docs/kernels.md):
  * kernels active (MXNET_KERNELS: pallas on TPU / interpret anywhere) +
    (no mask or causal/kv_len) + tile-able shapes  -> pallas kernel
  * everything else                                -> attention_reference
    (an observable fallback: kernels.fallbacks + once-per-reason warning)
Backward: when the Pallas forward ran, its saved row lse feeds the Pallas
backward kernels (mxnet_tpu/kernels/flash_bwd.py — dq then dk/dv, blockwise,
no score matrix; one program where one block is the sequence); otherwise a
hand-written blockwise jnp flash backward
(custom VJP) recomputes lse and accumulates dq/dk/dv inside lax.scan.
Either way no O(Tq*Tk) tensor is ever materialized, so training memory
stays O(T) end to end (the eager fallback forward still builds the full
score matrix; the pallas forward + these backwards never do).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels import registry as _kreg

__all__ = ["flash_attention", "attention_reference",
           "flash_attention_decode", "cache_append", "cache_page_copy",
           "quantize_kv", "dequantize_kv"]

_NEG_INF = float("-inf")


def attention_reference(q, k, v, mask=None, scale: Optional[float] = None):
    """Plain softmax attention on (B, H, T, D). ``mask`` is boolean
    broadcastable to (B, H, Tq, Tk): True = attend."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, _NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    if mask is not None:  # fully-masked rows -> zeros, not NaN
        w = jnp.where(jnp.isfinite(logits).any(-1, keepdims=True), w, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v)


def _pick_block(t: int, preferred=(512, 256, 128, 64, 32, 16, 8)) -> int:
    return _kreg.pick_block(t, preferred)


def _kernel_block(t: int) -> int:
    """Sequence-axis block for the Pallas kernels (0 = not tile-able).

    The per-position vectors (row lse/delta, int8 scales) ride as
    lane-major ``(1, 1, block)`` blocks, which Mosaic takes only when the
    block is a multiple of 128 or spans the whole axis — so: the largest
    128-multiple dividing ``t``, else one block over a short axis."""
    b = _pick_block(t, (512, 256, 128))
    if b == 0 and t <= 512 and t % 8 == 0:
        b = t
    return b


# what one training-attention program may hold in VMEM by the reckoning of
# ``_train_form``: Mosaic scopes 16 MiB to a kernel, and the rest is the
# compiler's own temporaries (operand casts, masks, the heads' results
# before they are stored side by side)
_TRAIN_VMEM_BUDGET = 10 << 20


def _train_form(h: int, tq: int, tk: int, d: int, dtype):
    """``(hg, bq, bk)`` of the training-attention programs (forward and
    backward alike), from the static shapes and the operands' dtype: heads
    a program, query rows and key rows a block.  ``hg = 0``: no form fits
    (an ineligible shape, decided before the call).

    The arrays go in as ``(B, T, H*d)`` (``_to_lanes``), so a program's
    block is ``hg * d`` lanes wide: ``hg`` divides ``h`` and either is
    ``h`` or makes whole 128-lane tiles (Mosaic's block rule).  Of those it
    is the largest whose program fits ``_TRAIN_VMEM_BUDGET``, reckoned for
    the backward (the larger of the two): q, k, v, g, out in and dq, dk,
    dv out, double-buffered by the pipeline, and the f32 accumulators of
    dk and dv, for ``hg`` heads; and four f32 ``(bq, bk)`` score tiles
    (s/p, dp, ds and one in flight) for the one head at work.  ``bq``/
    ``bk`` are ``_kernel_block``'s, halved (512, 256, 128) while no head
    group fits — 25 heads of 64 lanes have no divisor but 25, and run as
    128-row blocks.  At BERT-base's ``(12, 128, 128, 64)`` the form is
    every head of a batch row — 128 programs a layer where one head a
    program ran 1 536; at 1 024 rows it is 512-row blocks and 4 heads of
    64 or 2 of 128."""
    item = jnp.dtype(dtype).itemsize
    for cap in (512, 256, 128):
        bq, bk = (b if b % 128 else min(b, cap)
                  for b in (_kernel_block(tq), _kernel_block(tk)))
        head = 2 * (4 * bq + 4 * bk) * d * item + 2 * bk * d * 4
        tiles = 4 * bq * bk * 4
        fits = [g for g in range(1, h + 1)
                if h % g == 0 and (g == h or (g * d) % 128 == 0)
                and g * head + tiles <= _TRAIN_VMEM_BUDGET]
        if fits:
            return max(fits), bq, bk
    return 0, bq, bk


def _to_lanes(x):
    """``(B, H, T, d)`` -> ``(B, T, H*d)``: the layout the training
    kernels read and write — every head of a position side by side in the
    lanes, as a projection leaves them.  ``npx.multi_head_attention``
    makes its ``(B, H, T, d)`` views by the inverse reshape and transpose,
    so under ``jit`` the pair cancels and no head transpose is left in
    the step; a caller that holds real ``(B, H, T, d)`` arrays pays one
    copy an array here (as it paid one for the kernels' tiling before)."""
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _from_lanes(x, h: int):
    b, t, e = x.shape
    return x.reshape(b, t, h, e // h).transpose(0, 2, 1, 3)


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *rest, d: int,
                  scale: float, causal: bool, has_len: bool, bq: int,
                  bk: int, nk: int, with_lse: bool = False):
    """Grid ``(B, H // hg, nq, nk)``: a program holds ``hg`` heads of one
    batch row — ``(1, block, hg * d)`` blocks of the ``(B, T, H*d)``
    arrays, head ``h`` in lanes ``[h*d, (h+1)*d)`` — and walks them with
    one online-softmax step a head, so the mask is built once a program
    and a layer at BERT-base's shape is 128 programs (``_train_form``).

    The step works in the TRANSPOSED ``(bk, bq)`` domain, as the backward
    does: keys ride the sublanes and queries the lanes, so the running
    max, the sum and the row lse are lane-major ``(1, bq)`` rows — reduced
    over sublanes (elementwise across registers), stored as they lie —
    and the accumulator is ``o^T``, turned once a q block.  (Queries on
    the sublanes cost two cross-lane reductions a head and kv block and a
    ``(bq, 128)`` transpose for the lse: twice the kernel's time at
    BERT's shape, PERF.md section 6, PR 38.)"""
    import jax.experimental.pallas as pl

    from ..kernels.flash_bwd import _seen_t

    if with_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        lse_ref, (acc_ref, m_ref, l_ref) = None, rest

    hg = acc_ref.shape[0]
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # hoisted out of _step: program_id inside a pl.when body does not
    # survive interpret mode, and one SMEM read per step is enough
    cur_len = len_ref[pl.program_id(0)] if has_len else None
    operand = _kreg.operand_dtype(q_ref, k_ref, v_ref)

    def _step():
        seen = _seen_t(causal=causal, cur_len=cur_len, i=i, j=j, bq=bq,
                       bk=bk)                      # (bk, bq), every head's
        for h in range(hg):
            lanes = slice(h * d, (h + 1) * d)
            s = jax.lax.dot_general(
                k_ref[0, :, lanes].astype(operand),
                q_ref[0, :, lanes].astype(operand),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (bk, bq)
            if seen is not None:
                s = jnp.where(seen, s, _NEG_INF)
            m_prev = m_ref[h]                      # (1, bq)
            m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
            # fully-masked-so-far rows: keep exp() finite (a masked
            # logit is -inf less a finite number, so its p is 0)
            safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - safe_m)
            corr = jnp.exp(m_prev - safe_m)        # 0 where m_prev = -inf
            l_ref[h] = l_ref[h] * corr + p.sum(axis=0, keepdims=True)
            m_ref[h] = m_new
            pv = jax.lax.dot_general(              # v^T p^T = (p v)^T
                v_ref[0, :, lanes].astype(operand), p.astype(operand),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (d, bq)
            acc_ref[h] = acc_ref[h] * corr + pv

    run = jnp.bool_(True)
    if causal:
        # skip fully-masked kv blocks above the diagonal
        run = jnp.logical_and(run, j * bk <= i * bq + (bq - 1))
    if has_len:
        # skip kv blocks entirely past the row's valid length
        run = jnp.logical_and(run, j * bk < cur_len)
    pl.when(run)(_step)

    @pl.when(j == nk - 1)
    def _finish():
        outs = []
        for h in range(hg):
            l = l_ref[h]
            l = jnp.where(l == 0.0, 1.0, l)
            outs.append((acc_ref[h] / l).T)        # (bq, d)
            if with_lse:
                # row log-sum-exp for the backward kernels; fully-masked
                # rows keep m = -inf so their lse is -inf (bwd: p = 0)
                lse_ref[0, h] = m_ref[h] + jnp.log(l)
        # the heads side by side: one store of whole lane tiles
        o_ref[0] = jnp.concatenate(outs, axis=-1).astype(o_ref.dtype)


# jitted on its own: a model's layers call it with the same shapes, so the
# kernel body (hg heads unrolled) is traced and lowered once a step program
# and not once a layer (5 s of the BERT cell's warm set-up, PERF.md section 6)
@functools.partial(jax.jit, static_argnames=(
    "heads", "causal", "scale", "hg", "bq", "bk", "interpret", "return_lse"))
def _flash_forward_lanes(q, k, v, heads: int, causal: bool, scale: float,
                         hg: int, bq: int, bk: int, kv_len=None,
                         interpret: bool = False, return_lse: bool = False):
    """Flash attention of ``(B, T, H*d)`` arrays (``_to_lanes``) via
    pallas_call; returns ``(B, Tq, H*d)``, or ``(out, lse)`` with the
    (B, H, Tq) f32 row log-sum-exp when ``return_lse=True`` (the residual
    the Pallas backward consumes).  ``kv_len``: optional (B,) int32
    per-row valid key length.  ``hg``/``bq``/``bk``: the form the caller
    chose (``_train_form``)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, e = q.shape
    tk, d = k.shape[1], e // heads
    nq, nk = tq // bq, tk // bk
    has_len = kv_len is not None
    lens = (kv_len.astype(jnp.int32) if has_len
            else jnp.full((b,), tk, jnp.int32))

    kernel = functools.partial(_flash_kernel, d=d, scale=scale,
                               causal=causal, has_len=has_len, bq=bq, bk=bk,
                               nk=nk, with_lse=return_lse)
    q_spec = pl.BlockSpec((1, bq, hg * d), lambda b_, g, i, j: (b_, i, g))
    kv_spec = pl.BlockSpec((1, bk, hg * d), lambda b_, g, i, j: (b_, j, g))
    o_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if return_lse:
        # the per-query rows travel as (B, H, 1, T) with (1, hg, 1, bq)
        # blocks: Mosaic refuses a (1, bq) block over (H, T) (the last two
        # block dims must be (8, 128)-divisible or span the array)
        out_specs = [q_spec, pl.BlockSpec((1, hg, 1, bq),
                                          lambda b_, g, i, j: (b_, g, 0, i))]
        out_shape = [o_shape,
                     jax.ShapeDtypeStruct((b, heads, 1, tq), jnp.float32)]
    else:
        out_specs, out_shape = q_spec, o_shape
    out = pl.pallas_call(
        kernel,
        grid=(b, heads // hg, nq, nk),
        in_specs=[
            # whole (B,) lengths vector in SMEM (SMEM blocks must cover
            # the array); kernel indexes it by program_id(0).  1-D: a
            # (B, 1) block pads every row to 512 B of the chip's 1 MiB
            pl.BlockSpec((b,), lambda b_, g, i, j: (0,),
                         memory_space=pltpu.SMEM),
            q_spec, kv_spec, kv_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        # o^T, and the running max and sum as lane-major rows
        scratch_shapes=[_vmem((hg, d, bq)), _vmem((hg, 1, bq)),
                        _vmem((hg, 1, bq))],
        compiler_params=_train_params(),
        interpret=interpret,
        name="flash_fwd",
    )(lens, q, k, v)
    if return_lse:
        return out[0], out[1].reshape(b, heads, tq)
    return out


def _flash_forward_pallas(q, k, v, causal: bool, scale: float, kv_len=None,
                          interpret: bool = False, return_lse: bool = False,
                          hg: Optional[int] = None):
    """``_flash_forward_lanes`` for (B, H, T, D) arrays, in the form
    ``_train_form`` gives them: returns (B, H, T, D), or ``(out, lse)``.
    ``interpret=True`` runs the kernel under the pallas interpreter on any
    backend — how tests validate the KERNEL itself without a TPU.  ``hg``
    overrides the form's heads a program (tests and measurements walk the
    other divisors)."""
    form = _form_of(q, k, v)
    if hg is not None:
        form["hg"] = hg
    out = _flash_forward_lanes(_to_lanes(q), _to_lanes(k), _to_lanes(v),
                               q.shape[1], causal, scale, kv_len=kv_len,
                               interpret=interpret, return_lse=return_lse,
                               **form)
    if return_lse:
        return _from_lanes(out[0], q.shape[1]), out[1]
    return _from_lanes(out, q.shape[1])


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _tpu_params():
    return _kreg.tpu_compiler_params(("parallel", "parallel", "arbitrary"))


def _train_params():
    return _kreg.tpu_compiler_params(
        ("parallel", "parallel", "parallel", "arbitrary"))


def _select_kernel(q, k, mask):
    """Kernel-mode selection for this call: ``"pallas"``/``"interpret"``
    when the Pallas kernel should run, else None — with every miss
    reported through the kernels registry (mask form, tile-ability)."""
    kmode = _kreg.select("flash_attention")
    if kmode is None:
        return None
    if mask is not None:
        _kreg.fallback("flash_attention", "general boolean mask "
                       "(only causal/kv_valid_length stay on the kernel)")
        return None
    tq, tk, d = q.shape[2], k.shape[2], q.shape[-1]
    if not (_kernel_block(tq) > 0 and _kernel_block(tk) > 0 and d <= 256
            and d % 8 == 0 and _train_form(
                q.shape[1], tq, tk, d, _kreg.operand_dtype(q, k))[0] > 0):
        _kreg.fallback("flash_attention",
                       f"shape not tile-able (tq={tq}, tk={tk}, d={d})")
        return None
    why = _kreg.mesh_ineligible(q.shape[0])
    if why:
        _kreg.fallback("flash_attention", why)
        return None
    return kmode


def _form_of(q, k, v):
    """``_train_form`` of this call, as the attributes its dispatch is
    recorded with (``kernels.dispatch``, ``kernels.form.*``)."""
    hg, bq, bk = _train_form(q.shape[1], q.shape[2], k.shape[2], q.shape[3],
                             _kreg.operand_dtype(q, k, v))
    return {"hg": hg, "bq": bq, "bk": bk}


def _forward_call(q, k, v, kv_len, causal, scale, kmode, return_lse):
    """The Pallas forward as it must be called here: on the ``(B, T,
    H*d)`` views (made out here, where they cancel against the caller's
    own transposes), per batch shard under a traced multi-device mesh
    (kernels/registry.py:batch_mesh)."""
    heads, form = q.shape[1], _form_of(q, k, v)

    def call(q, k, v, kv_len):
        return _flash_forward_lanes(q, k, v, heads, causal, scale,
                                    kv_len=kv_len,
                                    interpret=kmode == "interpret",
                                    return_lse=return_lse, **form)

    out = _kreg.shard_over_batch(call)(_to_lanes(q), _to_lanes(k),
                                       _to_lanes(v), kv_len)
    _kreg.dispatched("flash_attention", kmode, **form)
    if return_lse:
        return _from_lanes(out[0], heads), out[1]
    return _from_lanes(out, heads)


def _merge_mask(mask, kv_len, tq, tk, causal):
    """Combine boolean mask, (B,) kv_len and causal flag into one boolean
    mask (or None). O(B*T + T^2) worst case — fallback path only."""
    m = mask
    if kv_len is not None:
        lm = (jnp.arange(tk)[None, :] < kv_len[:, None])[:, None, None, :]
        m = lm if m is None else jnp.logical_and(m, lm)
    if causal:
        cm = jnp.tril(jnp.ones((tq, tk), bool))[None, None]
        m = cm if m is None else jnp.logical_and(m, cm)
    return m


# ------------------------------------------------------------------ decode
def cache_append(cache, new, lengths, ring: bool = False):
    """Write ``new`` (B, H, T, d) into a fixed-capacity KV cache
    (B, H, C, d) at each row's ``lengths`` offset (B,) — prefill writes
    and per-step appends of the generative decode path share this one
    primitive; ``d`` is whatever the leaf's last axis holds (the
    transformer's payload leaf is K‖V, ``2*dh`` wide; an int8 cache's
    scale leaves are 1 wide).
    Per row: ``cache[b, :, lengths[b]:lengths[b]+T] = new[b]`` via one
    ``lax.dynamic_update_slice`` a row (no concatenate, no realloc — the
    donation-friendly in-place shape).  B small writes in a chain, not
    one vmapped update: that is a scatter, which XLA expands to a
    ``while`` over the rows with its index clamps as fusions of their
    own — 3.0-3.3 ms for the 48 leaves of a GPT-2 XL step at 16 slots
    against 0.82-0.86 ms this way (PERF.md section 6, PR 28).
    The caller guarantees ``lengths + T <= C``; dynamic_update_slice
    CLAMPS an overflowing start, which would silently overwrite the
    newest valid entries, so grow the cache to the next capacity bucket
    before appending.

    ``ring``: the leaf is a ring of ``C`` rows (a window layer's): the
    row of position ``p`` is ``p mod C``, so ``lengths`` may be any
    position and a chunk may wrap.  One token is one write at
    ``lengths mod C``; a chunk that may wrap is two writes of ``T`` rows
    at fixed shapes — the span that ends at the ring's end and the span
    that starts at row 0, each a select between the chunk rolled to its
    place and what the ring held — so no scatter here either."""
    lengths = jnp.asarray(lengths).astype(jnp.int32)
    # the scope rides in every device op's ``op_name``: a device trace
    # says how much of a step the append is (docs/tracing.md)
    with jax.named_scope("cache_append"):
        new = new.astype(cache.dtype)
        c, t = cache.shape[2], new.shape[2]
        if ring:
            if t > c:
                raise ValueError(f"cache_append: a chunk of {t} rows does "
                                 f"not fit a ring of {c}")
            lengths = lengths % c
        for row in range(cache.shape[0]):
            piece, start = new[row:row + 1], lengths[row]
            if ring and t > 1:
                # rows [head, head + T) end at the ring's end when the
                # chunk wraps (then d > 0 of its rows belong at row 0)
                head = jnp.minimum(start, c - t)
                d = start - head
                piece = jnp.roll(piece, d, axis=2)   # [u] = new[(u - d) % T]
                u = jax.lax.broadcasted_iota(jnp.int32, piece.shape, 2)
                held = jax.lax.dynamic_slice(cache, (row, 0, head, 0),
                                             piece.shape)
                cache = jax.lax.dynamic_update_slice(
                    cache, jnp.where(u >= d, piece, held), (row, 0, head, 0))
                piece = jnp.where(u < d, piece, cache[row:row + 1, :, :t])
                start = 0
            cache = jax.lax.dynamic_update_slice(cache, piece,
                                                 (row, 0, start, 0))
    return cache


def quantize_kv(x):
    """Symmetric per-position int8 quantization of K/V rows: ``x``
    (..., dh) float -> ``(q int8 (..., dh), scale f32 (..., 1))`` with
    one scale per dh-wide row — the granularity that keeps the dequant a
    cheap broadcast inside the decode kernel (docs/precision.md, "int8
    KV cache").  The decoder passes the new rows as (B, H, T, 2, dh), so
    K and V of one position each get their own scale, and reshapes the
    payload to the cache's (B, H, T, 2*dh) K‖V form.

    ``scale = amax / 127`` (symmetric, zero-point-free: attention keys
    and values are zero-centered post-projection); an all-zero block
    gets ``scale = 1/127`` so the roundtrip stays exact-zero instead of
    dividing by zero.  Quantize BEFORE :func:`cache_append` — the
    append casts payloads to the cache dtype, and a raw float->int8
    ``astype`` TRUNCATES instead of rounding-to-scale."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0 / 127.0)
    q = jnp.clip(jnp.round(xf / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv`: ``q`` int8 (..., dh) x ``scale``
    f32 (..., 1) -> float (..., dh).  The reference decode path and the
    host-side cache inspectors share this one definition so quantized
    caches round-trip identically everywhere."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def cache_page_copy(dst, src, n_pages: int, *, src_start=0, dst_start=0,
                    dst_row=0):
    """Copy ``n_pages`` consecutive KV-cache pages (capacity-axis rows)
    from ``src`` (B_s, H, C_s, d) into row ``dst_row`` of ``dst``
    (B_d, H, C_d, d) — the device half of a cache redistribution: the
    page window is the box intersection :mod:`~mxnet_tpu.parallel.layout`
    plans host-side, so only intersecting slices ever move.

    ``n_pages`` is STATIC (it is the copy's shape — one executable per
    (C_s, C_d, n) triple); ``src_start``/``dst_start``/``dst_row`` may
    be traced scalars, so one executable serves every slot and offset.
    Built on dynamic_slice + dynamic_update_slice (donation-friendly
    in-place shape, no concatenate — the same rule as
    :func:`cache_append`); both clamp an out-of-range start, so the
    caller guarantees the window fits both capacities."""
    if dst.ndim != 4 or src.ndim != 4:
        raise ValueError(
            f"cache_page_copy moves (B, H, C, d) page layouts, got "
            f"dst.ndim={dst.ndim}, src.ndim={src.ndim}")
    pages = jax.lax.dynamic_slice(
        src, (0, 0, jnp.asarray(src_start, jnp.int32), 0),
        (src.shape[0], src.shape[1], int(n_pages), src.shape[3]))
    return jax.lax.dynamic_update_slice(
        dst, pages.astype(dst.dtype),
        (jnp.asarray(dst_row, jnp.int32), 0,
         jnp.asarray(dst_start, jnp.int32), 0))


def _ring_rows(newest, c):
    """``(base, w)`` of a ring of ``c`` rows whose newest position is
    ``newest``: row ``r`` holds position ``base + r`` up to row ``w`` and
    ``base + r - c`` (the lap before; negative = never written) past it."""
    w = newest % c
    return newest - w, w


def _decode_mask(cache_len, tq, tk, window=None):
    """(B, 1, Tq, Tk) boolean chunk-causal cache mask: local query ``i``
    (appended at global position ``cache_len + i``) attends cache
    positions ``<= cache_len + i`` — with ``window``, only the last
    ``window`` of them, on a ring of ``tk`` rows.  Fallback path only —
    O(B*Tq*Tk)."""
    qpos = cache_len[:, None, None] + jnp.arange(tq, dtype=jnp.int32)[
        None, :, None]
    kpos = jnp.arange(tk, dtype=jnp.int32)[None, None, :]
    if window is not None:
        base, w = _ring_rows(cache_len + (tq - 1), tk)
        kpos = base[:, None, None] + kpos \
            - jnp.where(kpos > w[:, None, None], tk, 0)
        return ((kpos <= qpos) & (kpos > qpos - window) & (kpos >= 0))[:, None]
    return (kpos <= qpos)[:, None]


# a step-form program holds a group of heads' kv blocks twice (the pipeline
# double-buffers); this much a buffer leaves room for the f32 intermediates
# under Mosaic's 16 MiB of scoped VMEM
_STEP_KV_BLOCK_BYTES = 4 << 20


def _decode_form(h: int, tq: int, c: int, d2: int, kv_dtype):
    """``(hg, bq, bk)`` of the decode-attention program, from the static
    query length and the leaf: heads a program, padded query rows, cache
    rows a kv block.

    **Step form** (``tq <= 8``: the queries fit one sublane tile): one
    program holds every head of a slot (the largest divisor of ``h`` whose
    kv block fits ``_STEP_KV_BLOCK_BYTES``), so a layer at 16 slots runs
    16 x nk programs for 400 x nk; and the kv block is short (256 rows),
    because with the block skip a slot fetches ``ceil(live / bk) * bk``
    rows.  **Chunk form** (a prefill chunk): one head a program and the
    largest block, as the products are real matrix products there."""
    if tq > 8:
        return 1, -(-tq // 8) * 8, _kernel_block(c)
    bk = _pick_block(c, (256, 128)) or _kernel_block(c)
    # an int8 block is dequantized to f32 in the program: budgeted as such
    width = 4 if kv_dtype == jnp.int8 else jnp.dtype(kv_dtype).itemsize
    hg = max(g for g in range(1, h + 1)
             if h % g == 0 and (g == 1 or g * bk * d2 * width
                                <= _STEP_KV_BLOCK_BYTES))
    return hg, 8, bk


def _ring_blocks(cur_len, tq: int, window: int, c: int, bk: int):
    """Which kv blocks of a window layer's ring a chunk of ``tq`` queries
    appended at ``cur_len`` can see: the rows of positions ``(cur_len -
    window, cur_len + tq)`` lie in blocks ``a .. b``, round the ring's end
    when ``wrapped`` (then ``a >= b`` and the blocks between are dead).
    Scalars, for the kernel's skip and the kv ``index_map`` alike."""
    _, w = _ring_rows(cur_len + (tq - 1), c)
    lo_row = jnp.maximum(cur_len - (window - 1), 0) % c
    return lo_row // bk, w // bk, lo_row > w


def _ring_block_live(j, a, b, wrapped):
    return jnp.where(wrapped, (j <= b) | (j >= a), (j >= a) & (j <= b))


def _decode_kernel(len_ref, *refs, scale: float, tq: int, bk: int, nk: int,
                   with_lse: bool = False, quantized: bool = False,
                   grouped: bool = False, window: Optional[int] = None):
    """Flash attention of one (padded) query chunk against a packed KV
    cache: grid ``(B, H // hg, nk)`` — a program holds ``hg`` heads of one
    slot, kv blocks stream past it with the same online softmax as
    ``_flash_kernel``, batched over the head axis.  Both forms of
    ``_decode_form`` are this one body; they differ in ``hg``, the padded
    query rows and ``bk``.

    ``len_ref`` is the scalar-prefetched ``(B,)`` cache length; the causal
    rule is the chunk-offset one: ``kpos <= cache_len + qidx``.  Blocks
    wholly past ``cache_len + tq - 1`` are skipped here, and the kv
    ``index_map`` (``_decode_forward_pallas``) holds their block index at
    the slot's last live block, so they cost no DMA either.

    A kv block is ``(hg, bk, 2*dh)``: K in lanes ``[0, dh)``, V in
    ``[dh, 2*dh)`` of every position.  No lane is shuffled: ``q`` comes
    zero-padded to ``2*dh``, so ``q_pad . kv^T`` IS ``q . k^T``; ``p . kv``
    accumulates at the full width and ``_finish`` takes the V half once.

    Operands go into both products in the dtype they have (the wider of
    ``q``'s and the leaf's: bf16 x bf16 for a bf16 model, one MXU pass
    where an f32 product is emulated with several), accumulated in f32;
    scale, mask, running max, sum and the output accumulator are f32.

    ``quantized``: the kv block is int8 with per-position f32 scale
    blocks (``(hg, 1, bk)``, one for K and one for V) riding alongside —
    dequant happens HERE, per streamed kv block, so the cache stays int8
    in HBM end to end (the whole point of the precision ladder's decode
    half); its operands are f32.

    ``grouped``: the query rows of a program are the query HEADS that
    share its KV head (grouped-query attention, one token each), not a
    chunk's positions: every row sits at ``cache_len``.  ``window``: the
    leaf is a ring of ``nk * bk`` rows; a key is visible while it is
    among the last ``window`` positions of its query, and a block with no
    such row is neither computed nor fetched (``_ring_blocks``)."""
    import jax.experimental.pallas as pl

    if quantized:
        q_ref, kv_ref, ks_ref, vs_ref, o_ref, *rest = refs
    else:
        q_ref, kv_ref, o_ref, *rest = refs
        ks_ref = vs_ref = None
    if with_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        lse_ref, (acc_ref, m_ref, l_ref) = None, rest

    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cur_len = len_ref[pl.program_id(0)]
    bq = q_ref.shape[2]
    operand = (jnp.float32 if quantized
               else jnp.promote_types(q_ref.dtype, kv_ref.dtype))
    if window is not None:
        base, newest_row = _ring_rows(cur_len + (tq - 1), nk * bk)

    def _step():
        q = q_ref[0].astype(operand)               # (hg, bq, 2*dh), V half 0
        kv = kv_ref[0].astype(operand)             # (hg, bk, 2*dh)
        s = jax.lax.dot_general(
            q, kv, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # (hg, bq, bk)
        if quantized:
            # per-position scales ride as lane-major (1, bk) rows, so the
            # dequant folds into the logits (and into p below) as a
            # sublane broadcast instead of a (bk, 1) column transpose
            s = s * ks_ref[0]
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        qpos = cur_len if grouped else \
            cur_len + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        if window is None:
            seen = kpos <= qpos
        else:
            kpos = base + kpos - jnp.where(kpos > newest_row, nk * bk, 0)
            seen = (kpos <= qpos) & (kpos > qpos - window) & (kpos >= 0)
        s = jnp.where(seen[None], s, _NEG_INF)
        m_prev = m_ref[:, :, :1]
        cur = s.max(axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, cur)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(jnp.where(jnp.isfinite(s), s - safe_m, _NEG_INF))
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
        l_new = l_ref[:, :, :1] * corr + p.sum(axis=-1, keepdims=True)
        p = p * vs_ref[0] if quantized else p.astype(kv.dtype)
        pv = jax.lax.dot_general(
            p, kv, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)    # (hg, bq, 2*dh)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # the last key a real query may attend sits at cache_len+tq-1; kv
    # blocks wholly past it are skipped (and were not fetched) — the
    # kv_len block-skip machinery of _flash_kernel with the chunk offset
    # folded in; a ring's live rows have a lower end too
    if window is None:
        pl.when(j * bk < cur_len + tq)(_step)
    else:
        pl.when(_ring_block_live(
            j, *_ring_blocks(cur_len, tq, window, nk * bk, bk)))(_step)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[:, :, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        dh = o_ref.shape[-1]
        # the K half of the accumulator (p . k) is never read
        o_ref[0] = (acc_ref[:, :, dh:] / l).astype(o_ref.dtype)
        if with_lse:
            # lane-broadcast, as m and l lie; the caller takes lane 0
            lse_ref[0] = m_ref[...] + jnp.log(l)


def _decode_forward_pallas(q, kv, cache_len, scale: float,
                           interpret: bool = False,
                           return_lse: bool = False,
                           k_scale=None, v_scale=None,
                           window: Optional[int] = None):
    """(B, Hq, Tq, dh) x (B, Hkv, C, 2*dh) packed-cache decode attention
    via pallas_call, in the form ``_decode_form`` picks from the static
    ``Tq`` and the leaf.  The leaf goes in as it lies (4-D, no reshape); Tq is
    padded up to the sublane tile (the padded query rows compute garbage
    that is sliced off before returning) and the head axis with zeros up
    to the leaf's ``2*dh`` (``_decode_kernel``).  ``cache_len`` rides as
    scalar prefetch, so the kv ``index_map`` can hold a block past the
    slot's live rows at the last live block's index: the pipeline sees the
    index it already has and issues no DMA.  With ``k_scale``/``v_scale``
    (B, H, C, 1) the cache is int8 and the scales stream as ``(1, bk)``
    f32 blocks next to their kv blocks.

    ``Hq = g * Hkv``: query head ``h`` reads KV head ``h // g``.  One token
    a slot (``Tq == 1``, ``g > 1``) takes the step form with the group's
    ``g`` heads as the program's query rows, so a KV head's block is
    fetched once for all of them; a chunk takes one query head a program
    and maps it to its KV head."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    quantized = k_scale is not None
    b, hq, tq, d = q.shape
    hkv, c, d2 = kv.shape[1:]
    g = hq // hkv
    grouped = g > 1 and tq == 1
    if grouped:
        q = q.reshape(b, hkv, g, d)        # a KV head's heads are its rows
    h, rows = q.shape[1:3]
    # a chunk under grouped-query attention: one query head a program
    per_head = g > 1 and not grouped
    hg, bq, bk = _decode_form(1 if per_head else h, rows, c, d2, kv.dtype)
    nk = c // bk
    q = jnp.pad(q, ((0, 0), (0, 0), (0, bq - rows), (0, d2 - d)))

    if window is None:
        def live(j, lens, b_):
            return jnp.minimum(
                j, jnp.minimum(lens[b_] + (tq - 1), c - 1) // bk)
    else:
        def live(j, lens, b_):
            # a dead block holds the index of the live one before it
            lo, hi, wrapped = _ring_blocks(lens[b_], tq, window, c, bk)
            return jnp.where(wrapped,
                             jnp.where((j <= hi) | (j >= lo), j, hi),
                             jnp.clip(j, lo, hi))

    def head_map(b_, g_, j, lens):
        return (b_, g_, 0, 0)

    def kv_head(g_):
        return g_ // g if per_head else g_

    kernel = functools.partial(_decode_kernel, scale=scale, tq=tq, bk=bk,
                               nk=nk, with_lse=return_lse,
                               quantized=quantized, grouped=grouped,
                               window=window)
    out_specs = [pl.BlockSpec((1, hg, bq, d), head_map)]
    out_shape = [jax.ShapeDtypeStruct((b, h, bq, d), q.dtype)]
    if return_lse:
        out_specs.append(pl.BlockSpec((1, hg, bq, 128), head_map))
        out_shape.append(jax.ShapeDtypeStruct((b, h, bq, 128), jnp.float32))
    in_specs = [
        pl.BlockSpec((1, hg, bq, d2), head_map),
        pl.BlockSpec((1, hg, bk, d2),
                     lambda b_, g_, j, lens: (b_, kv_head(g_),
                                              live(j, lens, b_), 0)),
    ]
    operands = [q, kv]
    if quantized:
        sc_spec = pl.BlockSpec(
            (1, hg, 1, bk),
            lambda b_, g_, j, lens: (b_, kv_head(g_), 0, live(j, lens, b_)))
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale.astype(jnp.float32).reshape(b, hkv, 1, c),
                     v_scale.astype(jnp.float32).reshape(b, hkv, 1, c)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // hg, nk),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[_vmem((hg, bq, d2)), _vmem((hg, bq, 128)),
                            _vmem((hg, bq, 128))]),
        out_shape=out_shape,
        compiler_params=_tpu_params(),
        interpret=interpret,
        name="flash_decode",
    )(cache_len.astype(jnp.int32), *operands)
    o = out[0][:, :, :rows].reshape(b, hq, tq, d)
    if return_lse:
        return o, out[1][:, :, :rows, 0].reshape(b, hq, tq)
    return o


def _select_decode_kernel(q, kv):
    kmode = _kreg.select("flash_attention_decode")
    if kmode is None:
        return None
    tq, c, d = q.shape[2], kv.shape[2], q.shape[-1]
    if not (_kernel_block(c) > 0 and tq <= 512 and d <= 256 and d % 8 == 0):
        _kreg.fallback("flash_attention_decode",
                       f"shape not tile-able (tq={tq}, cache={c}, d={d})")
        return None
    why = _kreg.mesh_ineligible(None)     # serving runs on one device
    if why:
        _kreg.fallback("flash_attention_decode", why)
        return None
    return kmode


def flash_attention_decode(q, kv, cache_len, scale: Optional[float] = None,
                           return_lse: bool = False,
                           k_scale=None, v_scale=None,
                           window: Optional[int] = None):
    """Decode-mode attention: ``Tq`` freshly appended queries against a
    fixed-capacity KV cache (the generative hot path, docs/serving.md).

    q: (B, Hq, Tq, dh) — Tq = 1 (single decode step) or a small prefill
        chunk.
    kv: (B, Hkv, C, 2*dh) — the packed cache leaf, ``Hq = g * Hkv``
        (grouped-query attention: query head ``h`` reads KV head
        ``h // g``; ``g = 1`` is plain multi-head attention), K in
        ``[..., :dh]`` and
        V in ``[..., dh:]`` of every position, which ALREADY contains the
        chunk's own keys/values (append via :func:`cache_append` first).
        One leaf whose last axis fills whole 128-lane tiles at dh 64 has
        one natural layout in HBM, the same for XLA's in-place append
        and for the kernel — a (B, H, C, 64) leaf has two, and is
        re-laid-out between them on every step (PERF.md section 5).
    cache_len: (B,) int — valid cache entries BEFORE this chunk was
        appended.  Local query ``i`` sits at global position
        ``cache_len + i`` and attends cache positions ``<= cache_len + i``
        — for Tq=1 exactly ``kpos <= cache_len``, and garbage cache rows
        at and past ``cache_len + Tq`` are never attended (they are
        overwritten by later appends).  A row with ``cache_len + Tq``
        past the capacity must be grown first (see :func:`cache_append`).
    return_lse: also return the (B, H, Tq) f32 row log-sum-exp (same
        plumbing as the training kernel's residual).
    k_scale/v_scale: per-position f32 scales (B, Hkv, C, 1) of an int8
        kv leaf (:func:`quantize_kv`, K and V halves each by their own
        amax) — dequant runs inside the kernel per streamed block, so
        HBM holds int8 end to end (~4x smaller pages;
        docs/precision.md).  Pass both or neither.
    window: a query attends only the last ``window`` positions up to its
        own, and the leaf is a RING of ``C`` rows: position ``p`` lies in
        row ``p mod C`` (``cache_append(..., ring=True)``), so
        ``cache_len`` is not bounded by ``C``.  ``C >= window + Tq - 1``:
        the chunk's last row must not overwrite what its first query
        still sees.

    The kernel runs in one of two program forms, picked from the static
    ``Tq`` and the leaf (``_decode_form``; no flag): the **step form**
    (``Tq <= 8``) — a program per slot over all of its heads, 256-row kv
    blocks — and the **chunk form** (a prefill chunk) — a program per
    head, the largest block.  In both a kv block past a slot's live rows
    costs neither arithmetic nor DMA (its block index is held at the
    last live block through the scalar-prefetched ``cache_len``; with a
    ``window`` the live rows have a lower end as well, and a block wholly
    before it is skipped the same way), and
    both products take ``q`` and the leaf in the wider of their dtypes
    (bf16 x bf16 for a bf16 model, f32 for an f32 leaf; the int8 leaf
    dequantized to f32) with f32 accumulation.  ``return_lse`` and the
    int8 leaf ride the same two forms.  Under grouped-query attention the
    step form's query rows are the ``g`` heads of one KV head, whose
    block is fetched once for all of them; a chunk runs one query head a
    program.

    Rows may be inert (a freed serve slot): ``cache_len = 0`` with a
    dummy token attends only itself — finite output, no NaN.  No custom
    VJP: decode is inference-only; gradients fall to jax's autodiff of
    the reference path."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError("flash_attention_decode: pass both k_scale and "
                         "v_scale (quantized cache) or neither")
    dh = q.shape[-1]
    if kv.shape[-1] != 2 * dh:
        raise ValueError(
            f"flash_attention_decode: kv leaf {tuple(kv.shape)} is not "
            f"K‖V for head size {dh} (last axis must be {2 * dh})")
    hq, tq, hkv, c = q.shape[1], q.shape[2], kv.shape[1], kv.shape[2]
    if hq % hkv:
        raise ValueError(
            f"flash_attention_decode: {hq} query heads are no multiple of "
            f"the leaf's {hkv} KV heads")
    if window is not None and c < window + tq - 1:
        raise ValueError(
            f"flash_attention_decode: a ring of {c} rows cannot hold a "
            f"window of {window} beside a chunk of {tq} queries")
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    cache_len = jnp.asarray(cache_len).astype(jnp.int32)
    kmode = _select_decode_kernel(q, kv)
    if kmode:
        # selected by mode and shape: a kernel that then fails RAISES —
        # it never turns into the O(Tq*C) reference path behind the
        # caller's back
        out = _decode_forward_pallas(q, kv, cache_len, float(scale),
                                     interpret=kmode == "interpret",
                                     return_lse=return_lse,
                                     k_scale=k_scale, v_scale=v_scale,
                                     window=window)
        _kreg.dispatched("flash_attention_decode", kmode)
        return out
    k, v = kv[..., :dh], kv[..., dh:]
    if k_scale is not None:
        k = dequantize_kv(k, k_scale, dtype=q.dtype)
        v = dequantize_kv(v, v_scale, dtype=q.dtype)
    m = _decode_mask(cache_len, tq, c, window)
    b, g = q.shape[0], hq // hkv
    if g > 1:       # a KV head's g query heads as g x Tq rows of one head
        q = q.reshape(b, hkv, g * tq, dh)
        m = jnp.tile(m, (1, 1, g, 1))
    out = attention_reference(q, k, v, mask=m, scale=scale)
    out = out.reshape(b, hq, tq, dh)
    if return_lse:
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k
                            ).astype(jnp.float32) * scale
        logits = jnp.where(m, logits, _NEG_INF)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return out, lse.reshape(b, hq, tq)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash(q, k, v, mask, kv_len, causal: bool, scale: float):
    kmode = _select_kernel(q, k, mask)
    if kmode:
        return _forward_call(q, k, v, kv_len, causal, scale, kmode, False)
    m = _merge_mask(mask, kv_len, q.shape[2], k.shape[2], causal)
    return attention_reference(q, k, v, mask=m, scale=scale)


def _flash_fwd(q, k, v, mask, kv_len, causal, scale):
    kmode = _select_kernel(q, k, mask)
    if kmode:
        # the kernel saves the row lse — the residual that lets the
        # backward run as Pallas kernels instead of the jnp recompute
        out, lse = _forward_call(q, k, v, kv_len, causal, scale, kmode, True)
        return out, (q, k, v, mask, kv_len, out, lse)
    m = _merge_mask(mask, kv_len, q.shape[2], k.shape[2], causal)
    out = attention_reference(q, k, v, mask=m, scale=scale)
    return out, (q, k, v, mask, kv_len, out, None)


def _mask_block(mask, qi, kj, bq, bk):
    """Slice a (B,H?,Tq?,Tk?) broadcastable mask to the (qi,kj) block."""
    if mask is None:
        return None
    mq = (jax.lax.dynamic_slice_in_dim(mask, qi * bq, bq, axis=2)
          if mask.shape[2] != 1 else mask)
    return (jax.lax.dynamic_slice_in_dim(mq, kj * bk, bk, axis=3)
            if mask.shape[3] != 1 else mq)


def _block_logits(q_blk, k_blk, scale, causal, qi, kj, bq, bk, mask):
    """(B,H,bq,bk) masked logits for block pair (qi, kj)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk).astype(jnp.float32) * scale
    if causal:
        qpos = qi * bq + jnp.arange(bq)
        kpos = kj * bk + jnp.arange(bk)
        s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None], s, _NEG_INF)
    mb = _mask_block(mask, qi, kj, bq, bk)
    if mb is not None:
        s = jnp.where(mb, s, _NEG_INF)
    return s


def _flash_bwd(causal, scale, res, g):
    """Blockwise flash-attention backward: O(T) memory, two routes.

    When the Pallas forward ran (residual carries its row ``lse``), the
    gradient runs the Pallas backward kernels (kernels/flash_bwd.py) on
    the same blocks — dq then dk/dv, score matrix never materialized.
    Otherwise (reference forward, or kernels disabled between fwd and
    bwd) the jnp route below recomputes lse blockwise and accumulates
    dq/dk/dv inside lax.scan:
      D_i  = sum(g_i * out_i)
      p_ij = exp(s_ij - lse_i)
      ds   = p * (g @ v^T - D)
      dq_i = sum_j ds @ k_j * scale ; dk_j = sum_i ds^T @ q_i * scale
      dv_j = sum_i p^T @ g_i
    Only O(T)-sized tensors cross scan steps — never the full (Tq, Tk)
    score matrix."""
    q, k, v, mask, kv_len, out, lse = res
    if lse is not None:
        kmode = _kreg.select("flash_attention_bwd")
        if kmode:
            from ..kernels.flash_bwd import flash_attention_bwd_lanes

            # the forward ran the kernel, so the traced mesh (if any)
            # already passed mesh_ineligible: same per-shard wrap
            form, heads = _form_of(q, k, v), q.shape[1]
            grads = _kreg.shard_over_batch(functools.partial(
                flash_attention_bwd_lanes, heads=heads, causal=causal,
                scale=scale, interpret=kmode == "interpret", **form))(
                    *(_to_lanes(x) for x in (q, k, v, g, out)), lse, kv_len)
            _kreg.dispatched(
                "flash_attention_bwd", kmode, **form,
                one_program=q.shape[2] == form["bq"]
                and k.shape[2] == form["bk"])
            return (*(_from_lanes(x, heads) for x in grads), None, None)
        # select() reported any platform miss; mode "off" between forward
        # and backward degrades silently to the jnp route below
    b, h, tq, d = q.shape
    tk = k.shape[2]
    if kv_len is not None:
        lm = (jnp.arange(tk)[None, :] < kv_len[:, None])[:, None, None, :]
        mask = lm if mask is None else jnp.logical_and(mask, lm)
    bq = _pick_block(tq, (256, 128, 64, 32, 16, 8, 4, 2, 1))
    bk = _pick_block(tk, (256, 128, 64, 32, 16, 8, 4, 2, 1))
    nq, nk = tq // bq, tk // bk

    if mask is not None:  # normalize to 4-D for block slicing
        mask = mask.reshape((1,) * (4 - mask.ndim) + mask.shape)

    def blk(x, i, bsz):
        return jax.lax.dynamic_slice_in_dim(x, i * bsz, bsz, axis=2)

    # ---- pass 1: row lse, blockwise over kv ------------------------------
    def lse_row(qi):
        q_blk = blk(q, qi, bq).astype(jnp.float32)

        def body(carry, kj):
            m_run, l_run = carry
            s = _block_logits(q_blk, blk(k, kj, bk).astype(jnp.float32),
                              scale, causal, qi, kj, bq, bk, mask)
            m_new = jnp.maximum(m_run, s.max(-1))
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe[..., None]), 0.0)
            corr = jnp.where(jnp.isfinite(m_run),
                             jnp.exp(m_run - safe), 0.0)
            return (m_new, l_run * corr + p.sum(-1)), None

        m0 = jnp.full((b, h, bq), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, bq), jnp.float32)
        (m_f, l_f), _ = jax.lax.scan(body, (m0, l0), jnp.arange(nk))
        return m_f + jnp.log(jnp.where(l_f == 0.0, 1.0, l_f))

    _, lse = jax.lax.scan(lambda c, qi: (c, lse_row(qi)), 0, jnp.arange(nq))
    lse = lse.transpose(1, 2, 0, 3).reshape(b, h, tq)       # (B,H,Tq)

    gf = g.astype(jnp.float32)
    delta = (gf * out.astype(jnp.float32)).sum(-1)          # (B,H,Tq)

    # ---- pass 2: dq (outer q blocks, inner kv blocks) --------------------
    def dq_row(qi):
        q_blk = blk(q, qi, bq).astype(jnp.float32)
        g_blk = blk(gf, qi, bq)
        lse_blk = blk(lse.reshape(b, h, tq, 1), qi, bq)[..., 0]
        d_blk = blk(delta.reshape(b, h, tq, 1), qi, bq)[..., 0]

        def body(acc, kj):
            k_blk = blk(k, kj, bk).astype(jnp.float32)
            v_blk = blk(v, kj, bk).astype(jnp.float32)
            s = _block_logits(q_blk, k_blk, scale, causal, qi, kj, bq, bk,
                              mask)
            p = jnp.where(jnp.isfinite(s),
                          jnp.exp(s - lse_blk[..., None]), 0.0)
            dp = jnp.einsum("bhqd,bhkd->bhqk", g_blk, v_blk)
            ds = p * (dp - d_blk[..., None])
            return acc + jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk) * scale, None

        acc0 = jnp.zeros((b, h, bq, d), jnp.float32)
        dq_blk, _ = jax.lax.scan(body, acc0, jnp.arange(nk))
        return dq_blk

    _, dq_blocks = jax.lax.scan(lambda c, qi: (c, dq_row(qi)), 0,
                                jnp.arange(nq))
    dq = dq_blocks.transpose(1, 2, 0, 3, 4).reshape(b, h, tq, d)

    # ---- pass 3: dk/dv (outer kv blocks, inner q blocks) -----------------
    def dkv_col(kj):
        k_blk = blk(k, kj, bk).astype(jnp.float32)
        v_blk = blk(v, kj, bk).astype(jnp.float32)

        def body(carry, qi):
            dk_acc, dv_acc = carry
            q_blk = blk(q, qi, bq).astype(jnp.float32)
            g_blk = blk(gf, qi, bq)
            lse_blk = blk(lse.reshape(b, h, tq, 1), qi, bq)[..., 0]
            d_blk = blk(delta.reshape(b, h, tq, 1), qi, bq)[..., 0]
            s = _block_logits(q_blk, k_blk, scale, causal, qi, kj, bq, bk,
                              mask)
            p = jnp.where(jnp.isfinite(s),
                          jnp.exp(s - lse_blk[..., None]), 0.0)
            dp = jnp.einsum("bhqd,bhkd->bhqk", g_blk, v_blk)
            ds = p * (dp - d_blk[..., None])
            dk_acc = dk_acc + jnp.einsum("bhqk,bhqd->bhkd", ds, q_blk) * scale
            dv_acc = dv_acc + jnp.einsum("bhqk,bhqd->bhkd", p, g_blk)
            return (dk_acc, dv_acc), None

        z = jnp.zeros((b, h, bk, d), jnp.float32)
        (dk_blk, dv_blk), _ = jax.lax.scan(body, (z, z), jnp.arange(nq))
        return jnp.stack([dk_blk, dv_blk])

    _, dkv = jax.lax.scan(lambda c, kj: (c, dkv_col(kj)), 0, jnp.arange(nk))
    dk = dkv[:, 0].transpose(1, 2, 0, 3, 4).reshape(b, h, tk, d)
    dv = dkv[:, 1].transpose(1, 2, 0, 3, 4).reshape(b, h, tk, d)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, mask=None, causal: bool = False,
                    scale: Optional[float] = None, kv_valid_length=None):
    """Fused multi-head attention on (B, H, T, D) arrays.

    mask: optional boolean, broadcastable to (B, H, Tq, Tk); True = attend
        (general masks run the reference fallback).
    kv_valid_length: optional (B,) int lengths — key positions >= length are
        masked. Unlike ``mask``, this stays on the pallas kernel (the
        standard padded-batch case).
    causal: apply a lower-triangular mask (composable with the others).
    scale: logit scale; defaults to 1/sqrt(D).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if mask is not None and mask.dtype != jnp.bool_:
        mask = mask.astype(bool)
    if kv_valid_length is not None:
        kv_valid_length = kv_valid_length.astype(jnp.int32)
    return _flash(q, k, v, mask, kv_valid_length, bool(causal), float(scale))
