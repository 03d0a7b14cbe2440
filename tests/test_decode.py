"""Generative decode path — KV-cache flash attention + token-level
continuous batching (ISSUE 12).

The load-bearing claims under test: (1) decode-mode flash attention
matches the O(T^2) reference with a materialized chunk-causal mask at
every cache_len block boundary (the classic off-by-one site), on both
the public dispatch and the interpret-mode pallas kernel; (2)
cache_append is bit-exact — a prefill chunk plus N single-token appends
reproduces the one-shot write — and at the model level prefill + decode
steps reproduce the full-sequence forward, padded prompts included;
(3) mx.np.random.categorical is deterministic under a fixed key,
greedy at temperature<=0, top-k-restricted, and jit-safe; (4)
ModelEntry.slice_out cuts output axes by batch-level facts only, so a
boundary request (true size == bucket) gets the same rule as its
batch-mates; (5) hybridize(donate_args=...) maps block arg positions to
flat jit leaf indices, is dropped for training and for armed-cache-on-
CPU, and actually invalidates the donated buffers; (6) the decode
server adds zero compiles after registration warmup across capacity
growth and varying occupancy, batch-mates generate independently
(greedy output == the eager one-row reference), truncation at the last
capacity bucket is reported, sampling is deterministic under a fixed
seed, and the per-token telemetry rows land.
"""
from __future__ import annotations

import functools
import time

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import block as gblock
from mxnet_tpu.gluon.model_zoo import lstm_lm, transformer_lm
from mxnet_tpu.jit import ShapeBucketer
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.numpy import random as mrng
from mxnet_tpu.ops import attention as att
from mxnet_tpu.serve import ClosedError
from mxnet_tpu.serve.registry import ModelEntry


@pytest.fixture()
def fresh_telemetry():
    prev = tel.set_enabled(True)
    tel.reset()
    yield
    tel.reset()
    tel.set_enabled(prev)


def _nd_i32(a) -> NDArray:
    return NDArray(jnp.asarray(a, jnp.int32))


# ------------------------------------------------- decode attention parity
def _decode_reference(q, k, v, cache_len):
    """O(T^2) reference with the chunk-causal mask materialized
    independently of the code under test: local query i attends cache
    positions <= cache_len + i."""
    tq, c = q.shape[2], k.shape[2]
    qidx = jnp.arange(tq, dtype=jnp.int32)
    kpos = jnp.arange(c, dtype=jnp.int32)
    mask = kpos[None, None, None, :] <= (
        cache_len.astype(jnp.int32)[:, None, None, None] +
        qidx[None, None, :, None])
    return att.attention_reference(q, k, v, mask=mask)


def _pack(k, v):
    """The cache's storage form: one leaf, K‖V on the last axis."""
    return jnp.concatenate([k, v], axis=-1)


def _boundaries(c, tq):
    """cache_len values at kv-block edges (the off-by-one sites) plus
    the extremes."""
    bk = att._kernel_block(c)
    cand = {0, 1, bk - 1, bk, bk + 1, c - tq - 1, c - tq}
    return sorted(x for x in cand if 0 <= x <= c - tq)


def _assert_decode_parity(b, h, d, c, tq, seed):
    rs = onp.random.RandomState(seed)
    q = jnp.asarray((rs.rand(b, h, tq, d) - 0.5).astype("float32"))
    k = jnp.asarray((rs.rand(b, h, c, d) - 0.5).astype("float32"))
    v = jnp.asarray((rs.rand(b, h, c, d) - 0.5).astype("float32"))
    kv = _pack(k, v)
    scale = 1.0 / d ** 0.5
    for lo in _boundaries(c, tq):
        # rows get DIFFERENT lengths — per-row masking must not leak
        hi = min(lo + 3, c - tq)
        cache_len = jnp.asarray([lo, hi], jnp.int32)
        want = onp.asarray(_decode_reference(q, k, v, cache_len))
        got = onp.asarray(att.flash_attention_decode(q, kv, cache_len))
        onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                    err_msg=f"dispatch, cache_len={lo}")
        kern = onp.asarray(att._decode_forward_pallas(
            q, kv, cache_len, scale=scale, interpret=True))
        onp.testing.assert_allclose(kern, want, rtol=2e-5, atol=2e-5,
                                    err_msg=f"kernel, cache_len={lo}")
        assert onp.isfinite(got).all()


@pytest.mark.parametrize("c,tq", [(32, 1), (32, 8), (64, 1), (64, 8),
                                  (128, 1),
                                  (384, 1), (384, 8)])   # three kv blocks
def test_decode_attention_parity_at_block_boundaries(c, tq):
    _assert_decode_parity(2, 2, 8, c, tq, seed=c * 10 + tq)


@pytest.mark.parametrize("tq", [1, 8])
@pytest.mark.parametrize("d", [32, 64, 128])
def test_decode_attention_parity_on_the_packed_leaf_by_head_size(d, tq):
    """The leaf is 64, 128 and 256 lanes wide: under, at and over one
    tile.  Two kv blocks, so the block skip and the running softmax work
    across the zero-padded contraction."""
    _assert_decode_parity(2, 1, d, 256, tq, seed=d + tq)


def test_decode_attention_lse_and_packed_leaf_contract():
    b, h, d, c, tq = 2, 2, 8, 32, 4
    rs = onp.random.RandomState(77)
    q = jnp.asarray((rs.rand(b, h, tq, d) - 0.5).astype("float32"))
    k = jnp.asarray((rs.rand(b, h, c, d) - 0.5).astype("float32"))
    v = jnp.asarray((rs.rand(b, h, c, d) - 0.5).astype("float32"))
    cache_len = jnp.asarray([3, 17], jnp.int32)
    ref_out, ref_lse = att.flash_attention_decode(
        q, _pack(k, v), cache_len, return_lse=True)
    out, lse = att._decode_forward_pallas(
        q, _pack(k, v), cache_len, scale=1.0 / d ** 0.5, interpret=True,
        return_lse=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref_out),
                                rtol=2e-5, atol=2e-5)
    onp.testing.assert_allclose(onp.asarray(lse), onp.asarray(ref_lse),
                                rtol=2e-5, atol=2e-5)
    # a leaf that is not K‖V for this head size is refused by name
    with pytest.raises(ValueError, match="K‖V"):
        att.flash_attention_decode(q, k, cache_len)


def test_decode_attention_inert_row_is_finite():
    # a freed serve slot: cache_len=0, garbage cache — the fresh token
    # attends only itself, output finite (no NaN poisoning the batch)
    b, h, d, c = 1, 2, 8, 32
    rs = onp.random.RandomState(0)
    q = jnp.asarray(rs.rand(b, h, 1, d).astype("float32"))
    k = jnp.full((b, h, c, d), onp.nan, jnp.float32)
    k = k.at[:, :, 0].set(jnp.asarray(rs.rand(b, h, d), jnp.float32))
    v = jnp.asarray(rs.rand(b, h, c, d).astype("float32"))
    for out in (att.flash_attention_decode(
                    q, _pack(k, v), jnp.zeros((b,), jnp.int32)),
                # the kernel's K half of the accumulator goes NaN here
                # and must never reach the output
                att._decode_forward_pallas(
                    q, _pack(k, v), jnp.zeros((b,), jnp.int32),
                    scale=1.0 / d ** 0.5, interpret=True)):
        out = onp.asarray(out)
        assert onp.isfinite(out).all()
        # with cache_len=0 and tq=1 the result IS row 0's value
        onp.testing.assert_allclose(out[:, :, 0], onp.asarray(v[:, :, 0]),
                                    rtol=1e-6, atol=1e-6)


# ------------------------------------------------ the two program forms
_FORM_H, _FORM_C, _FORM_D = 25, 768, 16     # 25 heads: no multiple of 8


def _form_case(tq, dtype, seed):
    """Six slots in ONE call: an inert row, the three lengths around the
    first kv block's edge, the last row the capacity allows and a short
    one — blocks wholly past a slot's live rows are never fetched, and
    their stale contents here are NaN."""
    h, c, d = _FORM_H, _FORM_C, _FORM_D
    hg, bq, bk = att._decode_form(h, tq, c, 2 * d, dtype)
    lens = onp.asarray([0, bk - 1, bk, bk + 1, c - tq, 37], "int32")
    b = len(lens)
    rs = onp.random.RandomState(seed)
    q = (rs.rand(b, h, tq, d) - 0.5).astype("float32")
    k = (rs.rand(b, h, c, d) - 0.5).astype("float32")
    v = (rs.rand(b, h, c, d) - 0.5).astype("float32")
    for row, n in enumerate(lens):
        dead = -(-(n + tq) // bk) * bk      # first row of the first block
        k[row, :, dead:] = onp.nan          # no query of this slot needs
        v[row, :, dead:] = onp.nan
    q, k, v = (jnp.asarray(a).astype(dtype) for a in (q, k, v))
    return q, k, v, jnp.asarray(lens), (hg, bq, bk)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tq,form", [(1, "step"), (8, "step"),
                                     (9, "chunk"), (128, "chunk")])
def test_decode_kernel_forms_at_block_edges(tq, form, dtype):
    """Step form (every head of a slot in one program, short kv blocks)
    up to 8 queries, chunk form (a head a program) past them: both
    against the materialized mask, operands in the leaf's dtype."""
    q, k, v, lens, (hg, bq, bk) = _form_case(tq, dtype, seed=tq)
    assert (hg, bk) == ((_FORM_H, 256) if form == "step" else (1, 256))
    out, lse = att._decode_forward_pallas(
        q, _pack(k, v), lens, scale=1.0 / _FORM_D ** 0.5, interpret=True,
        return_lse=True)
    assert out.dtype == q.dtype and lse.shape == q.shape[:3]
    # the reference on the same (rounded) values in f32, dead rows zeroed:
    # it multiplies them by a zero weight, the kernel never reads them
    f32 = [jnp.nan_to_num(a.astype(jnp.float32)) for a in (q, k, v)]
    want = onp.asarray(_decode_reference(*f32, lens))
    tol = 2e-5 if dtype == "float32" else 4e-3   # p and out round to bf16
    onp.testing.assert_allclose(onp.asarray(out.astype(jnp.float32)), want,
                                rtol=tol, atol=tol)
    _, want_lse = att.flash_attention_decode(      # the reference path
        f32[0], _pack(f32[1], f32[2]), lens, return_lse=True)
    onp.testing.assert_allclose(onp.asarray(lse), onp.asarray(want_lse),
                                rtol=2e-5, atol=2e-5)


def test_decode_step_form_splits_heads_that_do_not_fit_vmem():
    """A program holds the largest group of heads whose kv block fits the
    budget; the group always divides the head count."""
    assert att._decode_form(25, 1, 1024, 128, jnp.bfloat16) == (25, 8, 256)
    hg, bq, bk = att._decode_form(96, 1, 1024, 256, jnp.float32)
    assert (bq, bk) == (8, 256) and 96 % hg == 0
    assert hg * bk * 256 * 4 <= att._STEP_KV_BLOCK_BYTES < 96 * bk * 256 * 4
    # int8 blocks are dequantized to f32 in the program: budgeted as such
    assert att._decode_form(96, 1, 1024, 256, jnp.int8)[0] == hg
    # short capacities ride one whole-axis block, as before
    assert att._decode_form(12, 1, 40, 128, jnp.bfloat16) == (12, 8, 40)
    assert att._decode_form(12, 9, 1024, 128, jnp.bfloat16) == (1, 16, 512)


@pytest.mark.parametrize("tq", [1, 8, 9])
@pytest.mark.parametrize("case", ["lse", "int8"])
def test_decode_lse_and_int8_are_served_by_the_kernel(fresh_telemetry, tq,
                                                      case):
    """``return_lse`` and the int8 leaf take the same program forms as the
    float cache — a dispatch is counted, a fallback is not."""
    q, k, v, lens, _ = _form_case(tq, "float32", seed=40 + tq)
    k, v = jnp.nan_to_num(k), jnp.nan_to_num(v)
    if case == "int8":
        (kq, ks), (vq, vs) = att.quantize_kv(k), att.quantize_kv(v)
        args = dict(k_scale=ks, v_scale=vs)
        leaf = _pack(kq, vq)
        k, v = att.dequantize_kv(kq, ks), att.dequantize_kv(vq, vs)
    else:
        args, leaf = dict(return_lse=True), _pack(k, v)
    want = onp.asarray(_decode_reference(q, k, v, lens))
    with mx.kernels.override("interpret"):
        got = att.flash_attention_decode(q, leaf, lens, **args)
    snap = tel.snapshot()
    assert snap["kernels.dispatches.flash_attention_decode"]["value"] == 1
    assert "kernels.fallbacks" not in snap
    out = got[0] if case == "lse" else got
    onp.testing.assert_allclose(onp.asarray(out), want, rtol=2e-5, atol=2e-5)


# ------------------------------------------------ cache_append round trip
def test_cache_append_round_trip_bit_exact():
    b, h, d, c, t = 2, 2, 2 * 4, 16, 12        # a K‖V leaf at head size 4
    rs = onp.random.RandomState(1)
    full = jnp.asarray(rs.rand(b, h, t, d).astype("float32"))
    zero = jnp.zeros((b, h, c, d), jnp.float32)
    lens0 = jnp.zeros((b,), jnp.int32)
    one_shot = att.cache_append(zero, full, lens0)
    # prefill 5, then 7 single-token appends — must be bit-identical,
    # zero tail included
    inc = att.cache_append(zero, full[:, :, :5], lens0)
    for i in range(5, t):
        inc = att.cache_append(inc, full[:, :, i:i + 1],
                               jnp.full((b,), i, jnp.int32))
    onp.testing.assert_array_equal(onp.asarray(one_shot), onp.asarray(inc))


def test_cache_append_per_row_offsets():
    b, h, d, c = 2, 1, 2 * 4, 8
    rs = onp.random.RandomState(2)
    base = jnp.asarray(rs.rand(b, h, c, d).astype("float32"))
    new = jnp.asarray(rs.rand(b, h, 2, d).astype("float32"))
    lens = onp.asarray([1, 5], onp.int32)
    out = onp.asarray(att.cache_append(base, new, jnp.asarray(lens)))
    want = onp.asarray(base).copy()
    for row in range(b):
        want[row, :, lens[row]:lens[row] + 2] = onp.asarray(new)[row]
    onp.testing.assert_array_equal(out, want)


# ------------------------------------- model-level prefill+steps parity
def _lm_eager(lm, tokens, cache, cache_len, n_tokens):
    """Eager forward (bypasses _CachedOp) — the reference path; adds
    no jit signatures, so server tests can use it freely."""
    logits, new_cache = lm.forward(_nd_i32(tokens), cache,
                                   _nd_i32(cache_len), _nd_i32(n_tokens))
    return logits.asnumpy(), new_cache


def _tiny_transformer(seed=3, vocab=32):
    mx.random.seed(seed)
    lm = transformer_lm(vocab_size=vocab, units=32, hidden_size=64,
                        num_heads=2, num_layers=1, max_length=64)
    lm.initialize(mx.init.Xavier())
    return lm


def _tiny_lstm(seed=11, vocab=32):
    mx.random.seed(seed)
    lm = lstm_lm(vocab_size=vocab, units=32, num_layers=1)
    lm.initialize(mx.init.Xavier())
    return lm


@pytest.mark.parametrize("family", ["transformer", "lstm"])
def test_prefill_plus_steps_matches_full_forward(family):
    lm = _tiny_transformer() if family == "transformer" else _tiny_lstm()
    rs = onp.random.RandomState(4)
    toks = rs.randint(0, 32, size=(1, 10))
    full, _ = _lm_eager(lm, toks, lm.begin_cache(1, 16), [0], [10])
    # unpadded prefill of the first 6, then 4 single-token steps
    logits, cache = _lm_eager(lm, toks[:, :6], lm.begin_cache(1, 16),
                              [0], [6])
    onp.testing.assert_allclose(logits, full[:, :6], rtol=1e-5, atol=1e-5)
    for t in range(6, 10):
        step, cache = _lm_eager(lm, toks[:, t:t + 1], cache, [t], [1])
        onp.testing.assert_allclose(step[:, 0], full[:, t],
                                    rtol=1e-5, atol=1e-5,
                                    err_msg=f"step at position {t}")


def test_transformer_cache_is_one_packed_leaf_a_layer():
    """The cache contract (gluon/model_zoo/decoder.py): per layer a tuple
    of 4-D page leaves, capacity on axis 2; the transformer's one payload
    leaf is K‖V on the last axis, and what a forward appends there is the
    fused projection's K and V rows, position by position."""
    lm = _tiny_transformer(seed=31)
    cache = lm.begin_cache(3, 16)
    assert len(cache) == 1 and len(cache[0]) == 1
    assert cache[0][0].shape == (3, 2, 16, 2 * 16)      # 2 heads of 16
    toks = onp.random.RandomState(31).randint(0, 32, size=(1, 5))
    _, new = _lm_eager(lm, toks, lm.begin_cache(1, 16), [0], [5])
    leaf = new[0][0].asnumpy()
    assert leaf.shape == (1, 2, 16, 32)
    assert onp.abs(leaf[:, :, :5]).min() > 0 and not leaf[:, :, 5:].any()
    # K and V halves are the cell's own projection of the same input
    cell = lm.layers[0]
    x = lm.word_embed(_nd_i32(toks)) + mx.np.take(
        lm.position_weight.data(), _nd_i32(onp.arange(5)[None]), axis=0)
    qkv = cell.attention.qkv(cell.ln_att(x)).asnumpy()   # (1, 5, 3*32)
    k, v = qkv[..., 32:64], qkv[..., 64:]
    for head in range(2):
        onp.testing.assert_allclose(
            leaf[0, head, :5, :16], k[0, :, head * 16:(head + 1) * 16],
            rtol=1e-6, atol=1e-6)
        onp.testing.assert_allclose(
            leaf[0, head, :5, 16:], v[0, :, head * 16:(head + 1) * 16],
            rtol=1e-6, atol=1e-6)


def test_prefill_plus_steps_through_the_interpreted_kernel():
    """The same model-level parity with the Pallas decode kernel in the
    loop (interpret mode), against the reference path's full forward."""
    lm = _tiny_transformer(seed=32)
    toks = onp.random.RandomState(32).randint(0, 32, size=(2, 10))
    full, _ = _lm_eager(lm, toks, lm.begin_cache(2, 16), [0, 0], [10, 10])
    with mx.kernels.override("interpret"):
        logits, cache = _lm_eager(lm, toks[:, :6], lm.begin_cache(2, 16),
                                  [0, 0], [6, 6])
        onp.testing.assert_allclose(logits, full[:, :6], rtol=2e-5,
                                    atol=2e-5)
        for t in range(6, 10):
            step, cache = _lm_eager(lm, toks[:, t:t + 1], cache, [t, t],
                                    [1, 1])
            onp.testing.assert_allclose(step[:, 0], full[:, t], rtol=2e-5,
                                        atol=2e-5,
                                        err_msg=f"step at position {t}")


@pytest.mark.parametrize("family", ["transformer", "lstm"])
def test_padded_prefill_matches_unpadded(family):
    # prompt padded to bucket 8 with true length 5: garbage tokens must
    # not contaminate positions < 5 (transformer: never attended;
    # LSTM: n_tokens freezes the state) and the subsequent decode step
    # must match the unpadded path (garbage cache rows overwritten)
    lm = _tiny_transformer() if family == "transformer" else _tiny_lstm()
    rs = onp.random.RandomState(5)
    prompt = rs.randint(0, 32, size=(1, 5))
    padded = onp.full((1, 8), 31, onp.int32)
    padded[:, :5] = prompt
    ref, ref_cache = _lm_eager(lm, prompt, lm.begin_cache(1, 16), [0], [5])
    pad, pad_cache = _lm_eager(lm, padded, lm.begin_cache(1, 16), [0], [5])
    onp.testing.assert_allclose(pad[:, :5], ref, rtol=1e-5, atol=1e-5)
    nxt = onp.argmax(ref[0, 4])[None, None]
    s_ref, _ = _lm_eager(lm, nxt, ref_cache, [5], [1])
    s_pad, _ = _lm_eager(lm, nxt, pad_cache, [5], [1])
    onp.testing.assert_allclose(s_pad, s_ref, rtol=1e-5, atol=1e-5)


# --------------------------------------------------- categorical sampler
def test_categorical_deterministic_under_fixed_key():
    rs = onp.random.RandomState(6)
    logits = jnp.asarray(rs.randn(64, 17).astype("float32"))
    key = jax.random.PRNGKey(42)
    a = mrng.categorical(key, logits, temperature=0.7)
    b = mrng.categorical(key, logits, temperature=0.7)
    onp.testing.assert_array_equal(onp.asarray(a), onp.asarray(b))
    c = mrng.categorical(jax.random.PRNGKey(43), logits, temperature=0.7)
    assert (onp.asarray(a) != onp.asarray(c)).any()


def test_categorical_greedy_and_topk():
    rs = onp.random.RandomState(7)
    logits = jnp.asarray(rs.randn(8, 17).astype("float32"))
    argmax = onp.argmax(onp.asarray(logits), axis=-1)
    key = jax.random.PRNGKey(0)
    onp.testing.assert_array_equal(
        onp.asarray(mrng.categorical(key, logits, temperature=0.0)), argmax)
    onp.testing.assert_array_equal(
        onp.asarray(mrng.categorical(key, logits, temperature=1.0,
                                     top_k=1)), argmax)
    top3 = onp.argsort(onp.asarray(logits), axis=-1)[:, -3:]
    for seed in range(16):
        ids = onp.asarray(mrng.categorical(jax.random.PRNGKey(seed),
                                           logits, temperature=1.5,
                                           top_k=3))
        for row in range(ids.shape[0]):
            assert ids[row] in top3[row]


def test_categorical_jit_safe_and_ndarray_wrapping():
    rs = onp.random.RandomState(8)
    logits = jnp.asarray(rs.randn(4, 9).astype("float32"))
    key = jax.random.PRNGKey(5)
    eager = mrng.categorical(key, logits, temperature=0.5, top_k=4)
    jitted = jax.jit(lambda k, l: mrng.categorical(k, l, temperature=0.5,
                                                   top_k=4))(key, logits)
    onp.testing.assert_array_equal(onp.asarray(eager), onp.asarray(jitted))
    wrapped = mrng.categorical(key, NDArray(logits), temperature=0.5,
                               top_k=4)
    assert isinstance(wrapped, NDArray)
    onp.testing.assert_array_equal(wrapped.asnumpy(), onp.asarray(eager))


# ---------------------------------------------------- slice_out regression
def test_slice_out_policy_gated_and_boundary_consistent():
    entry = ModelEntry.__new__(ModelEntry)  # slice_out needs only .bucketer
    entry.bucketer = ShapeBucketer({0: [4], 1: [8]})
    rs = onp.random.RandomState(9)
    # request 1 sits exactly AT the bucket (the old rule's divergence)
    reqs = [rs.rand(3, 5).astype("float32"),
            rs.rand(8, 5).astype("float32"),
            rs.rand(6, 5).astype("float32")]
    batch, _, slices = entry.bucketer.pad_requests(reqs, with_mask=False)
    ref_shape = batch.shape
    assert ref_shape == (4, 8, 5)
    # identity-shaped output: every request (boundary included) gets its
    # exact rows back
    for r, sl in zip(reqs, slices):
        onp.testing.assert_array_equal(entry.slice_out(batch, sl, ref_shape),
                                       r)
    # (B, V) head with V != padded extent: never cut, for ANY request
    vec = rs.rand(4, 5).astype("float32")
    for sl in slices:
        assert entry.slice_out(vec, sl, ref_shape).shape == (5,)
    # leaf without the batch axis: shared, untouched
    shared = rs.rand(7, 3).astype("float32")
    onp.testing.assert_array_equal(
        entry.slice_out(shared, slices[0], ref_shape), shared)
    # the documented residual ambiguity: an output axis that equals the
    # padded POLICY-axis extent is cut — but now for EVERY request
    # (boundary request takes the identical no-op slice), so batch-mates
    # never diverge on the cut decision
    amb = rs.rand(4, 8).astype("float32")
    cuts = [entry.slice_out(amb, sl, ref_shape).shape[0] for sl in slices]
    assert cuts == [3, 8, 6]


# -------------------------------------------------------- donation plumbing
def test_donate_args_aliases_cache_buffers(monkeypatch):
    # the CPU guard keys on the persistent compile cache being armed;
    # disarm it for this test so donation engages on the CPU backend
    monkeypatch.setattr(gblock._jit_cache, "ensure_cache", lambda: None)
    lm = _tiny_transformer(seed=13, vocab=16)
    lm.hybridize(donate_args=(1,))
    toks = _nd_i32(onp.zeros((1, 4)))
    # first call after hybridize runs EAGERLY (shape discovery) — burn
    # it with a throwaway cache so the call under test is the jitted one
    lm(toks, lm.begin_cache(1, 8), _nd_i32(onp.zeros(1)),
       _nd_i32(onp.asarray([4])))
    cache = lm.begin_cache(1, 8)
    _, new_cache = lm(toks, cache, _nd_i32(onp.zeros(1)),
                      _nd_i32(onp.asarray([4])))
    holder = next(iter(lm._cached_op._holders.values()))
    donated = holder["donate_argnums"]
    # one layer -> its one K‖V leaf donated, mapped to a flat jit index
    assert len(cache) == 1 and len(cache[0]) == 1
    assert len(donated) == 1
    # the donated buffers are DELETED after the call (XLA reused them);
    # the returned tree is the live cache now
    with pytest.raises(RuntimeError):
        cache[0][0].asnumpy()
    assert onp.isfinite(new_cache[0][0].asnumpy()).all()
    # second call with the RETURNED cache keeps working (steady decode)
    _, newer = lm(toks, new_cache, _nd_i32(onp.asarray([4])),
                  _nd_i32(onp.asarray([4])))
    assert onp.isfinite(newer[0][0].asnumpy()).all()


def test_donate_argnums_guards():
    lm = _tiny_transformer(seed=14, vocab=16)
    lm.hybridize(donate_args=(1,))
    cop = gblock._CachedOp(lm)
    args = (_nd_i32(onp.zeros((1, 4))), lm.begin_cache(1, 8),
            _nd_i32(onp.zeros(1)), _nd_i32(onp.asarray([4])))
    live = cop._donate_argnums(args, 3, training=False, cache_armed=False)
    assert live == (4,)         # 3 state arrays, tokens, then the one leaf
    # training graphs never donate (grads may re-read the cache)
    assert cop._donate_argnums(args, 3, training=True,
                               cache_armed=False) == ()
    # armed persistent cache on XLA:CPU drops donation (deserialized
    # executables corrupt donated buffers there)
    if jax.default_backend() == "cpu":
        assert cop._donate_argnums(args, 3, training=False,
                                   cache_armed=True) == ()


# ------------------------------------------- the admission's fresh row cache
FAMILIES = ["transformer", "int8", "lstm"]


@functools.lru_cache(maxsize=None)
def _family_entry(family):
    """A tiny warmed entry of one cache family over two capacity buckets
    (one a family for the whole file: its warm-up is most of a test): the
    transformer's one K‖V leaf a layer, its int8 ``(kv_q, k_scale,
    v_scale)`` triple, the LSTM's ``(h, c)``."""
    lm = _tiny_lstm(seed=31) if family == "lstm" \
        else _tiny_transformer(seed=31)
    return serve.DecodeEntry(
        f"rc_{family}", lm, slots=2, prompt_buckets=(4,),
        capacity_buckets=(16, 32), max_new_tokens=4,
        precision="int8" if family == "int8" else None)


def _admission_caps(entry):
    """The capacities an admission can ask for: the LSTM's state does not
    follow the capacity, so its loop stays on the first bucket."""
    return entry.capacity_buckets[:1] if entry.capacity_static \
        else entry.capacity_buckets


@pytest.mark.parametrize("family", FAMILIES)
def test_admission_row_cache_equals_begin_cache(family, monkeypatch):
    """What ``DecodeEntry.prefill`` hands the forward, at every capacity an
    admission can ask for, against the model's own eager ``begin_cache``."""
    entry = _family_entry(family)
    seen = []
    monkeypatch.setattr(entry, "prefill_window",
                        lambda toks, cache, cache_len, n_new:
                        seen.append(cache))
    for c in _admission_caps(entry):
        entry.prefill(onp.zeros((1, 4), onp.int32), 3, c)
        got, want = seen.pop(), entry.block.begin_cache(1, c)
        assert len(got) == len(want)
        for g_leaves, w_leaves in zip(got, want):
            assert len(g_leaves) == len(w_leaves)
            for g, w in zip(g_leaves, w_leaves):
                assert isinstance(g, NDArray)
                assert g.shape == w.shape, c
                assert g._data.dtype == w._data.dtype, c
                assert not onp.asarray(g._data).any()


@pytest.mark.parametrize("family", FAMILIES)
def test_two_admissions_in_a_row_each_get_a_live_tree(family):
    """The LM consumes (donates) the row cache it is given: a second
    admission must start from new buffers, not from the deleted ones."""
    entry = _family_entry(family)
    toks = onp.zeros((1, 4), onp.int32)
    toks[0, :3] = [1, 2, 3]
    cap = entry.capacity_buckets[0]
    first_logits, first = entry.prefill(toks, 3, cap)
    second_logits, second = entry.prefill(toks, 3, cap)
    onp.testing.assert_array_equal(first_logits, second_logits)
    assert onp.isfinite(second_logits).all()
    for a_leaves, b_leaves in zip(first, second):
        for a, b in zip(a_leaves, b_leaves):
            assert a is not b
            onp.testing.assert_array_equal(onp.asarray(a._data),
                                           onp.asarray(b._data))


@pytest.mark.parametrize("family", FAMILIES)
def test_row_cache_allocation_compiles_nothing_after_warmup(
        family, fresh_telemetry):
    """After the registration warm-up an allocation at any capacity an
    admission can ask for compiles nothing, and the timer counts one
    observation per cold admission."""
    entry = _family_entry(family)
    caps = _admission_caps(entry)
    for c in caps:
        entry.prefill(onp.zeros((1, 4), onp.int32), 3, c)
    snap = tel.snapshot()
    assert snap.get("hybridize.cache_misses", {"value": 0})["value"] == 0
    assert snap["serve.cache_alloc_seconds"]["count"] == len(caps)
    # warmed once: asking again compiles nothing either
    assert entry.allocator.warmup(
        [(entry._cap_ref(c),) for c in caps]) == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_logits_reads_are_warmed_without_a_forward(family, monkeypatch):
    """The registration warm-up compiles the eager slices that read the
    logits on zeros of their shapes (``HybridBlock.eval_shape``, which
    looks a traced signature up): the block is neither run nor traced
    again for it, and a donated sample's deleted cache will do."""
    entry = _family_entry(family)
    lm, calls = entry.block, []
    orig = type(lm).forward
    monkeypatch.setattr(type(lm), "forward", lambda self, *a:
                        calls.append(1) or orig(self, *a))
    sample = (_nd_i32(onp.zeros((1, 4))), lm.begin_cache(1, 16),
              _nd_i32(onp.zeros(1)), _nd_i32(onp.ones(1)))
    want = lm(*sample)[0]               # donates the sample's cache
    logits = lm.eval_shape(*sample)[0]
    assert (logits.shape, logits.dtype) == (want.shape, want._data.dtype)
    assert len(calls) <= 1              # a trace at most, by the real call
    seen = []
    monkeypatch.setattr(entry.block, "eval_shape", lambda *a:
                        seen.append(a[0].shape) or type(lm).eval_shape(lm, *a))
    before = len(calls)
    entry.warmup()
    assert len(seen) > 1 and len(calls) == before  # every program, no run


def test_row_cache_program_is_one_dispatch_with_its_own_lint_label(
        monkeypatch):
    """The allocator is a hybridized sibling of the mover and the grower:
    one jitted call whatever the number of leaves, labelled for the lint,
    and no child of it holds the LM's parameters."""
    entry = _family_entry("transformer")
    assert entry.allocator._xla_lint_label == "serve.rc_transformer.alloc"
    assert not entry.allocator.collect_params()
    cop, calls = entry.allocator._cached_op, []
    orig = type(cop).__call__
    monkeypatch.setattr(type(cop), "__call__",
                        lambda self, args, kwargs:
                        calls.append(self) or orig(self, args, kwargs))
    entry.prefill(onp.zeros((1, 4), onp.int32), 3, 16)
    # one call of the allocator's program, one of the LM's: none per leaf
    assert [c is cop for c in calls] == [True, False]


# ------------------------------------------------------ decode server tier
def _eager_greedy(lm, prompt, n_new, capacity=64):
    """One-row greedy reference: full re-forward per step, eager (no
    compiles) — what the server's incremental path must reproduce."""
    toks = list(prompt)
    out = []
    for _ in range(n_new):
        logits, _ = _lm_eager(lm, onp.asarray([toks]),
                              lm.begin_cache(1, capacity), [0], [len(toks)])
        nxt = int(onp.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def test_decode_server_end_to_end(fresh_telemetry):
    lm = _tiny_transformer(seed=21)
    entry = serve.DecodeEntry("tlm", lm, slots=2, prompt_buckets=(4, 8),
                              capacity_buckets=(16, 32), max_new_tokens=6)
    srv = serve.DecodeServer(entry)
    try:
        misses0 = tel.snapshot()["hybridize.cache_misses"]["value"]
        # more requests than slots: continuous admission, varying
        # occupancy (2 -> 1 -> 2 ...), every batch-mate independent
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9, 10], [11]]
        futs = [srv.submit(p) for p in prompts]
        res = [f.result(60.0) for f in futs]
        for p, toks in zip(prompts, res):
            assert toks == _eager_greedy(lm, p, 6), f"prompt {p}"
        # outgrow the first capacity bucket: 8 prompt + 12 new > 16
        long_fut = srv.submit(list(range(1, 9)), max_new_tokens=12)
        long = long_fut.result(60.0)
        assert long == _eager_greedy(lm, list(range(1, 8 + 1)), 12)
        assert not long_fut.truncated
        snap = tel.snapshot()
        assert snap["serve.cache_grows"]["value"] >= 1
        # THE gate: zero compiles after registration warmup, across two
        # capacity buckets and multiple occupancies
        assert snap["hybridize.cache_misses"]["value"] == misses0
        # sampled decoding is deterministic under a fixed seed
        a = srv.generate([2, 3, 4], timeout=60.0, temperature=0.8,
                         top_k=5, seed=123)
        b = srv.generate([2, 3, 4], timeout=60.0, temperature=0.8,
                         top_k=5, seed=123)
        assert a == b and len(a) == 6
        # per-token telemetry: every generated token is counted
        snap = tel.snapshot()
        expect = sum(len(t) for t in res) + len(long) + len(a) + len(b)
        assert snap["serve.tokens"]["value"] == expect
        assert snap["serve.decode_step_seconds"]["count"] >= 1
        assert snap["serve.prefill_seconds"]["count"] == len(prompts) + 3
        assert snap["serve.decode_slots_active"]["value"] == 0
        # a prompt past the largest CAPACITY fails ITS future (one past the
        # largest prompt bucket is forwarded in chunks); the server survives
        bad = srv.submit([1] * 40)
        with pytest.raises(MXNetError):
            bad.result(30.0)
        assert srv.generate([5], timeout=60.0) == _eager_greedy(lm, [5], 6)
    finally:
        srv.close(60.0)
    with pytest.raises(ClosedError):
        srv.submit([1])


def _covers(outer, inner):
    return outer["ts"] <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_decode_server_span_tree_and_timer_counts(fresh_telemetry):
    """One request through the tiny LM: an admission is ``serve.admit``
    > ``serve.prefill`` > ``serve.first_token`` > {``serve.cache_alloc``,
    ``serve.prefill_forward``} and then ``serve.cache_move``; a step is
    ``serve.decode_step`` > {``serve.step_dispatch``,
    ``serve.step_readback``} and then ``serve.sample``; the request's
    spans carry its ``serve_decode`` id; the timers count what the spans
    count.  Nothing here is a time."""
    from mxnet_tpu import trace

    lm = _tiny_transformer(seed=25)
    entry = serve.DecodeEntry("spans", lm, slots=2, prompt_buckets=(4,),
                              capacity_buckets=(16,), max_new_tokens=5)
    trace.reset()
    srv = serve.DecodeServer(entry)
    try:
        # the loop allocates its batch cache and then waits: submit after
        # that, so that ``serve.idle_wait`` below is no race with close()
        for _ in range(3000):
            if srv._cache is not None:
                break
            time.sleep(0.01)
        time.sleep(0.05)
        fut = srv.submit([1, 2, 3])
        assert len(fut.result(60.0)) == 5
    finally:
        srv.close(60.0)
    by = {}
    for ev in trace.events():
        if ev["kind"] == "X" and ev["name"].startswith("serve."):
            by.setdefault(ev["name"], []).append(ev)
    admit, = by["serve.admit"]
    prefill, = by["serve.prefill"]
    first, = by["serve.first_token"]
    alloc, = by["serve.cache_alloc"]
    forward, = by["serve.prefill_forward"]
    # the fresh row cache is a warmed program: nothing compiles under it
    assert not [e for e in trace.events() if e["name"] == "hybridize.compile"]
    move, = by["serve.cache_move"]
    assert _covers(admit, prefill) and _covers(prefill, first)
    assert _covers(first, alloc) and _covers(first, forward)
    assert alloc["ts"] + alloc["dur"] <= forward["ts"]
    assert _covers(prefill, move) and first["ts"] + first["dur"] <= move["ts"]
    assert admit["attrs"] == {"request": fut.id, "slot": 0}
    assert forward["attrs"] == {"tokens": 3, "bucket": 4}
    assert alloc["attrs"] == {"capacity": 16}
    for ev in (admit, prefill, first, alloc, forward, move):
        assert ev["corr"] == {"serve_decode": fut.id}, ev["name"]
    # 5 tokens = 1 from the prefill + 4 decode steps, one span of each
    # phase per step and none per slot or token
    steps = by["serve.decode_step"]
    assert len(steps) == 4
    for name in ("serve.step_dispatch", "serve.step_readback",
                 "serve.sample"):
        assert len(by[name]) == 4, name
    for step, disp, back, samp in zip(steps, by["serve.step_dispatch"],
                                      by["serve.step_readback"],
                                      by["serve.sample"]):
        assert _covers(step, disp) and _covers(step, back)
        assert disp["ts"] + disp["dur"] <= back["ts"]
        assert step["ts"] + step["dur"] <= samp["ts"]
        assert samp["attrs"] == {"slots": 1}
        assert step["corr"] == {} and samp["corr"] == {}
    # the loop waited for the request before it came and again after it
    assert len(by["serve.idle_wait"]) >= 1
    snap = tel.snapshot()
    count = {k: snap[k]["count"] for k in snap if k.endswith("_seconds")
             and k.startswith("serve.")}
    assert count["serve.queue_wait_seconds"] == 1
    assert count["serve.prefill_seconds"] == 1
    assert count["serve.first_token_seconds"] == 1
    assert count["serve.cache_alloc_seconds"] == 1
    assert count["serve.prefill_forward_seconds"] == 1
    assert count["serve.cache_move_seconds"] == 1
    assert count["serve.decode_step_seconds"] == 4
    assert count["serve.step_dispatch_seconds"] == 4
    assert count["serve.step_readback_seconds"] == 4
    assert count["serve.sample_seconds"] == 4
    assert count["serve.ttft_seconds"] == 1
    # a request's TTFT is its wait plus its first token, to the loop's
    # bookkeeping between the two
    assert snap["serve.ttft_seconds"]["total"] >= \
        snap["serve.first_token_seconds"]["total"]


def test_decode_pool_path_spans_and_queue_wait(fresh_telemetry):
    """Disaggregated: the pool worker's ``serve.first_token`` holds the
    prompt forward, the loop's ``serve.admit`` holds only the move, and
    every request's wait is observed once (off ``_pq``, not again when
    its shipment leaves ``_q``)."""
    from mxnet_tpu import trace

    lm = _tiny_transformer(seed=26)
    entry = serve.DecodeEntry("poolspans", lm, slots=2, prompt_buckets=(4,),
                              capacity_buckets=(16,), max_new_tokens=3)
    trace.reset()
    srv = serve.DecodeServer(entry, prefill_workers=1, prefix_cache=False)
    try:
        futs = [srv.submit(p) for p in ([1, 2], [3, 4, 5])]
        for f in futs:
            assert len(f.result(60.0)) == 3
    finally:
        srv.close(60.0)
    evs = [e for e in trace.events() if e["kind"] == "X"]
    for f in futs:
        mine = {e["name"]: e for e in evs
                if e["corr"] == {"serve_decode": f.id}}
        assert {"serve.first_token", "serve.prefill", "serve.cache_alloc",
                "serve.prefill_forward", "serve.admit",
                "serve.cache_move"} <= set(mine)
        assert _covers(mine["serve.first_token"], mine["serve.prefill"])
        assert _covers(mine["serve.admit"], mine["serve.cache_move"])
        assert mine["serve.first_token"]["thread"].startswith("mx-prefill-")
        assert mine["serve.admit"]["thread"].startswith("mx-decode-worker-")
    snap = tel.snapshot()
    assert snap["serve.queue_wait_seconds"]["count"] == 2
    assert snap["serve.first_token_seconds"]["count"] == 2
    assert snap["serve.cache_move_seconds"]["count"] == 2
    assert snap["serve.sample_seconds"]["count"] == \
        snap["serve.decode_step_seconds"]["count"]


def test_cache_append_lowers_under_its_named_scope():
    """The device trace finds the append by this scope (PERF.md section
    3, ``step.cache_append_share.serve``)."""
    cache = jnp.zeros((2, 2, 16, 4), jnp.float32)
    new = jnp.ones((2, 2, 1, 4), jnp.float32)
    lens = jnp.asarray([3, 5], jnp.int32)
    # jitted under another name, so only the scope can put the word there
    text = jax.jit(lambda c, n, l: att.cache_append(c, n, l)).lower(
        cache, new, lens).as_text(debug_info=True)
    scoped = [ln for ln in text.splitlines() if "/cache_append/" in ln]
    assert scoped, "no op of the lowered text carries the scope"
    assert any("dynamic_update_slice" in ln or "scatter" in ln
               for ln in scoped)


def test_decode_server_lstm_capacity_static(fresh_telemetry):
    lm = _tiny_lstm(seed=22)
    entry = serve.DecodeEntry("lstmlm", lm, slots=2, prompt_buckets=(4, 8),
                              capacity_buckets=(16, 32), max_new_tokens=5)
    # recurrent state IS the history: growth must be structurally a no-op
    assert entry.capacity_static
    srv = serve.DecodeServer(entry)
    try:
        misses0 = tel.snapshot()["hybridize.cache_misses"]["value"]
        prompts = [[1, 2, 3], [4, 5, 6, 7], [8]]
        futs = [srv.submit(p) for p in prompts]
        for p, f in zip(prompts, futs):
            assert f.result(60.0) == _eager_greedy(lm, p, 5), f"prompt {p}"
        snap = tel.snapshot()
        assert snap.get("serve.cache_grows", {"value": 0})["value"] == 0
        assert snap["hybridize.cache_misses"]["value"] == misses0
    finally:
        srv.close(60.0)


def test_decode_truncation_at_last_bucket(fresh_telemetry):
    lm = _tiny_transformer(seed=23)
    entry = serve.DecodeEntry("trunc", lm, slots=1, prompt_buckets=(4,),
                              capacity_buckets=(8,), max_new_tokens=32)
    srv = serve.DecodeServer(entry)
    try:
        fut = srv.submit([1, 2, 3, 4])
        toks = fut.result(60.0)
        # prompt fills 4 of 8; one token from prefill + one per step
        # until the append would overflow the LAST bucket
        assert fut.truncated
        assert len(toks) == 5
    finally:
        srv.close(60.0)


def test_decode_module_api_and_eos(fresh_telemetry):
    lm = _tiny_transformer(seed=24)
    # pick the model's own greedy first token as EOS: generation stops
    # at length 1 without touching a slot
    first = _eager_greedy(lm, [1, 2], 1)[0]
    serve.register_decode("api_lm", lm, slots=1, prompt_buckets=(4,),
                          capacity_buckets=(8,), max_new_tokens=4,
                          eos_id=first)
    try:
        assert serve.generate("api_lm", [1, 2], timeout=60.0) == [first]
        fut = serve.decode_submit("api_lm", [3], max_new_tokens=2)
        assert len(fut.result(60.0)) <= 2
        with pytest.raises(MXNetError):
            serve.decode_server("nope")
        with pytest.raises(MXNetError):
            serve.decode_submit("api_lm", [])
    finally:
        serve.shutdown_decode(60.0)
    with pytest.raises(MXNetError):
        serve.decode_server("api_lm")


def test_engine_check_no_false_positive_on_decode_worker(fresh_telemetry):
    """ISSUE 17 satellite: the DecodeServer worker loop never ran under
    the engine dependency checker.  With the checker active, a full
    decode session — registration warmup, ragged generate() traffic from
    concurrent clients at varying occupancy, drain + close — must
    produce ZERO diagnostics, while a seeded under-declared push in the
    same session is still caught (the checker is live, not disarmed)."""
    import threading

    from mxnet_tpu import engine
    from mxnet_tpu.analysis import engine_check as echk

    eng = echk.install()
    echk.clear()
    try:
        try:  # drain any first-error left by earlier exception tests on
            # the shared process-global engine (first error reports once)
            eng.wait_for_all()
        except MXNetError:
            pass
        lm = _tiny_transformer(seed=29)
        entry = serve.DecodeEntry("echk_lm", lm, slots=2,
                                  prompt_buckets=(4, 8),
                                  capacity_buckets=(16,),
                                  max_new_tokens=4)
        srv = serve.DecodeServer(entry)
        try:
            prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9, 10], [11],
                       [12, 13, 14]]
            results = [None] * len(prompts)
            errors = []

            def client(i):
                try:
                    results[i] = srv.generate(prompts[i], timeout=60.0)
                except Exception as e:  # noqa: BLE001
                    errors.append((i, repr(e)))

            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(len(prompts))]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert not errors, errors
            for p, toks in zip(prompts, results):
                assert toks == _eager_greedy(lm, p, 4), f"prompt {p}"
        finally:
            srv.close(60.0)
        assert echk.diagnostics() == [], \
            [d.format() for d in echk.diagnostics()]
        # ...and the checker is still live after the decode session
        shared = mx.nd.array(onp.arange(4, dtype="f4"))
        owner = engine.get().new_var()
        echk.bind(shared, owner)
        rogue = engine.get().new_var()
        engine.get().push(lambda: shared.asnumpy(), write=[rogue],
                          name="rogue")
        engine.get().wait_for_var(rogue)
        assert [d.code for d in echk.diagnostics()] == ["E001"]
        engine.get().delete_var(owner)
        engine.get().delete_var(rogue)
    finally:
        echk.uninstall()
