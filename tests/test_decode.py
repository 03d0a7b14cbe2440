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
    for blk in (lm, entry.stepper):     # the prefill grid, the step programs
        monkeypatch.setattr(blk, "eval_shape", lambda *a, blk=blk:
                            seen.append(a[0].shape)
                            or type(blk).eval_shape(blk, *a))
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


# ------------------------- the token stays on the device, one step in flight
def _build_family(family):
    """A fresh small LM of one family (same seed: same weights), from the
    family's own test file's builder."""
    if family == "kimi":
        from test_kimi_linear import build
        return build()[0]
    if family == "mellum":
        from test_mellum import build
        return build()[0]
    return _tiny_lstm(seed=41) if family == "lstm" \
        else _tiny_transformer(seed=41)


RUN_AHEAD_GRID = {      # prompt buckets, capacity buckets (their own files')
    "transformer": ((4, 8), (16, 32)), "lstm": ((4, 8), (16, 32)),
    "kimi": ((8, 16), (16, 32)), "mellum": ((4, 8, 16), (64, 128))}
RUN_AHEAD_FAMILIES = list(RUN_AHEAD_GRID)


def _plain_greedy(lm, prompt, n_new, bucket, capacity):
    """One request alone, a plain loop of block calls with a numpy argmax:
    the prompt in one padded piece from an empty cache, then a token a
    call."""
    toks = onp.zeros((1, bucket), onp.int32)
    toks[0, :len(prompt)] = prompt
    logits, cache, *_ = lm(_nd_i32(toks), lm.begin_cache(1, capacity),
                           _nd_i32([0]), _nd_i32([len(prompt)]))
    out = [int(onp.argmax(onp.asarray(logits._data[0, len(prompt) - 1])))]
    while len(out) < n_new:
        logits, cache, *_ = lm(_nd_i32([[out[-1]]]), cache,
                               _nd_i32([len(prompt) + len(out) - 1]),
                               _nd_i32([1]))
        out.append(int(onp.argmax(onp.asarray(logits._data[0, 0]))))
    return out


def _counter(name):
    return tel.snapshot().get(name, {"value": 0})["value"]


def _wait_until(cond, timeout=30.0):
    t_end = time.time() + timeout
    while not cond() and time.time() < t_end:
        time.sleep(0.01)
    assert cond()


@functools.lru_cache(maxsize=None)
def _served_greedy_batch(family):
    """More greedy requests than slots, of unequal lengths, through a
    server of ``family``: ``(what each gave, what each gives alone, the
    counters' gains after registration, every array pulled to the host
    through the entry's read as (shape, dtype), slots)``."""
    buckets, caps = RUN_AHEAD_GRID[family]
    rs = onp.random.RandomState(5)
    reqs = [([int(t) for t in rs.randint(1, 30, size=n)], n_new)
            for n, n_new in ((3, 9), (buckets[-1], 4), (1, 12), (5, 7),
                             (2, 10))]
    twin = _build_family(family)
    twin.hybridize()
    want = [_plain_greedy(twin, p, n, buckets[-1], caps[-1])
            for p, n in reqs]
    prev = tel.set_enabled(True)
    tel.reset()
    try:
        entry = serve.DecodeEntry(f"ahead_{family}", _build_family(family),
                                  slots=2, prompt_buckets=buckets,
                                  capacity_buckets=caps, max_new_tokens=6)
        pulled, read = [], entry.read
        entry.read = lambda x: pulled.append(
            (tuple(x.shape), str(x._data.dtype))) or read(x)
        before = {k: _counter(k) for k in
                  ("hybridize.cache_misses", "serve.steps_run_ahead",
                   "serve.slot_steps_dropped")}
        srv = serve.DecodeServer(entry)
        try:
            futs = [srv.submit(p, max_new_tokens=n) for p, n in reqs]
            got = [f.result(120.0) for f in futs]
        finally:
            srv.close(60.0)
        snap = tel.snapshot()
        gained = {k: _counter(k) - v for k, v in before.items()}
        gained["steps"] = snap["serve.decode_step_seconds"]["count"]
        gained["readbacks"] = snap["serve.step_readback_seconds"]["count"]
        gained["tokens"] = snap["serve.tokens"]["value"]
    finally:
        tel.reset()
        tel.set_enabled(prev)
    return got, want, gained, pulled, entry.slots


@pytest.mark.parametrize("family", RUN_AHEAD_FAMILIES)
def test_server_tokens_equal_a_plain_loop_with_steps_run_ahead(family):
    """The device's own argmax, fed from one step to the next without the
    host, gives every request the tokens a plain loop with numpy's argmax
    gives it alone; steps did run ahead; nothing compiled after the
    registration; every dispatched step was read, each once."""
    got, want, gained, *_ = _served_greedy_batch(family)
    assert got == want
    assert gained["serve.steps_run_ahead"] > 0
    assert gained["hybridize.cache_misses"] == 0
    # every request ended by its count: foreseen, so no step for nobody
    assert gained["serve.slot_steps_dropped"] == 0
    assert gained["steps"] == gained["readbacks"]
    assert gained["serve.steps_run_ahead"] < gained["steps"]
    assert gained["tokens"] == sum(len(t) for t in got)


@pytest.mark.parametrize("family", RUN_AHEAD_FAMILIES)
def test_a_greedy_step_reads_back_ids_and_counts_and_no_logits(family):
    """What the server pulls to the host in a greedy step is the ``(S,)``
    int32 ids and the block's small counts (a prefill's counts take the
    same way): nothing of the logits' ``(S, V)``, not one row."""
    *_, pulled, slots = _served_greedy_batch(family)
    assert ((slots,), "int32") in pulled
    other = {p for p in pulled if p != ((slots,), "int32")}
    # (routed layers, held experts) of the tiny Kimi and Mellum: 4 x 4, 8 x 4
    assert other == {"kimi": {((4, 4), "int32")},
                     "mellum": {((8, 4), "int32")}}.get(family, set())


def _eos_case(lm, buckets, caps):
    """A prompt whose greedy continuation meets a token for the first time
    at position k >= 2: that token as ``eos_id`` ends the request there
    and nowhere before."""
    for first in range(1, 30):
        g = _plain_greedy(lm, [first, first + 1], 8, buckets[-1], caps[-1])
        for k in range(2, len(g)):
            if g[k] not in g[:k]:
                return [first, first + 1], g, k
    raise AssertionError("no greedy continuation with a late new token")


@pytest.mark.parametrize("family", ["transformer", "lstm", "kimi"])
def test_eos_is_seen_one_step_late_and_the_slot_serves_the_next(
        family, fresh_telemetry):
    """``eos_id`` is what the host cannot foresee: the request ends AT the
    EOS token with ``finish_reason`` "stop", the step already dispatched
    for it is dropped and counted, and the next request on that slot --
    pages or recurrent state overwritten by its move -- gives the tokens
    it gives alone."""
    buckets, caps = RUN_AHEAD_GRID[family]
    twin = _build_family(family)
    twin.hybridize()
    prompt, g, k = _eos_case(twin, buckets, caps)
    eos = g[k]
    follower = [7, 3, 9]
    alone = _plain_greedy(twin, follower, 6, buckets[-1], caps[-1])
    if eos in alone:
        alone = alone[:alone.index(eos) + 1]
    entry = serve.DecodeEntry(f"eos_{family}", _build_family(family),
                              slots=1, prompt_buckets=buckets,
                              capacity_buckets=caps, max_new_tokens=6,
                              eos_id=eos)
    srv = serve.DecodeServer(entry)
    try:
        fut = srv.submit(prompt, max_new_tokens=16)
        assert fut.result(60.0) == g[:k + 1]
        assert fut.finish_reason == "stop"
        _wait_until(lambda: _counter("serve.slot_steps_dropped") == 1)
        nxt = srv.submit(follower, max_new_tokens=6)
        assert nxt.result(60.0) == alone
        assert nxt.finish_reason == ("stop" if alone[-1] == eos
                                     else "length")
    finally:
        srv.close(60.0)
    dropped = _counter("serve.slot_steps_dropped")
    assert dropped == (2 if alone[-1] == eos and len(alone) < 6 else 1)
    # a dropped step is a dispatched step: read, counted, its span closed
    snap = tel.snapshot()
    assert snap["serve.decode_step_seconds"]["count"] == \
        snap["serve.step_readback_seconds"]["count"] == \
        snap["serve.sample_seconds"]["count"]


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_cancel_and_deadline_with_a_step_in_flight(how, fresh_telemetry):
    """A cancel or a deadline that falls while a step is in flight frees
    the slot at the next boundary with the partial tokens -- a prefix of
    what the request gives alone -- and the slot serves the next request
    as if nothing had been computed for nobody."""
    from mxnet_tpu.serve.coalescer import DeadlineError

    twin = _tiny_transformer(seed=41)
    want = _eager_greedy(twin, [1, 2, 3], 12)
    alone = _eager_greedy(twin, [4, 5], 6)
    entry = serve.DecodeEntry(f"late_{how}", _tiny_transformer(seed=41),
                              slots=1, prompt_buckets=(4,),
                              capacity_buckets=(32,), max_new_tokens=6)
    srv = serve.DecodeServer(entry)
    box = {}

    def on_token(tok):
        if tok is None:
            return
        if how == "cancel" and len(box["fut"].tokens_so_far()) == 3:
            box["fut"].cancel()
        elif how == "deadline":
            time.sleep(0.05)            # 0.2 s pass after a few tokens

    try:
        box["fut"] = fut = srv.submit(
            [1, 2, 3], max_new_tokens=12, on_token=on_token,
            deadline=0.2 if how == "deadline" else None)
        if how == "cancel":
            assert fut.result(60.0) == want[:3]
            assert fut.finish_reason == "cancelled"
        else:
            with pytest.raises(DeadlineError):
                fut.result(60.0)
            assert fut.finish_reason == "deadline"
            part = fut.tokens_so_far()
            assert 1 <= len(part) < 12 and part == want[:len(part)]
        _wait_until(lambda: _counter("serve.slot_steps_dropped") >= 1)
        assert srv.generate([4, 5], timeout=60.0) == alone
    finally:
        srv.close(60.0)
    assert _counter("serve.slot_steps_dropped") == 1


def test_a_sampled_request_turns_the_overlap_off_while_it_holds_a_slot(
        fresh_telemetry):
    """A request at a temperature needs its row of the logits on the host:
    beside greedy ones it gives the tokens it gives alone with the same
    seed, the greedy ones give theirs, no step runs ahead while it holds
    a slot, and what is read back is the ids and ONE row of the logits."""
    twin = _tiny_transformer(seed=41)
    greedy = _eager_greedy(twin, [4, 5], 5)
    entry = serve.DecodeEntry("sampled", _tiny_transformer(seed=41), slots=2,
                              prompt_buckets=(4,), capacity_buckets=(32,),
                              max_new_tokens=6)
    pulled, read = [], entry.read
    entry.read = lambda x: pulled.append(tuple(x.shape)) or read(x)
    srv = serve.DecodeServer(entry)
    kw = dict(max_new_tokens=9, temperature=0.8, top_k=5, seed=77)
    try:
        alone = srv.generate([1, 2, 3], timeout=60.0, **kw)
        assert len(alone) == 9 and _counter("serve.steps_run_ahead") == 0
        sampled = srv.submit([1, 2, 3], **kw)
        beside = srv.submit([4, 5], max_new_tokens=5)   # ends first
        assert sampled.result(60.0) == alone
        assert beside.result(60.0) == greedy
        assert _counter("serve.steps_run_ahead") == 0
        assert set(pulled) == {(2,), (32,)}             # ids; a row of V
        # greedy requests alone: the overlap is back
        assert srv.generate([4, 5], timeout=60.0, max_new_tokens=5) == greedy
        assert _counter("serve.steps_run_ahead") > 0
    finally:
        srv.close(60.0)
    assert _counter("serve.slot_steps_dropped") == 0


def test_a_reply_answered_at_once_is_admitted_at_the_boundary_it_freed(
        monkeypatch):
    """After a request's terminal event, with nothing queued, the loop
    gives the caller a moment to answer before it dispatches the next
    step (``_REPLY_GRACE_S``), and ``submit()`` ends the wait: the other
    slot makes no step between the terminal event and the admission."""
    import threading

    from mxnet_tpu.serve import decode as dec

    monkeypatch.setattr(dec, "_REPLY_GRACE_S", 30.0)    # no race on a slow box
    entry = serve.DecodeEntry("grace", _tiny_transformer(seed=41), slots=2,
                              prompt_buckets=(4,), capacity_buckets=(64,),
                              max_new_tokens=6)
    srv = serve.DecodeServer(entry)
    seen, answered = {}, threading.Event()

    def answer():
        time.sleep(0.3)                 # the loop is waiting, not stepping
        seen["before"] = len(long.tokens_so_far())
        seen["next"] = srv.submit([7, 8], max_new_tokens=2, on_token=first)
        answered.set()

    def ended(tok):
        if tok is None:
            threading.Thread(target=answer, daemon=True).start()

    def first(tok):
        seen.setdefault("at_first", len(long.tokens_so_far()))

    try:
        long = srv.submit([1, 2, 3], max_new_tokens=40)
        short = srv.submit([4, 5], max_new_tokens=3, on_token=ended)
        assert len(short.result(60.0)) == 3
        assert answered.wait(60.0)
        assert len(seen["next"].result(60.0)) == 2
        assert len(long.result(60.0)) == 40
    finally:
        srv.close(60.0)
    assert seen["at_first"] == seen["before"] < 40


@pytest.mark.parametrize("family", FAMILIES)
def test_step_program_feeds_itself_donates_the_cache_and_owns_no_weights(
        family):
    """The entry's step program: the LM's parameters ride in as arguments
    (they are its ``collect_params``) though the LM is no child of it (the
    LM's own prefill programs stay on); the cache it is given is consumed;
    ``ids`` is the argmax of the logits it leaves on the device; and a
    slot's token is the step before's device ``ids`` unless the host's is
    marked fresh -- the same token either way gives the same logits."""
    entry = _family_entry(family)
    lm, stepper = entry.block, entry.stepper
    assert stepper._xla_lint_label == f"serve.rc_{family}.step"
    assert list(stepper.collect_params()) == list(lm.collect_params())
    assert not stepper._children and lm._active
    idle = onp.zeros(entry.slots, onp.int32)
    toks = idle + [5, 9]
    cache = lm.begin_cache(entry.slots, 16)
    donated = cache[0][0]
    ids, logits, _, counts = entry.step(_nd_i32(toks), idle, idle, idle,
                                        idle + 1, cache)
    with pytest.raises(RuntimeError):
        donated.asnumpy()
    assert counts == [] and ids.shape == (entry.slots,)
    assert str(ids._data.dtype) == "int32"
    got = onp.asarray(logits._data)
    onp.testing.assert_array_equal(entry.read(ids), got[:, 0, :].argmax(-1))
    # the host's token, marked fresh, against a stale device array
    _, fresh_logits, _, _ = entry.step(
        _nd_i32(idle), toks, idle + 1, idle, idle + 1,
        lm.begin_cache(entry.slots, 16))
    onp.testing.assert_array_equal(onp.asarray(fresh_logits._data), got)
    onp.testing.assert_array_equal(entry.logits_row(logits, 1), got[1, 0])


def test_what_the_step_program_assumes_of_hybridblock(fresh_telemetry):
    """``_DecodeStepper`` wraps the LM by three things that ``HybridBlock``
    does rather than promises, each held here against the base class so
    that a change there fails a test and not a chip run: (1) a block held
    in a tuple is no child, so the wrapper's ``hybridize()`` leaves the
    LM's own programs on and compiled; (2) ``_warmed_up``, set in
    ``hybridize()``, skips the eager first pass of ``warmup()`` and of the
    first call -- the LM's ``forward`` sees tracers only; (3) the
    parameters the program takes as arguments are ``collect_params()``'s:
    a weight changed after the compile changes the logits with no compile
    more (a weight baked in as a constant would not)."""
    from mxnet_tpu.serve.decode import DecodeEntry, _DecodeStepper

    lm = _tiny_transformer(seed=5)
    lm.hybridize(donate_args=(1,))

    def prefill():
        return lm(_nd_i32(onp.ones((1, 4))), lm.begin_cache(1, 16),
                  _nd_i32([0]), _nd_i32([4]))

    prefill()           # the LM's eager first call: its shapes are known
    prefill()           # its own program
    compiled = _counter("hybridize.cache_misses")
    assert compiled >= 1
    concrete, forward = [], lm.forward

    def watched(tokens, *rest):
        concrete.append(not isinstance(tokens._data, jax.core.Tracer))
        return forward(tokens, *rest)

    lm.forward = watched
    stepper = _DecodeStepper(lm)
    stepper.hybridize(donate_args=(2,))
    # (1)
    assert not stepper._children and lm._active
    prefill()
    assert _counter("hybridize.cache_misses") == compiled and not concrete
    # (2)
    idle = onp.zeros(2, onp.int32)

    def step():
        return stepper(_nd_i32(idle + 3),
                       DecodeEntry._step_inputs(idle, idle, idle, idle + 1),
                       lm.begin_cache(2, 16))

    assert stepper.warmup([(
        _nd_i32(idle), DecodeEntry._step_inputs(idle, idle, idle, idle + 1),
        lm.begin_cache(2, 16))]) == 1
    before = onp.asarray(step()[1]._data)
    assert concrete and not any(concrete)
    assert _counter("hybridize.cache_misses") == compiled + 1
    # (3)
    assert list(stepper.collect_params()) == list(lm.collect_params())
    for p in lm.collect_params().values():
        p.set_data(p.data() * 0.5)
    after = onp.asarray(step()[1]._data)
    assert _counter("hybridize.cache_misses") == compiled + 1
    assert not onp.allclose(before, after)


# ---------------------------- the admission's parts, the reply wait, the step
def _spans_by_name(events):
    by = {}
    for ev in events:
        if ev["kind"] == "X" and ev["name"].startswith("serve."):
            by.setdefault(ev["name"], []).append(ev)
    return by


def _apart(a, b):
    return a["ts"] + a["dur"] <= b["ts"] or b["ts"] + b["dur"] <= a["ts"]


def test_an_admission_opens_its_dispatch_and_readback_inside_its_forward(
        fresh_telemetry):
    """``serve.admit`` > ... > ``serve.prefill_forward`` > the prompt's
    dispatch (``serve.prefill_dispatch``, the forward's attrs) and then the
    wait for and read of its last logits (``serve.prefill_readback``), both
    under the request's ``serve_decode`` id: what
    ``chipbench/lib/admit_spans.py`` splits ``device.idle_in_admit.serve``
    by."""
    from mxnet_tpu import trace

    entry = serve.DecodeEntry("admitparts", _tiny_transformer(seed=27),
                              slots=2, prompt_buckets=(4,),
                              capacity_buckets=(16,), max_new_tokens=3)
    trace.reset()
    srv = serve.DecodeServer(entry)
    try:
        fut = srv.submit([1, 2, 3])
        assert len(fut.result(60.0)) == 3
    finally:
        srv.close(60.0)
    by = _spans_by_name(trace.events())
    admit, = by["serve.admit"]
    alloc, = by["serve.cache_alloc"]
    forward, = by["serve.prefill_forward"]
    dispatch, = by["serve.prefill_dispatch"]
    readback, = by["serve.prefill_readback"]
    move, = by["serve.cache_move"]
    assert _covers(admit, forward)
    assert _covers(forward, dispatch) and _covers(forward, readback)
    assert dispatch["ts"] + dispatch["dur"] <= readback["ts"]
    # the four parts of an admission are disjoint
    for a, b in ((alloc, dispatch), (readback, move), (alloc, move)):
        assert _apart(a, b)
    assert dispatch["attrs"] == forward["attrs"] == {"tokens": 3, "bucket": 4}
    for ev in (dispatch, readback):
        assert ev["corr"] == {"serve_decode": fut.id}, ev["name"]


def test_a_chunked_prompt_dispatches_each_piece_and_reads_back_once():
    """A prompt past the largest prompt bucket: one dispatch a piece, each
    inside its piece's forward with its attrs, and ONE readback, inside the
    last piece's forward (the pieces before it are not read)."""
    from mxnet_tpu import trace

    entry = _family_entry("transformer")
    prompt = list(range(1, 11))
    assert [n for _, n, _ in entry.prompt_chunks(len(prompt))] == [4, 4, 2]
    trace.reset()
    last, _ = entry.prefill_prompt(prompt, entry.capacity_buckets[0])
    assert last.shape == (32,)
    by = _spans_by_name(trace.events())
    forwards = by["serve.prefill_forward"]
    dispatches = by["serve.prefill_dispatch"]
    assert len(forwards) == len(dispatches) == 3
    for fwd, disp in zip(forwards, dispatches):
        assert _covers(fwd, disp)
        assert disp["attrs"] == fwd["attrs"]
    assert [d["attrs"]["tokens"] for d in dispatches] == [4, 4, 2]
    readback, = by["serve.prefill_readback"]
    assert _covers(forwards[-1], readback)


def test_a_slot_freed_with_nothing_queued_waits_for_a_reply_under_its_span():
    """The loop's wait for a reply at a boundary that freed a slot with the
    queue empty is ``serve.reply_wait``, on the loop's thread and inside no
    admission, step or sample: ``device.idle_unattributed.serve`` held it
    until PR 39."""
    from mxnet_tpu import trace

    entry = serve.DecodeEntry("replywait", _tiny_transformer(seed=43),
                              slots=2, prompt_buckets=(4,),
                              capacity_buckets=(64,), max_new_tokens=6)
    trace.reset()
    srv = serve.DecodeServer(entry)
    try:
        long = srv.submit([1, 2, 3], max_new_tokens=20)
        short = srv.submit([4, 5], max_new_tokens=3)
        assert len(short.result(60.0)) == 3
        assert len(long.result(60.0)) == 20
    finally:
        srv.close(60.0)
    by = _spans_by_name(trace.events())
    waits = by["serve.reply_wait"]
    assert waits
    loop = by["serve.decode_step"][0]["thread"]
    for wait in waits:
        assert wait["thread"] == loop
        for name in ("serve.admit", "serve.decode_step", "serve.sample"):
            for ev in by[name]:
                assert _apart(wait, ev), name


def _lowered_step(entry):
    """The entry's step program as jax lowers it, traced anew."""
    from mxnet_tpu.gluon.block import _CachedOp

    idle = onp.zeros(entry.slots, onp.int32)
    args = (_nd_i32(idle),
            serve.DecodeEntry._step_inputs(idle, idle, idle, idle + 1),
            entry.block.begin_cache(entry.slots, entry.capacity_buckets[0]))
    _, jit_fn, inputs, _ = _CachedOp(entry.stepper)._prepare(args, False)
    return jit_fn.lower(*(x._data for x in inputs))


def _entry_op_names(hlo_text):
    """The jax-side names (``op_name``) of the instructions of a compiled
    module's ENTRY computation, parameters left out: the ops a device
    trace shows, each under its ``tf_op``."""
    import re

    names, inside = [], False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            inside = True
        elif inside and line.startswith("}"):
            break
        elif inside and " parameter(" not in line:
            m = re.search(r'op_name="([^"]*)"', line)
            if m:
                names.append(m.group(1))
    return names


@pytest.mark.parametrize("family", FAMILIES)
def test_every_op_of_the_step_program_carries_the_decode_step_scope(family):
    """Every named op of the compiled step program is traced under the
    ``jax.named_scope`` ``decode_step`` (``device.step_ms.serve`` reads the
    program's device time by it), and no op of the prefill program is."""
    from mxnet_tpu.gluon.block import _CachedOp

    entry = _family_entry(family)
    names = _entry_op_names(_lowered_step(entry).compile().as_text())
    assert names
    unscoped = [n for n in names if "/decode_step/" not in n]
    assert not unscoped, unscoped[:5]
    lm, cap = entry.block, entry.capacity_buckets[0]
    _, jit_fn, inputs, _ = _CachedOp(lm)._prepare(
        (_nd_i32(onp.zeros((1, 4))), lm.begin_cache(1, cap), _nd_i32([0]),
         _nd_i32([3])), False)
    prefill = jit_fn.lower(*(x._data for x in inputs))
    assert "/decode_step/" not in prefill.as_text(debug_info=True)
    assert "/decode_step/" not in prefill.compile().as_text()


@pytest.mark.parametrize("family", FAMILIES)
def test_the_scope_leaves_the_step_program_as_it_was(family, monkeypatch):
    """The scope is metadata only: the step program jax hands XLA, without
    its locations, is the program traced with no scope at all."""
    import contextlib

    from mxnet_tpu.serve import decode as dec

    entry = _family_entry(family)
    scoped = _lowered_step(entry)
    assert "/decode_step/" in scoped.as_text(debug_info=True)

    class _NoScope:
        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def named_scope(name):
            return contextlib.nullcontext()

    monkeypatch.setattr(dec, "jax", _NoScope())
    plain = _lowered_step(entry)
    assert "/decode_step/" not in plain.as_text(debug_info=True)
    assert scoped.as_text() == plain.as_text()
