"""int8 KV cache + serve precision plumbing (ISSUE 20).

The load-bearing claims under test: (1) ``quantize_kv`` is symmetric
per-position int8 with the documented worst-case error bound, and
``dequantize_kv`` inverts it within that bound (all-zero rows exactly);
(2) ``flash_attention_decode`` with quantized KV + per-position scales
matches the dequantize-then-attend reference on both the dispatch path
and the interpret-mode pallas kernel, and rejects a half-passed scale
pair; (3) a ``TransformerLM(cache_dtype="int8")`` builds the 3-leaf
per-layer cache (one int8 K‖V payload leaf + K and V f32 scales,
capacity on axis 2 for every leaf so the grower/mover/page-copy
contracts hold), its greedy decode
agrees with the f32 twin on the same weights, and the cache pays
>= 1.8x fewer bytes at fixed capacity; (4) the serve plumbing:
``register_decode(..., precision="int8")`` flips the entry's cache and
serves greedy tokens identical to the eager int8 reference with the
``serve.cache_quant_bytes_saved`` gauge up, the LSTM carrier (no
per-position pages) is rejected, out-of-vocab prompt ids raise the
named ``TokenRangeError`` with an HTTP-mappable status 400, and
``Registry.register(precision=...)`` validates its precision string.
"""
from __future__ import annotations

import numpy as onp
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo import lstm_lm, transformer_lm
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import attention as att
from mxnet_tpu.serve import TokenRangeError


@pytest.fixture()
def fresh_telemetry():
    prev = tel.set_enabled(True)
    tel.reset()
    yield
    tel.reset()
    tel.set_enabled(prev)


def _nd_i32(a) -> NDArray:
    return NDArray(jnp.asarray(a, jnp.int32))


# --------------------------------------------------- quantize/dequantize
def test_quantize_kv_roundtrip_bound_and_dtypes():
    rs = onp.random.RandomState(0)
    x = jnp.asarray((rs.rand(2, 3, 16, 8) - 0.5).astype("float32")) * 4.0
    q, scale = att.quantize_kv(x)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert q.shape == x.shape and scale.shape == x.shape[:-1] + (1,)
    back = att.dequantize_kv(q, scale)
    # symmetric round-to-nearest: worst case half a quantization step
    bound = onp.asarray(scale) * 0.5 + 1e-7
    err = onp.abs(onp.asarray(back) - onp.asarray(x))
    assert (err <= bound).all()


def test_quantize_kv_zero_rows_exact():
    # an all-zero position (a fresh cache page) must quantize to q=0
    # with the 1/127 guard scale — no division by zero, exact dequant
    x = jnp.zeros((1, 1, 4, 8), jnp.float32)
    q, scale = att.quantize_kv(x)
    assert onp.asarray(q).max() == 0 and onp.asarray(q).min() == 0
    onp.testing.assert_allclose(onp.asarray(scale), 1.0 / 127.0)
    onp.testing.assert_array_equal(onp.asarray(att.dequantize_kv(q, scale)),
                                   onp.zeros((1, 1, 4, 8), "float32"))


def test_quantize_kv_through_npx_dispatch():
    from mxnet_tpu import numpy_extension as npx

    rs = onp.random.RandomState(1)
    x = mx.np.array((rs.rand(1, 2, 8, 4) - 0.5).astype("float32"))
    q, scale = npx.quantize_kv(x)
    back = npx.dequantize_kv(q, scale)
    assert q.asnumpy().dtype == onp.int8
    bound = scale.asnumpy() * 0.5 + 1e-7
    assert (onp.abs(back.asnumpy() - x.asnumpy()) <= bound).all()


# ------------------------------------------- quantized decode attention
def _pack(k, v):
    return jnp.concatenate([k, v], axis=-1)


@pytest.mark.parametrize("tq,c,d", [(1, 32, 8), (8, 256, 32), (1, 256, 64),
                                    (8, 256, 128)])
def test_decode_attention_quantized_matches_dequantized_reference(tq, c, d):
    b, h = 2, 2
    rs = onp.random.RandomState(2)
    k = jnp.asarray((rs.rand(b, h, c, d) - 0.5).astype("float32"))
    v = jnp.asarray((rs.rand(b, h, c, d) - 0.5).astype("float32")) * 3.0
    q = jnp.asarray((rs.rand(b, h, tq, d) - 0.5).astype("float32"))
    kq, ks = att.quantize_kv(k)
    vq, vs = att.quantize_kv(v)
    cache_len = jnp.asarray([5, 20], jnp.int32)
    # the reference semantic: dequantize, then ordinary decode attention
    want = onp.asarray(att.flash_attention_decode(
        q, _pack(att.dequantize_kv(kq, ks), att.dequantize_kv(vq, vs)),
        cache_len))
    got = onp.asarray(att.flash_attention_decode(
        q, _pack(kq, vq), cache_len, k_scale=ks, v_scale=vs))
    onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # interpret-mode pallas kernel: dequant happens INSIDE the kernel
    kern = onp.asarray(att._decode_forward_pallas(
        q, _pack(kq, vq), cache_len, scale=1.0 / d ** 0.5, interpret=True,
        k_scale=ks, v_scale=vs))
    onp.testing.assert_allclose(kern, want, rtol=2e-5, atol=2e-5)


def test_quantize_kv_of_packed_rows_scales_each_half_alone():
    """The decoder quantizes the new rows as ``(B, H, T, 2, dh)``: K and
    V of one position must not share an amax."""
    rs = onp.random.RandomState(3)
    k = jnp.asarray((rs.rand(1, 2, 4, 8) - 0.5).astype("float32"))
    v = jnp.asarray((rs.rand(1, 2, 4, 8) - 0.5).astype("float32")) * 50.0
    q, sc = att.quantize_kv(jnp.stack([k, v], axis=3))
    assert q.shape == (1, 2, 4, 2, 8) and sc.shape == (1, 2, 4, 2, 1)
    for half, x in enumerate((k, v)):
        want_q, want_s = att.quantize_kv(x)
        onp.testing.assert_array_equal(onp.asarray(q[:, :, :, half]),
                                       onp.asarray(want_q))
        onp.testing.assert_array_equal(onp.asarray(sc[:, :, :, half]),
                                       onp.asarray(want_s))


def test_decode_attention_half_scale_pair_rejected():
    b, h, c, d = 1, 1, 8, 4
    z = jnp.zeros((b, h, c, 2 * d), jnp.float32)
    q = jnp.zeros((b, h, 1, d), jnp.float32)
    s = jnp.ones((b, h, c, 1), jnp.float32)
    lens = jnp.zeros((b,), jnp.int32)
    with pytest.raises(ValueError, match="k_scale"):
        att.flash_attention_decode(q, z, lens, k_scale=s)
    with pytest.raises(ValueError, match="k_scale"):
        att.flash_attention_decode(q, z, lens, v_scale=s)


# ------------------------------------------------- model-level int8 cache
def _twin_lms(seed=7, vocab=32):
    """An f32 LM and an int8-cache LM sharing the same weights."""
    mx.random.seed(seed)
    f32 = transformer_lm(vocab_size=vocab, units=32, hidden_size=64,
                         num_heads=2, num_layers=2, max_length=64)
    f32.initialize(mx.init.Xavier())
    mx.random.seed(seed)
    q8 = transformer_lm(vocab_size=vocab, units=32, hidden_size=64,
                        num_heads=2, num_layers=2, max_length=64,
                        cache_dtype="int8")
    q8.initialize(mx.init.Xavier())
    return f32, q8


def _greedy(lm, prompt, n_new, capacity=64):
    toks = list(prompt)
    out = []
    for _ in range(n_new):
        logits, _ = lm.forward(_nd_i32([toks]), lm.begin_cache(1, capacity),
                               _nd_i32([0]), _nd_i32([len(toks)]))
        out.append(int(onp.argmax(logits.asnumpy()[0, len(toks) - 1])))
        toks.append(out[-1])
    return out


def _cache_bytes(cache):
    return sum(leaf.nbytes for pair in cache for leaf in pair)


def test_int8_cache_layout_and_compression():
    _f32, q8 = _twin_lms()
    cache = q8.begin_cache(2, 32)
    assert len(cache) == 2
    for leaves in cache:
        kvq, ks, vs = leaves
        assert kvq.dtype == jnp.int8
        assert ks.dtype == jnp.float32 and vs.dtype == jnp.float32
        # EVERY leaf keeps capacity on axis 2 — the grower/mover/page-
        # copy contract (docs/serving.md "Cache layout")
        assert kvq.ndim == 4 and ks.ndim == 4 and vs.ndim == 4
        assert kvq.shape[2] == 32 and ks.shape[2] == vs.shape[2] == 32
        # K‖V on the payload's last axis (head size 16), thin scales
        assert kvq.shape[-1] == 2 * 16
        assert ks.shape[-1] == vs.shape[-1] == 1
    f32_cache = _f32.begin_cache(2, 32)
    ratio = _cache_bytes(f32_cache) / _cache_bytes(cache)
    assert ratio >= 1.8, ratio  # the ISSUE 20 serving headline


def test_int8_cache_greedy_agrees_with_f32_twin():
    f32, q8 = _twin_lms()
    for name, p in f32.collect_params().items():
        assert onp.allclose(p.data().asnumpy(),
                            dict(q8.collect_params())[name].data().asnumpy())
    prompt = [1, 5, 9, 2]
    a = _greedy(f32, prompt, 12)
    b = _greedy(q8, prompt, 12)
    agree = sum(x == y for x, y in zip(a, b))
    # bounded greedy divergence: quantization noise may flip a late
    # near-tie, but the sequences must substantially agree
    assert agree >= 10, (a, b)


def test_invalid_cache_dtype_rejected():
    with pytest.raises((ValueError, MXNetError), match="cache_dtype"):
        transformer_lm(vocab_size=8, units=8, hidden_size=16, num_heads=2,
                       num_layers=1, max_length=8, cache_dtype="fp4")


# ----------------------------------------------------- serve plumbing
def test_register_decode_int8_serves_and_reports_savings(fresh_telemetry):
    _f32, q8 = _twin_lms(seed=13)
    entry = serve.DecodeEntry("q8lm", q8, slots=2, prompt_buckets=(4,),
                              capacity_buckets=(16,), precision="int8")
    assert entry.precision == "int8"
    srv = serve.DecodeServer(entry)
    try:
        got = srv.submit([1, 2, 3]).result(60.0)
        want = _greedy(q8, [1, 2, 3], len(got), capacity=16)
        assert got == want[:len(got)]
        snap = tel.snapshot()
        saved = snap.get("serve.cache_quant_bytes_saved")
        assert saved and saved["value"] > 0
    finally:
        srv.close(60.0)


def test_register_decode_int8_rejects_lstm():
    mx.random.seed(3)
    lm = lstm_lm(vocab_size=16, units=16, num_layers=1)
    lm.initialize(mx.init.Xavier())
    with pytest.raises(MXNetError, match="int8"):
        serve.DecodeEntry("lstm8", lm, slots=1, prompt_buckets=(4,),
                          capacity_buckets=(8,), precision="int8")


def test_decode_submit_out_of_vocab_raises_named_error():
    _f32, q8 = _twin_lms(seed=17)
    srv = serve.DecodeServer(serve.DecodeEntry(
        "vlm", q8, slots=1, prompt_buckets=(4,), capacity_buckets=(16,)))
    try:
        with pytest.raises(TokenRangeError, match="999") as ei:
            srv.submit([1, 999, 2])
        assert ei.value.status == 400  # edge maps it to HTTP 400
        assert isinstance(ei.value, MXNetError)
        # negative ids are equally out of range
        with pytest.raises(TokenRangeError):
            srv.submit([-1, 2])
        # in-range traffic still flows on the same server
        assert srv.submit([1, 2]).result(60.0)
    finally:
        srv.close(60.0)


def test_registry_precision_validation():
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.serve.registry import Registry

    mx.random.seed(0)
    net = nn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((1, 8)))
    with pytest.raises((ValueError, MXNetError), match="precision"):
        Registry().register("bad", net, bucketer={0: [2]},
                            sample=onp.zeros((8,), "float32"),
                            precision="fp8")
