"""Prefix-trie KV-cache reuse + disaggregated prefill pool (ISSUE 18).

The load-bearing claims under test: (1) the trie is block-aligned —
lookups match only full blocks, always leave at least one token to
forward, and inserts retain exactly the full valid blocks, sharing
existing nodes; (2) materialize reassembles retained pages bit-exactly
at any capacity bucket and rejects impossible requests; (3) eviction is
LRU over CHILDLESS nodes under the byte budget, and a zero budget
disables retention; (4) a prefix hit through the disaggregated server
reproduces the unified server's greedy tokens bit-exactly while adding
ZERO ``serve.prefill_seconds`` observations (the remainder runs under
``serve.prefix_fill_seconds``), with TTFT observed per request; (5) an
injected ``serve.prefill_transfer`` fault fails ONLY that request's
future — the batch cache is untouched, the slot stays free, and the
loop keeps serving; (6) the prefill pool threads carry stable
``mx-prefill-<model>-<i>`` names and no ``mx-*`` thread survives
``close()``; (7) capacity-independent caches cannot be prefix-sliced
(explicit request -> MXNetError).
"""
from __future__ import annotations

import threading

import numpy as onp
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo import transformer_lm
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.resilience import chaos
from mxnet_tpu.serve.prefix import PrefixCache


@pytest.fixture()
def fresh_telemetry():
    prev = tel.set_enabled(True)
    tel.reset()
    yield
    tel.reset()
    tel.set_enabled(prev)


@pytest.fixture()
def no_chaos():
    yield
    chaos.configure("")


def _fake_cache(capacity, layers=2, h=2, dh=4, scale=1.0):
    """Synthetic page-layout cache tree with position-distinguishable
    values: leaf ``(1, h, capacity, dh)``, value encodes (layer, kv,
    position)."""
    out = []
    for layer in range(layers):
        pair = []
        for kv in range(2):
            a = (onp.arange(capacity, dtype="float32")[None, None, :, None]
                 + layer * 1000 + kv * 100) * scale
            pair.append(NDArray(jnp.asarray(
                onp.broadcast_to(a, (1, h, capacity, dh)).copy())))
        out.append(tuple(pair))
    return tuple(out)


# ------------------------------------------------------------- trie units
def test_lookup_is_block_aligned_and_leaves_one_token():
    pc = PrefixCache(block=4, max_bytes=1 << 20)
    toks = list(range(1, 10))               # 9 tokens -> 2 full blocks
    assert pc.insert(toks, _fake_cache(16), 9) == 2
    matched, chain = pc.lookup(toks)
    assert matched == 8 and len(chain) == 2
    # an exactly-block-multiple prompt must still forward >= 1 token:
    # only len-1 tokens are matchable
    matched, chain = pc.lookup(toks[:8])
    assert matched == 4 and len(chain) == 1
    # a diverging block matches only the shared prefix
    matched, _ = pc.lookup(toks[:4] + [99, 99, 99, 99, 99])
    assert matched == 4
    matched, _ = pc.lookup([99] * 9)
    assert matched == 0


def test_insert_shares_existing_nodes():
    pc = PrefixCache(block=4, max_bytes=1 << 20)
    toks = list(range(1, 14))               # 13 tokens -> 3 full blocks
    assert pc.insert(toks, _fake_cache(16), 13) == 3
    assert pc.insert(toks, _fake_cache(16), 13) == 0      # all shared
    # same first 2 blocks, new third -> exactly one new node
    other = toks[:8] + [40, 41, 42, 43, 44]
    assert pc.insert(other, _fake_cache(16), 13) == 1
    assert pc.stats()["nodes"] == 4
    # valid_len caps retention below the token count
    assert pc.insert([7] * 12, _fake_cache(16), 5) == 1


def test_materialize_round_trip_and_bounds():
    pc = PrefixCache(block=4, max_bytes=1 << 20)
    toks = list(range(1, 10))
    src = _fake_cache(16)
    pc.insert(toks, src, 9)
    _, chain = pc.lookup(toks)
    out = pc.materialize(chain, 32)
    for layer, pair in enumerate(out):
        for kv, leaf in enumerate(pair):
            got = onp.asarray(leaf._data)
            assert got.shape == (1, 2, 32, 4)
            onp.testing.assert_array_equal(
                got[:, :, :8], onp.asarray(src[layer][kv]._data)[:, :, :8])
            assert not got[:, :, 8:].any()
    with pytest.raises(MXNetError):
        pc.materialize(chain, 4)            # matched 8 > capacity 4
    with pytest.raises(MXNetError):
        pc.materialize([], 32)


def test_eviction_is_lru_childless(fresh_telemetry):
    # one node = (1,2,4,4) f32 x 2 kv x 2 layers = 512 bytes
    pc = PrefixCache(block=4, max_bytes=1024)
    a = list(range(1, 10))
    b = [20 + i for i in range(9)]
    pc.insert(a, _fake_cache(16), 9)
    assert pc.stats()["bytes"] == 1024
    pc.insert(b, _fake_cache(16), 9)        # 2048 -> evict down to 1024
    st = pc.stats()
    assert st["nodes"] == 2 and st["bytes"] == 1024
    assert st["evictions"] == 2
    # chain A went (its leaf was oldest; its root became childless and
    # followed); chain B survived intact
    assert pc.lookup(a)[0] == 0
    assert pc.lookup(b)[0] == 8
    assert tel.snapshot()["serve.cache_evictions"]["value"] == 2
    assert tel.snapshot()["serve.cache_bytes"]["value"] == 1024


def test_zero_budget_disables_retention():
    pc = PrefixCache(block=4, max_bytes=0)
    assert pc.insert(list(range(9)), _fake_cache(16), 9) == 0
    assert pc.lookup(list(range(9)))[0] == 0
    assert pc.stats()["nodes"] == 0


def test_non_page_layout_cache_rejected():
    pc = PrefixCache(block=4, max_bytes=1 << 20)
    flat = ((NDArray(jnp.zeros((2, 8))),),)     # LSTM-style carrier
    with pytest.raises(MXNetError):
        pc.insert(list(range(9)), flat, 9)


def test_clear_resets_bytes():
    pc = PrefixCache(block=4, max_bytes=1 << 20)
    pc.insert(list(range(9)), _fake_cache(16), 9)
    pc.clear()
    st = pc.stats()
    assert st["nodes"] == 0 and st["bytes"] == 0


def test_capacity_static_model_cannot_take_prefix_cache():
    class _Static:
        name = "static_stub"
        cache_spec = (("state", "state"),)

    with pytest.raises(MXNetError):
        serve.DecodeServer(_Static(), prefill_workers=1, prefix_cache=True)


# --------------------------------------------------- disaggregated server
@pytest.fixture(scope="module")
def pfx_entry():
    mx.random.seed(41)
    lm = transformer_lm(vocab_size=32, units=32, hidden_size=64,
                        num_heads=2, num_layers=1, max_length=64)
    lm.initialize(mx.init.Xavier())
    return serve.DecodeEntry("pfx_lm", lm, slots=2, prompt_buckets=(4, 16),
                             capacity_buckets=(16, 32), max_new_tokens=5)


def test_prefix_hit_bit_exact_and_skips_prefill(pfx_entry, fresh_telemetry):
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]    # 10 tokens: matched 8
    short = [7, 8, 9]                           # below the block floor
    uni = serve.DecodeServer(pfx_entry, prefill_workers=0)
    try:
        want = uni.generate(prompt, timeout=60.0)
        want_short = uni.generate(short, timeout=60.0)
    finally:
        uni.close(60.0)

    dis = serve.DecodeServer(pfx_entry, prefill_workers=1)
    try:
        assert dis.prefix is not None           # auto-created
        cold = dis.generate(prompt, timeout=60.0)
        snap = tel.snapshot()
        prefills = snap["serve.prefill_seconds"]["count"]
        hit = dis.generate(prompt, timeout=60.0)
        snap = tel.snapshot()
        # bit-exact greedy parity: unified == disagg cold == prefix hit
        assert want == cold == hit
        # the hit added ZERO full prefills; its remainder forward ran
        # under the prefix_fill timer, and the trie counted the hit
        assert snap["serve.prefill_seconds"]["count"] == prefills
        assert snap["serve.prefix_fill_seconds"]["count"] == 1
        st = dis.prefix.stats()
        assert st["hits"] == 1 and st["misses"] == 1
        assert st["hit_rate"] == 0.5
        assert snap["serve.cache_hits"]["value"] == 1
        assert snap["serve.cache_hit_tokens"]["value"] == 8
        # every admission moves a row into the batch: the two unified
        # ones inline, both disagg requests through the mover seam
        assert snap["serve.cache_move_seconds"]["count"] == 4
        # a short prompt can't match (block floor) but must still serve
        assert dis.generate(short, timeout=60.0) == want_short
        # TTFT observed once per request across BOTH server modes
        assert snap["serve.ttft_seconds"]["count"] == 4
    finally:
        dis.close(60.0)


def test_prefill_transfer_fault_fails_only_that_request(
        pfx_entry, fresh_telemetry, no_chaos):
    prompt = [11, 12, 13, 14, 15, 16, 17, 18, 19]
    uni = serve.DecodeServer(pfx_entry, prefill_workers=0)
    try:
        want = uni.generate(prompt, timeout=60.0)
    finally:
        uni.close(60.0)

    srv = serve.DecodeServer(pfx_entry, prefill_workers=1,
                             prefix_cache=False)
    try:
        chaos.configure("serve.prefill_transfer:error:1.0")
        fut = srv.submit(prompt)
        with pytest.raises(MXNetError):
            fut.result(60.0)
        # the fault fired BEFORE the move: batch cache untouched, slot
        # free, loop alive — the next request serves normally
        assert all(r is None for r in srv._active)
        chaos.configure("")
        assert srv.generate(prompt, timeout=60.0) == want
    finally:
        srv.close(60.0)


def test_prefill_threads_named_and_joined(pfx_entry):
    srv = serve.DecodeServer(pfx_entry, prefill_workers=2)
    names = {t.name for t in threading.enumerate()}
    assert {"mx-prefill-pfx_lm-0", "mx-prefill-pfx_lm-1"} <= names
    srv.close(60.0)
    left = [t.name for t in threading.enumerate()
            if t.name.startswith("mx-prefill-pfx_lm")
            or t.name == "mx-decode-worker-pfx_lm"]
    assert not left


def test_register_decode_passes_pool_config(fresh_telemetry):
    mx.random.seed(43)
    lm = transformer_lm(vocab_size=32, units=32, hidden_size=64,
                        num_heads=2, num_layers=1, max_length=64)
    lm.initialize(mx.init.Xavier())
    serve.register_decode("pfx_api", lm, slots=1, prompt_buckets=(4,),
                          capacity_buckets=(16,), max_new_tokens=3,
                          prefill_workers=1)
    try:
        srv = serve.decode_server("pfx_api")
        assert srv._prefill_workers == 1 and srv.prefix is not None
        out = serve.generate("pfx_api", [1, 2, 3], timeout=60.0)
        assert len(out) == 3
    finally:
        serve.shutdown_decode(60.0)


def test_ttft_is_a_watched_hot_timer_with_default_slo():
    from mxnet_tpu import obs

    if not obs.enabled():
        pytest.skip("MXNET_OBS=0")
    assert "serve.ttft_seconds" in obs.HOT_TIMERS
    # re-wire (tests elsewhere reset the SLO registry) and check the
    # out-of-the-box objective rides along
    obs.set_enabled(False)
    obs.set_enabled(True)
    assert obs.DEFAULT_TTFT_SLO in obs.slos()
    assert "serve.ttft_seconds" in tel._TIMER_WATCHES
