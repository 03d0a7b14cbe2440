"""The Mellum decoder family against its plain float32 reference, at a tiny
preset on the CPU that keeps the published ratios (two periods of three
window layers and a full one, 4 query heads a KV head, 16 experts top-4 of
which 4 are held, YaRN with a small factor; seeded random weights; logits,
never tokens).

* the pieces: the softmax router, the rotary frequencies at the PUBLISHED
  sizes against the closed form, the grouped / window / ring forms of
  ``flash_decode`` under the interpreter against ``attention_reference``
  with the explicit mask, the ring append, the four ranks' expert parts
  against the uncut layer;
* the model: a prompt forwarded in chunks and then decoded through the
  cache, three rings long and more, == the reference's full forward; the
  same through the one ``DecodeServer``; chunked admission == one-shot
  prefill for ``TransformerLM`` too, and refused by name for Kimi-Linear;
* the third leaf kind through the serve tier: ``cache_spec`` names it, its
  bytes do not follow the capacity, mover and grower keep a wrapped ring;
* ``LOGIT_RTOL`` is tight: wrong models of the reference fail it;
  ``ATTN_RTOL`` and ``ROUTE_RTOL`` hold the attention path's and the
  router's precision through the functions the served layers call.
"""
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo import get_model, mellum
from mxnet_tpu.gluon.model_zoo.decoder import (CACHE_PAGED, CACHE_STATE,
                                               CACHE_WINDOW)
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import attention as att
from mxnet_tpu.parallel import moe
from test_kimi_linear import TINY as KIMI_TINY, greedy_gap, nd

WINDOW, CHUNK = 8, 16
RING = WINDOW + CHUNK
TINY = {
    "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 8,
    "head_dim": 8, "num_attention_heads": 8, "num_key_value_heads": 2,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_experts": 4, "published": {"num_experts": 16},
    "num_experts_per_tok": 4, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "mlp_layer_types": ["sparse"] * 8,
    "sliding_window": WINDOW, "use_sliding_window": True,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000,
                           "factor": 4, "beta_fast": 32, "beta_slow": 1,
                           "original_max_position_embeddings": 32,
                           "attention_factor": 0.1 * math.log(4) + 1},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    "deployment": {"held_start": 4},
    "assumed": {"qk_norm": True, "prefill_chunk": CHUNK}}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    """The benchmark's plain reference, by path: ``chipbench/`` is no
    package and holds the one copy."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_references_mellum",
        os.path.join(ROOT, "chipbench", "references", "mellum.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def build(config=TINY, seed=3, dtype=jnp.float32):
    mx.random.seed(seed)
    lm = get_model("mellum", config=config, dtype=dtype)
    lm.initialize()
    lm.hybridize()          # one compile a shape; eager is op-by-op slow
    return lm, {k: p.data()._data for k, p in lm.collect_params().items()}


@pytest.fixture(scope="module")
def tiny():
    return build()


@pytest.fixture()
def fresh_telemetry():
    prev = tel.set_enabled(True)
    tel.reset()
    yield
    tel.reset()
    tel.set_enabled(prev)


# ---------------------------------------------------------------- pieces
@pytest.mark.parametrize("renorm", [True, False])
def test_softmax_router_against_the_plain_form(renorm):
    rs = onp.random.RandomState(0)
    x = jnp.asarray(rs.randn(40, 32), jnp.float32)
    w_r = jnp.asarray(rs.randn(16, 32), jnp.float32)
    w, idx = moe.route_softmax_topk(x, w_r, 4, renorm)
    p = onp.asarray(jax.nn.softmax(onp.asarray(x, "float64")
                                   @ onp.asarray(w_r, "float64").T, -1))
    want_idx = onp.argsort(-p, -1)[:, :4]
    onp.testing.assert_array_equal(onp.sort(onp.asarray(idx), -1),
                                   onp.sort(want_idx, -1))
    want = onp.take_along_axis(p, onp.asarray(idx), -1)
    if renorm:
        want = want / want.sum(-1, keepdims=True)
        onp.testing.assert_allclose(onp.asarray(w).sum(-1), 1.0, rtol=1e-6)
    else:
        assert (onp.asarray(w).sum(-1) < 1.0).all()
    onp.testing.assert_allclose(onp.asarray(w), want, rtol=2e-5)
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("fn", [mellum.rope_inv_freq, ref.rope_inv_freq],
                         ids=["program", "reference"])
def test_yarn_frequencies_at_the_published_sizes(fn):
    """``low`` 18, ``high`` 35: pairs up to 18 keep their frequency, pairs
    from 35 on turn 16 times slower, a straight ramp between; cosine and
    sine times 0.1 ln 16 + 1."""
    rope = _published()["rope_parameters"]
    inv, gain = fn(rope["full_attention"], 128)
    f = 500000.0 ** (-onp.arange(64) / 64.0)
    c = lambda n: 128 * math.log(8192 / (2 * math.pi * n)) \
        / (2 * math.log(500000))
    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    ramp = onp.clip((onp.arange(64) - 18) / 17.0, 0, 1)
    onp.testing.assert_allclose(inv, (1 - ramp) * f + ramp * f / 16,
                                rtol=1e-6)
    onp.testing.assert_allclose(inv[:19], f[:19], rtol=1e-6)
    onp.testing.assert_allclose(inv[35:], f[35:] / 16, rtol=1e-6)
    assert inv.dtype == onp.float32 and inv.shape == (64,)
    assert gain == pytest.approx(1.2772588722239782, abs=1e-12)
    assert gain == pytest.approx(0.1 * math.log(16) + 1, abs=1e-9)
    plain, one = fn(rope["sliding_attention"], 128)
    onp.testing.assert_allclose(plain, f, rtol=1e-6)
    assert one == 1.0


def _ring_case(b, hq, hkv, dh, tq, rows, window, lens, seed=0):
    """A leaf filled position by position with a seeded history, the chunk
    appended through ``cache_append``; returns (q, leaf, position of each
    row or -1, lens)."""
    rs = onp.random.RandomState(seed)
    lens = onp.asarray(lens, "int32")
    hist = rs.randn(b, hkv, int(lens.max()) + tq, 2 * dh).astype("float32")
    leaf = onp.zeros((b, hkv, rows, 2 * dh), "float32")
    pos = -onp.ones((b, rows), "int32")
    where = (lambda p: p % rows) if window else (lambda p: p)
    for bi in range(b):
        for p in range(int(lens[bi])):
            leaf[bi, :, where(p)] = hist[bi, :, p]
            pos[bi, where(p)] = p
    new = onp.stack([hist[bi, :, lens[bi]:lens[bi] + tq] for bi in range(b)])
    leaf = att.cache_append(jnp.asarray(leaf), jnp.asarray(new),
                            jnp.asarray(lens), ring=bool(window))
    for bi in range(b):
        for i in range(tq):
            pos[bi, where(int(lens[bi]) + i)] = int(lens[bi]) + i
        for r in range(rows):           # the append put every row in place
            if pos[bi, r] >= 0:
                onp.testing.assert_array_equal(leaf[bi, :, r],
                                               hist[bi, :, pos[bi, r]])
    q = jnp.asarray(rs.randn(b, hq, tq, dh).astype("float32"))
    return q, leaf, pos, lens


@pytest.mark.parametrize("mode", ["interpret", "off"])
@pytest.mark.parametrize("hq,hkv,tq,rows,window,lens", [
    (8, 2, 1, 384, 256, [5, 300]),            # step, ring not wrapped
    (8, 2, 1, 384, 256, [383, 384, 2000]),    # step, wrapped, laps later
    (8, 2, 128, 384, 256, [380, 1000, 257]),  # chunk that wraps
    (8, 2, 16, 384, 256, [0, 100]),           # chunk, ring not full
    (8, 2, 1, 256, None, [5, 200]),           # grouped, no window: step
    (8, 2, 24, 256, None, [5, 200]),          # grouped, no window: chunk
    (4, 4, 1, 256, None, [5, 200]),           # g = 1 as before
    (8, 2, 8, RING, WINDOW, [3, 50]),         # the tiny model's own ring
    (8, 2, 1, RING, WINDOW, [3, 50]),
    (16, 1, 1, 384, 256, [700]),              # 16 heads a KV head
], ids=lambda v: str(v))
def test_decode_attention_forms_against_the_explicit_mask(
        mode, hq, hkv, tq, rows, window, lens):
    q, leaf, pos, lens = _ring_case(len(lens), hq, hkv, 16, tq, rows, window,
                                    lens)
    g = hq // hkv
    k = jnp.repeat(leaf[..., :16], g, axis=1)
    v = jnp.repeat(leaf[..., 16:], g, axis=1)
    kp = jnp.asarray(pos)[:, None, None, :]
    qp = jnp.asarray(lens[:, None] + onp.arange(tq)[None])[:, None, :, None]
    mask = (kp >= 0) & (kp <= qp)
    if window:
        mask = mask & (kp > qp - window)
    want = att.attention_reference(q, k, v, mask=mask)
    with mx.kernels.override(mode):
        got, lse = att.flash_attention_decode(q, leaf, jnp.asarray(lens),
                                              window=window, return_lse=True)
    onp.testing.assert_allclose(got, want, atol=2e-5)
    logits = jnp.where(mask, jnp.einsum("bhqd,bhkd->bhqk", q, k) / 4.0,
                       -jnp.inf)
    onp.testing.assert_allclose(lse, jax.scipy.special.logsumexp(logits, -1),
                                atol=2e-5)


@pytest.mark.parametrize("lens,tq", [([20, 23, 24, 47, 100], 16),
                                     ([0, 8, 9], 16), ([23, 24, 25], 1)])
def test_ring_append_wraps_without_touching_other_rows(lens, tq):
    b = len(lens)
    leaf = jnp.asarray(onp.random.RandomState(1).randn(b, 2, RING, 4),
                       jnp.float32)
    new = jnp.asarray(onp.random.RandomState(2).randn(b, 2, tq, 4),
                      jnp.float32)
    got = onp.asarray(att.cache_append(leaf, new, jnp.asarray(lens),
                                       ring=True))
    want = onp.asarray(leaf).copy()
    for bi, n in enumerate(lens):
        for i in range(tq):
            want[bi, :, (n + i) % RING] = onp.asarray(new)[bi, :, i]
    onp.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="does not fit a ring"):
        att.cache_append(leaf, jnp.zeros((b, 2, RING + 1, 4)),
                         jnp.asarray(lens), ring=True)


def test_decode_attention_refuses_what_it_cannot_hold():
    q = jnp.zeros((1, 6, 1, 8))
    with pytest.raises(ValueError, match="no multiple"):
        att.flash_attention_decode(q, jnp.zeros((1, 4, 16, 16)),
                                   jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="cannot hold a window"):
        att.flash_attention_decode(jnp.zeros((1, 4, 8, 8)),
                                   jnp.zeros((1, 4, 16, 16)),
                                   jnp.zeros((1,), jnp.int32), window=10)


@pytest.mark.parametrize("n,routed,slot", [
    (4, 16, None), (96, 16, 48), (24, 16, None)],
    ids=["dense", "slotted", "dense_under_a_tile"])
def test_the_four_ranks_parts_sum_to_the_uncut_layer(n, routed, slot):
    """Guide ``model-configs`` section 4's test: every rank routes over all
    16 experts and computes its own four; the four parts add up to the
    layer computed whole (no shared expert in this family) -- in both forms
    the static shapes choose: a decode step's few rows (every row through
    every held expert, until an expert expects a sublane tile of them) and a
    prefill chunk's (each expert's rows in a slot)."""
    assert moe._slot_rows(n, 4, routed) == slot
    rs = onp.random.RandomState(4)
    d, h, e, k = 32, 16, 16, 4
    x = jnp.asarray(rs.randn(n, d), jnp.float32)
    w_r = jnp.asarray(rs.randn(e, d), jnp.float32)
    w_g, w_u = (jnp.asarray(rs.randn(e, d, h) * d ** -0.5, jnp.float32)
                for _ in range(2))
    w_d = jnp.asarray(rs.randn(e, h, d) * h ** -0.5, jnp.float32)
    weights, idx = moe.route_softmax_topk(x, w_r, k)
    parts, counts = [], 0
    for rank in range(4):
        held = slice(4 * rank, 4 * rank + 4)
        y, c = moe.held_experts_ffn(x, weights, idx, w_g[held], w_u[held],
                                    w_d[held], held_start=4 * rank,
                                    routed=routed)
        parts.append(onp.asarray(y))
        counts += int(c.sum())
    assert counts == n * k                      # dropless, nothing twice
    x64, p = onp.asarray(x, "float64"), None
    p = onp.asarray(jax.nn.softmax(x64 @ onp.asarray(w_r, "float64").T, -1))
    top = onp.argsort(-p, -1)[:, :k]
    whole = onp.zeros((n, d))
    for t in range(n):
        z = p[t, top[t]].sum()
        for ex in top[t]:
            a = x64[t] @ onp.asarray(w_g[ex], "float64")
            up = x64[t] @ onp.asarray(w_u[ex], "float64")
            whole[t] += p[t, ex] / z * ((a / (1 + onp.exp(-a)) * up)
                                        @ onp.asarray(w_d[ex], "float64"))
    onp.testing.assert_allclose(sum(parts), whole, atol=2e-5)
    assert all(onp.abs(part).max() > 1e-3 for part in parts)


def _pairs_by_hand(x, weights, idx, w_g, w_u, w_d, held_start, real):
    """``held_experts_ffn`` pair by pair in float64."""
    f64 = lambda a: onp.asarray(a, "float64")
    x, weights, w_g, w_u, w_d = map(f64, (x, weights, w_g, w_u, w_d))
    y = onp.zeros_like(x)
    counts = onp.zeros(len(w_g), "int64")
    for t in onp.flatnonzero(onp.asarray(real)):
        for w, ex in zip(weights[t], onp.asarray(idx)[t] - held_start):
            if 0 <= ex < len(w_g):
                a = x[t] @ w_g[ex]
                y[t] += w * ((a / (1 + onp.exp(-a)) * (x[t] @ w_u[ex]))
                             @ w_d[ex])
                counts[ex] += 1
    return y, counts


@pytest.mark.parametrize("skewed", [False, True], ids=["fits", "overflows"])
def test_a_slot_drops_nothing(skewed):
    """The slotted form against the pairs one by one -- and with choices
    skewed onto two held experts, which fill their slots past twice the
    rows they expect: the call then takes the dense form."""
    rs = onp.random.RandomState(6)
    n, d, h, e, k, routed = 128, 32, 16, 4, 4, 16
    x = jnp.asarray(rs.randn(n, d), jnp.float32)
    w_g, w_u = (jnp.asarray(rs.randn(e, d, h) * d ** -0.5, jnp.float32)
                for _ in range(2))
    w_d = jnp.asarray(rs.randn(e, h, d) * h ** -0.5, jnp.float32)
    # three rows in four choose the held experts 4 and 5, the others spread
    idx = jnp.asarray(onp.where(rs.rand(n, 1) < (0.75 if skewed else 0.0),
                                [4, 5, 0, 9],
                                rs.randint(0, routed, size=(n, k))), "int32")
    weights = jnp.asarray(rs.dirichlet(onp.ones(k), size=n), jnp.float32)
    real = jnp.arange(n) < 120
    args = (x, weights, idx, w_g, w_u, w_d, 4, real)
    want, counts = _pairs_by_hand(*args)
    got, same = jax.jit(
        lambda *a: moe.held_experts_ffn(*a, routed=routed))(*args)
    slot = moe._slot_rows(n, k, routed)
    assert slot == 64 and (int(counts.max()) > slot) == skewed
    onp.testing.assert_array_equal(counts, same)
    assert int(counts.sum()) <= 120 * k
    onp.testing.assert_allclose(got, want, atol=2e-5)


# ----------------------------------------------------------------- model
def _forward_in_chunks(lm, seq, chunk, capacity):
    """The prompt through ``lm`` in pieces of ``chunk`` against one row
    cache: logits of every position, and the cache."""
    cache, rows = lm.begin_cache(1, capacity), []
    for start in range(0, len(seq), chunk):
        piece = seq[start:start + chunk]
        toks = onp.zeros((1, chunk), "int32")
        toks[0, :len(piece)] = piece
        logits, cache, _ = lm(nd(toks), cache, nd([start]), nd([len(piece)]))
        rows.append(onp.asarray(logits._data[0, :len(piece)]))
    return onp.concatenate(rows), cache


@pytest.mark.parametrize("n_prompt,n_decode", [(75, 10), (16, 70), (93, 3)])
def test_chunks_then_decode_through_the_ring_equal_the_full_forward(
        tiny, n_prompt, n_decode):
    """Prefill in chunks of 16 and decode token by token, the ring of 24
    rows lapped three times and more, against the reference's one forward
    over the whole sequence; every position's logits."""
    lm, params = tiny
    rs = onp.random.RandomState(n_prompt)
    seq = rs.randint(1, 96, size=n_prompt + n_decode)
    want = onp.asarray(ref.logits(params, TINY, seq))
    got, cache = _forward_in_chunks(lm, seq[:n_prompt], CHUNK, 128)
    rows = [got]
    for p in range(n_prompt, n_prompt + n_decode):
        logits, cache, counts = lm(nd([[seq[p]]]), cache, nd([p]), nd([1]))
        rows.append(onp.asarray(logits._data[0]))
    got = onp.concatenate(rows)
    assert len(seq) >= 3 * RING
    assert onp.abs(got - want).max() <= 2e-4 * onp.abs(want).max()
    assert counts.shape == (8, 4)


def test_ragged_slots_decode_together(tiny):
    """Two slots at different lengths, one wrapped and one not, step
    together; a free slot (``n_tokens`` 0) routes nowhere."""
    lm, params = tiny
    rs = onp.random.RandomState(7)
    seqs = [rs.randint(1, 96, size=n) for n in (60, 11)]
    caches = [_forward_in_chunks(lm, s[:-4], CHUNK, 64)[1] for s in seqs]
    batch = tuple(tuple(NDArray(jnp.concatenate(
        [a._data, b._data, jnp.zeros_like(a._data)])) for a, b in zip(x, y))
        for x, y in zip(*caches))
    lens = onp.asarray([56, 7, 0])
    for step in range(4):
        toks = [[seqs[0][56 + step]], [seqs[1][7 + step]], [0]]
        logits, batch, counts = lm(nd(toks), batch, nd(lens + [step, step, 0]),
                                   nd([1, 1, 0]))
    for i, s in enumerate(seqs):
        want = onp.asarray(ref.logits(params, TINY, s))[-1]
        assert onp.abs(onp.asarray(logits._data[i, 0]) - want).max() \
            <= 2e-4 * onp.abs(want).max()
    assert int(counts._data.sum()) <= 2 * 4 * 8     # two real tokens' picks


def test_parameters_of_the_published_cut_count_as_reckoned():
    """3.487 G parameters in the chip's share (ISSUE 33): counted on the
    built model's shapes, nothing allocated."""
    config = _published()
    lm = get_model("mellum", config=config)
    n = sum(int(onp.prod(p.shape)) for p in lm.collect_params().values())
    assert abs(n - 3.487e9) < 0.01 * 3.487e9, n
    assert len(lm.layers) == 28 and lm.attention_window == 1024
    leaves = jax.eval_shape(lambda: [
        [l._data for l in ls] for ls in lm.begin_cache(1, 8192)])
    assert [l[0].shape for l in leaves[:4]] == \
        [(1, 4, 1536, 256)] * 3 + [(1, 4, 8192, 256)]
    slot = sum(int(onp.prod(l[0].shape)) * 2 for l in leaves)
    assert slot == 7 * 8192 * 2048 + 21 * 1536 * 2048      # 183.5 MB


# ------------------------------------------------------------- serve tier
@pytest.fixture(scope="module")
def entry(tiny):
    lm, _ = build()          # its own copy: the entry re-hybridizes it
    return serve.DecodeEntry("mellum_tiny", lm, slots=2,
                             prompt_buckets=(4, 8, 16),
                             capacity_buckets=(64, 128), max_new_tokens=6)


def test_cache_spec_names_the_window_leaf(entry):
    kinds = [k for layer in entry.cache_spec for k in layer]
    assert kinds == ([CACHE_WINDOW] * 3 + [CACHE_PAGED]) * 2
    assert not entry.capacity_static
    small = entry.cache_bytes(entry.block.begin_cache(2, 64))
    large = entry.cache_bytes(entry.block.begin_cache(2, 128))
    leaf = 2 * 2 * 16 * 4                   # slots x KV heads x K‖V x f32
    assert small[CACHE_WINDOW] == large[CACHE_WINDOW] == 6 * RING * leaf
    assert (small[CACHE_PAGED], large[CACHE_PAGED]) == (2 * 64 * leaf,
                                                        2 * 128 * leaf)
    assert small[CACHE_STATE] == 0


def test_cache_spec_checks_what_a_block_names():
    class Named:
        def __init__(self, kinds):
            self.kinds = kinds

        def begin_cache(self, b, c):
            z = lambda *s: NDArray(jnp.zeros(s))
            return ((z(b, 2, c, 4), z(b, 2, 24, 4), z(b, 5)),)

        def cache_kinds(self):
            return (self.kinds,)

    good = (CACHE_PAGED, CACHE_WINDOW, CACHE_STATE)
    assert serve.decode.cache_spec(Named(good)) == (good,)
    for bad in [(CACHE_WINDOW, CACHE_WINDOW, CACHE_STATE),   # follows C
                (CACHE_PAGED, CACHE_PAGED, CACHE_STATE),     # does not
                (CACHE_PAGED, CACHE_WINDOW, CACHE_WINDOW),   # not 4-D
                (CACHE_PAGED, CACHE_WINDOW, "ring")]:
        with pytest.raises(MXNetError, match="named"):
            serve.decode.cache_spec(Named(bad))


def _filled(tree, seed):
    rs = onp.random.RandomState(seed)
    return tuple(tuple(NDArray(jnp.asarray(rs.randn(*l.shape), l._data.dtype))
                       for l in leaves) for leaves in tree)


@pytest.mark.parametrize("src_cap,dst_cap", [(64, 64), (64, 128), (128, 64)])
def test_mover_ships_a_wrapped_ring_whole_across_buckets(entry, src_cap,
                                                         dst_cap):
    batch = _filled(entry.block.begin_cache(2, dst_cap), 1)
    row = _filled(entry.block.begin_cache(1, src_cap), 2)
    want = [[onp.asarray(l._data).copy() for l in ls] for ls in batch]
    rows = [[onp.asarray(l._data) for l in ls] for ls in row]
    got = entry.move(batch, row, 1)
    win = min(src_cap, dst_cap)
    for kinds, g, w, r in zip(entry.cache_spec, got, want, rows):
        gl, wl, rl = onp.asarray(g[0]._data), w[0], r[0]
        onp.testing.assert_array_equal(gl[0], wl[0])       # slot 0 untouched
        if kinds[0] == CACHE_WINDOW:
            onp.testing.assert_array_equal(gl[1], rl[0])    # every ring row
        else:
            onp.testing.assert_array_equal(gl[1, :, :win], rl[0, :, :win])
            onp.testing.assert_array_equal(gl[1, :, win:], wl[1, :, win:])


def test_grower_extends_pages_and_leaves_the_ring_alone(entry):
    cache = _filled(entry.block.begin_cache(2, 64), 3)
    grown = entry.grow(cache, 128)
    for kinds, new, old in zip(entry.cache_spec, grown, cache):
        if kinds[0] == CACHE_WINDOW:
            assert new[0] is old[0]             # the same array, not a copy
        else:
            assert new[0].shape == (2, 2, 128, 16)
            onp.testing.assert_array_equal(new[0]._data[:, :, :64],
                                           old[0]._data)
            assert not onp.asarray(new[0]._data[:, :, 64:]).any()


def test_prefix_cache_refuses_a_tree_with_a_ring_by_name(entry):
    with pytest.raises(MXNetError, match=r"layer 0 leaf 0 \(window\).*ring"):
        serve.DecodeServer(entry, prefill_workers=1, prefix_cache=True)


def test_served_in_chunks_through_the_one_decode_server(entry, tiny,
                                                        fresh_telemetry):
    """Prompts up to 70 tokens on prompt buckets (4, 8, 16): five chunks,
    the ring lapped, the batch grown 64 -> 128 on the way, slots admitted
    at different steps; every chosen token is the reference's choice, and
    nothing compiles after the registration warm-up."""
    _, params = tiny
    compiles = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    srv = serve.DecodeServer(entry)
    rs = onp.random.RandomState(5)
    sizes, outs = (70, 3, 33, 16, 50), (6, 6, 2, 5, 40)
    prompts = [rs.randint(1, 96, size=n).tolist() for n in sizes]
    try:
        misses0 = tel.snapshot().get("hybridize.cache_misses",
                                     {"value": 0})["value"]
        futs = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, outs)]
        for p, n, f in zip(prompts, outs, futs):
            got = f.result(300.0)
            assert len(got) == n and not f.truncated
        # not even an eager read of the logits compiled (jax's own compile
        # events: hybridize.cache_misses does not see an eager op), in any
        # of the three prompt buckets or the step
        assert not compiles
        for p, n, f in zip(prompts, outs, futs):
            got = f.result(0)
            want = onp.asarray(ref.logits(params, TINY,
                                          onp.asarray(p + got, "int32")))
            assert greedy_gap(want, len(p), got) <= 1e-4, len(p)
        snap = tel.snapshot()
        assert snap.get("hybridize.cache_misses",
                        {"value": 0})["value"] == misses0
        assert snap["serve.prefill_chunks"]["value"] == 5 + 1 + 3 + 1 + 4
        assert snap["serve.prefill_seconds"]["count"] == 5
        assert snap["serve.prefill_tokens"]["value"] == sum(sizes)
        assert snap["serve.cache_grows"]["value"] == 1
        assert snap["serve.cache_window_bytes"]["value"] == \
            6 * RING * 2 * 2 * 16 * 4
        assert snap["serve.cache_paged_bytes"]["value"] == \
            2 * 128 * 2 * 2 * 16 * 4
        live = snap["serve.step_live_positions"]["value"]
        seen = snap["serve.step_window_positions"]["value"]
        steps = snap["serve.tokens"]["value"] - 5       # occupied slot-steps
        assert 0 < seen <= WINDOW * steps < live
        assert snap["serve.moe_held_picks"]["value"] > 0
    finally:
        srv.close(60.0)


def test_a_prompt_that_no_capacity_holds_fails_its_own_future(entry):
    srv = serve.DecodeServer(entry)
    try:
        with pytest.raises(MXNetError, match="past the largest capacity"):
            srv.submit([1] * 130).result(60.0)      # 128 + 4 rows > 128
        assert len(srv.generate([5, 6], timeout=120.0)) == 6
    finally:
        srv.close(60.0)


def test_chunked_admission_equals_one_shot_prefill_for_transformer_lm():
    """The same 27-token prompt through prompt buckets (4, 8) -- four
    pieces -- and through one bucket of 32: the same logits and the same
    27 cache rows."""
    mx.random.seed(11)
    kw = dict(vocab_size=32, units=16, hidden_size=32, num_heads=2,
              num_layers=2, max_length=64)
    lm = get_model("transformer_lm", **kw)
    lm.initialize()
    prompt = onp.random.RandomState(0).randint(1, 32, size=27).tolist()
    got = {}
    for buckets in ((4, 8), (32,)):
        twin = get_model("transformer_lm", **kw)
        twin.initialize()
        for name, p in twin.collect_params().items():
            p.set_data(lm.collect_params()[name].data())
        e = serve.DecodeEntry(f"tlm{len(buckets)}", twin, slots=1,
                              prompt_buckets=buckets, capacity_buckets=(32,),
                              max_new_tokens=2)
        assert len(e.prompt_chunks(27)) == (4 if len(buckets) > 1 else 1)
        assert e.prompt_rows(27) == (28 if len(buckets) > 1 else 32)
        last, cache = e.prefill_prompt(prompt, 32)
        got[buckets] = (last, [onp.asarray(l[0]._data[:, :, :27])
                               for l in cache])
    onp.testing.assert_allclose(got[(4, 8)][0], got[(32,)][0], atol=1e-5)
    for a, b in zip(got[(4, 8)][1], got[(32,)][1]):
        onp.testing.assert_allclose(a, b, atol=1e-5)


def test_kimi_linear_refuses_a_prompt_past_its_bucket_by_name():
    mx.random.seed(1)
    lm = get_model("kimi_linear", config=KIMI_TINY, dtype=jnp.float32)
    lm.initialize()
    e = serve.DecodeEntry("kimi_chunks", lm, slots=1, prompt_buckets=(8,),
                          capacity_buckets=(32,), warmup=False)
    assert e.prompt_chunks(8) == [(0, 8, 8)]
    with pytest.raises(MXNetError, match="EMPTY cache only.*chunks"):
        e.prompt_chunks(9)


# ------------------------------------------------------------- LOGIT_RTOL
@pytest.fixture(scope="module")
def long_case(tiny):
    _, params = tiny
    seq = onp.random.RandomState(9).randint(1, 96, size=96)
    return params, seq, onp.asarray(ref.logits(params, TINY, seq))


def test_the_sound_program_is_far_inside_logit_rtol(tiny, long_case):
    lm, _ = tiny
    _, seq, want = long_case
    got, _ = _forward_in_chunks(lm, seq, CHUNK, 128)
    chosen = got[31:].argmax(-1)
    assert greedy_gap(want, 32, chosen[:-1]) <= ref.LOGIT_RTOL / 100


@pytest.mark.parametrize("fault,length", [
    ("window_off_by_one", 96), ("no_renorm", 96), ("rotary_bf16", 384),
    ("no_yarn", 96), ("experts_off_by_one", 96)])
def test_a_wrong_model_fails_logit_rtol(tiny, fault, length):
    """The reference computed WRONG on purpose, its own greedy tokens held
    to the right reference as the benchmark holds the server's: a window
    one position too long, the chosen experts' weights not renormalised,
    the rotary angles in bf16 (a precision below the float32 the
    configuration states for them; it shows past position 256, where bf16
    no longer holds every integer), the full layers at the plain
    frequencies, each held expert given its neighbour's tokens."""
    _, params = tiny
    seq = onp.random.RandomState(9).randint(1, 96, size=length)
    want = onp.asarray(ref.logits(params, TINY, seq))
    wrong = onp.asarray(ref.logits(params, TINY, seq, fault=fault))
    chosen = wrong[31:-1].argmax(-1)
    assert greedy_gap(want, 32, chosen) > ref.LOGIT_RTOL, fault


def test_a_missing_attention_factor_moves_the_logits(long_case):
    """At this preset's YaRN factor of 4 (gain 1.139, the published 16
    gives 1.277) the reading stays under ``LOGIT_RTOL``; it is far over
    the sound program's."""
    params, seq, want = long_case
    wrong = onp.asarray(ref.logits(params, TINY, seq,
                                   fault="no_attention_factor"))
    assert greedy_gap(want, 32, wrong[31:-1].argmax(-1)) > 0.01


def test_route_rtol_holds_the_routers_precision(long_case, capfd):
    """The program's router on the reference's rows reads rounding; the
    same router with bf16 operands fails ``ROUTE_RTOL`` and the logits
    come back NaN, which ``greedy_agrees`` reads as not correct -- though
    ``LOGIT_RTOL`` alone could not tell (the tokens it would choose are
    the same)."""
    params, seq, want = long_case
    assert onp.isfinite(want).all()
    bad = onp.asarray(ref.logits(params, TINY, seq, router=ref.bf16_router))
    assert onp.isnan(bad).all()
    assert "NOT HELD" in capfd.readouterr().err
    free = onp.asarray(ref.logits(params, TINY, seq, router=None))
    onp.testing.assert_array_equal(free, want)


@pytest.mark.parametrize("control,length", [
    ("bf16_rotary_attention", 384), ("wide_window_attention", 96)])
def test_attn_rtol_holds_positions_window_and_precision(tiny, control,
                                                        length, capfd):
    """The program's own path from a layer's heads to its attention output
    (rotary, the ring, the kernel, in chunks) on the reference's heads
    reads rounding; the same path with its angles in bf16, or with a window
    one position too long, fails ``ATTN_RTOL`` in the window layer and the
    logits come back NaN -- at the published widths neither moves the
    greedy tokens past ``LOGIT_RTOL`` (PERF.md section 6, PR 33)."""
    _, params = tiny
    seq = onp.random.RandomState(9).randint(1, 96, size=length)
    assert onp.isfinite(onp.asarray(ref.logits(params, TINY, seq))).all()
    assert "attention" in capfd.readouterr().err
    bad = ref.logits(params, TINY, seq, attention=getattr(ref, control))
    assert onp.isnan(onp.asarray(bad)).all()
    assert "NOT HELD" in capfd.readouterr().err


def test_the_limits_hold_what_the_served_layer_calls(long_case, monkeypatch):
    """``ROUTE_RTOL`` and ``ATTN_RTOL`` go through the functions the served
    layers call (``mixer_lm.route_rows``, ``mellum.attend``), not through
    copies: a layer that rounded its rows to bf16 before the router, or
    wrote its keys and values one row late, is not correct."""
    from mxnet_tpu.gluon.model_zoo import mixer_lm

    def forget():           # the reference's jitted pieces hold what they
        ref._jitted_layer.cache_clear()                 # traced
        ref._program_piece.cache_clear()

    params, seq, want = long_case
    sound_route, sound_attend = mixer_lm.route_rows, mellum.attend
    monkeypatch.setattr(
        mixer_lm, "route_rows", lambda h, *a: sound_route(
            h.astype(jnp.bfloat16).astype(jnp.float32), *a))
    forget()
    assert onp.isnan(onp.asarray(ref.logits(params, TINY, seq))).all()
    monkeypatch.setattr(mixer_lm, "route_rows", sound_route)
    monkeypatch.setattr(
        mellum, "attend", lambda q, k, v, kv, n, *a: sound_attend(
            q, k, v, kv, n + 1, *a))
    forget()
    assert onp.isnan(onp.asarray(ref.logits(params, TINY, seq))).all()
    monkeypatch.undo()
    forget()
    onp.testing.assert_array_equal(
        onp.asarray(ref.logits(params, TINY, seq)), want)


def test_the_attention_under_test_runs_at_the_server_s_precision(long_case):
    """Outside the reference's own ``highest``: the decode kernel takes bf16
    operands into the MXU, and traced under ``highest`` Mosaic refuses it
    on the chip ("Bad lhs type": my chip run, PR 33)."""
    params, seq, _ = long_case
    seen = []

    def spy(*args):
        seen.append(jax.config.jax_default_matmul_precision)
        return ref.program_attention(*args)

    ref.logits(params, TINY, seq[:40], attention=spy, router=None)
    assert seen == [None, None]                 # one layer of each type


def test_unknown_fault_is_refused(long_case):
    params, seq, _ = long_case
    with pytest.raises(ValueError, match="unknown fault"):
        ref.logits(params, TINY, seq[:8], fault="nope")
