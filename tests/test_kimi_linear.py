"""The Kimi-Linear decoder family against its plain float32 reference, at a
tiny preset on the CPU (seeded random weights; logits, never tokens).

* the pieces: chunk-parallel KDA == the token recurrence (ragged rows and a
  strong decay included), the carried conv tail, absorbed MLA == expanded
  MLA, the dropless routed layer (every share of one layer, the shared
  expert once, adds up to the uncut layer; a skewed router drops nothing);
* the model: prefill then decode through the cache == the reference's
  full forward, ragged prompts in one padded bucket, and through the one
  ``DecodeServer`` with slots admitted at different steps;
* ``LOGIT_RTOL`` is tight: a decay shifted by one position, a router
  without renormalisation and experts multiplied through the wrong output
  projection each fail it; a bf16 state does not, and fails ``STATE_RTOL``
  (the program's own two KDA forms on the reference's rows against its
  float32 scan), which the program's float32 state holds;
* the mixed cache tree through the serve tier: the capacity probe, the
  mover at a traced slot across buckets, the grower over two buckets
  (state leaves untouched), the prefix cache's refusal by name -- with
  ``TransformerLM`` and ``LSTMLM`` as the two pure cases of the same code.
"""
import copy
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo import get_model
from mxnet_tpu.gluon.model_zoo.decoder import CACHE_PAGED, CACHE_STATE
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import kda, mla
from mxnet_tpu.parallel import moe

TINY = {
    "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 5,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_experts": 4, "published": {"num_experts": 16},
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "moe_renormalize": True,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-5,
    "num_attention_heads": 2, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "kv_lora_rank": 16,
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5],
                           "full_attn_layers": [4], "num_heads": 2,
                           "head_dim": 8, "short_conv_kernel_size": 4},
    "deployment": {"held_start": 4}, "assumed": {"gate_low_rank": 8}}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    """The benchmark's plain reference, by path: ``chipbench/`` is no
    package and holds the one copy."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_references_kimi_linear",
        os.path.join(ROOT, "chipbench", "references", "kimi_linear.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def nd(a):
    return NDArray(jnp.asarray(a, jnp.int32))


def build(config=TINY, seed=3, dtype=jnp.float32):
    mx.random.seed(seed)
    lm = get_model("kimi_linear", config=config, dtype=dtype)
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


def greedy_gap(ref_logits, n_prompt, generated):
    """lib/checks.greedy_agrees' worst gap: how far below its row's
    reference maximum a chosen token's reference logit lies, as a share of
    max|ref|."""
    gen = onp.asarray(generated)
    rows = ref_logits[n_prompt - 1:n_prompt - 1 + len(gen)]
    gap = rows.max(-1) - rows[onp.arange(len(gen)), gen]
    return float(gap.max()) / float(onp.abs(ref_logits).max())


# ------------------------------------------------------------------- KDA
def _kda_inputs(b, t, h=2, d=8, decay=0.1, seed=0):
    rs = onp.random.RandomState(seed)
    q, k, v = (jnp.asarray(rs.randn(b, t, h, d), jnp.float32) for _ in "qkv")
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -decay * jnp.asarray(rs.rand(b, t, h, d), jnp.float32)
    beta = jnp.asarray(rs.rand(b, t, h), jnp.float32)
    s0 = jnp.asarray(rs.randn(b, h, d, d), jnp.float32)
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("t,lens,decay", [
    (64, [64], 0.1), (128, [128, 70, 1], 0.1), (48, [48, 17], 0.1),
    (192, [192, 0], 0.1), (128, [128, 100], 8.0), (130, [130, 65], 40.0),
], ids=["one_chunk", "ragged", "short_padded", "empty_row", "strong_decay",
        "decay_past_f32_range"])
def test_kda_chunk_equals_the_token_recurrence(t, lens, decay):
    q, k, v, g, beta, s0 = _kda_inputs(len(lens), t, decay=decay)
    n = jnp.asarray(lens, jnp.int32)
    o_ref, s_ref = kda.kda_scan(q, k, v, g, beta, s0, n)
    o, s = kda.kda_chunk(q, k, v, g, beta, s0, n)
    assert onp.isfinite(onp.asarray(o)).all()
    onp.testing.assert_allclose(s, s_ref, rtol=2e-4, atol=1e-4)
    for row, m in enumerate(lens):        # rows past a length are garbage
        onp.testing.assert_allclose(o[row, :m], o_ref[row, :m], rtol=2e-4,
                                    atol=1e-4)
        if m == 0:
            onp.testing.assert_array_equal(s[row], s0[row])


def test_kda_step_is_one_row_of_the_recurrence():
    q, k, v, g, beta, s0 = _kda_inputs(3, 1)
    s, o = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0)
    sd = s0 * jnp.exp(g[:, 0])[..., None]
    u = beta[:, 0][..., None] * (v[:, 0] - jnp.einsum("bhk,bhkv->bhv",
                                                     k[:, 0], sd))
    want = sd + jnp.einsum("bhk,bhv->bhkv", k[:, 0], u)
    onp.testing.assert_allclose(s, want, rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(
        o, jnp.einsum("bhk,bhkv->bhv", q[:, 0], want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("form", ["step", "chunk", "scan"])
def test_each_form_hands_the_state_back_in_the_dtype_it_came_in(form):
    """So the cache holds what ``begin_cache`` allocated
    (``kda.STATE_DTYPE``), and ``STATE_RTOL``'s recurrence under test
    carries what the served one does."""
    q, k, v, g, beta, s0 = _kda_inputs(2, 16)
    for dtype in (jnp.float32, jnp.bfloat16):
        if form == "step":
            s, _ = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], s0.astype(dtype))
        else:
            fn = kda.kda_chunk if form == "chunk" else kda.kda_scan
            _, s = fn(q, k, v, g, beta, s0.astype(dtype),
                      jnp.asarray([16, 9], jnp.int32))
        assert s.dtype == dtype


@pytest.mark.parametrize("cut", [1, 5, 9])
def test_short_conv_carries_its_tail(cut):
    rs = onp.random.RandomState(1)
    x = jnp.asarray(rs.randn(2, 12, 6), jnp.float32)
    w = jnp.asarray(rs.randn(6, 4), jnp.float32)
    zero = jnp.zeros((2, 3, 6), jnp.float32)
    whole, _ = kda.short_conv(x, w, zero, jnp.asarray([12, 12]))
    # a padded first call (rows past `cut` are garbage), then the rest
    first = x.at[:, cut:].set(99.0)
    _, tail = kda.short_conv(first, w, zero, jnp.asarray([cut, cut]))
    rest, tail2 = kda.short_conv(x[:, cut:], w, tail,
                                 jnp.asarray([12 - cut, 0]))
    onp.testing.assert_allclose(rest, whole[:, cut:], rtol=1e-5, atol=1e-5)
    onp.testing.assert_array_equal(tail2[1], tail[1])      # n_tokens == 0
    onp.testing.assert_array_equal(tail2[0], x[0, -3:])


# ------------------------------------------------------------------- MLA
@pytest.mark.parametrize("t", [1, 7, 16])
def test_mla_absorbed_equals_expanded(t):
    rs = onp.random.RandomState(2)
    b, h, nope, rope, dv, rank, cap = 2, 2, 8, 4, 8, 16, 24
    q = jnp.asarray(rs.randn(b, 16, h, nope + rope), jnp.float32)
    c = jnp.asarray(rs.randn(b, 16, rank), jnp.float32)
    kpe = jnp.asarray(rs.randn(b, 16, rope), jnp.float32)
    w_kvb = jnp.asarray(rs.randn(h * (nope + dv), rank), jnp.float32)
    want = mla.mla_expanded(q, c, kpe, w_kvb, nope, dv)    # causal, 16 rows
    latent = jnp.zeros((b, 1, cap, rank + rope), jnp.float32)
    latent = latent.at[:, 0, :16].set(jnp.concatenate([c, kpe], -1))
    # the last t rows as one call against a cache holding all 16
    got = mla.mla_absorbed(q[:, 16 - t:], latent,
                           jnp.full((b,), 16 - t, jnp.int32), w_kvb, nope, dv)
    onp.testing.assert_allclose(got, want[:, 16 - t:], rtol=2e-4, atol=1e-4)


# ------------------------------------------------------------------- MoE
def _moe_inputs(n=24, d=16, hid=8, n_routed=16, seed=4, skew=0.0):
    rs = onp.random.RandomState(seed)
    x = jnp.asarray(rs.randn(n, d), jnp.float32)
    w = {"router.weight": jnp.asarray(rs.randn(n_routed, d), jnp.float32),
         "e_score_correction": jnp.asarray(
             0.05 * rs.randn(n_routed) + skew * (onp.arange(n_routed) < 4),
             jnp.float32),
         "experts_gate": jnp.asarray(rs.randn(n_routed, d, hid), jnp.float32),
         "experts_up": jnp.asarray(rs.randn(n_routed, d, hid), jnp.float32),
         "experts_down": jnp.asarray(rs.randn(n_routed, hid, d), jnp.float32)}
    for name in ("gate", "up", "down"):
        shape = (d, hid) if name == "down" else (hid, d)
        w[f"shared.{name}.weight"] = jnp.asarray(rs.randn(*shape), jnp.float32)
    return x, w


def _share(x, w, start, held, k=4):
    weights, idx = moe.route_sigmoid_topk(
        x, w["router.weight"], w["e_score_correction"], k, 2.446)
    sl = slice(start, start + held)
    return moe.held_experts_ffn(x, weights, idx, w["experts_gate"][sl],
                                w["experts_up"][sl], w["experts_down"][sl],
                                start, routed=len(w["router.weight"]))


@pytest.mark.parametrize("shares", [1, 4, 16])
def test_the_shares_of_one_moe_layer_add_up_to_the_uncut_layer(shares):
    """16 experts in ``shares`` shares: the routed parts every share gives
    plus the shared expert ONCE is the reference's whole layer."""
    x, w = _moe_inputs()
    with jax.default_matmul_precision("highest"):
        whole = ref._moe(x, w, 4, 2.446, 0, True)
        shared = ref._gated(x, w["shared.gate.weight"].T,
                            w["shared.up.weight"].T, w["shared.down.weight"].T)
        held = 16 // shares
        parts = [_share(x, w, r * held, held) for r in range(shares)]
    total = shared + sum(y for y, _ in parts)
    onp.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-4)
    # every token-expert pair was computed by exactly one share
    assert sum(int(c.sum()) for _, c in parts) == x.shape[0] * 4


@pytest.mark.parametrize("skew", [0.0, 5.0], ids=["even", "skewed"])
def test_no_token_is_dropped_whatever_the_router_does(skew):
    """With the selection bias pushing EVERY token onto experts 0-3 a
    capacity-bounded dispatch would drop most pairs; here each of the four
    held experts computes all 24 tokens."""
    x, w = _moe_inputs(skew=skew)
    y, counts = _share(x, w, 0, 4)
    if skew:
        assert counts.tolist() == [24, 24, 24, 24]
    cfgless = {k: v[:4] if k.startswith("experts_") else v
               for k, v in w.items()}
    with jax.default_matmul_precision("highest"):
        want = ref._moe(x, cfgless, 4, 2.446, 0, True) - ref._gated(
            x, w["shared.gate.weight"].T, w["shared.up.weight"].T,
            w["shared.down.weight"].T)
    onp.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)


def test_padding_rows_route_nowhere():
    x, w = _moe_inputs()
    weights, idx = moe.route_sigmoid_topk(
        x, w["router.weight"], w["e_score_correction"], 4, 2.446)
    real = jnp.arange(24) < 10
    args = (w["experts_gate"][:8], w["experts_up"][:8], w["experts_down"][:8])
    routed = len(w["router.weight"])
    y, counts = moe.held_experts_ffn(x, weights, idx, *args, 0, real,
                                     routed=routed)
    y10, counts10 = moe.held_experts_ffn(x[:10], weights[:10], idx[:10],
                                         *args, 0, routed=routed)
    assert counts.tolist() == counts10.tolist()
    onp.testing.assert_allclose(y[:10], y10, rtol=1e-5, atol=1e-5)
    assert not onp.asarray(y[10:]).any()


# ------------------------------------------------------------- the model
def _prefill_then_decode(lm, seq, n_prompt, bucket, capacity=128):
    toks = onp.zeros((1, bucket), "int32")
    toks[0, :n_prompt] = seq[:n_prompt]
    lg, cache, counts = lm(nd(toks), lm.begin_cache(1, capacity), nd([0]),
                           nd([n_prompt]))
    rows = [onp.asarray(lg._data[0, :n_prompt])]
    for i in range(n_prompt, len(seq)):
        lg, cache, _ = lm(nd([[seq[i]]]), cache, nd([i]), nd([1]))
        rows.append(onp.asarray(lg._data[0]))
    return onp.concatenate(rows, 0), counts


@pytest.mark.parametrize("n_prompt,bucket", [(40, 48), (64, 64), (5, 80)],
                         ids=["ragged_in_bucket", "whole_chunk", "mostly_pad"])
def test_prefill_then_decode_matches_the_reference_forward(tiny, n_prompt,
                                                           bucket):
    lm, params = tiny
    seq = onp.random.RandomState(n_prompt).randint(1, 96, size=70)
    want = onp.asarray(ref.logits(params, TINY, seq))
    got, counts = _prefill_then_decode(lm, seq, n_prompt, bucket)
    assert onp.abs(got - want).max() <= 2e-4 * onp.abs(want).max()
    # 4 MoE layers x 4 held experts; only the prompt's real rows routed
    assert counts.shape == (4, 4)
    assert 0 < int(counts._data.sum()) <= 4 * 4 * n_prompt


def test_ragged_prompts_share_one_padded_bucket(tiny):
    lm, params = tiny
    rs = onp.random.RandomState(9)
    lens = [48, 13, 30]
    seqs = [rs.randint(1, 96, size=n + 6) for n in lens]
    toks = onp.zeros((3, 48), "int32")
    for r, (s, n) in enumerate(zip(seqs, lens)):
        toks[r, :n] = s[:n]
    lg, cache, _ = lm(nd(toks), lm.begin_cache(3, 64), nd([0, 0, 0]), nd(lens))
    got = [[onp.asarray(lg._data[r, :n])] for r, n in enumerate(lens)]
    for i in range(6):                     # all three rows step together
        step = [[int(s[n + i])] for s, n in zip(seqs, lens)]
        lg, cache, _ = lm(nd(step), cache, nd([n + i for n in lens]),
                          nd([1, 1, 1]))
        for r in range(3):
            got[r].append(onp.asarray(lg._data[r]))
    for r, s in enumerate(seqs):
        want = onp.asarray(ref.logits(params, TINY, s))
        have = onp.concatenate(got[r], 0)
        assert onp.abs(have - want).max() <= 2e-4 * onp.abs(want).max(), r


def test_a_free_slot_keeps_its_state_and_routes_nowhere(tiny):
    lm, _ = tiny
    cache = lm.begin_cache(2, 16)
    _, cache, _ = lm(nd([[5, 6, 7, 8]] * 2), cache, nd([0, 0]), nd([4, 4]))
    before = [onp.asarray(l._data) for l in cache[0]]
    _, after, counts = lm(nd([[9], [9]]), cache, nd([4, 4]), nd([1, 0]))
    for old, new in zip(before, after[0]):
        onp.testing.assert_array_equal(new._data[1], old[1])
        assert onp.abs(onp.asarray(new._data[0]) - old[0]).max() > 0
    _, _, both = lm(nd([[9], [9]]), lm.begin_cache(2, 16), nd([0, 0]),
                    nd([1, 1]))
    assert int(counts._data.sum()) < int(both._data.sum()) or \
        int(both._data.sum()) == 0


def test_weights_are_born_in_their_dtype():
    lm, params = build(dtype=jnp.bfloat16)
    f32 = {k for k, v in params.items() if v.dtype == jnp.float32}
    assert all(k.endswith(("A_log", "dt_bias", "router.weight",
                           "e_score_correction")) for k in f32), f32
    assert all(v.dtype == jnp.bfloat16 for k, v in params.items()
               if k not in f32)
    # and without a gradient buffer beside them (inference only)
    assert all(p.data()._grad is None for p in lm.collect_params().values())


# -------------------------------------------------- LOGIT_RTOL is tight
MID = copy.deepcopy(TINY)
MID.update(vocab_size=512, hidden_size=128, num_hidden_layers=8,
           intermediate_size=256, moe_intermediate_size=64, num_experts=4,
           published={"num_experts": 32}, num_experts_per_token=8,
           num_attention_heads=4, qk_nope_head_dim=32, qk_rope_head_dim=16,
           v_head_dim=32, kv_lora_rank=64,
           linear_attn_config={"kda_layers": [1, 2, 3, 5, 6, 7],
                               "full_attn_layers": [4, 8], "num_heads": 4,
                               "head_dim": 32, "short_conv_kernel_size": 4},
           deployment={"held_start": 0},
           assumed={"gate_low_rank": 32, "routed_out_gain": 0.25})


@pytest.fixture(scope="module")
def mid():
    lm, params = build(MID, seed=11, dtype=jnp.bfloat16)
    seq = onp.random.RandomState(11).randint(1, 512, size=160)
    with jax.default_matmul_precision("highest"):
        return lm, params, seq, onp.asarray(ref.logits(params, MID, seq))


def test_the_bf16_program_is_inside_logit_rtol(mid):
    lm, _, seq, want = mid
    got, _ = _prefill_then_decode(lm, seq, 100, 128, capacity=192)
    chosen = got.astype("float32").argmax(-1)
    assert greedy_gap(want, 1, chosen) <= ref.LOGIT_RTOL


@pytest.mark.parametrize("fault", ["shift_decay", "no_renormalize",
                                   "wrong_expert"])
def test_a_fault_fails_logit_rtol(mid, fault):
    """What the reference itself chooses greedily when computed with the
    fault, judged like a server's tokens against the sound reference."""
    _, params, seq, want = mid
    bad = onp.asarray(ref.logits(params, MID, seq, faults=(fault,),
                                 recurrence=None))
    assert greedy_gap(want, 1, want.argmax(-1)) == 0.0
    assert greedy_gap(want, 1, bad.argmax(-1)) > ref.LOGIT_RTOL


def test_a_bf16_state_passes_logit_rtol_and_fails_state_rtol(mid):
    """Rounding the state to bf16 moves the logits as far as the bf16
    matrix products of the sound program do, so ``LOGIT_RTOL`` cannot tell
    it; as the recurrence under test it reads over ``STATE_RTOL`` and the
    logits come back NaN, which ``lib/checks.greedy_agrees`` reads as not
    correct (``chipbench/tests/test_kimi_linear.py`` runs that one)."""
    _, params, seq, want = mid
    bf16 = functools.partial(ref.scan_recurrence, state_dtype=jnp.bfloat16)
    bad = onp.asarray(ref.logits(params, MID, seq, state_dtype=jnp.bfloat16,
                                 recurrence=None))
    assert greedy_gap(want, 1, bad.argmax(-1)) <= ref.LOGIT_RTOL
    judged = onp.asarray(ref.logits(params, MID, seq, recurrence=bf16))
    assert onp.isnan(judged).all()


def test_the_program_s_float32_state_holds_state_rtol(mid, capfd):
    """The default: the program's chunk form and step form, the state in
    ``ops.kda.STATE_DTYPE``, on every KDA layer's rows."""
    _, params, seq, want = mid
    assert kda.STATE_DTYPE == jnp.float32
    assert onp.isfinite(want).all()          # the fixture ran that check
    onp.testing.assert_array_equal(
        want, onp.asarray(ref.logits(params, MID, seq, recurrence=None)))
    ref.logits(params, MID, seq[:40])
    said = capfd.readouterr().err
    assert "KDA state: 40 tokens" in said and ": held" in said


def test_a_state_in_bf16_in_the_program_fails_state_rtol(mid, monkeypatch):
    """What a later change that halves the state's bytes would meet."""
    _, params, seq, _ = mid
    monkeypatch.setattr(kda, "STATE_DTYPE", jnp.bfloat16)
    assert onp.isnan(onp.asarray(ref.logits(params, MID, seq))).all()


# ------------------------------------------ the cache tree and the server
def test_cache_spec_names_both_kinds_and_refuses_a_third(tiny):
    lm, _ = tiny
    spec = serve.decode.cache_spec(lm)
    assert [kinds for kinds in spec] == [
        (CACHE_STATE, CACHE_STATE)] * 3 + [(CACHE_PAGED,)] + [
        (CACHE_STATE, CACHE_STATE)]
    assert lm.begin_cache(1, 1)[0][0].dtype == kda.STATE_DTYPE

    class CapacityLast:
        @staticmethod
        def begin_cache(batch, capacity):
            return ((NDArray(jnp.zeros((batch, 2, 8, capacity))),),)

    with pytest.raises(MXNetError, match="axis 2"):
        serve.decode.cache_spec(CapacityLast)


@pytest.mark.parametrize("name,kw,kinds", [
    ("transformer_lm", dict(vocab_size=32, units=16, hidden_size=32,
                            num_heads=2, num_layers=2, max_length=32),
     {CACHE_PAGED}),
    ("lstm_lm", dict(vocab_size=32, units=16, num_layers=2), {CACHE_STATE}),
])
def test_the_two_pure_cases_take_the_same_probe(name, kw, kinds):
    lm = get_model(name, **kw)
    lm.initialize()
    spec = serve.decode.cache_spec(lm)
    assert {k for layer in spec for k in layer} == kinds


@pytest.fixture(scope="module")
def entry(tiny):
    lm, _ = build()          # its own copy: the entry re-hybridizes it
    return serve.DecodeEntry("kimi_tiny", lm, slots=2, prompt_buckets=(8, 16),
                             capacity_buckets=(16, 32), max_new_tokens=6)


def test_entry_reads_the_kinds_not_the_ranks(entry):
    assert not entry.capacity_static
    assert sum(k == CACHE_STATE for kinds in entry.cache_spec
               for k in kinds) == 8
    held = entry.cache_bytes(entry.block.begin_cache(2, 16))
    assert held[CACHE_STATE] == 4 * 2 * (2 * 8 * 8 * 4 + 3 * 48 * 4)
    assert held[CACHE_PAGED] == 2 * 16 * 128 * 4        # 20 lanes -> 128


@pytest.mark.parametrize("src_cap,dst_cap", [(16, 16), (16, 32), (32, 16)])
def test_mover_ships_both_kinds_at_a_traced_slot(entry, src_cap, dst_cap):
    rs = onp.random.RandomState(src_cap + dst_cap)
    fill = lambda tree: tuple(
        tuple(NDArray(jnp.asarray(rs.randn(*l.shape), l._data.dtype))
              for l in leaves) for leaves in tree)
    batch = fill(entry.block.begin_cache(2, dst_cap))
    row = fill(entry.block.begin_cache(1, src_cap))
    want = [[onp.asarray(l._data).copy() for l in leaves] for leaves in batch]
    rows = [[onp.asarray(l._data) for l in leaves] for leaves in row]
    got = entry.move(batch, row, 1)
    win = min(src_cap, dst_cap)
    for kinds, g, w, r in zip(entry.cache_spec, got, want, rows):
        for kind, gl, wl, rl in zip(kinds, g, w, r):
            gl = onp.asarray(gl._data)
            onp.testing.assert_array_equal(gl[0], wl[0])   # slot 0 untouched
            if kind == CACHE_STATE:
                onp.testing.assert_array_equal(gl[1], rl[0])
            else:
                onp.testing.assert_array_equal(gl[1, :, :win], rl[0, :, :win])
                onp.testing.assert_array_equal(gl[1, :, win:], wl[1, :, win:])


def test_grower_extends_pages_and_leaves_state_alone(entry):
    cache = entry.block.begin_cache(2, 16)
    cache = tuple(tuple(NDArray(l._data + 1) for l in leaves)
                  for leaves in cache)
    grown = entry.grow(cache, 32)
    for kinds, new, old in zip(entry.cache_spec, grown, cache):
        for kind, n, o in zip(kinds, new, old):
            if kind == CACHE_STATE:
                assert n is o                   # the same array, not a copy
            else:
                assert n.shape == (2, 1, 32, 128)
                onp.testing.assert_array_equal(n._data[:, :, :16], o._data)
                assert not onp.asarray(n._data[:, :, 16:]).any()


def test_admission_row_cache_is_begin_cache_in_one_warm_dispatch(
        entry, fresh_telemetry, monkeypatch):
    """The fresh row cache of an admission — f32 KDA state, conv tail and
    latent row from ONE warmed program — equals the model's own eager
    ``begin_cache(1, c)`` leaf for leaf at both capacity buckets, and
    asking for it compiles nothing after the registration warm-up."""
    seen = []
    monkeypatch.setattr(entry, "prefill_window",
                        lambda toks, cache, cache_len, n_new:
                        seen.append(cache))
    misses0 = tel.snapshot().get("hybridize.cache_misses",
                                 {"value": 0})["value"]
    for c in entry.capacity_buckets:
        entry.prefill(onp.zeros((1, 8), onp.int32), 5, c)
        got, want = seen.pop(), entry.block.begin_cache(1, c)
        assert [len(leaves) for leaves in got] == [2, 2, 2, 1, 2]
        for g_leaves, w_leaves in zip(got, want):
            for g, w in zip(g_leaves, w_leaves):
                assert g.shape == w.shape and g._data.dtype == w._data.dtype
                assert not onp.asarray(g._data).any()
        assert got[0][0]._data.dtype == kda.STATE_DTYPE
        assert got[3][0].shape == (1, 1, c, 128)
    snap = tel.snapshot()
    assert snap.get("hybridize.cache_misses",
                    {"value": 0})["value"] == misses0
    assert snap["serve.cache_alloc_seconds"]["count"] == 2


def test_two_admissions_in_a_row_each_get_a_live_tree(entry):
    """The LM donates the tree it is given — state, tail and latent row
    alike — so the next admission's must be new buffers."""
    toks = onp.zeros((1, 8), onp.int32)
    toks[0, :5] = [7, 8, 9, 10, 11]
    first_logits, first = entry.prefill(toks, 5, 16)
    second_logits, second = entry.prefill(toks, 5, 16)
    onp.testing.assert_array_equal(first_logits, second_logits)
    for a_leaves, b_leaves in zip(first, second):
        for a, b in zip(a_leaves, b_leaves):
            onp.testing.assert_array_equal(onp.asarray(a._data),
                                           onp.asarray(b._data))


def test_prefix_cache_refuses_a_tree_with_state_by_name(entry):
    with pytest.raises(MXNetError, match="layer 0 leaf 0.*recurrent state"):
        serve.DecodeServer(entry, prefill_workers=1, prefix_cache=True)
    srv = serve.DecodeServer(entry, prefill_workers=1)     # auto: none
    try:
        assert srv.prefix is None
    finally:
        srv.close(60.0)


def test_served_with_slots_admitted_at_different_steps(entry, tiny,
                                                       fresh_telemetry):
    """Two slots, four requests of different lengths: later ones are
    admitted while earlier ones decode, the batch grows 16 -> 32 on the
    way, and every chosen token is the reference's choice (float32
    program: the gap is rounding)."""
    _, params = tiny
    srv = serve.DecodeServer(entry)
    rs = onp.random.RandomState(5)
    prompts = [rs.randint(1, 96, size=n).tolist() for n in (3, 12, 7, 16)]
    outs = (6, 2, 5, 6)
    try:
        misses0 = tel.snapshot().get("hybridize.cache_misses",
                                     {"value": 0})["value"]
        futs = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, outs)]
        for p, n, f in zip(prompts, outs, futs):
            got = f.result(120.0)
            assert len(got) == n
            want = onp.asarray(ref.logits(params, TINY,
                                          onp.asarray(p + got, "int32")))
            assert greedy_gap(want, len(p), got) <= 1e-4, p
        snap = tel.snapshot()
        assert snap.get("hybridize.cache_misses",
                        {"value": 0})["value"] == misses0
        assert snap["serve.cache_grows"]["value"] == 1
        assert snap["serve.moe_held_picks"]["value"] > 0
        assert 0 < snap["serve.moe_experts_hit"]["value"] \
            <= snap["serve.moe_held_picks"]["value"]
        assert snap["serve.prefill_tokens"]["value"] == 3 + 12 + 7 + 16
        assert snap["serve.cache_state_bytes"]["value"] == \
            4 * 2 * (2 * 8 * 8 * 4 + 3 * 48 * 4)
        assert snap["serve.cache_paged_bytes"]["value"] == 2 * 32 * 128 * 4
        assert snap["serve.step_live_positions"]["value"] > 0
    finally:
        srv.close(60.0)
