"""The Ouro looped decoder against its plain float32 reference, at a tiny
preset on the CPU (``fixtures/tiny-ouro.json``, a benchmark configuration
file in float32 that ``chipbench/tests`` serves too: three layers of width 64,
four heads of 16, run THREE times so that an off-by-one in the pass index
shows; seeded random weights; logits, never tokens).

* the model: a prompt forwarded and then decoded through the ``R x L``
  caches == the reference's one forward; a prompt in chunks past the largest
  bucket == the one-shot forward; ragged slots step together; the exit
  gate's probabilities; the parameter and cache counts at the PUBLISHED
  sizes, from shapes;
* the loop in the cache tree: ``R x L`` paged leaves that the serve tier's
  spec, allocator, mover and grower carry; pass ``r`` reads its own leaf and
  no other; the prefix cache takes the tree; a stack walked once keeps the
  tree and the cell it had;
* through the one ``DecodeServer``: admission in chunks, steps run ahead,
  every chosen token the reference's choice, ``serve.stack_passes`` ``R`` a
  forward;
* ``LOGIT_RTOL`` is tight: wrong models of the reference fail it;
  ``STREAM_RTOL`` and ``LEAF_SHARE`` hold the residual stream's precision
  on the served block and its compiled programs, and the program with its
  stream in bf16 -- where a branch is added, or between the cells -- is not
  correct by them.
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve
from mxnet_tpu import telemetry as tel
from mxnet_tpu.gluon.model_zoo import get_model, mellum, mixer_lm
from mxnet_tpu.gluon.model_zoo.decoder import CACHE_PAGED
from mxnet_tpu.ndarray.ndarray import NDArray
from test_kimi_linear import TINY as KIMI_TINY, greedy_gap, nd
from test_mellum import TINY as MELLUM_TINY, _filled, fresh_telemetry  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "tests", "fixtures", "tiny-ouro.json")) as _f:
    TINY = json.load(_f)
R, L, VOCAB = TINY["total_ut_steps"], TINY["num_hidden_layers"], 96


def _load(*path):
    """A module of ``chipbench/`` by path: it is no package and holds the
    one copy of the reference and of the check that decides ``correct``."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + "_".join(path).replace(".py", ""),
        os.path.join(ROOT, "chipbench", *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references", "ouro.py")
checks = _load("lib", "checks.py")


def plain(params, config, seq, **kw):
    """The reference's logits and nothing of a served block's."""
    return onp.asarray(ref.logits(params, config, seq, served=None, **kw))


def build(config=TINY, seed=3, dtype=jnp.float32):
    mx.random.seed(seed)
    lm = get_model("ouro", config=config, dtype=dtype)
    lm.initialize()
    lm.hybridize()          # one compile a shape; eager is op-by-op slow
    return lm, {k: p.data()._data for k, p in lm.collect_params().items()}


@pytest.fixture(scope="module")
def tiny():
    return build()


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "ouro-2.6b.json")) as f:
        return json.load(f)


# ----------------------------------------------------------------- model
def _forward_in_chunks(lm, seq, chunk, capacity):
    """The prompt through ``lm`` in pieces of ``chunk`` against one row
    cache: logits of every position, and the cache."""
    cache, rows = lm.begin_cache(1, capacity), []
    for start in range(0, len(seq), chunk):
        piece = seq[start:start + chunk]
        toks = onp.zeros((1, chunk), "int32")
        toks[0, :len(piece)] = piece
        logits, cache = lm(nd(toks), cache, nd([start]), nd([len(piece)]))
        rows.append(onp.asarray(logits._data[0, :len(piece)]))
    return onp.concatenate(rows), cache


@pytest.mark.parametrize("n_prompt,n_decode,chunk", [
    (24, 16, 24), (75, 10, 16), (5, 40, 8)])
def test_prefill_then_decode_through_the_caches_equals_the_full_forward(
        tiny, n_prompt, n_decode, chunk):
    """One-shot and chunked prefill, then token by token through the nine
    caches, against the reference's one forward over the whole sequence;
    every position's logits."""
    lm, params = tiny
    seq = onp.random.RandomState(n_prompt).randint(1, VOCAB,
                                                   size=n_prompt + n_decode)
    want = plain(params, TINY, seq)
    got, cache = _forward_in_chunks(lm, seq[:n_prompt], chunk, 96)
    rows = [got]
    for p in range(n_prompt, n_prompt + n_decode):
        logits, cache = lm(nd([[seq[p]]]), cache, nd([p]), nd([1]))
        rows.append(onp.asarray(logits._data[0]))
    assert len(cache) == R * L
    assert onp.abs(onp.concatenate(rows) - want).max() \
        <= 2e-4 * onp.abs(want).max()


def test_a_prompt_in_chunks_equals_the_one_shot_forward(tiny):
    lm, _ = tiny
    seq = onp.random.RandomState(2).randint(1, VOCAB, size=61)
    whole, cache_w = _forward_in_chunks(lm, seq, 64, 64)
    pieces, cache_p = _forward_in_chunks(lm, seq, 8, 64)
    onp.testing.assert_allclose(pieces, whole, atol=2e-5)
    for a, b in zip(cache_w, cache_p):
        onp.testing.assert_allclose(a[0]._data[:, :, :61],
                                    b[0]._data[:, :, :61], atol=2e-5)


def test_ragged_slots_decode_together(tiny):
    """Two slots at different lengths and a free one step together."""
    lm, params = tiny
    rs = onp.random.RandomState(7)
    seqs = [rs.randint(1, VOCAB, size=n) for n in (60, 11)]
    caches = [_forward_in_chunks(lm, s[:-4], 16, 64)[1] for s in seqs]
    batch = tuple(tuple(NDArray(jnp.concatenate(
        [a._data, b._data, jnp.zeros_like(a._data)])) for a, b in zip(x, y))
        for x, y in zip(*caches))
    lens = onp.asarray([56, 7, 0])
    for step in range(4):
        toks = [[seqs[0][56 + step]], [seqs[1][7 + step]], [0]]
        logits, batch = lm(nd(toks), batch, nd(lens + [step, step, 0]),
                           nd([1, 1, 0]))
    for i, s in enumerate(seqs):
        want = plain(params, TINY, s)[-1]
        assert onp.abs(onp.asarray(logits._data[i, 0]) - want).max() \
            <= 2e-4 * onp.abs(want).max()


def test_exit_pdf_is_the_reference_s_and_threshold_one_exits_last(tiny):
    lm, params = tiny
    seq = onp.random.RandomState(4).randint(1, VOCAB, size=20)
    want = onp.asarray(ref.exit_pdf(params, TINY, seq))
    got = onp.asarray(lm.exit_pdf(nd([seq, seq[::-1]]))._data)
    assert got.shape == (2, 20, R)
    onp.testing.assert_allclose(got[0], want, atol=1e-5)
    onp.testing.assert_allclose(got.sum(-1), 1.0, atol=1e-6)
    assert (want > 1e-4).all() and (want < 1 - 1e-4).all()     # gate worked
    assert (ref.exit_pass(want, TINY["early_exit_threshold"]) == R).all()
    early = ref.exit_pass(want, 0.5)
    assert early.min() < R and (early >= 1).all()


def test_an_early_exit_threshold_is_refused_by_name():
    with pytest.raises(ValueError, match="early_exit_threshold 0.9 < 1.*"
                                         "different passes"):
        get_model("ouro", config=dict(TINY, early_exit_threshold=0.9))
    with pytest.raises(ValueError, match="full_attention layers only"):
        get_model("ouro", config=dict(
            TINY, layer_types=["sliding_attention"] * 3))
    with pytest.raises(ValueError, match="attention_bias"):
        get_model("ouro", config=dict(
            TINY, assumed=dict(TINY["assumed"], attention_bias=True)))


def test_parameters_and_cache_of_the_published_sizes_count_as_reckoned():
    """2 667 974 657 parameters, held once whatever the passes, and
    1 572 864 B of cache a position (ISSUE 37): counted on the built
    model's shapes, nothing allocated."""
    config = _published()
    lm = get_model("ouro", config=config)
    params = lm.collect_params()
    assert sum(int(onp.prod(p.shape)) for p in params.values()) \
        == 2_667_974_657
    layer = sum(int(onp.prod(p.shape)) for k, p in params.items()
                if k.startswith("layers.0."))
    assert layer == 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416
    assert len(lm.layers) == 48 and lm.loops == 4
    leaves = jax.eval_shape(lambda: [
        [l._data for l in ls] for ls in lm.begin_cache(1, 384)])
    assert len(leaves) == 192
    assert {(l[0].shape, l[0].dtype) for l in leaves} == {
        ((1, 16, 384, 256), jnp.dtype(jnp.bfloat16))}
    slot = sum(int(onp.prod(l[0].shape)) * 2 for l in leaves)
    assert slot == 384 * 1_572_864 == 603_979_776


# ------------------------------------------------------- the loop's caches
def test_pass_r_reads_its_own_leaf_and_no_other(tiny):
    """A step against a prefilled cache with ONE entry poisoned: the passes
    before the poisoned one end where they ended, it and the passes after
    it do not, and every entry of another pass is appended as before."""
    lm, _ = tiny
    seq = onp.random.RandomState(8).randint(1, VOCAB, size=13)
    _, cache = _forward_in_chunks(lm, seq[:12], 16, 32)
    step = (nd([[seq[12]]]), nd([12]), nd([1]))

    def run(poisoned=None):
        tree = tuple(
            (NDArray(jnp.full_like(ls[0]._data, 1e3)),) if i == poisoned
            else ls for i, ls in enumerate(cache))
        ends, new, _ = lm.stack(step[0], tree, step[1], step[2])
        return ([onp.asarray(e._data) for e in ends],
                [onp.asarray(ls[0]._data) for ls in new])

    clean_ends, clean_new = run()
    for r in range(R):
        entry = r * L + 1                   # pass r's cache of layer 1
        ends, new = run(entry)
        for q in range(R):
            same = onp.array_equal(ends[q], clean_ends[q])
            assert same == (q < r), (r, q)
        for i in range(r * L):              # the earlier passes' entries
            onp.testing.assert_array_equal(new[i], clean_new[i])
        assert not onp.array_equal(new[entry], clean_new[entry])


@pytest.fixture(scope="module")
def entry():
    lm, _ = build()          # its own copy: the entry re-hybridizes it
    return serve.DecodeEntry("ouro_tiny", lm, slots=2,
                             prompt_buckets=(4, 8, 16),
                             capacity_buckets=(32, 64), max_new_tokens=6)


def test_the_serve_tier_carries_r_times_l_paged_leaves(entry):
    """``cache_spec`` reads nine paged leaves; the allocator's one program
    makes them, the mover ships them across buckets and the grower extends
    them, as it does a stack's walked once."""
    assert entry.cache_spec == ((CACHE_PAGED,),) * (R * L)
    assert entry.stack_passes == R and not entry.capacity_static
    leaf = 2 * 4 * 32 * 4                   # slots x heads x K‖V lanes x f32
    assert entry.cache_bytes(entry.block.begin_cache(2, 64)) \
        == {"paged": R * L * 64 * leaf, "window": 0, "state": 0}
    fresh = entry._fresh_row(32)
    assert len(fresh) == R * L
    assert all(ls[0].shape == (1, 4, 32, 32) and not onp.asarray(
        ls[0]._data).any() for ls in fresh)
    batch = _filled(entry.block.begin_cache(2, 64), 1)
    row = _filled(entry.block.begin_cache(1, 32), 2)
    want = [onp.asarray(ls[0]._data).copy() for ls in batch]
    rows = [onp.asarray(ls[0]._data) for ls in row]
    moved = entry.move(batch, row, 1)
    for got, w, r in zip(moved, want, rows):
        got = onp.asarray(got[0]._data)
        onp.testing.assert_array_equal(got[0], w[0])
        onp.testing.assert_array_equal(got[1, :, :32], r[0])
        onp.testing.assert_array_equal(got[1, :, 32:], w[1, :, 32:])
    small = _filled(entry.block.begin_cache(2, 32), 3)
    grown = entry.grow(small, 64)
    assert len(grown) == R * L
    for new, old in zip(grown, small):
        assert new[0].shape == (2, 4, 64, 32)
        onp.testing.assert_array_equal(new[0]._data[:, :, :32], old[0]._data)
        assert not onp.asarray(new[0]._data[:, :, 32:]).any()


def test_a_stack_walked_once_keeps_its_tree_and_its_cell():
    """Without loops or post-norms ``MixerLM`` builds what it built before:
    one cache entry a layer, two norms a cell, no pass counted twice."""
    for name, config in (("mellum", MELLUM_TINY), ("kimi_linear", KIMI_TINY)):
        lm = get_model(name, config=config, dtype=jnp.float32)
        n = config["num_hidden_layers"]
        assert lm.loops == 1 and len(lm.layers) == n
        assert len(lm.cache_kinds()) == n
        assert len(jax.eval_shape(lambda: [
            [l._data for l in ls] for ls in lm.begin_cache(1, 8)])) == n
        cell = lm.layers[0]
        assert cell.post_mixer is None and cell.post_ffn is None
        # no name for the matrix products of a stack walked once
        assert getattr(cell.mixer, "dense_scope", None) is None
        assert getattr(cell.ffn, "dense_scope", None) is None
        assert {k.split(".")[0] for k in cell.collect_params()
                if k.endswith("gamma") and k.count(".") == 1} \
            == {"ln_mixer", "ln_ffn"}
    with pytest.raises(ValueError, match="at least once"):
        mixer_lm.MixerLM(8, 8, 1e-6, jnp.float32, [], loops=0)
    looped = get_model("ouro", config=TINY, dtype=jnp.float32).layers[0]
    assert looped.mixer.dense_scope == looped.ffn.dense_scope == "loop_dense"


def test_a_cache_of_another_depth_is_refused(tiny):
    lm, _ = tiny
    with pytest.raises(ValueError, match=r"3 entries for 3 pass\(es\) over 3"):
        lm.stack(nd([[1]]), lm.begin_cache(1, 8)[:L], nd([0]), nd([1]))


# ------------------------------------------------------------- serve tier
def test_served_through_the_one_decode_server(entry, tiny, fresh_telemetry):
    """Prompts up to 40 tokens on prompt buckets (4, 8, 16): chunked
    admission, the batch grown 32 -> 64 on the way, steps run ahead; every
    chosen token agrees with the reference by the check that decides a
    cell's ``correct``; nothing compiles after the registration warm-up;
    ``serve.stack_passes`` counts ``R`` a step and a piece."""
    _, params = tiny
    compiles = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    srv = serve.DecodeServer(entry)
    rs = onp.random.RandomState(5)
    sizes, outs = (40, 3, 33, 16), (6, 6, 2, 5)
    prompts = [rs.randint(1, VOCAB, size=n).tolist() for n in sizes]
    try:
        futs = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, outs)]
        for p, n, f in zip(prompts, outs, futs):
            got = f.result(300.0)
            assert len(got) == n and not f.truncated
        assert not compiles
        for p, n, f in zip(prompts, outs, futs):
            got = f.result(0)
            want = plain(params, TINY, onp.asarray(p + got, "int32"))
            ok, worst, _ = checks.greedy_agrees(want, len(p), got,
                                                ref.LOGIT_RTOL)
            assert ok and worst <= 1e-4, (len(p), worst)
        snap = tel.snapshot()
        pieces = 3 + 1 + 3 + 1
        assert snap["serve.prefill_chunks"]["value"] == pieces
        assert snap["serve.prefill_forward_seconds"]["count"] == pieces
        steps = snap["serve.decode_step_seconds"]["count"]
        assert snap["serve.stack_passes"]["value"] == R * (steps + pieces)
        assert snap["serve.steps_run_ahead"]["value"] > 0
        assert snap["serve.cache_grows"]["value"] == 1
        assert snap["serve.cache_paged_bytes"]["value"] \
            == R * L * 2 * 4 * 64 * 32 * 4
    finally:
        srv.close(60.0)


def test_the_prefix_cache_takes_the_tree(entry):
    """Every leaf is paged, so ``DecodeServer`` accepts a ``PrefixCache``
    for this tree (the first ``MixerLM`` family for which it does), and a
    prompt that shares a served one's beginning gives the tokens it gives
    alone."""
    rs = onp.random.RandomState(6)
    head = rs.randint(1, VOCAB, size=24).tolist()
    tails = [rs.randint(1, VOCAB, size=5).tolist() for _ in range(2)]
    alone = serve.DecodeServer(entry)
    try:
        want = [alone.generate(head + t, timeout=300.0) for t in tails]
    finally:
        alone.close(60.0)
    srv = serve.DecodeServer(entry, prefill_workers=1, prefix_cache=True)
    try:
        got = [srv.generate(head + t, timeout=300.0) for t in tails]
    finally:
        srv.close(60.0)
    assert got == want


# ------------------------------------------------------------- LOGIT_RTOL
@pytest.fixture(scope="module")
def long_case(tiny):
    _, params = tiny
    seq = onp.random.RandomState(9).randint(1, VOCAB, size=96)
    return params, seq, plain(params, TINY, seq)


def test_the_sound_program_is_far_inside_logit_rtol(tiny, long_case):
    lm, _ = tiny
    _, seq, want = long_case
    got, _ = _forward_in_chunks(lm, seq, 16, 128)
    chosen = got[31:].argmax(-1)
    assert greedy_gap(want, 32, chosen[:-1]) <= ref.LOGIT_RTOL / 100


@pytest.mark.parametrize("fault", [
    "no_post_norm", "no_pass_norm", "one_pass_short", "neighbour_cache"])
def test_a_wrong_model_fails_logit_rtol(long_case, fault):
    """The reference computed WRONG on purpose, its own greedy tokens held
    to the right reference as the benchmark holds the server's: a branch
    added without the norm on its output, the next pass started from the
    stream and not from the final norm of it, one pass fewer, a pass
    attending to the keys and values of the pass before it -- by
    ``LOGIT_RTOL`` alone (no served block)."""
    params, seq, want = long_case
    wrong = plain(params, TINY, seq, fault=fault)
    assert greedy_gap(want, 32, wrong[31:-1].argmax(-1)) > ref.LOGIT_RTOL


def test_logit_rtol_cannot_hold_the_stream_s_precision(long_case):
    """The reference's own bf16 stream (fault ``stream_bf16``) chooses the
    right tokens here, and at the published depth reads what a sound bf16
    server reads (PERF.md section 6, PR 37): why the precision is held on
    the served block, by the two limits below."""
    params, seq, want = long_case
    rounded = plain(params, TINY, seq, fault="stream_bf16")
    assert greedy_gap(want, 32, rounded[31:-1].argmax(-1)) < ref.LOGIT_RTOL


def test_unknown_fault_is_refused(long_case):
    params, seq, _ = long_case
    with pytest.raises(ValueError, match="unknown fault"):
        ref.logits(params, TINY, seq[:8], fault="nope", served=None)


# ------------------------------------- STREAM_RTOL, LEAF_SHARE: the served
def _bf16(x):
    """Rounded to bf16 and kept in float32: what a bf16 residual stream
    holds (``reduce_precision``: a cast there and back is the compiler's to
    drop)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _judged(lm, params, seq, want, capfd):
    """The check that decides ``correct`` on ``lm`` as the served block:
    ``(logits, verdict, stderr)``."""
    got = onp.asarray(ref.logits(params, TINY, seq, served=lm))
    ok, worst, _ = checks.greedy_agrees(got, 32, want[31:-1].argmax(-1),
                                        ref.LOGIT_RTOL)
    return got, (ok, worst), capfd.readouterr().err


def test_the_served_block_holds_both_limits(tiny, long_case, capfd):
    """The sound program, judged on the block that was built and the
    programs compiled from it: the logits are the plain reference's, both
    limits read rounding (float32 here), and the programs are read once a
    block."""
    lm, params = tiny
    _, seq, want = long_case
    got, verdict, err = _judged(lm, params, seq, want, capfd)
    onp.testing.assert_array_equal(got, want)
    assert verdict == (True, 0.0)
    assert "STREAM_RTOL" in err and "LEAF_SHARE" in err
    assert "NOT HELD" not in err
    for name in ("prefill 8", "prefill 16", "step 3"):      # deployment.served
        assert name in err
    _, _, err = _judged(lm, params, seq[:60], want, capfd)
    assert "STREAM_RTOL" in err and "LEAF_SHARE" not in err


def test_a_bf16_stream_where_a_branch_is_added_fails_stream_rtol(
        long_case, monkeypatch, capfd):
    """The control: the PROGRAM with its residual stream in bf16 wherever a
    branch is added (``mixer_lm._branch``, which the mixer's and the FFN's
    calls add through).  The served block's own cell reads it against the
    float32 layer, the logits come back NaN and ``greedy_agrees`` says not
    correct -- by ``STREAM_RTOL`` and not by ``LEAF_SHARE``: the programs
    are the composition of those cells all the same."""
    sound = mixer_lm._branch

    def rounding(x, y, post, eps):
        return _bf16(sound(_bf16(x), y, post, eps))

    monkeypatch.setattr(mixer_lm, "_branch", rounding)
    monkeypatch.setattr(mellum, "_branch", rounding)
    lm, params = build()
    _, seq, want = long_case
    got, verdict, err = _judged(lm, params, seq, want, capfd)
    assert onp.isnan(got).all() and verdict == (False, float("inf"))
    stream, programs = (next(l for l in err.splitlines() if name in l)
                        for name in ("STREAM_RTOL", "LEAF_SHARE"))
    assert "NOT HELD" in stream and "NOT HELD" not in programs


def test_a_bf16_stream_between_the_cells_fails_leaf_share(long_case, capfd):
    """The other control: every cell sound, and the STACK rounds the stream
    to bf16 between them (what a bf16 stream in ``MixerLM.stack``, at a call
    boundary or in the compiled step would do).  The cells hold
    ``STREAM_RTOL``; the served prefill and step programs leave the chain
    of their own cells, and the run is not correct by ``LEAF_SHARE``."""
    from mxnet_tpu.gluon.model_zoo.ouro import OuroLM
    from mxnet_tpu.ops.dispatch import call

    class RoundingStack(OuroLM):
        def stack(self, tokens, cache, cache_len, n_tokens):
            sound = mixer_lm.MixerCell.forward

            def forward(cell, x, leaves, step):
                x, leaves, counts = sound(cell, x, leaves, step)
                return call(_bf16, (x,), {}, name="round"), leaves, counts

            mixer_lm.MixerCell.forward = forward
            try:
                return super().stack(tokens, cache, cache_len, n_tokens)
            finally:
                mixer_lm.MixerCell.forward = sound

    mx.random.seed(3)
    lm = RoundingStack(config=TINY, dtype=jnp.float32)
    lm.initialize()
    lm.hybridize()
    params, seq, want = long_case
    got, verdict, err = _judged(lm, params, seq, want, capfd)
    assert onp.isnan(got).all() and verdict == (False, float("inf"))
    stream, programs = (next(l for l in err.splitlines() if name in l)
                        for name in ("STREAM_RTOL", "LEAF_SHARE"))
    assert "NOT HELD" not in stream and "NOT HELD" in programs


def test_a_cell_that_hands_back_another_dtype_is_not_held(tiny, long_case,
                                                          monkeypatch):
    """A stream that is bf16 by dtype has no float32 entries to compare:
    the reading is infinite, not a cast away."""
    lm, params = tiny
    _, seq, _ = long_case
    sound = mixer_lm.MixerCell.forward

    def forward(cell, x, leaves, step):
        x, leaves, counts = sound(cell, x, leaves, step)
        return x.astype("bfloat16"), leaves, counts

    monkeypatch.setattr(mixer_lm.MixerCell, "forward", forward)
    _, gaps = ref._forward(params, TINY, seq[:40], None, lm)
    assert gaps == [float("inf")] * R


def test_the_cell_under_test_runs_at_the_server_s_precision(tiny, long_case,
                                                            monkeypatch):
    """Outside the reference's own ``highest``: the decode kernel takes bf16
    operands into the MXU, and traced under ``highest`` Mosaic refuses it on
    the chip (PERF.md section 6, PR 33)."""
    lm, params = tiny
    _, seq, _ = long_case
    seen, real = [], ref.cell_gap

    def spy(*args):
        seen.append(jax.config.jax_default_matmul_precision)
        return real(*args)

    monkeypatch.setattr(ref, "cell_gap", spy)
    ref._forward(params, TINY, seq[:40], None, lm)
    assert seen == [None] * R                   # the last layer of each pass


def test_a_call_s_arguments_die_with_their_last_reference():
    """``gluon/block.py`` flattens a hybridized call's arguments without a
    closure that names itself, so an admission's row cache -- 192 leaves,
    0.6 GB at the published sizes -- is freed when the loop drops it and
    not when the cycle collector next runs (PERF.md section 6, PR 37: 1 to
    6 dead row caches stood in the chip's memory at a window's end)."""
    import gc
    import weakref

    from mxnet_tpu.gluon.block import _flatten_nd, _unflatten_nd

    gc.collect()
    gc.disable()
    try:
        leaf = nd(onp.zeros((2, 3)))
        seen = weakref.ref(leaf)
        leaves, tree = _flatten_nd(((leaf, None), {"n": 3}))
        back = _unflatten_nd(tree, leaves)
        assert back[0][0] is leaf and back[1] == {"n": 3}
        del leaf, leaves, tree, back
        assert seen() is None
    finally:
        gc.enable()
