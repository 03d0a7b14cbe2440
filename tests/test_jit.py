"""mx.jit — persistent compilation cache, shape bucketing, AOT warmup
(ISSUE 5).

The contract under test: a variable-shape workload compiles at most
``len(buckets)`` XLA programs (not one per shape); bucketed/padded
computation matches the unpadded computation exactly under the mask;
``warmup()`` / ``ShardedTrainer.compile()`` leave zero compiles for the
first real call; and the persistent cache arms lazily without fighting
an explicitly configured jax cache.
"""
from __future__ import annotations

import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
from mxnet_tpu.jit import ShapeBucketer
from mxnet_tpu.jit import cache as jit_cache

np_ = mx.np


def N(x):
    return x.asnumpy() if hasattr(x, "asnumpy") else onp.asarray(x)


@pytest.fixture()
def fresh_telemetry():
    prev = tel.set_enabled(True)
    tel.reset()
    yield
    tel.reset()
    tel.set_enabled(prev)


# ---------------------------------------------------------------------------
# ShapeBucketer unit behavior
# ---------------------------------------------------------------------------

def test_bucketer_policies():
    b = ShapeBucketer({0: [8, 32], 1: "pow2", 2: ("linear", 16)})
    assert b.bucket_shape((5, 9, 17)) == (8, 16, 32)
    assert b.bucket_shape((8, 16, 32)) == (8, 16, 32)  # exact: no-op
    with pytest.raises(MXNetError):
        b.bucket_shape((33, 1, 1))  # beyond the largest explicit bucket


def test_bucketer_bounded_enumeration():
    b = ShapeBucketer({1: ("pow2", 8, 64)})
    assert b.expand((4, 17)) == [(4, 8), (4, 16), (4, 32), (4, 64)]
    lin = ShapeBucketer({0: ("linear", 16, 16, 48)})
    assert lin.expand((10,)) == [(16,), (32,), (48,)]
    # unbounded policy degrades to the observed shape's own bucket
    unb = ShapeBucketer({0: "pow2"})
    assert unb.expand((10, 3)) == [(16, 3)]


def test_bucketer_pad_and_mask():
    b = ShapeBucketer({0: [8]})
    arr = onp.arange(12, dtype="f4").reshape(3, 4)
    padded, mask = b.pad(arr)
    assert padded.shape == (8, 4) and mask.shape == (8,)
    assert mask[:3].all() and not mask[3:].any()
    onp.testing.assert_array_equal(padded[:3], arr)
    assert (padded[3:] == 0).all()
    # seq bucketing masks per-token: (B_pad, T_pad), loss-aligned
    sb = ShapeBucketer({0: [4], 1: [8]})
    _, m2 = sb.pad(onp.ones((3, 5), "f4"))
    assert m2.shape == (4, 8) and m2.sum() == 15


def test_bucketer_pad_batch_masks_from_data_leaf():
    b = ShapeBucketer({0: [8]})
    x = onp.ones((5, 4), "f4")
    y = onp.arange(5, dtype="i4")
    (px, py), mask = b.pad_batch((x, y))
    assert px.shape == (8, 4) and py.shape == (8,)
    assert mask.shape == (8,) and mask.sum() == 5
    assert (py[5:] == 0).all()


def test_bucketer_invalid_specs():
    for bad in ({}, {0: []}, {0: "nope"}, {-1: [4]}, {0: ("linear", 0)}):
        with pytest.raises(MXNetError):
            ShapeBucketer(bad)


def test_bucketer_unaligned_lo_snaps_to_grid():
    # regression: an off-grid lo made bucket() and enumerate() disagree,
    # so the AOT warmup grid (expand) missed bucket shapes real calls
    # produce and the at-most-len(buckets) compile bound broke
    p = ShapeBucketer({1: ("pow2", 12, 64)})
    assert p.expand((4, 20)) == [(4, 16), (4, 32), (4, 64)]
    assert p.bucket_shape((4, 5)) == (4, 16)    # was (4, 12): off-grid
    lin = ShapeBucketer({1: ("linear", 16, 8, 128)})
    assert lin.bucket_shape((4, 20)) == (4, 32)
    assert (4, 32) in lin.expand((4, 20))       # grid anchored at 16
    for sz in range(1, 129):
        assert lin.bucket_shape((1, sz))[1] in \
            {s[1] for s in lin.expand((1, sz))}
    # lo rounding up past hi leaves no buckets: loud at construction
    with pytest.raises(MXNetError):
        ShapeBucketer({0: ("pow2", 33, 40)})
    with pytest.raises(MXNetError):
        ShapeBucketer({0: ("linear", 16, 120, 127)})


# ---------------------------------------------------------------------------
# pad_requests — the serve coalescer's growth path (ISSUE 9 satellite)
# ---------------------------------------------------------------------------

def test_pad_requests_single_leaf_ragged():
    b = ShapeBucketer({0: [4, 8], 1: ("pow2", 4, 16)})
    reqs = [onp.arange(1, 4, dtype="f4"), onp.arange(1, 7, dtype="f4"),
            onp.arange(1, 10, dtype="f4")]
    batch, mask, slices = b.pad_requests(reqs)
    assert batch.shape == (4, 16)  # 3 reqs -> 4 rows, max len 9 -> 16
    assert mask.shape == (4, 16)
    # slices recover each request bit-for-bit; padding is pad_value
    for r, sl in zip(reqs, slices):
        assert onp.array_equal(batch[sl], r)
    assert batch.sum() == sum(r.sum() for r in reqs)  # zeros elsewhere
    # mask is per-ROW ragged validity: exactly the real elements
    assert mask.sum() == sum(len(r) for r in reqs)
    assert not mask[3].any()                  # padding row all-False
    assert mask[0, :3].all() and not mask[0, 3:].any()


def test_pad_requests_tuple_leaves_and_scalars():
    """BERT-shaped requests: (tokens (T,), segments (T,), valid ())."""
    b = ShapeBucketer({0: [2, 4], 1: ("pow2", 8, 8)})
    reqs = [(onp.full((3,), 7, "int32"), onp.zeros((3,), "int32"),
             onp.asarray(3, "int32")),
            (onp.full((5,), 9, "int32"), onp.ones((5,), "int32"),
             onp.asarray(5, "int32")),
            (onp.full((8,), 2, "int32"), onp.zeros((8,), "int32"),
             onp.asarray(8, "int32"))]
    batch, mask, slices = b.pad_requests(reqs)
    assert isinstance(batch, tuple) and len(batch) == 3
    tok, seg, vl = batch
    assert tok.shape == seg.shape == (4, 8)
    assert vl.shape == (4,)                    # scalars stack to rows
    assert vl.tolist() == [3, 5, 8, 0]
    assert mask.shape == (4, 8)
    for r, sl in zip(reqs, slices):
        assert onp.array_equal(tok[sl], r[0])  # slices index the
        assert len(sl) == 2                    # reference (data) leaf


def test_pad_requests_with_mask_false_skips_mask():
    b = ShapeBucketer({0: [4], 1: ("pow2", 4, 8)})
    reqs = [onp.ones((3,), "f4"), onp.ones((5,), "f4")]
    batch, mask, slices = b.pad_requests(reqs, with_mask=False)
    assert mask is None
    wb, wm, wsl = b.pad_requests(reqs)  # batch and slices unchanged
    assert onp.array_equal(batch, wb) and wm is not None
    assert slices == wsl


def test_pad_requests_axis0_only_spec():
    b = ShapeBucketer({0: [8]})
    reqs = [onp.full((2, 3), i, "f4") for i in range(3)]
    batch, mask, slices = b.pad_requests(reqs)
    assert batch.shape == (8, 2, 3)
    assert mask.shape == (8,)                  # loss-aligned truncation
    assert mask.tolist() == [True] * 3 + [False] * 5
    assert onp.array_equal(batch[slices[1]], reqs[1])


def test_pad_requests_errors():
    b = ShapeBucketer({0: [4]})
    with pytest.raises(MXNetError, match="non-empty"):
        b.pad_requests([])
    with pytest.raises(MXNetError, match="leaf count"):
        b.pad_requests([(onp.zeros(2),), (onp.zeros(2), onp.zeros(2))])
    with pytest.raises(MXNetError, match="rank"):
        b.pad_requests([onp.zeros((2,)), onp.zeros((2, 2))])
    with pytest.raises(MXNetError, match="dtype"):
        b.pad_requests([onp.zeros(2, "f4"), onp.zeros(2, "i4")])
    # ragged on an axis with no bucket policy: no single batch shape
    with pytest.raises(MXNetError, match="no bucket policy"):
        b.pad_requests([onp.zeros((2,), "f4"), onp.zeros((3,), "f4")])
    # beyond the largest batch bucket: the policy's own loud error
    with pytest.raises(MXNetError, match="exceeds"):
        b.pad_requests([onp.zeros((2,), "f4")] * 5)


def test_axis_bound():
    b = ShapeBucketer({0: [4, 16], 1: ("pow2", 8, 64), 2: "pow2",
                       3: ("linear", 16, 16, 48)})
    assert b.axis_bound(0) == 16     # explicit: largest bucket
    assert b.axis_bound(1) == 64     # bounded pow2: largest grid bucket
    assert b.axis_bound(2) is None   # unbounded
    assert b.axis_bound(3) == 48     # bounded linear: largest bucket
    assert b.axis_bound(9) is None   # unbucketed axis
    # off-grid hi: the bound is the largest bucket the GRID holds (a raw
    # hi of 20 would admit 17..20-row batches that bucket() then rejects)
    off = ShapeBucketer({0: ("pow2", 8, 20)})
    assert off.axis_bound(0) == 16
    off.spec[0].bucket(off.axis_bound(0))  # the bound itself is padabble


# ---------------------------------------------------------------------------
# numeric equivalence: padded+masked == unpadded (the acceptance bar)
# ---------------------------------------------------------------------------

def _lenet():
    mx.random.seed(0)
    net = mx.gluon.model_zoo.get_model("lenet")
    net.initialize(mx.init.Xavier())
    net(np_.zeros((2, 1, 28, 28)))
    return net


def test_lenet_batch_pad_matches_unpadded():
    """Batch padding: every per-sample op (conv/pool/dense) reduces only
    within a sample, so rows 0..16 of the padded batch must reproduce
    the unpadded forward.  Tolerance is a few float32 ULPs, not zero:
    XLA:CPU picks shape-dependent GEMM/conv blocking, so batch-32 and
    batch-17 executables may round one accumulation differently — a
    real padding-contamination bug shows up ~1e-1, six orders louder."""
    net = _lenet()
    rs = onp.random.RandomState(3)
    x = rs.rand(17, 1, 28, 28).astype("f4")
    eager = N(net(np_.array(x)))             # eager, unpadded
    net.hybridize()
    net.warmup((17, 1, 28, 28))
    ref = N(net(np_.array(x)))               # jit, unpadded
    net.hybridize(bucketer={0: [32]})
    net.warmup((32, 1, 28, 28))
    out = N(net(np_.array(x)))               # jit, padded to 32 + sliced
    assert out.shape == (17, 10)
    onp.testing.assert_allclose(out, ref, rtol=3e-7, atol=3e-8)
    onp.testing.assert_allclose(out, eager, rtol=1e-6, atol=1e-7)


def test_lstm_seqlen_pad_matches_unpadded():
    """Seq-len padding: the LSTM is causal over time, so outputs at
    t < T_orig cannot depend on the zero-padded tail.  Tolerance is a
    few ULPs for the same shape-dependent-blocking reason as the LeNet
    case above."""
    mx.random.seed(1)
    from mxnet_tpu.gluon import rnn

    class LM(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.embedding = nn.Embedding(50, 8)
            self.lstm = rnn.LSTM(8, num_layers=1)
            self.decoder = nn.Dense(50, flatten=False)

        def forward(self, x):                    # (B, T) tokens
            e = self.embedding(x).transpose(1, 0, 2)
            return self.decoder(self.lstm(e)).transpose(1, 0, 2)

    net = LM()
    net.initialize(mx.init.Xavier())
    net(np_.zeros((2, 8), dtype="int32"))
    rs = onp.random.RandomState(5)
    toks = rs.randint(0, 50, size=(4, 17)).astype("i4")
    eager = N(net(np_.array(toks)))
    net.hybridize()
    net.warmup(((4, 17), "int32"))
    ref = N(net(np_.array(toks)))            # jit, unpadded
    net.hybridize(bucketer={1: [32]})
    net.warmup(((4, 32), "int32"))
    out = N(net(np_.array(toks)))            # jit, padded to 32 + sliced
    assert out.shape == (4, 17, 50)
    onp.testing.assert_allclose(out, ref, rtol=3e-7, atol=3e-8)
    onp.testing.assert_allclose(out, eager, rtol=1e-5, atol=1e-6)


def test_multi_input_ambiguous_axis_left_padded():
    """Two inputs padding the same axis to DIFFERENT (orig, padded)
    sizes: the inverse mapping is ambiguous, so outputs keep their
    padded size (documented) instead of being sliced wrong — and the
    valid rows still match the eager forward exactly."""
    mx.random.seed(0)

    class TwoHead(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.a = nn.Dense(3)
            self.b = nn.Dense(3)

        def forward(self, x, z):
            return self.a(x), self.b(z)

    net = TwoHead()
    net.initialize(mx.init.Xavier())
    net(np_.ones((1, 4)), np_.ones((1, 4)))
    rs = onp.random.RandomState(0)
    x = rs.rand(7, 4).astype("f4")
    z = rs.rand(3, 4).astype("f4")
    ref = [N(o) for o in net(np_.array(x), np_.array(z))]
    net.hybridize(bucketer={0: [16]})
    net.warmup((np_.array(x), np_.array(z)))
    out = net(np_.array(x), np_.array(z))
    # both padded to 16, (7,16)/(3,16) ambiguous -> stays padded
    assert out[0].shape == (16, 3) and out[1].shape == (16, 3)
    onp.testing.assert_allclose(N(out[0])[:7], ref[0], rtol=3e-7,
                                atol=3e-8)
    onp.testing.assert_allclose(N(out[1])[:3], ref[1], rtol=3e-7,
                                atol=3e-8)
    # same axis, same size on every leaf: unambiguous -> sliced back
    out2 = net(np_.array(x), np_.array(x))
    assert out2[0].shape == (7, 3) and out2[1].shape == (7, 3)


def test_dataloader_masked_loss_matches_unpadded(fresh_telemetry):
    """The DataLoader seam: padded batch + mask-weighted loss must equal
    the unpadded loss exactly (LeNet partial tail)."""
    net = _lenet()
    rs = onp.random.RandomState(7)
    x = rs.rand(11, 1, 28, 28).astype("f4")
    y = rs.randint(0, 10, size=(11,)).astype("i4")

    loader = DataLoader(ArrayDataset(x, y), batch_size=16,
                        last_batch="keep", bucket_spec={})
    (xb, yb, mask) = next(iter(loader))
    m = N(mask).astype("f4")
    out_p = N(net(xb))

    # per-sample NLL, computed in numpy from the logits
    def per_sample(logits, labels):
        z = logits - logits.max(-1, keepdims=True)
        logp = z - onp.log(onp.exp(z).sum(-1, keepdims=True))
        return -logp[onp.arange(len(labels)), labels]

    ref = per_sample(N(net(np_.array(x))), y).mean()
    padded = per_sample(out_p, N(yb).astype("i8"))
    masked = (padded * m).sum() / m.sum()
    onp.testing.assert_allclose(masked, ref, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# bounded compiles: the J001-storm killer
# ---------------------------------------------------------------------------

def test_varlen_stream_compiles_once_per_bucket(fresh_telemetry):
    """Lengths 17..64 through a pow2 bucketer: total compiles == number
    of buckets (2: 32 and 64), not number of distinct lengths (48)."""
    mx.random.seed(2)

    class Tagger(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.embedding = nn.Embedding(100, 16)
            self.dense = nn.Dense(5, flatten=False)

        def forward(self, x):
            return self.dense(self.embedding(x))

    net = Tagger()
    net.initialize(mx.init.Xavier())
    net(np_.zeros((2, 8), dtype="int32"))
    bucketer = ShapeBucketer({1: ("pow2", 32, 64)})
    net.hybridize(bucketer=bucketer)
    n = net.warmup(((2, 17), "int32"))
    assert n == bucketer.n_buckets((2, 17)) == 2
    rs = onp.random.RandomState(0)
    tel.reset()
    for length in range(17, 65):
        toks = rs.randint(0, 100, size=(2, length)).astype("i4")
        out = net(np_.array(toks))
        assert out.shape == (2, length, 5)
    snap = tel.snapshot()
    assert snap.get("hybridize.cache_misses", {}).get("value", 0) == 0, \
        "warmed buckets must absorb every length with zero new compiles"
    assert len(net._cached_op._traced) == 2
    assert snap["hybridize.cache_hits"]["value"] == 48


def test_warmup_then_call_zero_additional_misses(fresh_telemetry):
    net = _lenet()
    net.hybridize()
    assert net.warmup((8, 1, 28, 28)) == 1
    snap = tel.snapshot()
    misses0 = snap["hybridize.cache_misses"]["value"]
    assert snap["hybridize.warmup_compiles"]["value"] == 1
    assert snap["jit.warmup_seconds"]["count"] == 1
    out = net(np_.zeros((8, 1, 28, 28)))
    assert out.shape == (8, 10)
    snap = tel.snapshot()
    assert snap["hybridize.cache_misses"]["value"] == misses0
    assert snap["hybridize.cache_hits"]["value"] >= 1
    # repeated warmup on a compiled signature is free
    assert net.warmup((8, 1, 28, 28)) == 0


def test_warmup_background_handle(fresh_telemetry):
    net = _lenet()
    net.hybridize()
    h = net.warmup([(4, 1, 28, 28), (8, 1, 28, 28)], background=True)
    assert h.wait(300) == 2
    assert h.done()
    tel.reset()
    net(np_.zeros((4, 1, 28, 28)))
    assert tel.snapshot().get("hybridize.cache_misses",
                              {}).get("value", 0) == 0


def test_warmup_requires_hybridize():
    net = _lenet()
    with pytest.raises(MXNetError):
        net.warmup((2, 1, 28, 28))


def test_warmup_train_mode_compiles_training_graph(fresh_telemetry):
    """Dropout nets: train and eval are distinct graphs; warmup must be
    able to pre-compile the training one."""
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16), nn.Dropout(0.5))
    net.initialize()
    net(np_.ones((2, 8)))
    net.hybridize()
    net.warmup((4, 8), train_mode=True)
    tel.reset()
    with mx.autograd.record(train_mode=True):
        out = net(np_.ones((4, 8)))
    assert (N(out) == 0).any()  # dropout actually masked
    assert tel.snapshot().get("hybridize.cache_misses",
                              {}).get("value", 0) == 0


# ---------------------------------------------------------------------------
# DataLoader epoch-tail regression (satellite #1)
# ---------------------------------------------------------------------------

def test_partial_tail_compile_count_flat_across_epochs(fresh_telemetry):
    net = _lenet()
    net.hybridize()
    rs = onp.random.RandomState(0)
    x = rs.rand(50, 1, 28, 28).astype("f4")
    y = rs.randint(0, 10, size=(50,)).astype("i4")
    loader = DataLoader(ArrayDataset(x, y), batch_size=16,
                        last_batch="keep", bucket_spec={})
    seen = set()
    for _epoch in range(2):
        for xb, yb, mask in loader:
            seen.add(tuple(xb.shape))
            net(xb)
    snap = tel.snapshot()
    assert seen == {(16, 1, 28, 28)}
    assert snap["hybridize.cache_misses"]["value"] == 1, \
        "the epoch tail must reuse the full-batch program"
    assert snap["dataloader.padded_batches"]["value"] == 2  # one per epoch


def test_bucketed_loader_with_workers_pads_in_consumer(fresh_telemetry):
    x = onp.arange(40, dtype="f4").reshape(10, 4)
    y = onp.arange(10, dtype="i4")
    with DataLoader(ArrayDataset(x, y), batch_size=4, last_batch="keep",
                    num_workers=2, bucket_spec={}) as loader:
        batches = list(loader)
    assert len(batches) == 3
    for xb, yb, mask in batches:
        assert xb.shape == (4, 4) and mask.shape == (4,)
    # tail: 2 real rows
    assert N(batches[-1][2]).sum() == 2


def test_explicit_bucketer_instance_respected():
    x = onp.ones((10, 4), "f4")
    b = ShapeBucketer({0: [4, 8]})
    loader = DataLoader(ArrayDataset(x), batch_size=3, last_batch="keep",
                        bucket_spec=b)
    shapes = {tuple(batch[0].shape) for batch in loader}
    assert shapes == {(4, 4)}  # 3-row batches pad to the 4-bucket


# ---------------------------------------------------------------------------
# ShardedTrainer.compile (AOT step)
# ---------------------------------------------------------------------------

def _trainer(net=None, **kw):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    def ce(pred, y):
        logp = jax.nn.log_softmax(pred.astype(jnp.float32))
        return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]

    if net is None:
        net = _lenet()
    mesh = make_mesh({"dp": -1}, devices=jax.devices()[:1])
    return ShardedTrainer(net, ce, mesh=mesh, optimizer="sgd",
                          learning_rate=0.05, momentum=0.9, **kw)


def test_trainer_compile_then_step_no_new_compiles(fresh_telemetry):
    rs = onp.random.RandomState(0)
    x = rs.rand(8, 1, 28, 28).astype("f4")
    y = rs.randint(0, 10, size=(8,)).astype("i4")
    ref = _trainer()
    want = [float(ref.step(x, y)) for _ in range(3)]

    tr = _trainer()
    tel.reset()
    assert tr.compile((x, y)) == 1
    snap = tel.snapshot()
    assert snap["hybridize.warmup_compiles"]["value"] == 1
    compile_count = snap["hybridize.compile_seconds"]["count"]
    got = [float(tr.step(x, y)) for _ in range(3)]
    snap = tel.snapshot()
    assert snap["hybridize.compile_seconds"]["count"] == compile_count, \
        "AOT-compiled steps must not compile again"
    assert got == want, "AOT step must be bit-identical to the jit step"
    # recompiling the same batch signature is free
    assert tr.compile((x, y)) == 0


def test_trainer_compile_shape_mismatch_falls_back(fresh_telemetry):
    rs = onp.random.RandomState(0)
    x = rs.rand(8, 1, 28, 28).astype("f4")
    y = rs.randint(0, 10, size=(8,)).astype("i4")
    tr = _trainer()
    tr.compile((x, y))
    # a different batch size misses the AOT signature and takes the jit
    # path — correctness over speed
    loss = float(tr.step(x[:4], y[:4]))
    assert onp.isfinite(loss)
    loss2 = float(tr.step(x, y))  # AOT signature still dispatches
    assert onp.isfinite(loss2)


def test_trainer_compile_grad_accum(fresh_telemetry):
    rs = onp.random.RandomState(0)
    x = rs.rand(8, 1, 28, 28).astype("f4")
    y = rs.randint(0, 10, size=(8,)).astype("i4")
    mx.random.seed(0)
    ref = _trainer(grad_accum=2)
    want = [float(ref.step(x, y)) for _ in range(4)]
    mx.random.seed(0)
    tr = _trainer(grad_accum=2)
    assert tr.compile((x, y)) == 2   # grad + apply executables
    got = [float(tr.step(x, y)) for _ in range(4)]
    assert got == want


def test_trainer_compile_rejects_bad_batch():
    tr = _trainer()
    with pytest.raises(MXNetError):
        tr.compile(onp.ones((2, 1, 28, 28), "f4"))


@pytest.fixture
def armed_cache(monkeypatch, tmp_path):
    """The persistent cache armed at ``tmp_path/jitcache`` the one way it
    is placed from outside (JAX_COMPILATION_CACHE_DIR); conftest turns it
    off for the rest of the suite.  Restores jax's config afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    d = str(tmp_path / "jitcache")
    monkeypatch.setenv("MXNET_COMPILE_CACHE", "1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    prev = jax.config.jax_compilation_cache_dir
    prev_canon = jax.config.jax_hlo_source_file_canonicalization_regex
    jit_cache.reset()
    try:
        yield d
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          prev_canon)
        cc.reset_cache()
        jit_cache.reset()


def test_resume_with_persistent_cache_identical_trajectory(tmp_path,
                                                           armed_cache):
    """Regression: save → load into a fresh trainer → step, with the
    persistent cache armed.  The fresh trainer's step executable comes
    back DESERIALIZED from the cache, and on XLA:CPU a deserialized
    executable mishandles donated-buffer aliasing — params silently
    filled with garbage (~1e6) on the second post-resume step until
    make_train_step learned to drop donation on cpu-with-cache.  The
    trajectory must match the uninterrupted run exactly."""
    import jax.numpy as jnp

    assert jit_cache.ensure_cache() == armed_cache
    f = str(tmp_path / "ckpt.npz")
    rs = onp.random.RandomState(0)
    x = rs.rand(8, 1, 28, 28).astype("f4")
    y = rs.randint(0, 10, size=(8,)).astype("i4")
    tr = _trainer()
    for _ in range(2):
        tr.step(x, y)
    tr.save_states(f)
    ref = [float(tr.step(x, y)) for _ in range(4)]

    tr2 = _trainer()
    tr2.load_states(f)
    got = [float(tr2.step(x, y)) for _ in range(4)]
    assert got == ref
    sane = max(float(jnp.abs(p).max()) for p in tr2.pvals)
    assert sane < 1e3, f"post-resume params corrupt (max |p| = {sane})"


# ---------------------------------------------------------------------------
# persistent cache lifecycle (in-process; the cross-process win is
# gated by tools/warmup_smoke.py / `make warmup-smoke`)
# ---------------------------------------------------------------------------

def test_ensure_cache_disabled_by_env(monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_CACHE", "0")
    jit_cache.reset()
    try:
        assert jit_cache.ensure_cache() is None
        assert not jit_cache.is_active()
    finally:
        jit_cache.reset()


def test_ensure_cache_uses_only_the_external_dir(armed_cache):
    """JAX_COMPILATION_CACHE_DIR set: that directory, and no other, is
    what the process opens — jax's config points at it and nothing is
    created in the checkout."""
    import jax

    checkout_cache = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    existed = os.path.exists(checkout_cache)
    assert jit_cache.cache_dir() == armed_cache
    assert jit_cache.ensure_cache() == armed_cache
    assert jit_cache.is_active()
    assert jax.config.jax_compilation_cache_dir == armed_cache
    assert os.path.exists(checkout_cache) == existed
    # the key must not follow the checkout directory (source locations
    # inside a kernel's payload): paths under it are made relative
    import re

    canon = jax.config.jax_hlo_source_file_canonicalization_regex
    assert re.match(canon, os.path.abspath(__file__))
    assert not re.match(canon, "/somewhere/else/x.py")


def test_cache_dir_two_locations(monkeypatch):
    """The cache has exactly two homes: $JAX_COMPILATION_CACHE_DIR when
    set, else the fixed ``<checkout>/.jax_cache`` — never a home
    directory, a temp dir, a pid or a time."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/mxjit-test-dir")
    assert jit_cache.cache_dir() == "/tmp/mxjit-test-dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert jit_cache.cache_dir() == os.path.join(checkout, ".jax_cache")
    assert jit_cache.cache_dir() == jit_cache.cache_dir()


def test_unwritable_cache_dir_is_reported(monkeypatch, tmp_path):
    """A cache directory that cannot be created warns (naming it) before
    the process carries on uncached — never a silent uncached run."""
    blocker = tmp_path / "file"
    blocker.write_text("x")
    monkeypatch.setenv("MXNET_COMPILE_CACHE", "1")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jit_cache, "_CHECKOUT", str(blocker))
    jit_cache.reset()
    try:
        with pytest.warns(RuntimeWarning, match="cannot create the compile"):
            assert jit_cache.ensure_cache() is None
        assert not jit_cache.is_active()
    finally:
        jit_cache.reset()
