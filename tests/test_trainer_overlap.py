"""Bucketed collective/compute overlap under ZeRO-1 (ISSUE 14).

The load-bearing claims under test: (1) ``bucket_layouts`` walks leaves
in REVERSE declaration order and closes size-bounded buckets whose
arenas stay kernel/shard aligned; (2) ``overlap=True`` is an explicit
opt-in with LOUD failures — it refuses non-zero1 partitions, the arena
fused path, and non-fusible optimizers instead of silently falling
back; (3) the flat-segment update math is BIT-EXACT against the
per-leaf optimizer on identical gradients (elementwise ops are
indifferent to where leaf boundaries fall — the invariant that makes
arbitrary bucket/shard cuts safe); (4) the overlap trainer trains in
parity with classic zero1, keeps its state dp-sharded, publishes the
``trainer.overlap_bucket_count`` gauge, and round-trips through
save_states/load_states.
"""
from __future__ import annotations

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import optimizer as optmod
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.kernels.opt_arena import bucket_layouts
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.parallel.trainer import ShardedTrainer, _OverlapOptAdapter


def _ce(pred, y):
    logp = jax.nn.log_softmax(pred.astype(jnp.float32))
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]


def _mlp(units=128, seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(units, activation="relu", in_units=8))
    net.add(nn.Dense(4, in_units=units))
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 8)))
    return net


def _batch(seed=0, n=16):
    rs = onp.random.RandomState(seed)
    return (rs.rand(n, 8).astype("float32"),
            rs.randint(0, 4, (n,)).astype("int32"))


def _trainer(momentum=0.9, bucket_bytes=None, monkeypatch=None, **kw):
    if bucket_bytes is not None:
        monkeypatch.setenv("MXNET_OVERLAP_BUCKET_BYTES", str(bucket_bytes))
    return ShardedTrainer(_mlp(), _ce, mesh=make_mesh({"dp": 8}),
                          optimizer="sgd", learning_rate=0.05,
                          momentum=momentum, **kw)


# ---------------------------------------------------------------------------
# bucket_layouts
# ---------------------------------------------------------------------------

def test_bucket_layouts_reverse_order_bounds_and_padding():
    shapes = [(4,), (100,), (300,), (1000,)]
    # 1600 bytes = 400 f32: leaf 3 (4000 B) overflows alone, 2 (1200 B)
    # + 1 (400 B) exactly fill one bucket, 0 spills into the next
    buckets, layouts = bucket_layouts(shapes, bucket_bytes=1600,
                                      shard_multiple=8)
    assert buckets == ((3,), (2, 1), (0,))
    assert [lay.total for lay in layouts] == [1000, 400, 4]
    for lay in layouts:
        assert lay.padded % 8 == 0
        assert lay.padded >= lay.total
    # layout leaf bookkeeping stays in bucket order
    assert layouts[1].sizes == (300, 100)
    assert layouts[1].offsets == (0, 300)


def test_bucket_layouts_rejects_nonpositive_bound():
    with pytest.raises(ValueError, match="bucket_bytes"):
        bucket_layouts([(4,)], bucket_bytes=0)


def test_bucket_layouts_single_bucket_when_bound_is_large():
    buckets, layouts = bucket_layouts([(10,), (20,)], bucket_bytes=1 << 30)
    assert buckets == ((1, 0),)
    assert layouts[0].total == 30


# ---------------------------------------------------------------------------
# explicit opt-in: loud refusals, no silent fallback
# ---------------------------------------------------------------------------

def test_overlap_requires_zero1():
    with pytest.raises(MXNetError, match="overlap"):
        _trainer(partition="replicated", overlap=True)


def test_overlap_rejects_arena_combo():
    with pytest.raises(MXNetError, match="overlap"):
        _trainer(partition="zero1", overlap=True, fused_opt="arena")


def test_overlap_rejects_non_fusible_optimizer():
    net = _mlp()
    with pytest.raises(MXNetError, match="overlap=True unavailable"):
        ShardedTrainer(net, _ce, mesh=make_mesh({"dp": 8}),
                       optimizer="rmsprop", learning_rate=0.01,
                       partition="zero1", overlap=True)


def test_overlap_env_selector(monkeypatch):
    monkeypatch.setenv("MXNET_OVERLAP", "1")
    tr = ShardedTrainer(_mlp(), _ce, mesh=make_mesh({"dp": 8}),
                        optimizer="sgd", learning_rate=0.05,
                        partition="zero1")
    assert isinstance(tr._adapter, _OverlapOptAdapter)


# ---------------------------------------------------------------------------
# flat-segment update math: bit-exact vs per-leaf on identical grads
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_flat_segment_update_bit_exact(momentum):
    """The overlap adapter's core numeric claim: the registry optimizer
    replayed on a flat concatenation of leaves produces bitwise the
    same elements as per-leaf updates — elementwise math cannot see
    leaf boundaries.  (Whole-trajectory bitwise equality across two
    separately COMPILED executables is NOT claimed — XLA may
    FMA-contract one program and not the other; tools/spmd_smoke.py
    gates that at tolerance.)"""
    rs = onp.random.RandomState(0)
    ws = [rs.randn(37).astype("f4"), rs.randn(8, 5).astype("f4")]
    gs = [rs.randn(*w.shape).astype("f4") for w in ws]

    def run_per_leaf():
        opt = optmod.create("sgd", learning_rate=0.05, momentum=momentum)
        outs = []
        for i, (w, g) in enumerate(zip(ws, gs)):
            wn = NDArray(jnp.asarray(w))
            st = opt.create_state(i, wn)
            opt.update(i, wn, NDArray(jnp.asarray(g)), st)
            outs.append(onp.asarray(wn._data).ravel())
        return onp.concatenate(outs)

    def run_flat():
        opt = optmod.create("sgd", learning_rate=0.05, momentum=momentum)
        wf = NDArray(jnp.concatenate([jnp.asarray(w).ravel() for w in ws]))
        gf = NDArray(jnp.concatenate([jnp.asarray(g).ravel() for g in gs]))
        st = opt.create_state(0, wf)
        opt.update(0, wf, gf, st)
        return onp.asarray(wf._data)

    a, b = run_per_leaf(), run_flat()
    assert onp.array_equal(a, b)


# ---------------------------------------------------------------------------
# the overlap trainer end to end
# ---------------------------------------------------------------------------

def test_overlap_parity_sharding_and_gauges(monkeypatch):
    # small bucket bound => several buckets, so the multi-bucket flush
    # is what parity is measured on
    monkeypatch.setenv("MXNET_OVERLAP_BUCKET_BYTES", str(4 << 10))
    x, y = _batch()
    tr_z1 = _trainer(partition="zero1")
    tr_ov = _trainer(partition="zero1", overlap=True)
    assert isinstance(tr_ov._adapter, _OverlapOptAdapter)
    assert len(tr_ov._adapter.buckets) >= 2
    for i in range(4):
        a = float(tr_z1.step(x, y, block=True))
        b = float(tr_ov.step(x, y, block=True))
        assert abs(a - b) / max(abs(a), 1.0) < 1e-5
    # state arenas live dp-sharded (the ZeRO-1 memory win, unchanged)
    for leaf in tr_ov.opt_state:
        assert leaf.sharding.spec == P("dp")
    snap = tel.snapshot()
    assert snap["trainer.overlap_bucket_count"]["value"] == \
        len(tr_ov._adapter.buckets)
    # byte accounting: overlap still moves the zero1 gather volume
    assert tr_ov.param_gather_bytes > 0
    assert tr_ov.collective_bytes_per_step > tr_ov.param_gather_bytes


def test_overlap_checkpoint_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_OVERLAP_BUCKET_BYTES", str(4 << 10))
    x, y = _batch()
    tr = _trainer(partition="zero1", overlap=True)
    tr.step(x, y, block=True)
    tr.step(x, y, block=True)
    fname = str(tmp_path / "ovl.npz")
    tr.save_states(fname)
    want_p = [onp.asarray(v) for v in tr.pvals]
    want_s = [onp.asarray(v) for v in tr.opt_state]
    tr.step(x, y, block=True)  # drift past the snapshot
    tr.load_states(fname)
    for a, b in zip(want_p, tr.pvals):
        onp.testing.assert_array_equal(a, onp.asarray(b))
    for a, b in zip(want_s, tr.opt_state):
        onp.testing.assert_array_equal(a, onp.asarray(b))
    # restored state steps on, sharded as before
    loss = float(tr.step(x, y, block=True))
    assert onp.isfinite(loss)
    for leaf in tr.opt_state:
        assert leaf.sharding.spec == P("dp")
