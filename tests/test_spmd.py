"""2-D mesh SPMD tests: ZeRO-1 sharded weight update + tensor model
parallelism (ISSUE 6; 8-device virtual CPU mesh via conftest).

The bar, per docs/sharding.md: every (mesh shape, partition) combination
must train the SAME math — loss trajectories match the single-device run
(few-ULP for linear optimizers; ratio-based optimizers like Adam amplify
the reduce-scatter's different summation order for near-zero gradients,
so their parity is convergence-level, asserted in the smoke), and zero1
must actually divide the optimizer memory across the data axis.
"""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tel
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.parallel.trainer import (ShardedTrainer, fsdp_spec_fn,
                                        mp_spec_fn, replicated_spec_fn)
from jax.sharding import NamedSharding, PartitionSpec as P

MESHES = {"8x1": {"dp": 8}, "4x2": {"dp": 4, "mp": 2},
          "2x4": {"dp": 2, "mp": 4}}


def _ce(pred, y):
    logp = jax.nn.log_softmax(pred.astype(jnp.float32))
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]


def _build_mlp():
    mx.random.seed(7)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.BatchNorm(axis=-1),
            nn.Dense(8))
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 16)))
    return net


def _batch(n=16):
    rs = onp.random.RandomState(2)
    x = rs.rand(n, 16).astype("float32")
    y = rs.randint(0, 8, size=(n,)).astype("int32")
    return x, y


def _train(mesh, partition, steps=8, **kw):
    tr = ShardedTrainer(_build_mlp(), _ce, mesh=mesh, optimizer="sgd",
                        learning_rate=0.05, momentum=0.9,
                        partition=partition, **kw)
    x, y = _batch()
    losses = [float(tr.step(x, y, block=True)) for _ in range(steps)]
    return tr, losses


@pytest.fixture(autouse=True)
def _tiny_zero1_min(monkeypatch):
    # the test MLP's largest weight is 1024 elements — below the default
    # MXNET_ZERO1_MIN_SIZE=2048 latency guard, which would make zero1 a
    # no-op here
    monkeypatch.setenv("MXNET_ZERO1_MIN_SIZE", "1")


@pytest.fixture(scope="module")
def single_device_ref():
    """Loss trajectory of the identical workload on a 1-device mesh."""
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    _, losses = _train(mesh, "replicated")
    return losses


@pytest.mark.parametrize("mesh_name", sorted(MESHES))
def test_mesh_partition_sweep(mesh_name, single_device_ref):
    """ISSUE 6 acceptance: {8x1, 4x2, 2x4} x {replicated, zero1} all
    reproduce the single-device trajectory, zero1 matches replicated to
    few ULP, and zero1 opt-state bytes/device ~= replicated/dp."""
    mesh = make_mesh(MESHES[mesh_name])
    dp = mesh.shape["dp"]
    tr_r, loss_r = _train(mesh, "replicated")
    tr_z, loss_z = _train(mesh, "zero1")
    onp.testing.assert_allclose(loss_r, single_device_ref, rtol=1e-5)
    # zero1 vs replicated on the SAME mesh: identical math, identical
    # gradient partials — only the reduce-scatter's summation order can
    # differ, so the bar is few-ULP
    onp.testing.assert_allclose(loss_z, loss_r, rtol=2e-6)
    r_bytes = tr_r.opt_state_bytes_per_device
    z_bytes = tr_z.opt_state_bytes_per_device
    assert z_bytes <= r_bytes / dp * 1.1, (z_bytes, r_bytes, dp)
    assert tr_r.param_gather_bytes == 0
    if dp > 1:
        assert tr_z.param_gather_bytes > 0
    # trained params also match between the partitions
    for n, a, b in zip(tr_z.train_names, tr_z.pvals, tr_r.pvals):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-5, atol=1e-6, err_msg=n)


def test_zero1_opt_state_placement_and_gauges():
    """The leaves actually LIVE dp-sharded (NamedSharding at init), and
    the telemetry gauges carry the measured bytes."""
    prev = tel.set_enabled(True)
    tel.reset()
    try:
        mesh = make_mesh({"dp": 4, "mp": 2})
        tr, _ = _train(mesh, "zero1", steps=1)
        sharded = [s for s in tr.opt_state
                   if any(e is not None for e in tuple(s.sharding.spec))]
        assert sharded, "no optimizer-state leaf is sharded under zero1"
        for s in sharded:
            names = set()
            for e in tuple(s.sharding.spec):
                if e is not None:
                    names.update(e if isinstance(e, tuple) else (e,))
            assert "dp" in names
        snap = tel.snapshot()
        assert snap["trainer.opt_state_bytes_per_device"]["value"] == \
            tr.opt_state_bytes_per_device
        assert snap["trainer.param_gather_bytes"]["value"] == \
            tr.param_gather_bytes > 0
    finally:
        tel.reset()
        tel.set_enabled(prev)


def test_zero1_padded_dims_match_replicated():
    """Params whose dims don't divide dp take the PADDED shard path
    (zeros are inert through the optimizer); trajectories must still be
    ULP-equal and the state must restore unpadded across partitions."""
    def build():
        mx.random.seed(3)
        net = nn.HybridSequential()
        net.add(nn.Dense(50, activation="relu"), nn.Dense(6))  # 50, 6 !% 8
        net.initialize(mx.init.Xavier())
        net(mx.np.zeros((2, 16)))
        return net

    rs = onp.random.RandomState(1)
    x = rs.rand(16, 16).astype("float32")
    y = rs.randint(0, 6, size=(16,)).astype("int32")
    mesh = make_mesh({"dp": 8})
    out = {}
    for part in ("replicated", "zero1"):
        tr = ShardedTrainer(build(), _ce, mesh=mesh, optimizer="sgd",
                            learning_rate=0.05, momentum=0.9, partition=part)
        out[part] = ([float(tr.step(x, y, block=True)) for _ in range(8)], tr)
    onp.testing.assert_allclose(out["zero1"][0], out["replicated"][0],
                                rtol=2e-6)
    tr_z = out["zero1"][1]
    dp = mesh.shape["dp"]
    assert tr_z.opt_state_bytes_per_device <= \
        out["replicated"][1].opt_state_bytes_per_device / dp * 1.1
    # padded leaves exist (50 pads to 56) but checkpoints strip padding:
    # a replicated trainer restores the file and continues identically
    assert any(u is not None for u in tr_z._leaf_unpad)
    with tempfile.TemporaryDirectory() as d:
        f = os.path.join(d, "ck.npz")
        tr_z.save_states(f)
        with onp.load(f) as z:
            for i, s in enumerate(tr_z.opt_state):
                assert z[f"opt/{i}"].shape != s.shape or \
                    tr_z._leaf_unpad[i] is None
        tr_r = ShardedTrainer(build(), _ce, mesh=mesh, optimizer="sgd",
                              learning_rate=0.05, momentum=0.9,
                              partition="replicated")
        tr_r.load_states(f)
        tr_z2 = ShardedTrainer(build(), _ce, mesh=make_mesh({"dp": 4,
                                                             "mp": 2}),
                               optimizer="sgd", learning_rate=0.05,
                               momentum=0.9, partition="zero1")
        tr_z2.load_states(f)
        l_r = [float(tr_r.step(x, y, block=True)) for _ in range(3)]
        l_z = [float(tr_z2.step(x, y, block=True)) for _ in range(3)]
        onp.testing.assert_allclose(l_z, l_r, rtol=2e-6)


def test_zero1_grad_accum_matches_replicated():
    """The sharded update threads through the split grad/apply path
    exactly like the replicated one."""
    mesh = make_mesh({"dp": 8})
    ref, loss_ref = _train(mesh, "replicated", grad_accum=2, steps=6)
    got, loss_got = _train(mesh, "zero1", grad_accum=2, steps=6)
    onp.testing.assert_allclose(loss_got, loss_ref, rtol=2e-6)
    for n, a, b in zip(got.train_names, got.pvals, ref.pvals):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-5, atol=1e-6, err_msg=n)


def test_mp_spec_fn_specs():
    fn = mp_spec_fn(min_size=1)
    assert fn("encoder.qkv.weight", (96, 32)) == P("mp", None)
    assert fn("encoder.proj.weight", (32, 32)) == P(None, "mp")
    assert fn("ffn.ffn2.weight", (32, 64)) == P(None, "mp")
    assert fn("dense.bias", (64,)) == P()  # 1-D stays replicated
    assert mp_spec_fn()("small.weight", (8, 8)) == P()  # below min_size
    # non-divisible dims degrade to replication through shard_params'
    # sanitizer instead of crashing trainer construction (5 and 7 both
    # indivisible by mp=2)
    net = nn.Dense(5)
    net.initialize()
    net(mx.np.zeros((2, 7)))
    tr = ShardedTrainer(net, _ce, mesh=make_mesh({"dp": 4, "mp": 2}),
                        spec_fn=mp_spec_fn(min_size=1))
    assert all(not any(e is not None for e in tuple(s)) for s in tr.specs)


def test_bert_mp2_tensor_parallel_matches_unsharded():
    """ISSUE 6 acceptance: BERT layers run with mp=2 tensor sharding
    end-to-end (forward + backward + update) matching the unsharded
    single-device run; zero1 composes on top."""
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.bert import BERTForPretrain, get_bert

    def build():
        mx.random.seed(0)
        bert = get_bert("bert_12_768_12", vocab_size=97, max_length=32,
                        num_layers=2, units=32, hidden_size=64,
                        num_heads=4, dropout=0.0)
        net = BERTForPretrain(bert, vocab_size=97)
        net.initialize(mx.init.Xavier())
        return net

    B, T, PP = 8, 16, 4
    rs = onp.random.RandomState(2)
    x = (rs.randint(0, 97, (B, T)).astype("int32"),
         onp.zeros((B, T), "int32"), onp.full((B,), T, "int32"),
         rs.randint(0, T, (B, PP)).astype("int32"))
    y = (rs.randint(0, 97, (B, PP)).astype("int32"),
         rs.randint(0, 2, (B,)).astype("int32"))
    L = gloss.SoftmaxCrossEntropyLoss()

    def loss_fn(preds, yy):
        (scores, nsp), (mlm_l, nsp_l) = preds, yy
        a = L(mx.nd.NDArray(scores), mx.nd.NDArray(mlm_l))._data.mean()
        b = L(mx.nd.NDArray(nsp), mx.nd.NDArray(nsp_l))._data.mean()
        return a + b

    def run(mesh, spec_fn, partition):
        tr = ShardedTrainer(build(), loss_fn, mesh=mesh, optimizer="sgd",
                            learning_rate=0.05, momentum=0.9,
                            spec_fn=spec_fn, partition=partition)
        return tr, [float(tr.step(x, y, block=True)) for _ in range(3)]

    ref_mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr_ref, l_ref = run(ref_mesh, replicated_spec_fn, "replicated")
    mesh = make_mesh({"dp": 4, "mp": 2})
    tr_mp, l_mp = run(mesh, mp_spec_fn(min_size=64), "replicated")
    n_mp = sum(1 for s in tr_mp.specs
               if any(e is not None for e in tuple(s)))
    assert n_mp >= 8, f"only {n_mp} params mp-sharded — spec_fn broken?"
    onp.testing.assert_allclose(l_mp, l_ref, rtol=2e-5)
    for n, a, b in zip(tr_mp.train_names, tr_mp.pvals, tr_ref.pvals):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-5, err_msg=n)
    _, l_z = run(mesh, mp_spec_fn(min_size=64), "zero1")
    onp.testing.assert_allclose(l_z, l_ref, rtol=2e-5)


def test_put_2d_batch_placement():
    """The 2-D placement rule (docs/sharding.md): batch dim shards over
    dp (errors loudly when it can't — a config bug), trailing dims shard
    over their axis when divisible and REPLICATE when not (seq lens are a
    data property), size-1 dims always replicate (mask broadcast)."""
    net = nn.Dense(4)
    net.initialize()
    net(mx.np.zeros((2, 8)))
    mesh = make_mesh({"dp": 4, "mp": 2})
    tr = ShardedTrainer(net, _ce, mesh=mesh, batch_spec=P("dp", "mp"))

    def shard_shape(v):
        a = tr._put(onp.zeros(v, "float32"))
        return a.sharding.shard_shape(a.shape)

    assert shard_shape((8, 6)) == (2, 3)      # both axes divide
    assert shard_shape((8, 5)) == (2, 5)      # 5 % mp: replicate over mp
    assert shard_shape((1, 6)) == (1, 3)      # size-1 batch: mask row
    assert shard_shape((8, 1)) == (2, 1)      # size-1 trailing
    with pytest.raises(Exception):
        tr._put(onp.zeros((6, 4), "float32"))  # 6 % dp: loud config error


@pytest.mark.parametrize("mesh_name", ["8x1", "4x2"])
def test_aot_compile_per_mesh_and_signature(mesh_name):
    """ISSUE 6 acceptance: compile() warms the zero1 step per
    (mesh-shape, batch-signature) — the first real step after warmup
    pays ZERO new compiles, and a second batch signature coexists with
    the first instead of evicting it."""
    prev = tel.set_enabled(True)
    tel.reset()
    try:
        mesh = make_mesh(MESHES[mesh_name])
        tr = ShardedTrainer(_build_mlp(), _ce, mesh=mesh, optimizer="sgd",
                            learning_rate=0.05, momentum=0.9,
                            partition="zero1")
        x, y = _batch(16)
        assert tr.compile((x, y)) == 1
        c0 = tel.snapshot()["hybridize.compile_seconds"]["count"]
        l0 = float(tr.step(x, y, block=True))
        assert tel.snapshot()["hybridize.compile_seconds"]["count"] == c0, \
            "first real step after warmup recompiled"
        x2, y2 = _batch(8)
        assert tr.compile((x2, y2)) == 1
        c1 = tel.snapshot()["hybridize.compile_seconds"]["count"]
        tr.step(x2, y2, block=True)
        tr.step(x, y, block=True)   # first signature still AOT-served
        assert tel.snapshot()["hybridize.compile_seconds"]["count"] == c1
        assert onp.isfinite(l0)
    finally:
        tel.reset()
        tel.set_enabled(prev)


def test_j003_replicated_optimizer_state_hint():
    """J003 repro + clean twins: fires for a big fully-replicated
    optimizer state on a multi-device mesh; silent for zero1, for an
    fsdp spec_fn (state already sharded), for a single-device mesh, and
    for a small net."""
    from mxnet_tpu.analysis import spmd_hints

    def build(units=16):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(units, activation="relu"), nn.Dense(4))
        net.initialize(mx.init.Xavier())
        net(mx.np.zeros((2, 16)))
        return net

    prev_min = spmd_hints.set_min_params(100)
    prev_tel = tel.set_enabled(True)
    tel.reset()
    spmd_hints.reset()
    try:
        # repro: replicated partition, 8-device mesh, net over threshold
        ShardedTrainer(build(), _ce, mesh=make_mesh({"dp": 8}),
                       partition="replicated")
        diags = spmd_hints.report()
        assert [d.code for d in diags] == ["J003"]
        assert "zero1" in diags[0].message
        assert tel.snapshot()["trainer.zero1_hint_warnings"]["value"] == 1
        # once per net type
        ShardedTrainer(build(), _ce, mesh=make_mesh({"dp": 8}),
                       partition="replicated")
        assert len(spmd_hints.report()) == 1

        # clean twins
        spmd_hints.reset()
        ShardedTrainer(build(), _ce, mesh=make_mesh({"dp": 8}),
                       partition="zero1")                      # sharded
        ShardedTrainer(build(), _ce, mesh=make_mesh({"dp": 8}),
                       spec_fn=fsdp_spec_fn("dp", min_size=16))  # fsdp
        ShardedTrainer(build(), _ce,
                       mesh=make_mesh({"dp": 1},
                                      devices=jax.devices()[:1]))  # 1-dev
        spmd_hints.set_min_params(10 ** 6)
        ShardedTrainer(build(), _ce, mesh=make_mesh({"dp": 8}))  # small
        assert spmd_hints.report() == [], spmd_hints.report()
    finally:
        spmd_hints.set_min_params(prev_min)
        spmd_hints.reset()
        tel.reset()
        tel.set_enabled(prev_tel)
