"""SPMD / parallel subsystem tests (8-device virtual CPU mesh via conftest).

Covers the TPU-native replacement for the reference's distributed stack
(SURVEY.md §2.3): mesh construction, ShardedTrainer DP/FSDP training,
aux-state (BatchNorm running stats) propagation, and sequence-parallel ring
attention (capability beyond the reference, SURVEY.md §5).
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.mesh import make_mesh, default_mesh
from mxnet_tpu.parallel.trainer import (ShardedTrainer, fsdp_spec_fn,
                                        replicated_spec_fn)
from jax.sharding import PartitionSpec as P


def _ce(pred, y):
    logp = jax.nn.log_softmax(pred.astype(jnp.float32))
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]


def test_make_mesh_auto_axis():
    mesh = make_mesh({"dp": -1, "tp": 2})
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2
    with pytest.raises(mx.MXNetError):
        make_mesh({"dp": 3, "tp": 3})


def test_sharded_trainer_converges():
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(4))
    net.initialize()
    net(mx.np.zeros((2, 8)))
    tr = ShardedTrainer(net, _ce, mesh=default_mesh(), optimizer="adam",
                        learning_rate=1e-2)
    rs = onp.random.RandomState(0)
    x = rs.rand(64, 8).astype("float32")
    y = (x.sum(axis=1) > 4.0).astype("int32")
    first = tr.step(x, y)
    for _ in range(30):
        last = tr.step(x, y)
    assert last < first * 0.5, (first, last)


def test_sharded_trainer_updates_bn_stats():
    """Regression: grad_req='null' aux params (BN running stats) must take
    the forward's in-place updates, not optimizer updates."""
    net = nn.HybridSequential()
    net.add(nn.Dense(16), nn.BatchNorm(), nn.Dense(2))
    net.initialize()
    net(mx.np.zeros((2, 8)))
    params = net.collect_params()
    bn_mean_name = next(n for n in params if "running_mean" in n)
    before = onp.array(params[bn_mean_name].data().asnumpy())
    tr = ShardedTrainer(net, _ce, mesh=default_mesh(), optimizer="sgd",
                        learning_rate=0.1, weight_decay=1e-3)
    rs = onp.random.RandomState(1)
    x = (rs.rand(32, 8) * 3 + 5).astype("float32")  # mean ≈ 6.5, not 0
    y = rs.randint(0, 2, size=(32,)).astype("int32")
    tr.step(x, y)
    after = onp.array(params[bn_mean_name].data().asnumpy())
    # must move toward the batch mean (momentum update), not be wd-decayed
    assert not onp.allclose(after, before), "BN running_mean never updated"
    assert onp.abs(after).max() > 1e-3, "BN stats were optimizer-decayed"


def test_fsdp_matches_replicated():
    """FSDP-sharded training step computes the same math as replicated."""
    def build():
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(64, activation="relu"), nn.Dense(4))
        net.initialize()
        net(mx.np.zeros((2, 16)))
        return net

    rs = onp.random.RandomState(2)
    x = rs.rand(16, 16).astype("float32")
    y = rs.randint(0, 4, size=(16,)).astype("int32")

    losses = []
    for spec_fn in (replicated_spec_fn, fsdp_spec_fn("dp", min_size=16)):
        net = build()
        tr = ShardedTrainer(net, _ce, mesh=default_mesh(), optimizer="sgd",
                            learning_rate=0.05, spec_fn=spec_fn)
        losses.append([tr.step(x, y) for _ in range(3)])
    onp.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)


def test_ring_attention_matches_reference():
    from jax import shard_map
    from mxnet_tpu.parallel.ring import ring_attention, attention_reference

    mesh = make_mesh({"sp": 8})
    b, h, t, d = 2, 2, 64, 16
    rs = onp.random.RandomState(3)
    q, k, v = (jnp.asarray(rs.rand(b, h, t, d), jnp.float32) for _ in range(3))
    spec = P(None, None, "sp", None)
    for causal in (False, True):
        ring = shard_map(
            lambda q, k, v, c=causal: ring_attention(q, k, v, axis_name="sp",
                                                     causal=c),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        out = jax.jit(ring)(q, k, v)
        if causal:
            pos = jnp.arange(t)
            mask = (pos[:, None] >= pos[None, :])[None, None]
        else:
            mask = None
        ref = attention_reference(q, k, v, mask=mask)
        onp.testing.assert_allclose(onp.array(out), onp.array(ref),
                                    atol=2e-5)


def test_blockwise_attention_matches_reference():
    from mxnet_tpu.parallel.ring import (blockwise_attention,
                                         attention_reference)

    b, h, t, d = 2, 2, 70, 16  # t not divisible by block => padding path
    rs = onp.random.RandomState(4)
    q, k, v = (jnp.asarray(rs.rand(b, h, t, d), jnp.float32) for _ in range(3))
    for causal in (False, True):
        out = blockwise_attention(q, k, v, block_size=32, causal=causal)
        if causal:
            pos = jnp.arange(t)
            mask = (pos[:, None] >= pos[None, :])[None, None]
        else:
            mask = None
        ref = attention_reference(q, k, v, mask=mask)
        onp.testing.assert_allclose(onp.array(out), onp.array(ref), atol=2e-5)


from mxnet_tpu.test_utils import train_mlp_to_params as _train_to_params


@pytest.mark.parametrize("axes", ["dp", "dp_tp", "fsdp"])
def test_multichip_matches_single_chip(axes):
    """The nightly bar the reference holds its dist kvstore to
    (tests/nightly/dist_sync_kvstore.py:102-419), on the pjit path: an
    8-device sharded training run must produce the SAME trained parameters
    and BatchNorm statistics as a 1-device run of the identical global
    batch, for dp, dp×tp, and fsdp shardings."""
    if axes == "dp":
        mesh = make_mesh({"dp": -1})
        spec_fn = replicated_spec_fn
    elif axes == "dp_tp":
        mesh = make_mesh({"dp": -1, "tp": 2})
        spec_fn = fsdp_spec_fn("tp", min_size=64)
    else:
        mesh = make_mesh({"dp": -1})
        spec_fn = fsdp_spec_fn("dp", min_size=64)
    ref_mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    ref_p, ref_a, ref_loss = _train_to_params(ref_mesh, replicated_spec_fn)
    got_p, got_a, got_loss = _train_to_params(mesh, spec_fn)
    assert set(got_p) == set(ref_p) and set(got_a) == set(ref_a)
    onp.testing.assert_allclose(got_loss, ref_loss, rtol=1e-5)
    for n in sorted(ref_p):
        onp.testing.assert_allclose(got_p[n], ref_p[n], rtol=1e-5,
                                    atol=1e-5, err_msg=n)
    for n in sorted(ref_a):
        onp.testing.assert_allclose(got_a[n], ref_a[n], rtol=1e-5,
                                    atol=1e-5, err_msg=n)


def test_sharded_trainer_bf16_compute():
    """compute_dtype=bfloat16: fp32 master params, bf16 forward; must
    still converge and keep param/aux dtypes fp32 across steps."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from jax.sharding import PartitionSpec as P

    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(32, activation="relu"),
            mx.gluon.nn.BatchNorm(axis=-1),
            mx.gluon.nn.Dense(2))
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 8)))

    def ce(pred, y):
        logp = jax.nn.log_softmax(pred.astype(jnp.float32))
        return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    tr = ShardedTrainer(net, ce, mesh=mesh, optimizer="adam",
                        learning_rate=5e-3, batch_spec=P("dp"),
                        compute_dtype=jnp.bfloat16)
    rs = onp.random.RandomState(0)
    x = rs.rand(32, 8).astype("float32")
    y = (x.sum(1) > 4).astype("int32")
    losses = [tr.step(x, y) for _ in range(40)]
    assert losses[-1] < losses[0] * 0.5, losses
    for v in tr.pvals:
        assert v.dtype == jnp.float32  # master params stay fp32
    for v in tr.avals:
        if jnp.issubdtype(v.dtype, jnp.floating):
            assert v.dtype == jnp.float32  # BN stats stay fp32


def test_update_respects_per_index_multipliers():
    """The per-parameter update reads each index's lr_mult/wd_mult: after
    one momentum-SGD step from the same weights a frozen index has not
    moved, an undecayed index equals the wd=0 run, and every other index
    is the wd=0 run less ``lr * wd * w0``."""
    from mxnet_tpu import optimizer as opt_mod

    lr, wd = 0.1, 0.01

    def build_and_step(wd, mults):
        mx.random.seed(5)
        net = nn.HybridSequential()
        for _ in range(3):
            net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(2))
        net.initialize(mx.init.Xavier())
        net(mx.np.zeros((2, 8)))
        opt = opt_mod.create("sgd", learning_rate=lr, momentum=0.9, wd=wd)
        if mults:
            opt.set_lr_mult({1: 0.0})   # freeze 0.weight
            opt.set_wd_mult({3: 0.0})   # no decay on 1.weight
        tr = ShardedTrainer(net, _ce, mesh=default_mesh(), optimizer=opt,
                            fused_opt="off")
        w0 = [onp.asarray(v) for v in tr.pvals]
        rs = onp.random.RandomState(4)
        x = rs.rand(8, 8).astype("float32")
        y = rs.randint(0, 2, size=(8,)).astype("int32")
        tr.step(x, y)
        return tr.train_names, w0, [onp.asarray(v) for v in tr.pvals]

    names, w0, got = build_and_step(wd, mults=True)
    _, _, plain = build_and_step(0.0, mults=False)     # w0 - lr * g
    assert any(onp.abs(p - w).max() > 1e-4 for p, w in zip(plain, w0))
    for i, n in enumerate(names):
        want = {1: w0[i], 3: plain[i]}.get(i, plain[i] - lr * wd * w0[i])
        onp.testing.assert_allclose(got[i], want, rtol=1e-6, atol=1e-7,
                                    err_msg=n)
    assert onp.abs(plain[1] - w0[1]).max() > 1e-4      # index 1 had a gradient
    assert onp.abs(w0[3]).max() * lr * wd > 1e-5       # index 3 had a decay


def test_engine_check_no_false_positive_on_parallel_workloads():
    """ISSUE 2 acceptance: with the engine dependency checker active
    (MXNET_ENGINE_CHECK semantics via install()), correctly-declared
    concurrent engine work — disjoint writers from many threads plus a
    fan-out of declared read/read consumers over one shared array — and
    a real sharded training step must produce ZERO diagnostics, while a
    seeded under-declared push in the same session is still caught."""
    import threading

    from mxnet_tpu import engine
    from mxnet_tpu.analysis import engine_check as echk

    eng = echk.install()
    echk.clear()
    try:
        try:  # drain any first-error left by earlier exception tests on
            # the shared process-global engine (first error reports once)
            eng.wait_for_all()
        except mx.MXNetError:
            pass
        # disjoint-var writers from 16 threads (the existing
        # test_concurrent_engine_pushes pattern, now under checking)
        out = [0] * 16

        def work(i):
            var = eng.new_var()
            eng.push(lambda j=i: out.__setitem__(j, j * j), write=[var],
                     name=f"disjoint{i}")
            eng.wait_for_var(var)
            eng.delete_var(var)

        ts = [threading.Thread(target=work, args=(i,)) for i in range(16)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert out == [i * i for i in range(16)]

        # declared read/read fan-out over one shared, owned array
        owner = eng.new_var()
        shared = mx.nd.array(onp.arange(16, dtype="f4"))
        echk.bind(shared, owner)
        sums = []
        vars_ = []
        for i in range(8):
            v = eng.new_var()
            vars_.append(v)
            eng.push(lambda: sums.append(float(shared.asnumpy().sum())),
                     read=[owner], write=[v], name=f"fanout{i}")
        eng.wait_for_all()
        assert sums == [120.0] * 8

        # a real SPMD training step under checking stays silent too
        net = nn.Dense(4)
        net.initialize()
        net(mx.np.zeros((2, 8)))
        tr = ShardedTrainer(net, _ce, mesh=default_mesh(), optimizer="sgd",
                            learning_rate=0.1)
        rs = onp.random.RandomState(0)
        tr.step(rs.rand(16, 8).astype("float32"),
                rs.randint(0, 4, size=(16,)).astype("int32"))

        assert echk.diagnostics() == [], echk.diagnostics()

        # ...and the checker is still live: a seeded under-declared read
        # in the same session is caught
        rogue = eng.new_var()
        eng.push(lambda: shared.asnumpy(), write=[rogue], name="rogue")
        eng.wait_for_var(rogue)
        assert [d.code for d in echk.diagnostics()] == ["E001"]
        for v in [owner, rogue] + vars_:
            eng.delete_var(v)
    finally:
        echk.uninstall()


def test_telemetry_sharded_trainer_and_collectives_tick():
    """ISSUE 1 wiring: a real SPMD run must leave step timings and
    collective call/byte counts in the registry."""
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.parallel import collectives as coll
    from jax import shard_map

    prev = tel.set_enabled(True)
    tel.reset()
    try:
        net = nn.Dense(4)
        net.initialize()
        net(mx.np.zeros((2, 8)))
        tr = ShardedTrainer(net, _ce, mesh=default_mesh(), optimizer="sgd",
                            learning_rate=0.1)
        rs = onp.random.RandomState(0)
        x = rs.rand(16, 8).astype("float32")
        y = rs.randint(0, 4, size=(16,)).astype("int32")
        for _ in range(3):
            tr.step(x, y)
        snap = tel.snapshot()
        assert snap["trainer.step_seconds"]["count"] == 3
        assert snap["trainer.step_seconds"]["total"] > 0

        mesh = default_mesh()
        fn = shard_map(lambda v: coll.all_reduce(v, "dp"), mesh=mesh,
                       in_specs=P("dp"), out_specs=P("dp"))
        fn(jnp.ones((8, 4), jnp.float32))
        snap = tel.snapshot()
        assert snap["collectives.all_reduce_calls"]["value"] >= 1
        assert snap["collectives.all_reduce_bytes"]["value"] > 0
    finally:
        tel.reset()
        tel.set_enabled(prev)
