"""mx.telemetry — registry semantics, disabled-mode no-op, percentiles,
JSON schema, thread safety, and the cross-layer wiring (engine, ndarray,
dataloader, profiler merge, TensorBoard export, Monitor taps).

Every test snapshots/restores the enabled flag and resets the registry so
the process-global state never leaks between tests (the registry is shared
with every other suite running in this process).
"""
from __future__ import annotations

import json
import os
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tel

np_ = mx.np


@pytest.fixture(autouse=True)
def _clean_registry():
    prev = tel.set_enabled(True)
    tel.reset()
    yield
    tel.reset()
    tel.set_enabled(prev)


# -- registry semantics ------------------------------------------------------

def test_counter_gauge_timer_basics():
    tel.inc("t.count")
    tel.inc("t.count", 4)
    tel.set_gauge("t.depth", 3)
    tel.set_gauge("t.depth", 1)
    tel.observe("t.lat", 0.5)
    tel.observe("t.lat", 1.5)
    snap = tel.snapshot()
    assert snap["t.count"] == {"type": "counter", "value": 5}
    depth = dict(snap["t.depth"])
    assert depth.pop("last_update_ts") == pytest.approx(time.time(), abs=60)
    assert depth == {"type": "gauge", "value": 1, "max": 3}
    t = snap["t.lat"]
    assert t["count"] == 2
    assert t["total"] == pytest.approx(2.0)
    assert t["min"] == pytest.approx(0.5)
    assert t["max"] == pytest.approx(1.5)
    # "value" mirrors total on timers (uniform consumer field)
    assert t["value"] == t["total"]


def test_metric_kind_collision_raises():
    tel.inc("kind.clash")
    with pytest.raises(TypeError):
        tel.timer("kind.clash")


def test_timer_context_manager_and_decorator():
    with tel.timer("cm.seconds"):
        pass
    calls = []

    @tel.timed("deco.seconds")
    def f(x):
        calls.append(x)
        return x * 2

    assert f(3) == 6
    snap = tel.snapshot()
    assert snap["cm.seconds"]["count"] == 1
    assert snap["deco.seconds"]["count"] == 1
    assert calls == [3]


def test_timer_percentiles():
    t = tel.timer("p.seconds")
    for v in range(1, 101):          # 1..100 ms
        t.observe(v / 1000.0)
    s = t.summary()
    assert s["p50"] == pytest.approx(0.050, abs=0.002)
    assert s["p99"] == pytest.approx(0.100, abs=0.002)
    assert s["min"] == pytest.approx(0.001)
    assert s["max"] == pytest.approx(0.100)


def test_timer_reservoir_is_recency_biased():
    t = tel.timer("r.seconds")
    for _ in range(tel.Timer.RESERVOIR):
        t.observe(100.0)             # old regime: huge (compile steps)
    for _ in range(tel.Timer.RESERVOIR):
        t.observe(0.001)             # steady state
    s = t.summary()
    assert s["p99"] == pytest.approx(0.001)   # old samples aged out
    assert s["max"] == pytest.approx(100.0)   # exact aggregates keep them
    assert s["count"] == 2 * tel.Timer.RESERVOIR


# -- disabled mode -----------------------------------------------------------

def test_disabled_mode_is_a_no_op():
    tel.set_enabled(False)
    tel.inc("off.count")
    tel.set_gauge("off.gauge", 9)
    tel.observe("off.seconds", 1.0)
    with tel.timer("off.scope"):
        pass

    @tel.timed("off.deco")
    def f():
        return 42

    assert f() == 42
    assert tel.snapshot() == {}
    assert tel.dumps() == ""


def test_disabled_mode_instrumented_paths_still_work():
    tel.set_enabled(False)
    a = np_.ones((4, 4))
    assert a.asnumpy().sum() == 16
    a.wait_to_read()
    eng = mx.engine.NaiveEngine()
    v = eng.new_var()
    eng.push(lambda: None, write=(v,))
    eng.wait_for_var(v)
    eng.wait_for_all()
    assert tel.snapshot() == {}


def test_set_enabled_returns_previous():
    assert tel.set_enabled(False) is True
    assert tel.set_enabled(True) is False


# -- thread safety -----------------------------------------------------------

def test_thread_safety_smoke():
    n_threads, n_iter = 8, 1000

    def work():
        t = tel.timer("mt.seconds")
        for _ in range(n_iter):
            tel.inc("mt.count")
            t.observe(0.001)
            tel.set_gauge("mt.gauge", 1)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    snap = tel.snapshot()
    assert snap["mt.count"]["value"] == n_threads * n_iter
    assert snap["mt.seconds"]["count"] == n_threads * n_iter
    assert snap["mt.seconds"]["total"] == pytest.approx(
        n_threads * n_iter * 0.001)


# -- export: JSON schema, table, profiler merge, tensorboard ----------------

def test_dump_json_schema(tmp_path):
    tel.inc("js.count", 2)
    tel.observe("js.seconds", 0.25)
    path = str(tmp_path / "sub" / "telemetry.json")
    returned = tel.dump_json(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc == json.loads(json.dumps(returned))
    assert doc["version"] == 1
    assert doc["enabled"] is True
    assert doc["pid"] == os.getpid()
    assert doc["ts"] > 0
    m = doc["metrics"]
    assert m["js.count"]["value"] == 2
    timer = m["js.seconds"]
    for field in ("type", "count", "value", "total", "min", "max",
                  "p50", "p99"):
        assert field in timer, field


def test_dumps_table_and_profiler_merge():
    tel.inc("tab.count", 7)
    tel.observe("tab.seconds", 0.125)
    table = tel.dumps()
    assert "Telemetry Statistics:" in table
    assert "tab.count" in table and "tab.seconds" in table
    merged = mx.profiler.dumps()
    assert "Profile Statistics:" in merged
    assert "tab.count" in merged


def test_dumps_reset():
    tel.inc("reset.count")
    assert "reset.count" in tel.dumps(reset=True)
    assert tel.dumps() == ""


def test_write_tensorboard_emits_event_file(tmp_path):
    tel.inc("tb.count", 3)
    tel.observe("tb.seconds", 0.5)
    logdir = str(tmp_path / "tb")
    tel.write_tensorboard(logdir, step=2)
    files = os.listdir(logdir)
    assert len(files) == 1 and files[0].startswith("events.out.tfevents.")
    blob = open(os.path.join(logdir, files[0]), "rb").read()
    # tag names ride in the protobuf payload as plain bytes
    assert b"telemetry/tb.count" in blob
    assert b"telemetry/tb.seconds/p99" in blob


# -- the instrumented seams --------------------------------------------------

def test_ndarray_sync_metrics_tick():
    a = mx.NDArray(onp.ones((8, 8), "float32"))  # host-sourced => h2d
    a.asnumpy()
    a.wait_to_read()
    snap = tel.snapshot()
    assert snap["ndarray.h2d_bytes"]["value"] >= 256
    assert snap["ndarray.d2h_bytes"]["value"] >= 256
    assert snap["ndarray.asnumpy_seconds"]["count"] == 1
    assert snap["ndarray.wait_to_read_seconds"]["count"] == 1


def test_engine_metrics_tick():
    eng = mx.engine.NaiveEngine()
    v = eng.new_var()
    for _ in range(3):
        eng.push(lambda: None, write=(v,))
    eng.wait_for_var(v)
    eng.wait_for_all()
    snap = tel.snapshot()
    assert snap["engine.ops_pushed"]["value"] == 3
    assert snap["engine.wait_for_var_seconds"]["count"] == 1
    assert snap["engine.wait_for_all_seconds"]["count"] == 1


def test_dataloader_metrics_tick():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    x = onp.random.rand(32, 3).astype("float32")
    y = onp.arange(32).astype("int32")
    loader = DataLoader(ArrayDataset(x, y), batch_size=8)
    n = sum(1 for _ in loader)
    assert n == 4
    snap = tel.snapshot()
    assert snap["dataloader.batches"]["value"] == 4
    assert snap["dataloader.wait_seconds"]["count"] == 4
    assert snap["dataloader.wait_seconds"]["total"] > 0


def test_collectives_counters_tick():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    from mxnet_tpu.parallel import collectives as coll

    devs = onp.array(jax.devices()[:4])
    mesh = Mesh(devs, ("dp",))
    x = jnp.ones((4, 8), jnp.float32)

    fn = shard_map(lambda v: coll.all_reduce(v, "dp"), mesh=mesh,
                   in_specs=P("dp"), out_specs=P("dp"))
    out = fn(x)
    assert out.shape == (4, 8)
    snap = tel.snapshot()
    assert snap["collectives.all_reduce_calls"]["value"] >= 1
    # trace-time byte accounting: per-shard (1, 8) fp32 = 32 bytes
    assert snap["collectives.all_reduce_bytes"]["value"] >= 32


def test_kvstore_pushpull_metrics_tick():
    kv = mx.kv.create("local")
    a = np_.ones((16,))
    b = np_.ones((16,))
    kv.broadcast("w", a, out=b)
    kv.pushpull("w", [a, b], out=[a, b])
    snap = tel.snapshot()
    assert snap["kvstore.broadcast_calls"]["value"] == 1
    assert snap["kvstore.pushpull_calls"]["value"] == 1
    assert snap["kvstore.pushpull_bytes"]["value"] == 2 * 16 * 4
    assert snap["kvstore.pushpull_seconds"]["count"] == 1


def test_gluon_trainer_step_metrics_tick():
    from mxnet_tpu.gluon import nn

    net = nn.Dense(2)
    net.initialize()
    x = np_.ones((4, 3))
    with mx.autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1})
    trainer.step(4)
    snap = tel.snapshot()
    assert snap["trainer.step_seconds"]["count"] == 1
    assert snap["trainer.step_seconds"]["total"] > 0


# -- Monitor on top of the registry -----------------------------------------

def test_monitor_taps_layer_stats_into_registry():
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.monitor import Monitor

    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(2))
    net.initialize()
    net(np_.ones((2, 3)))

    mon = Monitor(interval=1, sort=True).install(net)
    mon.tic()
    net(np_.ones((2, 3)))
    stats = mon.toc()
    assert stats, "monitor collected nothing"
    names = [n for _, n, _ in stats]
    assert any("dense" in n or "hybridsequential" in n for n in names)
    for _, _, stat in stats:
        assert onp.isfinite(stat)
    snap = tel.snapshot()
    tapped = [k for k in snap if k.startswith("monitor.")]
    assert tapped, snap.keys()
    assert snap["monitor.collections"]["value"] == 1
    # interval honored: second tic on interval=2 monitor collects nothing
    mon2 = Monitor(interval=2).install(net)
    mon2.tic()
    net(np_.ones((2, 3)))
    assert mon2.toc()
    mon2.tic()   # step 1 of 2 — inactive
    net(np_.ones((2, 3)))
    assert mon2.toc() == []


def test_monitor_pattern_filters_layers():
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.monitor import Monitor

    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dropout(0.5))
    net.initialize()
    net(np_.ones((2, 3)))
    mon = Monitor(pattern=r".*\.0$").install(net)   # only the Dense child
    mon.tic()
    net(np_.ones((2, 3)))
    stats = mon.toc()
    assert stats and all(name.endswith(".0_output") for _, name, _ in stats)


def test_monitor_survives_hybridized_net():
    """Regression (review finding): hooks firing inside a jit trace see
    tracer-backed NDArrays — Monitor must skip them, tap the root's real
    outputs, and never crash in toc()."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.monitor import Monitor

    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(2))
    net.initialize()
    net(np_.ones((2, 3)))
    net.hybridize()
    net(np_.ones((2, 3)))          # warmup (eager)
    mon = Monitor(interval=1).install(net)
    for _ in range(2):             # trace call + steady-state call
        mon.tic()
        net(np_.ones((2, 3)))
        stats = mon.toc()          # must not raise on tracer leftovers
        assert stats, "root block output not tapped"
        for _, _, stat in stats:
            assert onp.isfinite(stat)


def test_sharded_trainer_books_compile_seconds():
    """ShardedTrainer compiles count toward hybridize.compile_seconds —
    including per-shape recompiles and the grad-accumulation fns."""
    import jax.numpy as jnp  # noqa: F401 — parity with parallel tests
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.mesh import default_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    def ce(pred, y):
        import jax

        logp = jax.nn.log_softmax(pred.astype("float32"))
        return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]

    net = nn.Dense(4)
    net.initialize()
    net(np_.zeros((2, 8)))
    tr = ShardedTrainer(net, ce, mesh=default_mesh(), optimizer="sgd",
                        learning_rate=0.1, grad_accum=2)
    rs = onp.random.RandomState(0)
    x = rs.rand(16, 8).astype("float32")
    y = rs.randint(0, 4, size=(16,)).astype("int32")
    # window 1 compiles grad_fn+apply_fn; window 2 genuinely recompiles
    # both (post-update params carry different shardings/committedness) —
    # exactly the silent recompile cost this metric exists to expose
    for _ in range(4):
        tr.step(x, y)
    snap = tel.snapshot()
    assert snap["hybridize.compile_seconds"]["count"] >= 2
    assert snap["hybridize.compile_seconds"]["total"] > 0
    before = snap["hybridize.compile_seconds"]["count"]
    for _ in range(4):             # steady state: caches stop growing
        tr.step(x, y)
    snap = tel.snapshot()
    assert snap["hybridize.compile_seconds"]["count"] == before


def test_concurrent_first_calls_book_one_compile():
    """Review regression: threads racing the same NEW jit signature must
    record exactly one compile/miss; the lock-waiters book as hits (their
    elapsed time is the winner's compile, not their own)."""
    import threading

    from mxnet_tpu.gluon import nn

    net = nn.Dense(3)
    net.initialize()
    net(np_.ones((1, 4)))
    net.hybridize()
    net(np_.ones((2, 4)))          # warmup (eager)
    net(np_.ones((2, 4)))          # existing signature
    tel.reset()
    x = np_.ones((6, 4))           # new signature raced by all threads
    barrier = threading.Barrier(4)

    def run():
        barrier.wait()
        net(x)

    threads = [threading.Thread(target=run) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = tel.snapshot()
    assert snap["hybridize.cache_misses"]["value"] == 1
    assert snap["hybridize.compile_seconds"]["count"] == 1
    assert snap["hybridize.cache_hits"]["value"] == 3
