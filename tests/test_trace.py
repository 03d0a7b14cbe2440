"""mx.trace (ISSUE 7): span recorder, cross-thread correlation, the
Perfetto exporter and the flight recorder.

The load-bearing claims under test: (1) spans record onto bounded
per-thread rings and also tick the matching telemetry timer (no double
instrumentation); (2) correlation IDs survive crossing into the
DevicePrefetcher producer thread and the ``warmup(background=True)``
thread, and the ``InflightQueue`` attributes its step-(t−K) wait to
the step that PUSHED the handle, not the step draining it; (3) there
is exactly one Chrome-trace emitter and its output parses with the
documented structure; (4) an
``MXNetError`` (fault-injection included) leaves a flight dump when
armed, and the hang watchdog fires on a stalled event stream.
"""
import json
import os
import threading
import time

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tel
from mxnet_tpu import trace
from mxnet_tpu.base import DeferredInitializationError, MXNetError
from mxnet_tpu.engine import InflightQueue
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.data import ArrayDataset, DataLoader, DevicePrefetcher
from mxnet_tpu.parallel.mesh import default_mesh
from mxnet_tpu.parallel.trainer import ShardedTrainer
from mxnet_tpu.trace import flight


def _ce(pred, y):
    logp = jax.nn.log_softmax(pred.astype(jnp.float32))
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]


def _trainer(feat=8, classes=4, **kw):
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(classes))
    net.initialize()
    net(mx.np.zeros((2, feat)))
    return ShardedTrainer(net, _ce, mesh=default_mesh(), optimizer="sgd",
                          learning_rate=0.05, **kw)


def _batch(n=16, feat=8, classes=4, seed=0):
    rs = onp.random.RandomState(seed)
    return (rs.rand(n, feat).astype("float32"),
            rs.randint(0, classes, size=(n,)).astype("int32"))


@pytest.fixture(autouse=True)
def _clean_rings():
    trace.reset()
    yield
    trace.reset()
    trace.set_enabled(True)


def _names(evs):
    return [e["name"] for e in evs]


# ---------------------------------------------------------------------------
# recorder basics
# ---------------------------------------------------------------------------

def test_span_records_event_with_attrs_and_duration():
    with trace.span("unit.outer", model="x"):
        with trace.span("unit.inner"):
            pass
    evs = [e for e in trace.events() if e["name"].startswith("unit.")]
    # events() sorts by start time: the outer span opened first
    assert _names(evs) == ["unit.outer", "unit.inner"]
    assert evs[0]["attrs"] == {"model": "x"}
    assert evs[0]["dur"] >= evs[1]["dur"] >= 0.0


def test_span_ticks_matching_telemetry_timer_exactly_once():
    t = tel.timer("unit.span_seconds")
    n0 = t.count
    with trace.span("unit.timed", timer="unit.span_seconds"):
        pass
    assert t.count == n0 + 1
    # trace disabled, telemetry on: the timer still ticks (spans REPLACE
    # the old `with telemetry.timer(...)` call sites) but no event lands
    n_evs = sum(1 for e in trace.events() if e["name"] == "unit.timed")
    trace.set_enabled(False)
    with trace.span("unit.timed", timer="unit.span_seconds"):
        pass
    assert t.count == n0 + 2
    assert sum(1 for e in trace.events()
               if e["name"] == "unit.timed") == n_evs
    trace.set_enabled(True)


def test_disabled_trace_records_nothing():
    trace.set_enabled(False)
    with trace.span("unit.off"):
        pass
    trace.instant("unit.off_instant")
    assert not any(e["name"].startswith("unit.off")
                   for e in trace.events())
    trace.set_enabled(True)


def test_span_records_error_attr_on_exception():
    t = tel.timer("unit.fail_seconds")
    n0 = t.count
    with pytest.raises(ValueError):
        with trace.span("unit.fails", timer="unit.fail_seconds"):
            raise ValueError("nope")
    ev = [e for e in trace.events() if e["name"] == "unit.fails"][0]
    assert ev["attrs"]["error"] == "ValueError"
    # the metric keeps success-only semantics (the event still records)
    assert t.count == n0
    with pytest.raises(ValueError):
        with trace.span("unit.fails", timer="unit.fail_seconds",
                        timer_on_error=True):  # wait-seam semantics
            raise ValueError("nope")
    assert t.count == n0 + 1


def test_ring_is_bounded_per_thread():
    cap = trace.recorder.ring_capacity()
    for i in range(cap + 50):
        trace.instant("unit.flood", i=i)
    mine = [e for e in trace.events() if e["name"] == "unit.flood"]
    assert len(mine) == cap
    # oldest events aged out: the smallest surviving index is 50
    assert min(e["attrs"]["i"] for e in mine) == 50


def test_correlate_nests_and_restores():
    with trace.correlate(step=3):
        with trace.correlate(micro=1):
            trace.instant("unit.corr")
        assert trace.correlation() == {"step": 3}
    assert trace.correlation() == {}
    ev = [e for e in trace.events() if e["name"] == "unit.corr"][0]
    assert ev["corr"] == {"step": 3, "micro": 1}


# ---------------------------------------------------------------------------
# cross-thread correlation (the ISSUE's satellite test requirement)
# ---------------------------------------------------------------------------

def test_capture_attach_moves_correlation_across_threads():
    with trace.correlate(step=9):
        token = trace.capture()
    out = {}

    def worker():
        trace.attach(token)
        with trace.span("unit.worker"):
            out["corr"] = trace.correlation()

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert out["corr"] == {"step": 9}
    ev = [e for e in trace.events() if e["name"] == "unit.worker"][0]
    assert ev["corr"] == {"step": 9}


def test_prefetcher_producer_spans_carry_owner_correlation():
    """Spans opened in DevicePrefetcher's producer thread must carry
    the correlation context of the loop that OWNS the epoch."""
    x, y = _batch(n=48)
    loader = DataLoader(ArrayDataset(x, y), batch_size=16)
    with trace.correlate(step=41):
        batches = list(DevicePrefetcher(loader))
    assert len(batches) == 3
    fetches = [e for e in trace.events() if e["name"] == "pipeline.fetch"]
    assert fetches, "producer thread recorded no pipeline.fetch spans"
    assert all(e["corr"].get("step") == 41 for e in fetches)
    assert all(e["thread"] == "mx-prefetch" for e in fetches)
    # the producer labels each batch it stages; the last fetch span is
    # the end-of-epoch StopIteration probe (marked with an error attr)
    good = [e for e in fetches if not (e["attrs"] or {}).get("error")]
    assert sorted(e["attrs"]["batch"] for e in good) == [0, 1, 2]
    h2d = [e for e in trace.events() if e["name"] == "pipeline.h2d"]
    assert h2d and all(e["corr"].get("step") == 41 for e in h2d)


def test_background_warmup_spans_carry_warmup_correlation():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    net(mx.np.zeros((2, 8)))
    net.hybridize()
    with trace.correlate(owner="loop"):
        handle = net.warmup((4, 8), background=True)
        n = handle.wait(60)
    assert n == 1
    warm = [e for e in trace.events() if e["name"] == "jit.warmup"]
    assert warm, "no jit.warmup span recorded"
    ev = warm[-1]
    assert ev["thread"] == "mx-jit-warmup"
    assert ev["corr"].get("owner") == "loop"  # owner context crossed over
    assert isinstance(ev["corr"].get("warmup"), int)  # its own warmup id
    # the compile spans inside the warmup carry the same warmup id
    wid = ev["corr"]["warmup"]
    compiles = [e for e in trace.events()
                if e["name"] == "hybridize.compile"
                and e["corr"].get("warmup") == wid]
    assert compiles and all(e["thread"] == "mx-jit-warmup"
                            for e in compiles)


def test_inflight_queue_attributes_wait_to_pushing_step():
    """Draining step t-K's handle while dispatching step t must record
    the stall against step t-K (the owner of the handle)."""
    q = InflightQueue(limit=1)
    with trace.correlate(step=1):
        q.push(jnp.zeros(4))
    with trace.correlate(step=2):
        q.push(jnp.zeros(4))  # forces the wait on step 1's handle
    stalls = [e for e in trace.events() if e["name"] == "pipeline.stall"]
    assert len(stalls) == 1
    assert stalls[0]["corr"] == {"step": 1}
    with trace.correlate(step=99):
        q.drain()  # step 2's handle retires under its own id
    stalls = [e for e in trace.events() if e["name"] == "pipeline.stall"]
    assert stalls[-1]["corr"] == {"step": 2}


def test_trainer_steps_stamp_step_correlation():
    trainer = _trainer()
    x, y = _batch()
    for _ in range(3):
        trainer.step(x, y)
    trainer.drain()
    steps = [e for e in trace.events() if e["name"] == "trainer.step"]
    assert [e["corr"].get("step") for e in steps] == [1, 2, 3]
    # dispatch spans nest under the same correlation
    disp = [e for e in trace.events() if e["name"] == "trainer.dispatch"]
    assert sorted(e["corr"].get("step") for e in disp) == [1, 2, 3]


# ---------------------------------------------------------------------------
# exporter
# ---------------------------------------------------------------------------

def test_chrome_export_structure_and_thread_metadata():
    with trace.correlate(step=5):
        with trace.span("unit.export", k="v"):
            time.sleep(0.001)
    doc = json.loads(trace.dumps_chrome())
    assert doc["displayTimeUnit"] == "ms"
    assert doc["metadata"]["pid"] == os.getpid()
    evs = [e for e in doc["traceEvents"] if e.get("name") == "unit.export"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["ph"] == "X" and ev["cat"] == "unit"
    assert ev["dur"] >= 1000  # microseconds
    assert ev["args"] == {"step": 5, "k": "v"}
    meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert any(m["args"]["name"] == threading.current_thread().name
               for m in meta)


def test_exporter_merges_engine_chrome_events():
    engine_str = ('{"name":"op_a","ph":"X","ts":1,"dur":2,"pid":0,'
                  '"tid":7}')
    evs = trace.export.chrome_events(engine_events=engine_str)
    native = [e for e in evs if e.get("name") == "op_a"]
    assert len(native) == 1
    assert native[0]["pid"] == os.getpid()  # folded into this process
    assert native[0]["cat"] == "engine"


def test_profiler_dumps_trace_passthrough_and_objects():
    task = mx.profiler.Task(name="unit_task")
    task.start()
    task.stop()
    ctr = mx.profiler.Counter(None, "unit_ctr", 1)
    ctr.increment(2)
    with mx.profiler.Scope("unit_scope"):
        pass
    doc = json.loads(mx.profiler.dumps(format="trace"))
    names = {e.get("name") for e in doc["traceEvents"]}
    assert "profiler.unit_task" in names
    assert "profiler.unit_ctr" in names
    assert "profiler.unit_scope" in names
    ctr_evs = [e for e in doc["traceEvents"]
               if e.get("name") == "profiler.unit_ctr"]
    assert ctr_evs[-1]["ph"] == "C" and ctr_evs[-1]["args"]["value"] == 3


def test_phased_span_emits_begin_end_pair():
    with trace.span("unit.phased", phased=True):
        pass
    kinds = [e["kind"] for e in trace.events()
             if e["name"] == "unit.phased"]
    assert kinds == ["B", "E"]
    # a phased span that never closes still leaves its begin event —
    # the wedged-barrier flight-recorder case
    sp = trace.span("unit.wedged", phased=True)
    sp.__enter__()
    assert [e["kind"] for e in trace.events()
            if e["name"] == "unit.wedged"] == ["B"]
    sp.__exit__(None, None, None)


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------

def _profiled(tmp_path, work):
    """Run ``work`` on a worker thread inside a ``jax.profiler`` session
    with the benchmark's options; return {name: [event, ...]} of the
    host plane's ``unit.*`` events (``start_ns``, ``end_ns``, ``stats``)."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t = threading.Thread(target=work, name="mx-unit-worker")
        t.start()
        t.join()
    finally:
        jax.profiler.stop_trace()
    paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(paths) == 1
    found = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("unit."):
                    found.setdefault(ev.name, []).append(
                        {"start_ns": ev.start_ns,
                         "end_ns": ev.start_ns + ev.duration_ns,
                         "stats": dict(ev.stats)})
    return found


def test_span_lands_on_profiler_host_plane_with_attrs_and_correlation(
        tmp_path):
    def work():
        with trace.correlate(serve_decode=7):
            with trace.span("unit.prof_outer", request=3, ratio=0.5,
                            label="x", shape=(1, 2)):
                with trace.span("unit.prof_inner"):
                    time.sleep(0.002)
                with trace.span("unit.prof_phased", phased=True):
                    time.sleep(0.001)

    found = _profiled(tmp_path, work)
    assert sorted(found) == ["unit.prof_inner", "unit.prof_outer",
                             "unit.prof_phased"]
    outer, = found["unit.prof_outer"]
    inner, = found["unit.prof_inner"]
    phased, = found["unit.prof_phased"]
    # attrs AND correlation ride as stats; a non-scalar as its str
    assert outer["stats"] == {"serve_decode": 7, "request": 3, "ratio": 0.5,
                              "label": "x", "shape": "(1, 2)"}
    assert inner["stats"] == {"serve_decode": 7}
    # nested spans nest on the trace's clock, siblings do not overlap
    assert outer["start_ns"] <= inner["start_ns"] \
        and inner["end_ns"] <= phased["start_ns"] \
        and phased["end_ns"] <= outer["end_ns"]
    assert inner["end_ns"] - inner["start_ns"] >= 2e6
    # one event each in the ring too: the same span, two clocks
    ring = [e["name"] for e in trace.events()
            if e["name"].startswith("unit.prof_")]
    assert sorted(ring) == ["unit.prof_inner", "unit.prof_outer",
                            "unit.prof_phased", "unit.prof_phased"]


def test_corr_override_is_what_the_annotation_carries(tmp_path):
    def work():
        with trace.correlate(step=9):
            with trace.span("unit.prof_deferred", corr=(("step", 4),)):
                pass

    found = _profiled(tmp_path, work)
    assert found["unit.prof_deferred"][0]["stats"] == {"step": 4}


def test_disabled_recording_builds_no_annotation(tmp_path, monkeypatch):
    built = []
    real = trace.recorder._Annotation

    def counting(name, **kw):
        built.append(name)
        return real(name, **kw)

    monkeypatch.setattr(trace.recorder, "_Annotation", counting)
    trace.set_enabled(False)

    def work():
        with trace.span("unit.prof_off", timer="unit.prof_off_seconds"):
            pass
        trace.record_span("unit.prof_off_recorded", 0.0, 1.0)

    found = _profiled(tmp_path, work)
    assert built == [] and found == {}
    trace.set_enabled(True)
    # after-the-fact events stay ring-only even when recording is on
    trace.record_span("unit.prof_recorded", time.perf_counter(), 0.001)
    trace.instant("unit.prof_instant")
    trace.counter("unit.prof_counter", 1)
    assert built == []
    with trace.span("unit.prof_on"):
        pass
    assert built == ["unit.prof_on"]


def test_span_disabled_mid_flight_still_closes_its_annotation():
    sp = trace.span("unit.prof_flip")
    sp.__enter__()
    trace.set_enabled(False)
    sp.__exit__(None, None, None)       # must not leak the open TraceMe
    trace.set_enabled(True)
    assert not any(e["name"] == "unit.prof_flip" for e in trace.events())


def test_profiler_objects_build_one_annotation_each(monkeypatch):
    """Scope and Task carry no annotation of their own: the span does."""
    built = []
    real = trace.recorder._Annotation
    monkeypatch.setattr(trace.recorder, "_Annotation",
                        lambda name, **kw: built.append(name) or real(name))
    with mx.profiler.Scope("unit_once"):
        pass
    task = mx.profiler.Task(name="unit_once_task")
    task.start()
    task.stop()
    assert built == ["profiler.unit_once", "profiler.unit_once_task"]
    import inspect
    assert "TraceAnnotation(" not in inspect.getsource(mx.profiler)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_dump_on_mxnet_error_when_armed(tmp_path):
    flight.arm(str(tmp_path))
    try:
        trace.instant("unit.before_crash")
        try:
            raise MXNetError("unit crash")
        except MXNetError:
            pass  # caught — the dump must STILL have happened
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight-")]
        assert len(dumps) == 1
        doc = json.load(open(tmp_path / dumps[0]))
        assert "unit crash" in doc["metadata"]["flight"]["reason"]
        assert any(e.get("name") == "unit.before_crash"
                   for e in doc["traceEvents"])
    finally:
        flight.disarm()
    # disarmed: no more dumps
    try:
        raise MXNetError("after disarm")
    except MXNetError:
        pass
    assert len([f for f in os.listdir(tmp_path)
                if f.startswith("flight-")]) == 1


def test_flight_skips_deferred_init_and_rate_limits(tmp_path,
                                                    monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_FLIGHT_MAX", "2")
    flight.arm(str(tmp_path))
    try:
        try:
            raise DeferredInitializationError("normal control flow")
        except DeferredInitializationError:
            pass
        assert not [f for f in os.listdir(tmp_path)
                    if f.startswith("flight-")]
        for i in range(5):
            try:
                raise MXNetError(f"storm {i}")
            except MXNetError:
                pass
        assert len([f for f in os.listdir(tmp_path)
                    if f.startswith("flight-")]) == 2  # capped
    finally:
        flight.disarm()


def test_flight_chaos_barrier_fault_leaves_dump(tmp_path):
    from mxnet_tpu.parallel import dist
    from mxnet_tpu.resilience import chaos

    flight.arm(str(tmp_path))
    try:
        chaos.configure("dist.barrier:error:1.0")
        with pytest.raises(chaos.ChaosError):
            dist.barrier("trace_unit_fault")
    finally:
        chaos.reset()
        flight.disarm()
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight-")]
    assert len(dumps) == 1
    doc = json.load(open(tmp_path / dumps[0]))
    assert "ChaosError" in doc["metadata"]["flight"]["reason"]
    # the wedged collective's BEGIN event made it into the dump even
    # though the barrier never completed cleanly (phased span)
    assert any(e.get("name") == "dist.barrier" and e.get("ph") == "B"
               for e in doc["traceEvents"])


def test_hang_watchdog_dumps_on_stalled_event_stream(tmp_path):
    flight.arm(str(tmp_path), hang_timeout=0.3)
    try:
        trace.instant("unit.heartbeat")  # arm the "activity seen" state
        deadline = time.time() + 10.0
        dumps = []
        while time.time() < deadline and not dumps:
            time.sleep(0.1)  # no events recorded: the stream is stalled
            # endswith filters out export.write's in-flight *.tmp.<pid>
            # file — this loop races the watchdog's atomic rename
            dumps = [f for f in os.listdir(tmp_path)
                     if f.startswith("flight-") and f.endswith(".json")]
        assert dumps, "watchdog never fired on a stalled event stream"
        doc = json.load(open(tmp_path / dumps[0]))
        assert "hang" in doc["metadata"]["flight"]["reason"]
    finally:
        flight.disarm()
