"""mx.analysis.thread_check: the runtime lock-order witness (ISSUE 17).

The witness must PROVE it can find something (a forced T101 inversion
and a forced T102 long hold are caught), stay silent on the correct
patterns (condition-variable waits, consistent lock order), and the
named threads the serving tier spawns must carry their stable ``mx-*``
names and all die at subsystem close — the lifecycle half of the
concurrency contract docs/analysis.md documents.
"""
from __future__ import annotations

import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx  # noqa: F401 — telemetry/trace integration below
from mxnet_tpu import telemetry as tel
from mxnet_tpu.analysis import thread_check as tchk


@pytest.fixture()
def witness():
    """Armed witness in warn mode, fully reset around each test."""
    tchk.install(raise_on_violation=False)
    tchk.clear()
    yield tchk
    tchk.uninstall()


# ---------------------------------------------------------------------------
# T101 lock-order inversion
# ---------------------------------------------------------------------------

def test_t101_forced_inversion_is_caught(witness):
    a, b = tchk.lock("wa"), tchk.lock("wb")
    with a:
        with b:
            pass
    with b:
        with a:  # opposite order — the seeded deadlock
            pass
    diags = tchk.diagnostics()
    assert [d.code for d in diags] == ["T101"]
    assert "wa" in diags[0].message and "wb" in diags[0].message
    # the order graph remembers both directions
    edges = tchk.order_edges()
    assert "wb" in edges.get("wa", set())
    assert "wa" in edges.get("wb", set())


def test_t101_consistent_order_is_silent(witness):
    a, b = tchk.lock("ca"), tchk.lock("cb")
    for _ in range(3):
        with a:
            with b:
                pass
    assert tchk.diagnostics() == []


def test_t101_cross_thread_inversion(witness):
    """The real shape: thread 1 teaches a->b, thread 2 attempts b->a.
    Sequential phases so the test cannot actually deadlock."""
    a, b = tchk.lock("xa"), tchk.lock("xb")

    def forward():
        with a:
            with b:
                pass

    def backward():
        with b:
            with a:
                pass

    t1 = threading.Thread(target=forward)
    t1.start()
    t1.join()
    t2 = threading.Thread(target=backward)
    t2.start()
    t2.join()
    assert [d.code for d in tchk.diagnostics()] == ["T101"]


def test_t101_raise_mode_raises():
    tchk.install(raise_on_violation=True)
    try:
        a, b = tchk.lock("ra"), tchk.lock("rb")
        with a:
            with b:
                pass
        with pytest.raises(tchk.ThreadCheckError, match="T101"):
            with b:
                with a:
                    pass
    finally:
        tchk.uninstall()


def test_reentrant_rlock_is_not_an_inversion(witness):
    r = tchk.rlock("rr")
    with r:
        with r:
            pass
    assert tchk.diagnostics() == []


# ---------------------------------------------------------------------------
# T102 long hold
# ---------------------------------------------------------------------------

def test_t102_long_hold_is_caught():
    tchk.install(raise_on_violation=False, hold_ms=10)
    tchk.clear()
    try:
        lk = tchk.lock("slow")
        with lk:
            time.sleep(0.05)
        diags = tchk.diagnostics()
        assert [d.code for d in diags] == ["T102"]
        assert "slow" in diags[0].message
    finally:
        tchk.uninstall()


def test_t102_condition_wait_does_not_count_as_hold():
    """cv.wait releases the lock — a long wait must not bill the lock's
    hold time (the canonical dispatcher idle loop)."""
    tchk.install(raise_on_violation=False, hold_ms=10)
    tchk.clear()
    try:
        cv = tchk.condition("idle")
        with cv:
            cv.wait(0.05)  # longer than the threshold
        assert tchk.diagnostics() == []
    finally:
        tchk.uninstall()


def test_t102_disabled_when_threshold_unset(witness):
    lk = tchk.lock("unmetered")
    with lk:
        time.sleep(0.02)
    assert tchk.diagnostics() == []


# ---------------------------------------------------------------------------
# arming / disarming / integration
# ---------------------------------------------------------------------------

def test_disarmed_proxies_are_plain_locks():
    assert not tchk.enabled()
    lk = tchk.lock("plain")
    with lk:
        pass
    assert not lk.locked()
    assert tchk.diagnostics() == []


def test_env_mode_parsing(monkeypatch):
    for raw, want in (("", ""), ("0", ""), ("off", ""), ("1", "warn"),
                      ("true", "warn"), ("raise", "raise"),
                      ("RAISE", "raise")):
        monkeypatch.setenv("MXNET_THREAD_CHECK", raw)
        assert tchk.env_mode() == want, raw
    monkeypatch.delenv("MXNET_THREAD_CHECK")
    assert tchk.env_mode() == ""


def test_findings_tick_telemetry(witness):
    prev = tel.set_enabled(True)
    tel.reset()
    try:
        a, b = tchk.lock("ta"), tchk.lock("tb")
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        snap = tel.snapshot()
        assert snap["analysis.thread_check_findings"]["value"] == 1
        assert snap["analysis.thread_check.T101"]["value"] == 1
    finally:
        tel.reset()
        tel.set_enabled(prev)


def test_clear_resets_findings_and_graph(witness):
    a, b = tchk.lock("za"), tchk.lock("zb")
    with a:
        with b:
            pass
    tchk.clear()
    assert tchk.diagnostics() == []
    assert tchk.order_edges() == {}
    # the forgotten order means the opposite order is now first — silent
    with b:
        with a:
            pass
    assert tchk.diagnostics() == []


def test_condition_wait_repush_keeps_stack_sane(witness):
    cv = tchk.condition("cvq")

    def waiter():
        with cv:
            cv.wait(0.2)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cv:
        cv.notify_all()
    t.join(5.0)
    assert not t.is_alive()
    assert tchk.diagnostics() == []


# ---------------------------------------------------------------------------
# stable thread names + lifecycle (satellites 1 and 2)
# ---------------------------------------------------------------------------

def _mx_threads():
    return {t.name for t in threading.enumerate()
            if t.is_alive() and t.name.startswith("mx-")}


class _StubBlock:
    def begin_cache(self, slots, cap):
        return None


class _StubEntry:
    name = "stub"
    slots = 2
    capacity_buckets = (8,)
    max_new_tokens = 4
    window = None
    block = _StubBlock()


def test_serve_thread_names_and_close(witness):
    from mxnet_tpu.serve.server import Server

    srv = Server()
    srv._ensure_threads()
    names = _mx_threads()
    assert "mx-serve-dispatcher" in names
    assert "mx-serve-completer" in names
    srv.close(timeout=10.0)
    left = _mx_threads()
    assert "mx-serve-dispatcher" not in left
    assert "mx-serve-completer" not in left
    assert tchk.diagnostics() == []


def test_decode_worker_name_and_close(witness):
    from mxnet_tpu.serve.decode import DecodeServer

    srv = DecodeServer(_StubEntry())
    assert "mx-decode-worker-stub" in _mx_threads()
    srv.close(timeout=10.0)
    assert "mx-decode-worker-stub" not in _mx_threads()
    assert tchk.diagnostics() == []


def test_obs_http_thread_name_and_close(witness):
    from mxnet_tpu.obs.http import MetricsServer

    srv = MetricsServer(0)
    assert "mx-obs-http" in _mx_threads()
    srv.close()
    assert "mx-obs-http" not in _mx_threads()
    assert tchk.diagnostics() == []


def test_edge_thread_names_and_close(witness):
    import urllib.request

    from mxnet_tpu.serve.edge import EdgeServer

    srv = EdgeServer(port=0)
    try:
        assert "mx-edge-loop" in _mx_threads()
        # force a wait-pool thread into existence via a live request
        with urllib.request.urlopen(srv.url + "/healthz", timeout=10.0) as r:
            assert r.status == 200
    finally:
        srv.close(10.0)
    left = {n for n in _mx_threads() if n.startswith("mx-edge")}
    assert not left, f"edge threads survived close: {sorted(left)}"
    assert tchk.diagnostics() == []


def test_fleet_supervisor_thread_name_and_close(witness):
    from mxnet_tpu.serve.fleet import Fleet, Replica

    class _Stub(Fleet):
        def _spawn_once(self):
            return Replica(1, proc=None, edge_url="http://127.0.0.1:1",
                           obs_url="http://127.0.0.1:1",
                           doc={"pid": 0, "startup_secs": 0.01})

    fleet = _Stub("stub:build", min_replicas=1, max_replicas=1,
                  heartbeat_every=60.0)
    try:
        assert "mx-fleet-supervisor" in _mx_threads()
    finally:
        fleet.close(10.0)
    assert "mx-fleet-supervisor" not in _mx_threads()
    assert tchk.diagnostics() == []


def test_ckpt_writer_thread_name_and_close(witness, tmp_path):
    from mxnet_tpu.resilience.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr._enqueue(lambda: None)
    assert "mx-ckpt-writer" in _mx_threads()
    mgr.close()
    assert "mx-ckpt-writer" not in _mx_threads()
    assert tchk.diagnostics() == []


def test_flight_watchdog_thread_name_and_close(witness, tmp_path):
    from mxnet_tpu.trace import flight

    flight.arm(str(tmp_path), hang_timeout=60.0)
    try:
        assert "mx-flight-watchdog" in _mx_threads()
    finally:
        flight.disarm()
    assert "mx-flight-watchdog" not in _mx_threads()
    assert tchk.diagnostics() == []


def test_prefetch_thread_name_and_close(witness):
    from mxnet_tpu.gluon.data.prefetch import DevicePrefetcher

    def batches():
        for _ in range(4):
            yield onp.zeros((2,), "float32")

    pf = DevicePrefetcher(batches())
    it = iter(pf)
    next(it)
    assert "mx-prefetch" in _mx_threads()
    pf.close()
    deadline = time.time() + 5.0
    while "mx-prefetch" in _mx_threads() and time.time() < deadline:
        time.sleep(0.01)
    assert "mx-prefetch" not in _mx_threads()
    assert tchk.diagnostics() == []


def test_no_mx_thread_survives_subsystem_close(witness, tmp_path):
    """The fleet-wide lifecycle assert: spin up every cheap threaded
    subsystem, close them all, and require that NO new ``mx-*`` thread
    is left alive — a leak here is a T004 the static pass missed."""
    from mxnet_tpu.gluon.data.prefetch import DevicePrefetcher
    from mxnet_tpu.obs.http import MetricsServer
    from mxnet_tpu.resilience.checkpoint import CheckpointManager
    from mxnet_tpu.serve.decode import DecodeServer
    from mxnet_tpu.serve.edge import EdgeServer
    from mxnet_tpu.serve.server import Server
    from mxnet_tpu.trace import flight

    before = _mx_threads()

    srv = Server()
    srv._ensure_threads()
    dec = DecodeServer(_StubEntry())
    edge = EdgeServer(port=0)
    obs = MetricsServer(0)
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr._enqueue(lambda: None)
    flight.arm(str(tmp_path), hang_timeout=60.0)

    def batches():
        yield onp.zeros((2,), "float32")
        yield onp.zeros((2,), "float32")

    pf = DevicePrefetcher(batches())
    next(iter(pf))

    assert _mx_threads() - before, "expected live mx-* threads mid-test"

    pf.close()
    flight.disarm()
    mgr.close()
    obs.close()
    edge.close(timeout=10.0)
    dec.close(timeout=10.0)
    srv.close(timeout=10.0)

    deadline = time.time() + 5.0
    while (_mx_threads() - before) and time.time() < deadline:
        time.sleep(0.02)
    leaked = _mx_threads() - before
    assert not leaked, f"mx-* threads survived close: {sorted(leaked)}"
    # and the whole dance ran witnessed without a single finding
    assert tchk.diagnostics() == []
