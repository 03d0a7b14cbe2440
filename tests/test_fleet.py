"""mx.serve.fleet — router + supervisor semantics (ISSUE 19).

The load-bearing claims under test, all against stub replicas (no
worker subprocesses in the fast tier — the full multi-process drill
lives in tools/fleet_smoke.py and the slow-marked test below): (1) the
router picks the least-loaded ready replica and round-robins ties;
(2) an idempotent ``predict`` retries a SIBLING on dispatch failure
with bounded backoff and surfaces an exhausted budget as a named
:class:`DispatchError`; an edge 503 (shed — never admitted) retries
and surfaces as :class:`RejectedError`; (3) a ``generate`` that
already reached a replica fails FAST by name instead of silently
double-generating, and an SSE stream that dies without its terminal
event is the same named failure; (4) the ``fleet.dispatch`` and
``fleet.spawn`` chaos seams drive exactly those paths; (5) spec
resolution accepts ``module:callable`` and ``file.py:callable`` and
rejects garbage by name.
"""
from __future__ import annotations

import http.server
import json
import socket
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx  # noqa: F401
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.resilience import chaos
from mxnet_tpu.serve.coalescer import DeadlineError, RejectedError
from mxnet_tpu.serve.fleet import (DispatchError, Fleet, NoReplicaError,
                                   Replica, Router, _load_spec, _split_host)


@pytest.fixture()
def fresh_telemetry():
    prev = tel.set_enabled(True)
    tel.reset()
    yield
    tel.reset()
    tel.set_enabled(prev)


# ---------------------------------------------------------- stub plumbing
class _Provider:
    """A static Fleet stand-in: Router only needs ready_replicas()."""

    def __init__(self, reps):
        self.reps = list(reps)

    def ready_replicas(self):
        return [r for r in self.reps if r.state == "ready"]


def _replica(idx, url, load=0.0):
    rep = Replica(idx, proc=None, edge_url=url, obs_url=url)
    rep.load = load
    return rep


def _dead_port():
    """A port with nothing listening (connect is refused)."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _stub_edge(respond):
    """Minimal HTTP server impersonating a replica edge; ``respond``
    gets the handler after the body was read (``handler.body``)."""

    class H(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0) or 0)
            self.body = self.rfile.read(n)
            respond(self)

        def log_message(self, *a):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    return srv, url


def _json_200(handler, doc):
    body = json.dumps(doc).encode()
    handler.send_response(200)
    handler.send_header("Content-Type", "application/json")
    handler.send_header("Content-Length", str(len(body)))
    handler.end_headers()
    handler.wfile.write(body)


def _shed_503(handler):
    body = json.dumps({"error": "stub shed", "shed": True}).encode()
    handler.send_response(503)
    handler.send_header("Content-Length", str(len(body)))
    handler.end_headers()
    handler.wfile.write(body)


def _fast_router(provider, retries=2):
    return Router(provider, retries=retries, backoff_base=0.01,
                  backoff_cap=0.05, timeout=10.0)


# ----------------------------------------------------------------- picking
def test_split_host():
    assert _split_host("http://127.0.0.1:8080") == ("127.0.0.1", 8080)
    assert _split_host("http://10.0.0.3:81/v1/x") == ("10.0.0.3", 81)


def test_router_picks_least_loaded_and_round_robins_ties():
    a = _replica(1, "http://a", load=3.0)
    b = _replica(2, "http://b", load=0.0)
    c = _replica(3, "http://c", load=0.0)
    router = _fast_router(_Provider([a, b, c]))
    picks = {router._pick().edge_url for _ in range(8)}
    assert picks == {"http://b", "http://c"}  # ties rotate, a never
    # exclusion steers to the remaining candidate
    assert router._pick(exclude={"http://b"}).edge_url == "http://c"
    # every candidate excluded -> fall back to the full ready set
    assert router._pick(exclude={"http://a", "http://b", "http://c"}) \
        in (a, b, c)


def test_router_no_ready_replica_raises_503_analogue():
    a = _replica(1, "http://a")
    a.state = "draining"
    router = _fast_router(_Provider([a]))
    with pytest.raises(NoReplicaError) as ei:
        router._pick()
    assert ei.value.status == 503


# ----------------------------------------------------------------- predict
def test_predict_retries_sibling_on_dispatch_failure(fresh_telemetry):
    seen = []
    srv, url = _stub_edge(
        lambda h: (seen.append(json.loads(h.body)),
                   _json_200(h, {"model": "m", "outputs": [[1.0]]})))
    try:
        dead = _replica(1, f"http://127.0.0.1:{_dead_port()}", load=0.0)
        good = _replica(2, url, load=5.0)   # worse load: tried SECOND
        router = _fast_router(_Provider([dead, good]))
        out = router.predict("m", [onp.ones((2,), "float32")])
        assert out["outputs"] == [[1.0]]
        assert seen[0]["model"] == "m"
        assert seen[0]["inputs"] == [[1.0, 1.0]]
        assert tel.snapshot()["fleet.dispatch_retries"]["value"] >= 1
    finally:
        srv.shutdown()


def test_predict_exhausted_budget_is_named(fresh_telemetry):
    dead = _replica(1, f"http://127.0.0.1:{_dead_port()}")
    router = _fast_router(_Provider([dead]), retries=2)
    with pytest.raises(DispatchError, match="after 3 attempt"):
        router.predict("m", [[0.0]])


def test_predict_shed_503_surfaces_as_rejected():
    srv, url = _stub_edge(_shed_503)
    try:
        router = _fast_router(_Provider([_replica(1, url)]), retries=1)
        with pytest.raises(RejectedError, match="shed"):
            router.predict("m", [[0.0]])
    finally:
        srv.shutdown()


def test_predict_non_shed_http_error_is_surfaced_not_retried():
    calls = []

    def respond(h):
        calls.append(1)
        body = json.dumps({"error": "deadline 5.0ms already expired",
                           "shed": False}).encode()
        h.send_response(504)
        h.send_header("Content-Length", str(len(body)))
        h.end_headers()
        h.wfile.write(body)

    srv, url = _stub_edge(respond)
    try:
        router = _fast_router(_Provider([_replica(1, url)]), retries=3)
        with pytest.raises(DeadlineError, match="expired"):
            router.predict("m", [[0.0]], deadline_ms=5.0)
        assert len(calls) == 1  # a real answer: never re-dispatched
    finally:
        srv.shutdown()


# ---------------------------------------------------------------- generate
def test_generate_connect_failure_retries_then_good_sse(fresh_telemetry):
    def respond(h):
        h.send_response(200)
        h.send_header("Content-Type", "text/event-stream")
        h.end_headers()
        h.wfile.write(
            b'data: {"i": 0, "token": 7}\n\n'
            b'data: {"i": 1, "token": 9}\n\n'
            b'event: done\ndata: {"finish_reason": "stop", "tokens": 2,'
            b' "truncated": false}\n\n')

    srv, url = _stub_edge(respond)
    try:
        dead = _replica(1, f"http://127.0.0.1:{_dead_port()}", load=0.0)
        good = _replica(2, url, load=5.0)
        router = _fast_router(_Provider([dead, good]))
        got = []
        out = router.generate("m", [1, 2], stream=True,
                              on_token=got.append)
        assert out["tokens"] == [7, 9] == got
        assert out["finish_reason"] == "stop"
        assert len(out["chunk_ts"]) == 2
        assert tel.snapshot()["fleet.dispatch_retries"]["value"] >= 1
    finally:
        srv.shutdown()


def test_generate_shed_retries_sibling_then_rejected():
    srv, url = _stub_edge(_shed_503)
    try:
        router = _fast_router(_Provider([_replica(1, url)]), retries=1)
        with pytest.raises(RejectedError, match="shed"):
            router.generate("m", [1], stream=False)
    finally:
        srv.shutdown()


def test_generate_stream_dying_without_terminal_fails_fast_by_name():
    def respond(h):
        h.send_response(200)
        h.send_header("Content-Type", "text/event-stream")
        h.end_headers()
        h.wfile.write(b'data: {"i": 0, "token": 7}\n\n')
        # ... and the replica "dies": connection closes, no done event

    srv, url = _stub_edge(respond)
    try:
        router = _fast_router(_Provider([_replica(1, url)]), retries=3)
        with pytest.raises(DispatchError, match="not idempotent"):
            router.generate("m", [1], stream=True)
    finally:
        srv.shutdown()


def test_generate_inflight_transport_death_is_not_retried():
    calls = []

    def respond(h):
        calls.append(1)
        # read the request, then slam the connection: the dispatch
        # REACHED the replica, so the router must not re-run it
        h.wfile.close()

    srv, url = _stub_edge(respond)
    try:
        router = _fast_router(_Provider([_replica(1, url)]), retries=3)
        with pytest.raises(DispatchError, match="NOT retried"):
            router.generate("m", [1], stream=False)
        assert len(calls) == 1
    finally:
        srv.shutdown()


# ------------------------------------------------------------- chaos seams
def test_chaos_fleet_dispatch_error_drives_retry_path(fresh_telemetry):
    srv, url = _stub_edge(
        lambda h: _json_200(h, {"model": "m", "outputs": [[2.0]]}))
    try:
        router = _fast_router(_Provider([_replica(1, url)]), retries=4)
        # seed 2 at prob 0.5 draws fire-then-pass at this site: the
        # first dispatch fails at the seam, the retry goes through
        chaos.configure("fleet.dispatch:error:0.5", seed=2)
        try:
            out = router.predict("m", [[0.0]])
        finally:
            chaos.reset()
        assert out["outputs"] == [[2.0]]
        snap = tel.snapshot()
        assert snap["chaos.injected.fleet.dispatch"]["value"] >= 1
        assert snap["fleet.dispatch_retries"]["value"] >= 1
    finally:
        srv.shutdown()


class _NoSpawnFleet(Fleet):
    """Fleet whose spawns are in-process stubs — exercises the spawn
    retry/backoff/bookkeeping machinery without subprocesses."""

    def __init__(self, fail_first=0, **kw):
        self._fail_first = fail_first
        self._spawn_calls = 0
        kw.setdefault("heartbeat_every", 60.0)  # supervisor stays idle
        super().__init__("stub:build", **kw)

    def _spawn_once(self):
        self._spawn_calls += 1
        if chaos.active():
            chaos.maybe_fail("fleet.spawn")
        if self._spawn_calls <= self._fail_first:
            raise ConnectionError(f"stub spawn #{self._spawn_calls}")
        return Replica(self._spawn_calls, proc=None,
                       edge_url="http://127.0.0.1:1",
                       obs_url="http://127.0.0.1:1",
                       doc={"pid": 0, "startup_secs": 0.01,
                            "build_secs": 0.005})


def test_fleet_spawn_retry_is_bounded_and_counted(fresh_telemetry):
    fleet = _NoSpawnFleet(fail_first=2, min_replicas=1, max_replicas=2)
    try:
        assert len(fleet.ready_replicas()) == 1
        assert fleet._spawn_calls == 3
        assert fleet.stats["spawn_failures"] == 2
        assert fleet.stats["cold_start_secs"] == 0.01
        assert fleet.stats["cold_build_secs"] == 0.005
        snap = tel.snapshot()
        assert snap["fleet.spawn_retries"]["value"] == 2
        assert snap["fleet.replicas"]["value"] == 1
    finally:
        fleet.close(10.0)
    assert tel.snapshot()["fleet.replicas"]["value"] == 0


def test_fleet_spawn_chaos_exhausts_by_name(fresh_telemetry):
    chaos.configure("fleet.spawn:error:1.0", seed=0)
    try:
        with pytest.raises(MXNetError, match="spawn failed after"):
            _NoSpawnFleet(min_replicas=1, max_replicas=1)
        assert tel.snapshot()[
            "chaos.injected.fleet.spawn"]["value"] >= 1
    finally:
        chaos.reset()


def test_fleet_min_max_validation():
    with pytest.raises(MXNetError, match="MXNET_FLEET_MIN"):
        Fleet("stub:build", min_replicas=0, max_replicas=1)
    with pytest.raises(MXNetError, match="MXNET_FLEET_MIN"):
        Fleet("stub:build", min_replicas=3, max_replicas=2)


# ---- one process per chip (docs/serving.md "Chip hosts") ------------------
class _ChipStubFleet(_NoSpawnFleet):
    """Stub spawns that claim a chip the way the real spawn does."""

    def _spawn_once(self):
        env, chip = self._claim_chip()
        rep = super()._spawn_once()
        rep.chip = chip
        self.envs = getattr(self, "envs", []) + [env]
        return rep


def test_fleet_gives_each_chip_worker_its_own_chip(monkeypatch):
    from mxnet_tpu.serve import fleet as fleet_mod

    monkeypatch.setattr(fleet_mod, "_host_chips", lambda: 2)
    fleet = _ChipStubFleet(min_replicas=2, max_replicas=2,
                           env={"JAX_PLATFORMS": "tpu,cpu"})
    try:
        assert sorted(r.chip for r in fleet.replicas()) == [0, 1]
        assert [e["TPU_VISIBLE_CHIPS"] for e in fleet.envs] == ["0", "1"]
        assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in fleet.envs)
        with pytest.raises(MXNetError, match="all 2 chip"):
            fleet._claim_chip()
        # a retired replica's chip is free again
        gone = fleet.replicas()[0]
        fleet._retire(gone, detected_at=None)
        assert fleet._claim_chip()[1] == gone.chip
    finally:
        fleet.close(10.0)


def test_fleet_refuses_more_chip_workers_than_chips(monkeypatch):
    from mxnet_tpu.serve import fleet as fleet_mod

    monkeypatch.setattr(fleet_mod, "_host_chips", lambda: 1)
    with pytest.raises(MXNetError, match="exceeds the 1 chip"):
        _NoSpawnFleet(min_replicas=1, max_replicas=2,
                      env={"JAX_PLATFORMS": "tpu"})
    # workers pinned to the host CPU are not chip workers
    fleet = _NoSpawnFleet(min_replicas=1, max_replicas=2,
                          env={"JAX_PLATFORMS": "cpu"})
    try:
        assert fleet._claim_chip() == (fleet._env, None)
    finally:
        fleet.close(10.0)


def test_fleet_refuses_a_parent_that_holds_the_chips(monkeypatch):
    from mxnet_tpu.serve import fleet as fleet_mod

    monkeypatch.setattr(fleet_mod, "_host_chips", lambda: 4)
    monkeypatch.setattr(fleet_mod, "_parent_holds_chips", lambda: True)
    with pytest.raises(MXNetError, match="holds the host's chip"):
        _NoSpawnFleet(min_replicas=1, max_replicas=1,
                      env={"JAX_PLATFORMS": "tpu"})
    monkeypatch.undo()
    assert fleet_mod._parent_holds_chips() is False   # this CPU suite


def test_fleet_supervisor_thread_lifecycle():
    fleet = _NoSpawnFleet(min_replicas=1, max_replicas=1)
    try:
        names = {t.name for t in threading.enumerate() if t.is_alive()}
        assert "mx-fleet-supervisor" in names
    finally:
        fleet.close(10.0)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if not any(t.name == "mx-fleet-supervisor"
                   for t in threading.enumerate() if t.is_alive()):
            break
        time.sleep(0.02)
    assert not any(t.name == "mx-fleet-supervisor"
                   for t in threading.enumerate() if t.is_alive())
    fleet.close(5.0)  # idempotent


# ------------------------------------------------------------------- specs
def test_load_spec_module_and_file(tmp_path):
    fn = _load_spec("mxnet_tpu.serve.fleet:worker_main")
    assert callable(fn)
    p = tmp_path / "spec.py"
    p.write_text("def build():\n    return {'ok': 1}\n")
    assert _load_spec(str(p) + ":build")() == {"ok": 1}
    with pytest.raises(MXNetError, match="bad --spec"):
        _load_spec("no_colon_here")
    with pytest.raises(MXNetError, match="no callable"):
        _load_spec("mxnet_tpu.serve.fleet:nope")


# ------------------------------------------------------- real worker (slow)
@pytest.mark.slow
def test_fleet_single_replica_end_to_end(tmp_path):
    """One real worker subprocess: spawn -> READY -> routed predict ->
    graceful close.  The heavier drills (SIGKILL recovery, warm
    respawn, streaming parity) live in tools/fleet_smoke.py."""
    spec = tmp_path / "spec.py"
    spec.write_text(
        "import numpy as onp\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import serve\n"
        "from mxnet_tpu.gluon import nn\n\n"
        "def build():\n"
        "    mx.random.seed(0)\n"
        "    net = nn.HybridSequential()\n"
        "    net.add(nn.Dense(16, activation='relu', in_units=8))\n"
        "    net.add(nn.Dense(4, in_units=16))\n"
        "    net.initialize(mx.init.Xavier())\n"
        "    net(mx.np.zeros((1, 8)))\n"
        "    serve.register('mlp', net, bucketer={0: [2]},\n"
        "                   sample=onp.zeros((8,), 'float32'))\n")
    fleet = Fleet(str(spec) + ":build", min_replicas=1, max_replicas=1,
                  heartbeat_every=0.5, spawn_timeout=600.0)
    try:
        reps = fleet.ready_replicas()
        assert len(reps) == 1
        assert reps[0].pid and reps[0].edge_url and reps[0].obs_url
        assert fleet.stats["cold_start_secs"] > 0
        out = fleet.router.predict(
            "mlp", [onp.ones((8,), "float32")], timeout=60.0)
        assert len(out["outputs"]) == 1
        assert len(out["outputs"][0]) == 4
    finally:
        fleet.close(30.0)
    assert fleet.replicas() == []
