"""mx.serve.fleet — an elastic replica fleet behind one router
(docs/serving.md, "Network edge + fleet").

One process per replica: each worker subprocess builds its models (a
user-supplied *spec* callable), starts an obs endpoint
(``/metrics``/``/readyz``/``/statusz``) and an
:class:`~mxnet_tpu.serve.edge.EdgeServer`, and announces itself with
one ``READY`` line.  The parent runs:

* a **router** (:class:`Router`) that picks the least-loaded ready
  replica using the scraped ``serve.queue_depth`` /
  ``serve.decode_slots_active`` gauges (``mx.obs.aggregate`` over the
  workers' obs endpoints — the FleetView's per-worker gauge rows) and
  dispatches over HTTP with bounded retry + exponential backoff
  (:func:`mxnet_tpu.parallel.dist.backoff_delay`).  Idempotent
  ``predict`` retries a SIBLING on dispatch failure; a ``generate``
  whose request already reached a replica is non-idempotent and fails
  fast with a named :class:`DispatchError` instead of silently
  double-generating.  An edge 503 is a *shed* — the request was never
  admitted, so retrying a sibling is always safe.
* a **supervisor** thread (``mx-fleet-supervisor``) heartbeating every
  replica's ``/readyz`` each ``MXNET_FLEET_HEARTBEAT_EVERY`` seconds.
  A replica that answers 503 is **drained** (router stops routing; the
  worker flips ``obs.set_fleet_state(draining=True)`` + edge
  admissions so its ``/readyz`` names the ``draining`` check while
  in-flight work finishes or deadlines out) and then retired; a
  replica whose process died or that misses
  ``MXNET_FLEET_HEARTBEAT_FAILS`` consecutive probes is killed
  outright.  Every loss is **respawned** — replica cold start is a
  deterministic replay of the persistent compile cache
  (``JAX_COMPILATION_CACHE_DIR``), which is what makes respawn
  warm-start time gateable (tools/fleet_smoke.py: warm ≤ 50% of
  cold) — and the detection→ready recovery time lands in
  ``fleet.recovery_seconds``.
* **autoscaling** between ``MXNET_FLEET_MIN`` and ``MXNET_FLEET_MAX``
  on a windowed per-replica queue-depth signal: sustained depth above
  ``MXNET_FLEET_SCALE_UP_DEPTH`` adds a replica, a sustained idle
  window drains one down to the floor.

Chaos seams (docs/resilience.md): ``fleet.dispatch`` fires on every
router dispatch attempt (``error`` = failed dispatch → the retry
path), ``fleet.spawn`` on every replica spawn attempt (``error`` =
failed spawn → the supervisor's bounded spawn retry), and the worker
side inherits ``MXNET_FAULT_INJECT`` from the parent environment so
``edge.request`` faults can target replicas.  Telemetry
(docs/telemetry.md): ``fleet.replicas`` gauge, ``fleet.respawns``,
``fleet.drains``, ``fleet.dispatch_retries``, ``fleet.spawn_retries``,
``fleet.recovery_seconds``.

Worker protocol (``python -m mxnet_tpu.serve.fleet --worker --spec
<module-or-file.py>:<callable>``): the spec callable registers models
(serve and/or decode) in the worker process; the worker then prints
``READY {json}`` (edge/obs URLs, pid, startup seconds, compile-cache
stats) and serves until ``DRAIN`` arrives on stdin (drain admissions)
or stdin closes (graceful shutdown).
"""
from __future__ import annotations

import http.client
import json
import os
import select
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from collections import deque
from typing import Dict, List, Optional, Sequence

from .. import telemetry as _tel
from ..analysis import thread_check as _tchk
from ..base import MXNetError, get_env
from ..parallel.dist import backoff_delay as _backoff_delay
from ..resilience import chaos as _chaos
from .coalescer import DeadlineError, RejectedError
from .edge import DEADLINE_HEADER

__all__ = ["Fleet", "Router", "Replica", "FleetError", "NoReplicaError",
           "DispatchError", "worker_main"]

_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


class FleetError(MXNetError):
    """Base class for fleet routing/supervision failures."""


class NoReplicaError(FleetError):
    """No ready replica to route to (fleet draining or still
    respawning) — the 503-analogue at the fleet tier."""

    status = 503


class DispatchError(FleetError):
    """A dispatch that already reached a replica failed mid-flight.
    ``predict`` never raises this (idempotent — it retries a sibling);
    an in-flight ``generate`` does, by name, instead of silently
    running the prompt twice."""


class Replica:
    """One worker: the subprocess handle plus the router's view of it.

    ``state``: ``starting`` → ``ready`` → (``draining`` →) gone.
    ``load`` is the scraped ``serve.queue_depth +
    serve.decode_slots_active`` the router balances on."""

    __slots__ = ("idx", "proc", "edge_url", "obs_url", "pid",
                 "startup_secs", "doc", "state", "hb_fails", "load",
                 "draining_since", "spawned_ts", "chip")

    def __init__(self, idx: int, proc=None, edge_url: Optional[str] = None,
                 obs_url: Optional[str] = None, doc: Optional[dict] = None):
        doc = doc or {}
        self.idx = idx
        self.proc = proc
        self.edge_url = edge_url or doc.get("edge")
        self.obs_url = obs_url or doc.get("obs")
        self.pid = doc.get("pid")
        self.startup_secs = doc.get("startup_secs")
        self.doc = doc
        self.state = "ready"
        self.hb_fails = 0
        self.load = 0.0
        self.draining_since: Optional[float] = None
        self.spawned_ts = time.monotonic()
        self.chip: Optional[int] = None    # the host chip this worker owns

    def __repr__(self):
        return (f"Replica(#{self.idx} pid={self.pid} {self.state} "
                f"load={self.load} {self.edge_url})")


# ---------------------------------------------------------------- router
class Router:
    """Least-loaded dispatch over the fleet's ready replicas (module
    docstring).  ``provider`` is any object with
    ``ready_replicas() -> List[Replica]`` — normally the
    :class:`Fleet`, a static stub in tests."""

    def __init__(self, provider, retries: Optional[int] = None,
                 backoff_base: Optional[float] = None,
                 backoff_cap: Optional[float] = None,
                 timeout: Optional[float] = None):
        self._provider = provider
        self._retries = retries if retries is not None \
            else get_env("MXNET_FLEET_RETRIES", 4, int)
        self._base = backoff_base if backoff_base is not None \
            else get_env("MXNET_FLEET_BACKOFF_BASE", 0.05, float)
        self._cap = backoff_cap if backoff_cap is not None \
            else get_env("MXNET_FLEET_BACKOFF_CAP", 1.0, float)
        self._timeout = timeout if timeout is not None \
            else get_env("MXNET_FLEET_DISPATCH_TIMEOUT", 120.0, float)
        self._lock = _tchk.lock("serve.fleet_router")
        self._rr = 0

    def _pick(self, exclude=()) -> Replica:
        reps = self._provider.ready_replicas()
        cands = [r for r in reps if r.edge_url not in exclude] or reps
        if not cands:
            raise NoReplicaError(
                "fleet: no ready replica (all draining/respawning); "
                "retry with backoff")
        lo = min(r.load for r in cands)
        ties = [r for r in cands if r.load <= lo]
        with self._lock:
            self._rr += 1
            return ties[self._rr % len(ties)]

    @staticmethod
    def _headers(deadline_ms):
        h = {"Content-Type": "application/json"}
        if deadline_ms is not None:
            h[DEADLINE_HEADER] = str(float(deadline_ms))
        return h

    def _chaos_dispatch(self):
        if not _chaos.active():
            return
        kind = _chaos.draw("fleet.dispatch")
        if kind == "delay":
            time.sleep(get_env("MXNET_FAULT_DELAY", 0.05, float))
        elif kind is not None:
            raise ConnectionError(
                "injected fault at 'fleet.dispatch'")

    @staticmethod
    def _raise_http(e: urllib.error.HTTPError):
        try:
            msg = json.loads(e.read().decode()).get("error", str(e))
        except Exception:  # noqa: BLE001 — non-JSON error body
            msg = str(e)
        if e.code == 503:
            raise RejectedError(f"fleet: request shed ({msg})") from e
        if e.code == 504:
            raise DeadlineError(f"fleet: {msg}") from e
        raise MXNetError(f"fleet: HTTP {e.code}: {msg}") from e

    def predict(self, model: str, inputs: Sequence,
                deadline_ms: Optional[float] = None,
                timeout: Optional[float] = None) -> dict:
        """POST ``/v1/predict`` to the least-loaded replica; dispatch
        failures (connect errors, a mid-flight replica kill) retry a
        sibling — predict is idempotent, so an ambiguous failure is
        safe to re-run.  An edge 503 is a shed (never admitted):
        surfaced as :class:`RejectedError` after the retry budget."""
        body = json.dumps({"model": model,
                           "inputs": [x.tolist() if hasattr(x, "tolist")
                                      else x for x in inputs]}).encode()
        timeout = timeout if timeout is not None else self._timeout
        tried: set = set()
        attempt = 0
        last: Optional[BaseException] = None
        while attempt <= self._retries:
            attempt += 1
            rep = self._pick(tried)
            try:
                self._chaos_dispatch()
                req = urllib.request.Request(
                    rep.edge_url + "/v1/predict", data=body,
                    headers=self._headers(deadline_ms))
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    return json.loads(r.read().decode())
            except urllib.error.HTTPError as e:
                # the edge ANSWERED: a 503 shed may retry a sibling
                # (the request was never admitted), anything else is a
                # real answer — surface it
                if e.code != 503 or attempt > self._retries:
                    self._raise_http(e)
                tried.add(rep.edge_url)
                last = e
            except Exception as e:  # noqa: BLE001 — dispatch failure
                tried.add(rep.edge_url)
                last = e
                if _tel._ENABLED:
                    _tel.inc("fleet.dispatch_retries")
                if attempt > self._retries:
                    break
                time.sleep(_backoff_delay(attempt, base=self._base,
                                          cap=self._cap))
        raise DispatchError(
            f"fleet: predict for {model!r} failed after {attempt} "
            f"attempt(s) across {len(tried)} replica(s); last error: "
            f"{type(last).__name__}: {last}") from last

    def generate(self, model: str, prompt: Sequence[int],
                 stream: bool = False, on_token=None,
                 deadline_ms: Optional[float] = None,
                 timeout: Optional[float] = None, **kw) -> dict:
        """POST ``/v1/generate``.  Connection failures BEFORE the
        request reaches a replica retry a sibling; once the request is
        on the wire the dispatch is non-idempotent and any failure
        raises :class:`DispatchError` by name.  With ``stream=True``
        the SSE frames are parsed incrementally (``on_token`` fires per
        token) and the returned dict carries the terminal event."""
        doc = dict(kw, model=model, prompt=[int(t) for t in prompt],
                   stream=bool(stream))
        body = json.dumps(doc).encode()
        timeout = timeout if timeout is not None else self._timeout
        tried: set = set()
        attempt = 0
        last: Optional[BaseException] = None
        while attempt <= self._retries:
            attempt += 1
            rep = self._pick(tried)
            host, port = _split_host(rep.edge_url)
            conn = http.client.HTTPConnection(host, port,
                                              timeout=timeout)
            sent = False
            try:
                self._chaos_dispatch()
                conn.connect()
                sent = True        # bytes may reach the replica now
                conn.request("POST", "/v1/generate", body,
                             self._headers(deadline_ms))
                resp = conn.getresponse()
                if resp.status != 200:
                    err = urllib.error.HTTPError(
                        rep.edge_url, resp.status, resp.reason,
                        resp.headers, resp)
                    if resp.status == 503 and attempt <= self._retries:
                        # shed: never admitted, safe on a sibling
                        try:
                            msg = json.loads(
                                resp.read().decode()).get("error", "")
                        except Exception:  # noqa: BLE001
                            msg = resp.reason
                        tried.add(rep.edge_url)
                        last = RejectedError(f"fleet: shed ({msg})")
                        continue
                    self._raise_http(err)
                if stream:
                    return self._read_sse(resp, on_token)
                return json.loads(resp.read().decode())
            except (MXNetError,):
                raise
            except Exception as e:  # noqa: BLE001 — transport failure
                last = e
                if sent:
                    raise DispatchError(
                        f"fleet: in-flight generate for {model!r} on "
                        f"{rep.edge_url} failed ({type(e).__name__}: "
                        f"{e}); NOT retried — generation is not "
                        "idempotent once dispatched") from e
                tried.add(rep.edge_url)
                if _tel._ENABLED:
                    _tel.inc("fleet.dispatch_retries")
                if attempt > self._retries:
                    break
                time.sleep(_backoff_delay(attempt, base=self._base,
                                          cap=self._cap))
            finally:
                # the stream branch returns only after _read_sse drained
                # the terminal event, so closing here is always safe
                try:
                    conn.close()
                except Exception:  # noqa: BLE001
                    pass
        if isinstance(last, RejectedError):
            raise last
        raise DispatchError(
            f"fleet: generate for {model!r} could not be dispatched "
            f"after {attempt} attempt(s); last error: "
            f"{type(last).__name__}: {last}") from last

    @staticmethod
    def _read_sse(resp, on_token) -> dict:
        """Parse the edge's SSE stream incrementally; returns
        ``{"tokens": [...], **terminal_event, "chunk_ts": [...]}``
        (chunk arrival timestamps — the first-chunk-before-last-token
        smoke gate reads them)."""
        tokens: List[int] = []
        ts: List[float] = []
        event = None
        terminal: Optional[dict] = None
        for raw in resp:
            line = raw.decode("utf-8", "replace").strip("\r\n")
            if not line:
                event = None
                continue
            if line.startswith("event:"):
                event = line[len("event:"):].strip()
            elif line.startswith("data:"):
                payload = json.loads(line[len("data:"):].strip())
                if event == "done":
                    terminal = payload
                    break
                tokens.append(int(payload["token"]))
                ts.append(time.perf_counter())
                if on_token is not None:
                    on_token(int(payload["token"]))
        if terminal is None:
            raise DispatchError(
                "fleet: SSE stream ended without a terminal 'done' "
                "event (replica died mid-stream?); NOT retried — "
                "generation is not idempotent once dispatched")
        out = dict(terminal)
        out["tokens"] = tokens
        out["chunk_ts"] = ts
        return out


def _split_host(url: str):
    rest = url.split("://", 1)[-1]
    host, _, port = rest.partition(":")
    return host, int(port.split("/", 1)[0] or 80)


# ----------------------------------------------------------------- fleet
def _host_chips() -> int:
    """Accelerator chips on this host, counted from their device files
    (``/dev/vfio/<n>`` on v5e and later, ``/dev/accel<n>`` before) so the
    parent never initialises a backend to find out — a process that has
    touched the TPU holds it, and its workers then fail or hang."""
    import glob

    return len(glob.glob("/dev/vfio/[0-9]*")) \
        or len(glob.glob("/dev/accel[0-9]*"))


def _parent_holds_chips() -> bool:
    """Whether THIS process already initialised an accelerator backend."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized() \
        and jax.default_backend() != "cpu"


class Fleet:
    """Spawn + supervise + scale the replica set (module docstring).

    ``spec`` is ``"module:callable"`` (or ``"/path/file.py:callable"``)
    resolved INSIDE each worker process; the callable registers the
    models the replicas serve.  ``env`` overlays the inherited
    environment (set ``JAX_COMPILATION_CACHE_DIR`` here to place the
    persistent cache respawns warm-start from)."""

    def __init__(self, spec: str, min_replicas: Optional[int] = None,
                 max_replicas: Optional[int] = None,
                 env: Optional[Dict[str, str]] = None,
                 heartbeat_every: Optional[float] = None,
                 spawn_timeout: float = 300.0):
        self.spec = spec
        self.min = min_replicas if min_replicas is not None \
            else get_env("MXNET_FLEET_MIN", 1, int)
        self.max = max_replicas if max_replicas is not None \
            else get_env("MXNET_FLEET_MAX", max(2, self.min), int)
        if not 1 <= self.min <= self.max:
            raise MXNetError(
                f"fleet: need 1 <= MXNET_FLEET_MIN({self.min}) <= "
                f"MXNET_FLEET_MAX({self.max})")
        self.heartbeat_every = heartbeat_every \
            if heartbeat_every is not None \
            else get_env("MXNET_FLEET_HEARTBEAT_EVERY", 0.5, float)
        self._hb_fail_limit = get_env("MXNET_FLEET_HEARTBEAT_FAILS",
                                      3, int)
        self._probe_timeout = get_env("MXNET_FLEET_PROBE_TIMEOUT",
                                      2.0, float)
        self._drain_timeout = get_env("MXNET_FLEET_DRAIN_TIMEOUT",
                                      10.0, float)
        self._spawn_retries = get_env("MXNET_FLEET_SPAWN_RETRIES",
                                      3, int)
        self._up_depth = get_env("MXNET_FLEET_SCALE_UP_DEPTH",
                                 4.0, float)
        self._spawn_timeout = spawn_timeout
        self._env = dict(os.environ)
        self._env["PYTHONPATH"] = _ROOT + os.pathsep + \
            self._env.get("PYTHONPATH", "")
        if env:
            self._env.update(env)
        # One process per chip.  Workers that may reach an accelerator
        # (JAX_PLATFORMS does not pin them to the cpu) each get ONE chip
        # of this host for themselves; what cannot work is refused here,
        # by name, instead of hanging a worker on libtpu's lock.
        on_cpu = self._env.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"
        self._chips = 0 if on_cpu else _host_chips()
        if self._chips:
            if _parent_holds_chips():
                raise MXNetError(
                    "fleet: this process has initialised the accelerator "
                    "backend and holds the host's chip(s); its workers "
                    "could not reach them. Start the fleet from a process "
                    "that has not touched jax (or pin the workers to the "
                    "host with env={'JAX_PLATFORMS': 'cpu'})")
            if self.max > self._chips:
                raise MXNetError(
                    f"fleet: max_replicas={self.max} exceeds the "
                    f"{self._chips} chip(s) of this host — every chip "
                    "worker owns one chip")
        self._lock = _tchk.lock("serve.fleet")
        self._spawning: set = set()      # chips of workers not yet listed
        self._replicas: List[Replica] = []
        self._seq = 0
        self._closed = False
        self._wake = threading.Event()
        # autoscale signal: mean queue depth per ready replica,
        # windowed so one burst doesn't flap the fleet size
        self._load_window: deque = deque(
            maxlen=get_env("MXNET_FLEET_SCALE_WINDOW", 6, int))
        # failure detection timestamps awaiting a respawn (recovery
        # time = detection -> replacement READY)
        self._pending_losses: List[float] = []
        self.stats: dict = {"cold_start_secs": None,
                            "warm_start_secs": [],
                            "cold_build_secs": None,
                            "warm_build_secs": [], "respawns": 0,
                            "drains": 0, "recoveries_secs": [],
                            "spawn_failures": 0}
        for _ in range(self.min):
            self._add_replica()
        self.router = Router(self)
        self._supervisor = threading.Thread(
            target=self._supervise, name="mx-fleet-supervisor",
            daemon=True)
        self._supervisor.start()

    # -------------------------------------------------------------- views
    def ready_replicas(self) -> List[Replica]:
        with self._lock:
            return [r for r in self._replicas if r.state == "ready"]

    def replicas(self) -> List[Replica]:
        with self._lock:
            return list(self._replicas)

    # ----------------------------------------------------------- spawning
    def _claim_chip(self):
        """``(worker env, chip)``: on a chip host the lowest chip no live
        or starting replica owns, with libtpu's own switches for "this
        process is a one-chip host"; ``(self._env, None)`` for CPU
        workers.  No chip left is a named error, not a worker hung on
        libtpu's lock."""
        if not self._chips:
            return self._env, None
        with self._lock:
            held = {r.chip for r in self._replicas} | self._spawning
            free = [c for c in range(self._chips) if c not in held]
            if not free:
                raise MXNetError(
                    f"fleet: all {self._chips} chip(s) of this host are "
                    "held by replicas; refusing to start another chip "
                    "worker")
            self._spawning.add(free[0])
        return dict(self._env, TPU_VISIBLE_CHIPS=str(free[0]),
                    TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                    TPU_PROCESS_BOUNDS="1,1,1"), free[0]

    def _spawn_once(self) -> Replica:
        if _chaos.active():
            _chaos.maybe_fail("fleet.spawn")
        with self._lock:
            self._seq += 1
            idx = self._seq
        env, chip = self._claim_chip()
        # -c instead of -m: runpy would import the serve package (which
        # imports this module) and then RE-execute this file as
        # __main__ — two copies of every class
        proc = subprocess.Popen(
            [sys.executable, "-c",
             "import sys; from mxnet_tpu.serve.fleet import worker_main"
             "; sys.exit(worker_main())",
             "--worker", "--spec", self.spec],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=None, text=True, env=env, cwd=_ROOT)
        deadline = time.monotonic() + self._spawn_timeout
        try:
            while True:
                line = _read_line(proc, deadline)
                if line.startswith("READY "):
                    doc = json.loads(line[len("READY "):])
                    rep = Replica(idx, proc=proc, doc=doc)
                    # the chip stays in _spawning until _add_replica
                    # lists the replica (and with it, its chip)
                    rep.chip = chip
                    return rep
        except BaseException:
            try:
                proc.kill()
                proc.wait(5.0)
            except Exception:  # noqa: BLE001
                pass
            with self._lock:
                self._spawning.discard(chip)
            raise

    def _add_replica(self, recovery_from: Optional[float] = None):
        """Spawn with bounded retry + backoff (``fleet.spawn`` chaos
        fires per attempt); records cold/warm start and recovery."""
        attempt = 0
        while True:
            attempt += 1
            try:
                rep = self._spawn_once()
                break
            except BaseException as e:  # noqa: BLE001 — retry bounded
                self.stats["spawn_failures"] += 1
                if _tel._ENABLED:
                    _tel.inc("fleet.spawn_retries")
                if attempt > self._spawn_retries or self._closed:
                    raise MXNetError(
                        f"fleet: replica spawn failed after {attempt} "
                        f"attempt(s): {type(e).__name__}: {e}") from e
                time.sleep(_backoff_delay(attempt, base=0.1, cap=2.0))
        with self._lock:
            self._replicas.append(rep)
            self._spawning.discard(rep.chip)
            n = len(self._replicas)
        if self.stats["cold_start_secs"] is None:
            self.stats["cold_start_secs"] = rep.startup_secs
            self.stats["cold_build_secs"] = rep.doc.get("build_secs")
        else:
            self.stats["warm_start_secs"].append(rep.startup_secs)
            self.stats["warm_build_secs"].append(
                rep.doc.get("build_secs"))
        if recovery_from is not None:
            rec = time.monotonic() - recovery_from
            self.stats["recoveries_secs"].append(round(rec, 3))
            if _tel._ENABLED:
                _tel.observe("fleet.recovery_seconds", rec)
        if _tel._ENABLED:
            _tel.set_gauge("fleet.replicas", n)
        return rep

    # --------------------------------------------------------- supervision
    def _probe(self, rep: Replica):
        """GET the replica's ``/readyz``: (ok, http_code|None)."""
        try:
            req = urllib.request.Request(rep.obs_url + "/readyz")
            with urllib.request.urlopen(
                    req, timeout=self._probe_timeout) as r:
                return True, r.status
        except urllib.error.HTTPError as e:
            return False, e.code
        except Exception:  # noqa: BLE001 — unreachable = failed probe
            return False, None

    def _drain(self, rep: Replica, reason: str):
        """Take the replica out of rotation and tell it to drain: the
        worker flips its ``draining`` readiness check + edge
        admissions, in-flight work finishes (bounded by
        ``MXNET_FLEET_DRAIN_TIMEOUT``), then the process is retired."""
        rep.state = "draining"
        rep.draining_since = time.monotonic()
        self.stats["drains"] += 1
        if _tel._ENABLED:
            _tel.inc("fleet.drains")
        try:
            rep.proc.stdin.write("DRAIN\n")
            rep.proc.stdin.flush()
        except Exception:  # noqa: BLE001 — already dead: retire below
            pass

    def _stop_proc(self, rep: Replica, kill: bool = False):
        proc = rep.proc
        if proc is None:
            return
        try:
            if kill:
                proc.kill()
            else:
                proc.stdin.close()      # EOF = graceful shutdown
            proc.wait(5.0 if not kill else 2.0)
        except Exception:  # noqa: BLE001 — escalate to kill
            try:
                proc.kill()
                proc.wait(2.0)
            except Exception:  # noqa: BLE001
                pass

    def _retire(self, rep: Replica, detected_at: Optional[float]):
        with self._lock:
            if rep in self._replicas:
                self._replicas.remove(rep)
            n = len(self._replicas)
        if detected_at is not None:
            self._pending_losses.append(detected_at)
        if _tel._ENABLED:
            _tel.set_gauge("fleet.replicas", n)

    def _refresh_loads(self):
        """One ``obs.aggregate`` scrape over the ready replicas; the
        per-worker gauge rows become each replica's ``load``."""
        reps = self.ready_replicas()
        if not reps:
            return
        from ..obs import aggregate as _aggregate

        view = _aggregate([r.obs_url for r in reps],
                          timeout=self._probe_timeout)
        depth = view.gauge("serve.queue_depth")["workers"]
        slots = view.gauge("serve.decode_slots_active")["workers"]
        total = 0.0
        for r in reps:
            d = depth.get(r.obs_url, {}).get("value", 0.0)
            s = slots.get(r.obs_url, {}).get("value", 0.0)
            r.load = d + s
            total += d
        self._load_window.append(total / max(1, len(reps)))

    def _supervise(self):
        while not self._closed:
            self._wake.wait(self.heartbeat_every)
            if self._closed:
                return
            try:
                self._pass()
            except Exception:  # noqa: BLE001 — one bad pass must not
                # kill supervision; the next tick retries
                pass

    def _pass(self):
        now = time.monotonic()
        for rep in self.replicas():
            if rep.proc is not None and rep.proc.poll() is not None:
                # process died (SIGKILL under load, OOM, crash): out of
                # rotation immediately, respawn below
                self._retire(rep, detected_at=now)
                if rep.state != "draining":
                    self.stats["drains"] += 1
                    if _tel._ENABLED:
                        _tel.inc("fleet.drains")
                continue
            if rep.state == "draining":
                if now - rep.draining_since >= self._drain_timeout \
                        or rep.load <= 0:
                    self._stop_proc(rep)
                    self._retire(rep, detected_at=None)
                continue
            ok, code = self._probe(rep)
            if ok:
                rep.hb_fails = 0
            elif code is not None:
                # the replica ANSWERED unready (503): drain it —
                # in-flight work finishes, the router already stopped
                # routing the moment state flipped
                rep.hb_fails = 0
                self._drain(rep, reason=f"readyz {code}")
            else:
                rep.hb_fails += 1
                if rep.hb_fails >= self._hb_fail_limit:
                    self._stop_proc(rep, kill=True)
                    self._retire(rep, detected_at=now)
        try:
            self._refresh_loads()
        except Exception:  # noqa: BLE001 — scrape hiccup: keep old loads
            pass
        self._reconcile()

    def _reconcile(self):
        """Respawn losses and apply the windowed autoscale signal."""
        with self._lock:
            alive = [r for r in self._replicas
                     if r.state in ("ready", "starting")]
            n = len(alive)
        desired = max(n, self.min)
        if len(self._load_window) == self._load_window.maxlen:
            avg = sum(self._load_window) / len(self._load_window)
            if avg > self._up_depth:
                desired = n + 1
            elif avg <= 0 and n > self.min:
                desired = n - 1
        desired = max(self.min, min(self.max, desired))
        while desired > n and not self._closed:
            lost = self._pending_losses.pop(0) \
                if self._pending_losses else None
            is_respawn = lost is not None
            try:
                self._add_replica(recovery_from=lost)
            except MXNetError:
                break               # spawn retries exhausted; next tick
            if is_respawn:
                self.stats["respawns"] += 1
                if _tel._ENABLED:
                    _tel.inc("fleet.respawns")
            n += 1
            self._load_window.clear()
        if desired < n:
            victim = max(self.ready_replicas(),
                         key=lambda r: r.idx, default=None)
            if victim is not None:
                self._drain(victim, reason="scale-down")
                self._load_window.clear()

    # ------------------------------------------------------------ shutdown
    def close(self, timeout: float = 60.0):
        """Stop supervision, drain and stop every replica (graceful
        stdin-EOF shutdown, kill on timeout).  Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._wake.set()
        self._supervisor.join(timeout)
        for rep in self.replicas():
            self._stop_proc(rep)
            self._retire(rep, detected_at=None)
        if _tel._ENABLED:
            _tel.set_gauge("fleet.replicas", 0)

    def __enter__(self) -> "Fleet":
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def _read_line(proc, deadline: float) -> str:
    """Read one stdout line from ``proc`` with a wall-clock deadline
    (select on the pipe, so a silently-dead worker cannot hang the
    spawner)."""
    fd = proc.stdout
    while True:
        left = deadline - time.monotonic()
        if left <= 0:
            raise MXNetError(
                "fleet: worker did not print READY before the spawn "
                "deadline")
        if proc.poll() is not None:
            raise MXNetError(
                f"fleet: worker exited rc={proc.returncode} before "
                "READY (see its stderr above)")
        r, _w, _x = select.select([fd], [], [], min(0.25, left))
        if r:
            line = fd.readline()
            if line:
                return line.rstrip("\n")


# ---------------------------------------------------------------- worker
def _load_spec(spec: str):
    """Resolve ``module:callable`` or ``/path/file.py:callable``."""
    target, _, fn_name = spec.rpartition(":")
    if not target or not fn_name:
        raise MXNetError(
            f"fleet: bad --spec {spec!r} (want module:callable or "
            "file.py:callable)")
    if target.endswith(".py") or os.sep in target:
        import importlib.util

        name = "_mx_fleet_spec"
        mspec = importlib.util.spec_from_file_location(name, target)
        mod = importlib.util.module_from_spec(mspec)
        sys.modules[name] = mod
        mspec.loader.exec_module(mod)
    else:
        import importlib

        mod = importlib.import_module(target)
    try:
        return getattr(mod, fn_name)
    except AttributeError:
        raise MXNetError(
            f"fleet: spec {target!r} has no callable {fn_name!r}"
        ) from None


def worker_main(argv: Optional[List[str]] = None) -> int:
    """Replica subprocess entry (module docstring): build models via
    the spec, stand up obs + edge, announce READY, serve until DRAIN /
    stdin EOF."""
    argv = list(sys.argv[1:] if argv is None else argv)
    spec = None
    for i, a in enumerate(argv):
        if a == "--spec" and i + 1 < len(argv):
            spec = argv[i + 1]
    if spec is None:
        print("fleet worker: missing --spec", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    from .. import obs
    from .. import serve as _serve
    from .. import telemetry as tel
    from .edge import EdgeServer

    obs.set_fleet_state(role="worker", draining=False)
    build = _load_spec(spec)
    tb = time.perf_counter()
    info = build() or {}
    build_secs = time.perf_counter() - tb
    metrics = obs.serve_metrics(0)
    if metrics is None:
        from ..obs.http import MetricsServer

        metrics = MetricsServer(0)
    edge = EdgeServer(port=0)
    snap = tel.snapshot()

    def _cnt(name):
        return snap.get(name, {}).get("value", 0)

    # per-model precision (f32 vs int8 PTQ / int8 KV cache) so the
    # fleet's READY docs carry what each worker actually serves —
    # the worker-spec half of the precision ladder (docs/precision.md)
    reg = _serve.default_registry()
    precisions = {n: reg.get(n).precision or "f32"
                  for n in _serve.models()}
    from .decode import servers as _decode_servers

    precisions.update({
        n: s.entry.precision or "f32"
        for n, s in _decode_servers().items()})
    doc = {"edge": edge.url, "obs": metrics.url, "pid": os.getpid(),
           "precisions": precisions,
           "startup_secs": round(time.perf_counter() - t0, 3),
           # model build + warmup alone — the phase the persistent
           # compile cache replays (the warm-respawn gate's numerator)
           "build_secs": round(build_secs, 3),
           "warmup_compiles": _cnt("hybridize.warmup_compiles"),
           "persistent_cache_hits": _cnt(
               "hybridize.persistent_cache_hits"),
           "misses_at_ready": _cnt("hybridize.cache_misses")}
    doc.update(info if isinstance(info, dict) else {})
    print("READY " + json.dumps(doc), flush=True)
    for line in sys.stdin:
        if line.strip() == "DRAIN":
            obs.set_fleet_state(draining=True)
            edge.drain()
            print("DRAINING", flush=True)
    # stdin EOF: graceful shutdown — edge first (stops admissions,
    # drains), then the serving tiers, then exposition
    edge.close(30.0)
    try:
        _serve.shutdown(30.0)
    finally:
        _serve.shutdown_decode(30.0)
        obs.stop_metrics()
    return 0


if __name__ == "__main__":
    if "--worker" in sys.argv[1:]:
        sys.exit(worker_main())
    print(__doc__)
    sys.exit(0)
