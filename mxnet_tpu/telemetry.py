"""mx.telemetry — process-global runtime metrics registry.

The reference ships a 2.9k-LoC native profiler (src/profiler/) whose
aggregate mode answers "where did the time go" per op; this module is the
TPU-native equivalent for the *host-side seams* XLA cannot see: jit
compiles, engine queue waits, input-pipeline stalls, host↔device traffic,
collective bytes.  Device-side kernel timing stays in the XProf trace
(mx.profiler); the two meet in ``profiler.dumps()``, which appends this
registry's aggregate table.

Three metric kinds:

  * :class:`Counter` — monotonically accumulated value (``inc``).
  * :class:`Gauge`   — last-written value + high-water mark (``set``).
  * :class:`Timer`   — duration summary: count/total/min/max plus p50/p99
    over a bounded reservoir of recent samples (``observe`` /
    ``with timer(name):`` / ``@timed(name)``).

Percentile semantics: the Timer reservoir holds the most recent
``RESERVOIR`` *samples* regardless of age, so ``snapshot()``'s
``p50``/``p99`` are **sample-count-windowed, not time-windowed** — a
warmup burst stays in the tail until 1024 later samples push it out,
which on a low-rate timer can be the whole run.  The observability
layer (``mx.obs``, docs/obs.md) attaches a *time-windowed* histogram to
hot timers via :func:`watch_timer`; when one is attached the summary
grows ``p50_windowed``/``p99_windowed``/``p999_windowed`` keys and the
:func:`dumps` table + :func:`write_tensorboard` tail columns read the
windowed values (the reservoir fields stay for back-compat).

The registry is also the evidence layer for the resilience stack
(docs/resilience.md): checkpoint durability (``ckpt.{saves,restores,
corrupt_skipped,save_failures}``), injected faults (``chaos.injected``
and per-site counters), and bring-up retries (``dist.init_retries``,
``dist.deadline_exceeded``) all tick here, so "did the recovery path
actually run" is an assertable fact, not a log grep.  The compile-cost
stack (docs/jit.md) reports the same way: ``hybridize.cache_misses``
split into cold XLA compiles vs ``hybridize.persistent_cache_hits``
(on-disk cache, fed by a ``jax.monitoring`` listener),
``hybridize.warmup_compiles``/``jit.warmup_seconds`` for AOT warmup,
and ``dataloader.padded_batches`` for the bucketing seam — so "did the
second process actually skip XLA" is a counter, not a hunch.

Overhead contract: every instrumented call site guards on the single
module flag ``_ENABLED`` (``MXNET_TELEMETRY=0`` disables), so a disabled
build pays one global read per event — no locks, no allocation.  Enabled,
each event is one per-metric lock plus a few float ops; events fire per
batch/step/sync, never per element.  The registry is shared across
threads by design: pipeline producers (the DevicePrefetcher transfer
thread, engine workers) report into the same metrics, so byte/time
accounting stays truthful when work moves off the main thread
(docs/pipeline.md).  Site convention: per-batch/step
seams (trainer, kvstore) use the ``with timer(name):`` scope; per-op hot
seams (ndarray sync, engine push/wait) hand-roll the
``if _ENABLED: t0 = perf_counter() ... observe()`` pattern to skip the
scope's registry lookup and thread-local stack.

Exports:

  * ``dumps()``         — aligned aggregate table (merged into
    ``profiler.dumps()``).
  * ``dump_json(path)`` — structured snapshot;
    ``MXNET_TELEMETRY_JSON=<path>`` writes one at interpreter exit.
  * ``write_tensorboard(logdir)`` — scalars via
    ``contrib.tensorboard.SummaryWriter``.

The metric catalog (names, units, which subsystem ticks them) is
documented in docs/telemetry.md.
"""
from __future__ import annotations

import atexit
import functools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Union

from .base import get_env

__all__ = ["enabled", "set_enabled", "counter", "gauge", "timer", "timed",
           "inc", "set_gauge", "observe", "snapshot", "reset", "dumps",
           "dump_json", "write_tensorboard", "Counter", "Gauge", "Timer",
           "peek", "watch_timer", "unwatch_timer"]

# The one flag every instrumented call site checks (module-global read).
# Default ON: the registry is the evidence layer perf work reads, and its
# enabled cost is a per-event lock, not a per-element one.
_ENABLED: bool = bool(get_env("MXNET_TELEMETRY", 1, int))

_REGISTRY: "Dict[str, Union[Counter, Gauge, Timer]]" = {}
_REG_LOCK = threading.Lock()


def enabled() -> bool:
    """Whether the registry records events (``MXNET_TELEMETRY``)."""
    return _ENABLED


def set_enabled(flag: bool) -> bool:
    """Flip recording at runtime (tests / notebooks); returns the previous
    state.  Existing metrics keep their values — call :func:`reset` to
    clear them."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(flag)
    return prev


class Counter:
    """Monotonic accumulator (ops pushed, bytes moved, seconds summed)."""

    __slots__ = ("name", "_value", "_lock")
    kind = "counter"

    def __init__(self, name: str):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, delta: Union[int, float] = 1):
        with self._lock:
            self._value += delta

    @property
    def value(self):
        return self._value

    def summary(self) -> dict:
        v = self._value
        return {"type": "counter",
                "value": round(v, 9) if isinstance(v, float) else v}


class Gauge:
    """Last-written value + high-water mark (queue depth, occupancy).

    Every ``set`` also stamps ``last_update_ts`` (unix seconds), so a
    reader can tell a *stale* gauge from an idle one — a worker whose
    ``serve.queue_depth`` has not moved in minutes is wedged, not
    empty.  ``/statusz`` and the fleet aggregator (docs/obs.md) read
    the stamp; ``0.0`` means "never written"."""

    __slots__ = ("name", "_value", "_max", "_ts", "_lock")
    kind = "gauge"

    def __init__(self, name: str):
        self.name = name
        self._value = 0
        self._max = 0
        self._ts = 0.0
        self._lock = threading.Lock()

    def set(self, value: Union[int, float]):
        with self._lock:
            self._value = value
            self._ts = time.time()
            if value > self._max:
                self._max = value

    def reset_max(self):
        """Collapse the high-water mark to the current value.  Owners of
        a *windowed* gauge (the inflight queues) call this when a new
        measurement window opens, so ``max`` answers "since the last
        drain", not "since process start"."""
        with self._lock:
            self._max = self._value

    @property
    def value(self):
        return self._value

    @property
    def last_update_ts(self) -> float:
        """Unix timestamp of the last ``set`` (0.0 = never written)."""
        return self._ts

    def summary(self) -> dict:
        return {"type": "gauge", "value": self._value, "max": self._max,
                "last_update_ts": round(self._ts, 3)}


class Timer:
    """Duration summary.  Aggregates are exact (count/total/min/max);
    percentiles come from a bounded reservoir of the most recent
    ``RESERVOIR`` samples — recency-biased on purpose, the way a training
    loop wants its p99 (the first compiled steps should age out)."""

    RESERVOIR = 1024
    __slots__ = ("name", "count", "total", "min", "max", "_samples",
                 "_lock", "_starts", "hist")
    kind = "timer"

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = 0.0
        self._samples: deque = deque(maxlen=self.RESERVOIR)
        self._lock = threading.Lock()
        self._starts = threading.local()  # per-thread start stack
        # optional time-windowed histogram (mx.obs), fed alongside the
        # reservoir — attached via watch_timer, None costs one read
        self.hist = None

    def observe(self, seconds: float):
        with self._lock:
            self.count += 1
            self.total += seconds
            if seconds < self.min:
                self.min = seconds
            if seconds > self.max:
                self.max = seconds
            self._samples.append(seconds)
        h = self.hist
        if h is not None:
            h.observe(seconds)

    # -- context-manager form: ``with telemetry.timer("x"):`` ------------
    # Start times live on a per-thread stack so concurrent/nested scopes
    # on the same (shared, registry-owned) Timer cannot cross-talk.
    def __enter__(self):
        stack = getattr(self._starts, "stack", None)
        if stack is None:
            stack = self._starts.stack = []
        stack.append(time.perf_counter())
        return self

    def __exit__(self, *exc):
        t0 = self._starts.stack.pop()
        if _ENABLED:  # scope may span a set_enabled(False); drop cleanly
            self.observe(time.perf_counter() - t0)

    def percentile(self, q: float) -> float:
        with self._lock:
            samples = sorted(self._samples)
        if not samples:
            return 0.0
        idx = min(len(samples) - 1, int(round(q * (len(samples) - 1))))
        return samples[idx]

    def summary(self) -> dict:
        with self._lock:
            samples = sorted(self._samples)
            count, total = self.count, self.total
            mn = self.min if count else 0.0
            mx = self.max

        def pct(q):
            if not samples:
                return 0.0
            return samples[min(len(samples) - 1,
                               int(round(q * (len(samples) - 1))))]

        # "value" mirrors total so consumers can read every metric kind
        # uniformly (the smoke gates).  p50/p99 are the
        # RESERVOIR percentiles (module docstring: sample-count-windowed);
        # an attached mx.obs histogram adds the time-windowed tails.
        out = {"type": "timer", "count": count,
               "value": round(total, 9), "total": round(total, 9),
               "min": round(mn, 9), "max": round(mx, 9),
               "p50": round(pct(0.50), 9), "p99": round(pct(0.99), 9)}
        h = self.hist
        if h is not None:
            out["p50_windowed"] = round(h.percentile(0.50), 9)
            out["p99_windowed"] = round(h.percentile(0.99), 9)
            out["p999_windowed"] = round(h.percentile(0.999), 9)
            out["window_secs"] = h.window_secs
        return out


# name -> hook(Timer); applied when the named Timer is (re)created, so a
# watch registered before any sample — or surviving a reset() — still
# lands on the live object.  mx.obs uses this to attach windowed
# histograms to hot timers without eagerly creating zero-count metrics.
_TIMER_WATCHES: Dict[str, Callable] = {}


def _get(name: str, cls):
    m = _REGISTRY.get(name)
    if m is None:
        with _REG_LOCK:
            m = _REGISTRY.get(name)
            if m is None:
                m = _REGISTRY[name] = cls(name)
                if cls is Timer:
                    hook = _TIMER_WATCHES.get(name)
                    if hook is not None:
                        hook(m)
    if not isinstance(m, cls):
        raise TypeError(f"metric {name!r} already registered as {m.kind}")
    return m


def peek(name: str):
    """The live metric object for ``name``, or None — a read-only lookup
    that never creates (readiness probes must not mint zero-count
    metrics just by asking)."""
    return _REGISTRY.get(name)


def watch_timer(name: str, hook: Callable):
    """Register ``hook(timer)`` to run when Timer ``name`` is created
    (and immediately, if it already exists).  One watch per name —
    re-registering replaces.  The hook typically sets ``timer.hist``."""
    with _REG_LOCK:
        _TIMER_WATCHES[name] = hook
    m = _REGISTRY.get(name)
    if isinstance(m, Timer):
        hook(m)


def unwatch_timer(name: str):
    """Drop the watch for ``name`` and detach any attached histogram."""
    with _REG_LOCK:
        _TIMER_WATCHES.pop(name, None)
    m = _REGISTRY.get(name)
    if isinstance(m, Timer):
        m.hist = None


def counter(name: str) -> Counter:
    """Get-or-create the named Counter."""
    return _get(name, Counter)


def gauge(name: str) -> Gauge:
    """Get-or-create the named Gauge."""
    return _get(name, Gauge)


class _NullScope:
    """Shared no-op context for disabled-mode ``with timer(...)``."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def observe(self, seconds: float):
        pass


_NULL_SCOPE = _NullScope()


def timer(name: str):
    """Get-or-create the named Timer.  Usable directly as a scope::

        with telemetry.timer("trainer.step_seconds"):
            ...

    Disabled mode returns a shared no-op scope (no registry mutation)."""
    if not _ENABLED:
        return _NULL_SCOPE
    return _get(name, Timer)


def timed(name: str) -> Callable:
    """Decorator form: time every call of ``fn`` into Timer ``name``."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            if not _ENABLED:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                _get(name, Timer).observe(time.perf_counter() - t0)
        return inner
    return wrap


# -- module-level fast helpers (flag check inside) ---------------------------

def inc(name: str, delta: Union[int, float] = 1):
    if _ENABLED:
        _get(name, Counter).inc(delta)


def set_gauge(name: str, value: Union[int, float]):
    if _ENABLED:
        _get(name, Gauge).set(value)


def observe(name: str, seconds: float):
    if _ENABLED:
        _get(name, Timer).observe(seconds)


# -- export ------------------------------------------------------------------

def snapshot(reset_after: bool = False) -> Dict[str, dict]:
    """Point-in-time aggregate of every metric: ``{name: summary_dict}``.
    Every summary carries ``type`` and a uniform ``value`` field (counter
    value / gauge value / timer total seconds)."""
    with _REG_LOCK:
        items = sorted(_REGISTRY.items())
    out = {name: m.summary() for name, m in items}
    if reset_after:
        reset()
    return out


def reset():
    """Drop every metric (tests; ``dumps(reset=True)``)."""
    with _REG_LOCK:
        _REGISTRY.clear()


def dumps(reset: bool = False) -> str:
    """Aggregate table ('' when nothing recorded).  Also rendered inside
    ``profiler.dumps()`` so one call shows native counters + telemetry."""
    snap = snapshot(reset_after=reset)
    if not snap:
        return ""
    name_w = max(len("Name"), max(len(n) for n in snap))
    head = (f"{'Name':<{name_w}}  {'Type':<7}  {'Count':>8}  "
            f"{'Total/Value':>14}  {'Min':>10}  {'Max':>10}  "
            f"{'p50':>10}  {'p99':>10}")
    lines = ["Telemetry Statistics:", head, "-" * len(head)]
    for name, s in snap.items():
        if s["type"] == "timer":
            # tail columns prefer the time-windowed histogram when one
            # is attached (mx.obs): steady-state p99, warmup aged out
            p50 = s.get("p50_windowed", s["p50"])
            p99 = s.get("p99_windowed", s["p99"])
            lines.append(
                f"{name:<{name_w}}  {'timer':<7}  {s['count']:>8}  "
                f"{s['total']:>14.6f}  {s['min']:>10.6f}  "
                f"{s['max']:>10.6f}  {p50:>10.6f}  {p99:>10.6f}")
        else:
            val = s["value"]
            sval = f"{val:.6f}" if isinstance(val, float) else str(val)
            extra = f"  (max {s['max']})" if s["type"] == "gauge" else ""
            lines.append(f"{name:<{name_w}}  {s['type']:<7}  {'':>8}  "
                         f"{sval:>14}{extra}")
    return "\n".join(lines)


def dump_json(path: str, extra: Optional[dict] = None) -> dict:
    """Write the structured snapshot to ``path`` and return it.

    Schema (stable; version bumps on change)::

        {"version": 1, "ts": <unix seconds>, "pid": <int>,
         "enabled": <bool>, "metrics": {name: summary, ...}}
    """
    doc = {"version": 1, "ts": round(time.time(), 3), "pid": os.getpid(),
           "enabled": _ENABLED, "metrics": snapshot()}
    if extra:
        doc.update(extra)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return doc


def write_tensorboard(logdir: str, step: int = 0, writer=None):
    """Emit the snapshot as TensorBoard scalars (one point per metric at
    ``global_step=step``; call per epoch/eval for a time series).  Pass an
    existing ``contrib.tensorboard.SummaryWriter`` as ``writer`` to append
    to an open event file; otherwise one is created under ``logdir`` and
    closed before returning."""
    from .contrib.tensorboard import SummaryWriter

    own = writer is None
    w = writer if writer is not None else SummaryWriter(logdir)
    try:
        for name, s in snapshot().items():
            if s["type"] == "timer":
                w.add_scalar(f"telemetry/{name}/total", s["total"], step)
                w.add_scalar(f"telemetry/{name}/count", s["count"], step)
                # same windowed-tail preference as dumps()
                w.add_scalar(f"telemetry/{name}/p50",
                             s.get("p50_windowed", s["p50"]), step)
                w.add_scalar(f"telemetry/{name}/p99",
                             s.get("p99_windowed", s["p99"]), step)
            else:
                w.add_scalar(f"telemetry/{name}", s["value"], step)
        w.flush()
    finally:
        if own:
            w.close()
    return w if not own else None


# MXNET_TELEMETRY_JSON=<path>: snapshot at interpreter exit — the zero-code
# way to collect a run's metrics (`make telemetry-smoke` rides this).
# Disabled mode emits nothing.
_JSON_AT_EXIT = os.environ.get("MXNET_TELEMETRY_JSON")
if _JSON_AT_EXIT:
    @atexit.register
    def _dump_at_exit(path=_JSON_AT_EXIT):
        if _ENABLED and _REGISTRY:
            try:
                dump_json(path)
            except OSError:
                pass
