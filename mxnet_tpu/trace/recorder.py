"""Span recorder — the event stream under ``mx.trace`` (docs/tracing.md).

Telemetry (PR 1) answers "how much, in aggregate"; this recorder answers
"when, on which thread, belonging to which step".  Every instrumented
seam opens a :class:`span` — a context manager that records a
``(name, start, duration, correlation, attrs)`` event into a bounded
per-thread ring — and the exporter (``trace.export``) turns the rings
into one Chrome-trace/Perfetto JSON timeline.

Design constraints, in order:

  * **Low overhead.**  One module flag (``MXNET_TRACE=0`` disables)
    guards every seam, mirroring ``telemetry._ENABLED``.  An enabled
    span costs two ``perf_counter`` reads, one small tuple, one locked
    deque append and one ``jax.profiler.TraceAnnotation`` (inert while
    no profiler session is on); a disabled one costs two module-global
    reads and no clock call.  Events fire per batch/step/collective,
    never per element — ``make trace-smoke`` gates the end-to-end
    overhead at ≤5% of step wall time.
  * **Thread-aware.**  Each thread records into its own ring
    (``MXNET_TRACE_RING`` events, default 4096), registered globally so
    :func:`events` / the flight recorder can snapshot every thread
    without stopping the world.  The rings are also the flight
    recorder's black box: always-on, bounded memory, dumpable at the
    moment of failure (``trace.flight``).
  * **Correlated.**  A thread carries a correlation context — e.g.
    ``{"step": 17}`` or ``{"warmup": 3}`` — stamped onto every event it
    records.  :func:`capture` / :func:`attach` move that context across
    thread hops (``DevicePrefetcher`` producers, background warmup,
    the ``InflightQueue``'s deferred step-(t−K) wait), so a span that
    *executes* on a helper thread is still *attributed* to the step
    that owns it.

No double instrumentation: a span constructed with ``timer=`` also
observes the matching telemetry timer on exit, so seams migrate from
``with telemetry.timer(name):`` to ``with trace.span(...)`` without
changing the metric catalog.

Two clocks, one span.  The ring stamps ``time.perf_counter`` — on Linux
the same CLOCK_MONOTONIC the native engine's profiler stamps its events
with, so ring spans and engine ops merge on one timebase in the Chrome
export.  The device trace (``jax.profiler``'s ``.xplane.pb``) knows
nothing of that clock, so an open :class:`span` ALSO holds a
``jax.profiler.TraceAnnotation`` of the same name, with its attrs and
the thread's correlation as stats: while a profiler session is on, the
span lands on the trace's host plane on the profiler's own clock, next
to the device's op lines, and a reader splits device idle time by the
span that covers it (docs/tracing.md, "Spans in the profiler's trace").
:func:`record_span`, :func:`instant` and :func:`counter` are stamped
after the fact and stay ring-only.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _Annotation

from .. import telemetry as _tel
from ..analysis import thread_check as _tchk
from ..base import get_env

__all__ = ["span", "instant", "counter", "record_span", "correlate",
           "capture", "attach", "correlation", "events", "reset",
           "enabled", "set_enabled", "next_id", "last_event_time",
           "ring_capacity"]

_ENABLED: bool = bool(get_env("MXNET_TRACE", 1, int))
_RING: int = max(16, get_env("MXNET_TRACE_RING", 4096, int))

# perf_counter -> unix-epoch mapping, fixed at import so every export of
# this process shares one base (exports stamp it into metadata)
EPOCH_OFFSET: float = time.time() - time.perf_counter()

# heartbeat the hang watchdog reads: perf_counter end time of the last
# recorded event.  Unsynchronized on purpose — a stale read only delays
# the watchdog by one event, never corrupts anything.
_LAST_EVENT: float = 0.0

_REG_LOCK = _tchk.lock("trace.registry")
_STATES: "List[_ThreadState]" = []
_MAX_STATES = 256  # dead-thread rings pruned past this
_TLS = threading.local()
_SEQS: Dict[str, Any] = {}


class _ThreadState:
    """One thread's ring + correlation context."""

    __slots__ = ("tid", "name", "ring", "lock", "corr", "thread")

    def __init__(self):
        t = threading.current_thread()
        self.tid = t.ident or 0
        self.name = t.name
        self.thread = t
        self.ring: deque = deque(maxlen=_RING)
        self.lock = threading.Lock()
        self.corr: Tuple[Tuple[str, Any], ...] = ()


def _state() -> _ThreadState:
    st = getattr(_TLS, "state", None)
    if st is None:
        st = _TLS.state = _ThreadState()
        with _REG_LOCK:
            _STATES.append(st)
            if len(_STATES) > _MAX_STATES:
                # keep live threads + the newest dead rings (short-lived
                # prefetch/warmup threads would otherwise accrete forever)
                dead = [s for s in _STATES if not s.thread.is_alive()]
                for s in dead[:len(_STATES) - _MAX_STATES]:
                    _STATES.remove(s)
    return st


def _record(kind: str, name: str, t0: float, dur: float,
            attrs: Optional[dict], corr=None):
    global _LAST_EVENT
    st = _state()
    with st.lock:
        st.ring.append((kind, name, t0, dur,
                        st.corr if corr is None else corr, attrs))
    _LAST_EVENT = t0 + dur


# -- enable / config ----------------------------------------------------------

def enabled() -> bool:
    """Whether spans record events (``MXNET_TRACE``)."""
    return _ENABLED


def set_enabled(flag: bool) -> bool:
    """Flip recording at runtime; returns the previous state.  Rings
    keep their contents — :func:`reset` clears them."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(flag)
    return prev


def ring_capacity() -> int:
    """Per-thread ring size (``MXNET_TRACE_RING``)."""
    return _RING


def last_event_time() -> float:
    """perf_counter end time of the newest recorded event (0.0 when
    nothing recorded) — the hang watchdog's progress heartbeat."""
    return _LAST_EVENT


def next_id(kind: str) -> int:
    """Monotonic per-kind sequence (warmup ids, flight-dump names)."""
    with _REG_LOCK:
        seq = _SEQS.get(kind)
        if seq is None:
            seq = _SEQS[kind] = itertools.count(1)
    return next(seq)


# -- correlation context ------------------------------------------------------

def correlation() -> Dict[str, Any]:
    """This thread's current correlation context as a dict copy."""
    return dict(_state().corr)


def capture() -> Tuple[Tuple[str, Any], ...]:
    """Snapshot this thread's correlation context as an opaque token —
    hand it to the thread that will do the work and :func:`attach` it
    there, so helper-thread spans stay attributed to their owner."""
    return _state().corr


def attach(token) -> Tuple[Tuple[str, Any], ...]:
    """Install a captured correlation token on THIS thread (worker
    thread entry points); returns the previous context."""
    st = _state()
    prev = st.corr
    st.corr = tuple(token) if token else ()
    return prev


class correlate:
    """Scope a correlation key onto the current thread::

        with trace.correlate(step=17):
            ...every span recorded here (and every token captured
            here) carries step=17...

    Keys merge over the enclosing context and restore on exit."""

    __slots__ = ("_kv", "_prev")

    def __init__(self, **kv):
        self._kv = kv

    def __enter__(self):
        st = _state()
        self._prev = st.corr
        merged = dict(st.corr)
        merged.update(self._kv)
        st.corr = tuple(sorted(merged.items()))
        return self

    def __exit__(self, *exc):
        _state().corr = self._prev
        return False


# -- recording ----------------------------------------------------------------

def _annotation(name: str, attrs: Optional[dict], corr) -> _Annotation:
    """The profiler's view of a span: correlation first, attrs over it
    (as the Chrome export merges them); scalars as they are, anything
    else as ``str``."""
    stats = dict(_state().corr if corr is None else corr)
    if attrs:
        stats.update(attrs)
    return _Annotation(name, **{
        k: v if isinstance(v, (bool, int, float, str)) else str(v)
        for k, v in stats.items()})


class span:
    """One timed region.  ``timer=`` also observes the named telemetry
    Timer on exit (the no-double-instrumentation contract) — on CLEAN
    exit only by default, preserving the metric semantics of the
    hand-rolled ``t0 ... observe()`` sites these spans replaced
    (``timer_on_error=True`` restores try/finally semantics for wait
    seams, where blocked time is real even when the wait raises).  The
    trace event itself always records, with an ``error`` attr on
    exception.  ``corr=`` overrides the thread context for this event
    only (deferred attribution — the InflightQueue's step-(t−K) wait);
    ``phased=True`` emits begin/end ("B"/"E") events instead of one
    complete event, so a hang inside the span still leaves its *begin*
    in the ring for the flight recorder (dist collectives use this).
    While recording is enabled the span also holds a profiler
    annotation of its name (module docstring, "Two clocks"), carrying
    the attrs known at entry."""

    __slots__ = ("name", "timer", "attrs", "corr", "phased",
                 "timer_on_error", "_t0", "_tr", "_tl", "_ann")

    def __init__(self, name: str, timer: Optional[str] = None,
                 corr=None, phased: bool = False,
                 timer_on_error: bool = False, **attrs):
        self.name = name
        self.timer = timer
        self.corr = corr
        self.phased = phased
        self.timer_on_error = timer_on_error
        self.attrs = attrs or None

    def __enter__(self):
        self._tr = _ENABLED
        self._tl = self.timer is not None and _tel._ENABLED
        if self._tr or self._tl:
            self._t0 = time.perf_counter()
            if self._tr:
                if self.phased:
                    _record("B", self.name, self._t0, 0.0, self.attrs,
                            self.corr)
                self._ann = _annotation(self.name, self.attrs, self.corr)
                self._ann.__enter__()
        return self

    def set(self, **attrs) -> "span":
        """Annotate the span mid-flight (e.g. the step id discovered
        after entry)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(attrs)
        return self

    def __exit__(self, exc_type, exc, tb):
        if not (self._tr or self._tl):
            return False
        t1 = time.perf_counter()
        dur = t1 - self._t0
        if self._tr:
            self._ann.__exit__(exc_type, exc, tb)
        if self._tr and _ENABLED:
            attrs = self.attrs
            if exc_type is not None:
                attrs = dict(attrs or ())
                attrs["error"] = exc_type.__name__
            if self.phased:
                _record("E", self.name, t1, 0.0, attrs, self.corr)
            else:
                _record("X", self.name, self._t0, dur, attrs, self.corr)
        if self._tl and _tel._ENABLED and (exc_type is None
                                           or self.timer_on_error):
            _tel.observe(self.timer, dur)
        return False


def record_span(name: str, t0: float, dur: float, corr=None, **attrs):
    """Record an already-timed region (seams that hand-roll their
    ``perf_counter`` pair for telemetry reuse it here)."""
    if _ENABLED:
        _record("X", name, t0, dur, attrs or None, corr)


def instant(name: str, **attrs):
    """Zero-duration marker event."""
    if _ENABLED:
        _record("i", name, time.perf_counter(), 0.0, attrs or None)


def counter(name: str, value) -> None:
    """Counter sample (Chrome "C" event) — the profiler's Counter
    objects mirror through here so their trajectory lands on the
    timeline next to the spans."""
    if _ENABLED:
        _record("C", name, time.perf_counter(), 0.0, {"value": value})


# -- snapshot -----------------------------------------------------------------

def events() -> List[dict]:
    """Every buffered event across all threads, oldest first::

        {"kind": "X"|"B"|"E"|"i"|"C", "name": ..., "ts": <perf_counter>,
         "dur": <seconds>, "tid": ..., "thread": ...,
         "corr": {...}, "attrs": {...}|None}
    """
    with _REG_LOCK:
        states = list(_STATES)
    out: List[dict] = []
    for st in states:
        with st.lock:
            items = list(st.ring)
        for kind, name, t0, dur, corr, attrs in items:
            out.append({"kind": kind, "name": name, "ts": t0, "dur": dur,
                        "tid": st.tid, "thread": st.name,
                        "corr": dict(corr), "attrs": attrs})
    out.sort(key=lambda e: e["ts"])
    return out


def reset():
    """Drop every buffered event (tests, smoke phases)."""
    with _REG_LOCK:
        states = list(_STATES)
    for st in states:
        with st.lock:
            st.ring.clear()
