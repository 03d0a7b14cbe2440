"""mx.profiler — tracing/profiling API over jax.profiler + mx.trace.

Ref: python/mxnet/profiler.py + src/profiler/ (2.9k LoC chrome-tracing
collector). TPU-native: XProf/perfetto traces come from jax.profiler
(start_trace/stop_trace; every mx.trace span is annotated into a running
session ≈ ProfileTask/named scopes);
set_config/set_state/dumps keep the reference API. Autostart via
MXNET_PROFILER_AUTOSTART like the reference (env_var.md:246).

The reference's host-side event stream is mx.trace (docs/tracing.md):
Scope/Domain/Task/Frame/Event/Counter/Marker all record onto the span
recorder, and ``set_state("stop")`` writes ONE Chrome-trace file —
host spans + native-engine op records, via the single emitter in
``trace.export`` — next to the configured filename
(``<filename minus ext>_trace.json``; open in Perfetto).
``dumps(format="trace")`` returns the same document as a string.
"""
from __future__ import annotations

import atexit
import os
import time
from typing import Optional

import jax

from . import trace as _trace
from .base import get_env

__all__ = ["set_config", "set_state", "state", "dump", "dumps", "pause",
           "resume", "Scope", "Domain", "Task", "Frame", "Event",
           "Counter", "Marker"]

_config = {"filename": "profile.json", "profile_all": False, "aggregate_stats": False}
_state = {"running": False, "dir": None}
_counters = {}


def set_config(**kwargs):
    """Ref profiler.py set_config: filename, profile_{symbolic,imperative,
    memory,api,all}, aggregate_stats... The trace directory derives from
    filename."""
    _config.update(kwargs)


def set_state(state_name: str = "stop", profile_process: str = "worker"):
    from . import engine as _engine

    if state_name == "run" and not _state["running"]:
        logdir = os.path.splitext(_config.get("filename", "profile.json"))[0] + "_xprof"
        os.makedirs(logdir, exist_ok=True)
        jax.profiler.start_trace(logdir)
        eng = _engine.get()
        if hasattr(eng, "profile_start"):
            eng.profile_start()  # host-side engine ops join the trace
        _state.update(running=True, dir=logdir)
    elif state_name == "stop" and _state["running"]:
        jax.profiler.stop_trace()
        eng = _engine.get()
        engine_events = ""
        if hasattr(eng, "profile_stop"):
            eng.profile_stop()
            try:
                eng.wait_for_all()  # in-flight ops finish recording first
            except Exception:
                # wait_for_all rethrows the engine's sticky first-error,
                # which may belong to ops long before this profiling
                # session; quiescing is all the profiler needs
                pass
            if hasattr(eng, "profile_dump"):
                engine_events = eng.profile_dump()
        # ONE Chrome-trace emitter (trace.export): recorder spans +
        # engine op records in a single document; the device timeline
        # and the same spans on its clock are the session's .xplane.pb
        path = os.path.splitext(_config.get("filename", "profile.json"))[0] \
            + "_trace.json"
        _state["trace"] = _trace.export.write(
            path, engine_events=engine_events or None)
        # back-compat key: callers that looked up the old engine-only
        # chrome dump find the merged file
        _state["engine_trace"] = _state["trace"]
        _state.update(running=False)


def state() -> str:
    return "run" if _state["running"] else "stop"


def pause(profile_process="worker"):
    set_state("stop")


def resume(profile_process="worker"):
    set_state("run")


def dump(finished: bool = True, profile_process: str = "worker"):
    if _state["running"]:
        set_state("stop")


def dumps(reset: bool = False, format: str = "table") -> str:
    """Aggregate-stats text (ref profiler.py dumps): profiler counters +
    the telemetry registry's aggregate table (one call shows both);
    kernel-level stats live in the XProf trace.

    ``format="trace"`` instead returns the Chrome-trace/Perfetto JSON of
    everything the span recorder holds (the same document
    ``set_state("stop")`` writes) — the passthrough to mx.trace."""
    from . import telemetry

    if format == "trace":
        return _trace.export.dumps()
    lines = ["Profile Statistics:"]
    for name, v in _counters.items():
        lines.append(f"  {name}: {v}")
    if reset:
        _counters.clear()
    tel = telemetry.dumps(reset=reset)
    if tel:
        lines.append(tel)
    return "\n".join(lines)


class Scope:
    """Named scope: one ``mx.trace`` span ``profiler.<name>``, which
    lands in the span recorder and, while a profiler session is on, in
    the device timeline too (the span carries the annotation,
    ≈ ProfileOperator)."""

    def __init__(self, name: str = "<unk>:"):
        self.name = name
        self._span = None

    def __enter__(self):
        self._span = _trace.span(f"profiler.{self.name}")
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)


class Domain:
    """Category grouping for profiling sub-objects (ref profiler.py
    Domain — part of 'categories' in chrome://tracing output).  Child
    objects carry ``domain.name`` as a prefix in the trace."""

    def __init__(self, name: str):
        self.name = name

    def __str__(self):
        return self.name

    def new_task(self, name="task"):
        return Task(self, name)

    def new_frame(self, name="frame"):
        return Frame(self, name)

    def new_event(self, name="event"):
        return Event(self, name)

    def new_counter(self, name="counter", value=0):
        return Counter(self, name, value)

    def new_marker(self, name="marker"):
        return Marker(self, name)


def _domain_name(domain, name):
    """Children prefix their domain whether built via Domain.new_* or
    constructed directly (ref allows both paths interchangeably)."""
    return f"{domain.name}::{name}" if domain is not None else name


class Task:
    """Ref profiler.py Task — host-side duration, recorded as a span."""

    def __init__(self, domain=None, name: str = "task"):
        self.name = _domain_name(domain, name)
        self._start = None
        self._span = None

    def start(self):
        self._start = time.perf_counter()
        self._span = _trace.span(f"profiler.{self.name}")
        self._span.__enter__()

    def stop(self):
        if self._start is not None:
            self._span.__exit__(None, None, None)
            _counters[f"task:{self.name}:sec"] = \
                time.perf_counter() - self._start
            self._start = None


Frame = Task
Event = Task


class Counter:
    """Ref profiler.py Counter — every write also lands a Chrome "C"
    counter sample on the trace timeline."""

    def __init__(self, domain=None, name: str = "counter", value: int = 0):
        self.name = _domain_name(domain, name)
        self._set(value)

    def _set(self, v):
        _counters[self.name] = v
        _trace.counter(f"profiler.{self.name}", v)

    def set_value(self, v):
        self._set(v)

    def increment(self, delta=1):
        self._set(_counters.get(self.name, 0) + delta)

    def decrement(self, delta=1):
        self._set(_counters.get(self.name, 0) - delta)


class Marker:
    def __init__(self, domain=None, name: str = "marker"):
        self.name = _domain_name(domain, name)

    def mark(self, scope="process"):
        _counters[f"marker:{self.name}"] = time.monotonic()
        _trace.instant(f"profiler.{self.name}", scope=scope)


if get_env("MXNET_PROFILER_AUTOSTART", 0, int):
    set_state("run")
    atexit.register(lambda: set_state("stop"))
