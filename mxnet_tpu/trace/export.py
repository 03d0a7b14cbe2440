"""Chrome-trace / Perfetto export — the ONE timeline emitter.

Everything that produces a trace file goes through here: the span
recorder's host events, the native engine's op records
(``engine.profile_dump`` — already chrome-event JSON objects on the
same CLOCK_MONOTONIC timebase), and the flight recorder's crash
dumps.  The device timeline is not in this file: a ``jax.profiler``
session writes an ``.xplane.pb``, which holds the device's ops AND
every span open while it ran (the recorder's annotations), on the
profiler's own clock.  ``mx.profiler`` used to hand-roll its own engine-event schema
(``_dump_engine_chrome_trace``); that emitter is gone — it calls
:func:`write` now.

Output is the Chrome Trace Event Format (load in Perfetto's
https://ui.perfetto.dev or chrome://tracing)::

    {"displayTimeUnit": "ms",
     "metadata": {...},            # pid, unix epoch of ts 0, reason
     "traceEvents": [
       {"name": "trainer.step", "cat": "trainer", "ph": "X",
        "ts": <us>, "dur": <us>, "pid": ..., "tid": ...,
        "args": {"step": 17}},
       ...]}

``cat`` is the span name's subsystem prefix (the segment before the
first dot) — the Perfetto query surface ``make trace-smoke`` counts
subsystem coverage with.  Timestamps stay in the process's
``perf_counter`` domain (microseconds); ``metadata.epoch_unix_ts``
maps them back to wall-clock.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from . import recorder as _rec

__all__ = ["chrome_events", "document", "dumps", "write"]


def _cat(name: str) -> str:
    return name.split(".", 1)[0]


_PH = {"X": "X", "B": "B", "E": "E", "i": "i", "C": "C"}


def chrome_events(engine_events: Optional[str] = None) -> List[dict]:
    """Buffered recorder events (+ the engine's) as chrome dicts.

    ``engine_events`` is the comma-separated chrome-JSON string
    ``engine.profile_dump()`` returns (the caller drains the engine —
    this function must not steal events from a live profiling session)."""
    pid = os.getpid()
    out: List[dict] = []
    threads = {}
    for e in _rec.events():
        threads.setdefault(e["tid"], e["thread"])
        args: Dict[str, Any] = dict(e["corr"])
        if e["attrs"]:
            args.update(e["attrs"])
        ev = {"name": e["name"], "cat": _cat(e["name"]),
              "ph": _PH.get(e["kind"], "X"), "pid": pid, "tid": e["tid"],
              "ts": round(e["ts"] * 1e6, 3)}
        if e["kind"] == "X":
            ev["dur"] = round(e["dur"] * 1e6, 3)
        if e["kind"] == "i":
            ev["s"] = "t"  # instant scope: thread
        if e["kind"] == "C":
            ev["args"] = {"value": args.get("value", 0)}
        elif args:
            ev["args"] = args
        out.append(ev)
    for tid, name in threads.items():
        out.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": name}})
    if engine_events:
        try:
            native = json.loads("[" + engine_events + "]")
        except ValueError:
            native = []
        for ev in native:
            # engine.cc stamps pid 0; fold its ops into this process's
            # track (same CLOCK_MONOTONIC microsecond domain) under a
            # cat of their own
            ev["pid"] = pid
            ev.setdefault("cat", "engine")
            out.append(ev)
    return out


def document(engine_events: Optional[str] = None,
             metadata: Optional[dict] = None) -> dict:
    """The full exportable trace document."""
    meta = {"pid": os.getpid(),
            "epoch_unix_ts": round(_rec.EPOCH_OFFSET, 6),
            "unix_ts": round(time.time(), 3),
            "trace_enabled": _rec.enabled(),
            "ring_capacity": _rec.ring_capacity()}
    if metadata:
        meta.update(metadata)
    return {"displayTimeUnit": "ms", "metadata": meta,
            "traceEvents": chrome_events(engine_events)}


def dumps(engine_events: Optional[str] = None,
          metadata: Optional[dict] = None) -> str:
    """The trace document as a JSON string."""
    return json.dumps(document(engine_events, metadata))


def write(path: str, engine_events: Optional[str] = None,
          metadata: Optional[dict] = None) -> str:
    """Write the trace document to ``path`` (atomic rename) and return
    the path — ``mx.profiler.set_state("stop")`` and the flight
    recorder both land through here."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(document(engine_events, metadata), f)
        f.write("\n")
    os.replace(tmp, path)
    return path
