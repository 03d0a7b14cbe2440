"""mx.trace — span timeline, Perfetto export, flight recorder
(docs/tracing.md).

The observability layer PR 1's aggregate telemetry cannot provide: a
*timeline*.  Three pieces:

  * :mod:`recorder <mxnet_tpu.trace.recorder>` — ``trace.span(name)``
    context managers + the implicit spans wired through engine
    push/wait, the data path (DataLoader / DevicePrefetcher), the
    hybridize compile seams, ``ShardedTrainer.step``/apply, kvstore and
    dist collectives, and checkpoint save/restore.  Thread-aware,
    bounded per-thread rings, step/warmup correlation IDs that survive
    thread hops (``capture``/``attach``/``correlate``).
  * :mod:`export <mxnet_tpu.trace.export>` — the one Chrome-trace /
    Perfetto emitter: host spans + native-engine op records in one
    document.
    ``mx.profiler.dumps(format="trace")`` passes through here.
  * :mod:`flight <mxnet_tpu.trace.flight>` — black-box dumps of the
    span rings on ``MXNetError``, fault-injection abort, or a
    ``MXNET_TRACE_HANG_TIMEOUT`` watchdog firing.  Armed by
    ``MXNET_TRACE_DIR`` (this import does it) or ``flight.arm()``.

Env vars: ``MXNET_TRACE`` (default 1; 0 disables recording),
``MXNET_TRACE_RING`` (events per thread, default 4096),
``MXNET_TRACE_DIR`` (arm the flight recorder; dumps land here),
``MXNET_TRACE_HANG_TIMEOUT`` (seconds; hang watchdog),
``MXNET_TRACE_FLIGHT_MAX`` (dump cap per process, default 5).
"""
from __future__ import annotations

import os as _os

from . import export, flight, recorder
from .recorder import (attach, capture, correlate, correlation, counter,
                       enabled, events, instant, next_id, record_span,
                       reset, set_enabled, span)

__all__ = ["span", "instant", "counter", "record_span", "correlate",
           "capture", "attach", "correlation", "events", "reset",
           "enabled", "set_enabled", "next_id",
           "recorder", "export", "flight",
           "export_chrome", "dumps_chrome"]

# re-exported conveniences
dumps_chrome = export.dumps
export_chrome = export.write

# Env-driven arming, chaos-style: a run launched with MXNET_TRACE_DIR
# (and/or MXNET_TRACE_HANG_TIMEOUT) set needs no code changes to get
# flight dumps.
if _os.environ.get("MXNET_TRACE_DIR") \
        or _os.environ.get("MXNET_TRACE_HANG_TIMEOUT"):
    flight.arm()
