"""XLA cost attribution — the standing version of PERF.md's analysis.

PERF.md round 2 had to reconstruct "what does the chip actually
execute" by hand: lower the step, ``compiled.cost_analysis()``, divide
by wall time, compare against peak.  This module makes that a
registry: every cached executable the stack compiles can
:func:`register` its XLA-counted FLOPs / bytes-accessed, and
:func:`publish` turns a measured seconds-per-execution into standing
telemetry gauges —

    ``trainer.xla_flops_per_sec``   achieved FLOP/s against XLA's own
                                    count of the compiled program
    ``trainer.xla_utilization``     that rate over the chip's peak
                                    (0.0 on a CPU host, which has no
                                    device peak — see :func:`peak_flops`)
    ``trainer.xla_bytes_per_sec``   cost_analysis "bytes accessed" rate
    ``trainer.xla_hbm_utilization`` over peak HBM bandwidth (same
                                    CPU-host convention)

— so ``bench.py`` rows carry BOTH the paper-FLOP MFU (the external
comparison number) and the XLA-counted utilization (what fraction of
the hardware the *compiled program* achieved; PERF.md: ~15% vs ~28% on
ResNet-50).  Caveat carried over from PERF.md: XLA's "bytes accessed"
over-counts per-fusion operand reads, so the HBM figure is an upper
bound on real traffic, not a measurement.

Peaks: ONE table (:data:`PEAKS`) keyed by the exact ``device_kind``
jax reports, with its source.  An accelerator that is not in the table
is an error where a peak is asked for — never a default, a substring
guess or an environment override.  A CPU host has no device peak:
:func:`publish` computes no utilization there.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

from .. import telemetry as _tel
from ..base import MXNetError

__all__ = ["extract", "register", "get", "snapshot", "reset", "PEAKS",
           "peak_flops", "peak_hbm_bytes_per_sec", "publish"]

_LOCK = threading.Lock()
_COSTS: Dict[Any, Dict[str, Any]] = {}

# Per-chip peaks keyed by ``jax.devices()[0].device_kind``: dense bf16
# FLOP/s and HBM bytes/s.  Source: Google Cloud TPU documentation, the
# system-architecture page of each generation ("TPU v5e": 197 TFLOP/s
# bf16, 819 GB/s; "TPU v4": 275, 1228; "TPU v5p": 459, 2765; "TPU v6e":
# 918, 1640).  The kinds are what jax 0.9.0 / libtpu 0.0.34 report for
# a described topology of each (v5p reports plain "TPU v5").
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_sec": 819e9},
    "TPU v4": {"flops": 275e12, "hbm_bytes_per_sec": 1228e9},
    "TPU v5": {"flops": 459e12, "hbm_bytes_per_sec": 2765e9},
    "TPU v6 lite": {"flops": 918e12, "hbm_bytes_per_sec": 1640e9},
}


def _peak(what: str, device=None) -> float:
    import jax

    kind = (device or jax.devices()[0]).device_kind
    if kind not in PEAKS:
        raise MXNetError(
            f"no peak {what} known for device kind {kind!r}; the table "
            f"(mxnet_tpu/trace/cost.py PEAKS) has {sorted(PEAKS)} — add "
            "the kind with its source rather than assuming one")
    return PEAKS[kind][what]


def peak_flops(device=None) -> float:
    """Peak dense bf16 FLOP/s of ``device`` (default: the first jax
    device) from :data:`PEAKS`; an unknown ``device_kind`` raises."""
    return _peak("flops", device)


def peak_hbm_bytes_per_sec(device=None) -> float:
    """Peak HBM bytes/s of ``device`` from :data:`PEAKS`; an unknown
    ``device_kind`` raises."""
    return _peak("hbm_bytes_per_sec", device)


def extract(compiled) -> Optional[Dict[str, float]]:
    """Pull ``cost_analysis()`` off a jax compiled executable →
    ``{"flops": ..., "bytes_accessed": ...}`` (None when the backend
    offers no analysis)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    if flops is None and nbytes is None:
        return None
    return {"flops": float(flops or 0.0),
            "bytes_accessed": float(nbytes or 0.0)}


def register(key, compiled=None, info: Optional[dict] = None,
             accumulate: bool = False) -> Optional[Dict[str, Any]]:
    """Record the cost of one executable under ``key`` (any hashable —
    the trainer keys on ``(net type, slot, batch signature)``).  Pass
    either the compiled executable or a pre-extracted ``info`` dict.
    ``accumulate=True`` adds onto an existing entry (the grad-accum
    trainer sums its grad and apply executables into one step cost).
    Returns the stored entry, or None when nothing was extractable."""
    if info is None:
        if compiled is None:
            return None
        info = extract(compiled)
        if info is None:
            return None
    with _LOCK:
        cur = _COSTS.get(key)
        if cur is not None and accumulate:
            cur = {"flops": cur["flops"] + info.get("flops", 0.0),
                   "bytes_accessed": cur["bytes_accessed"]
                   + info.get("bytes_accessed", 0.0)}
        else:
            cur = {"flops": float(info.get("flops", 0.0)),
                   "bytes_accessed": float(info.get("bytes_accessed",
                                                    0.0))}
        _COSTS[key] = cur
        n = len(_COSTS)
    if _tel._ENABLED:
        _tel.set_gauge("trace.cost_executables", n)
    return dict(cur)


def get(key) -> Optional[Dict[str, Any]]:
    with _LOCK:
        info = _COSTS.get(key)
    return dict(info) if info is not None else None


def snapshot() -> Dict[str, Dict[str, Any]]:
    """Every registered executable's cost, keyed by ``str(key)``."""
    with _LOCK:
        return {str(k): dict(v) for k, v in _COSTS.items()}


def reset():
    with _LOCK:
        _COSTS.clear()


def publish(key, seconds_per_execution: float,
            prefix: str = "trainer") -> Dict[str, Any]:
    """Turn a measured wall time per execution of ``key`` into the
    utilization gauges + a row-ready dict (bench columns).  Unknown
    ``key`` → ``{}``.  On a CPU host there is no device peak: the
    utilization gauges publish 0.0 and the returned dict carries None,
    so artifacts stay honest.  An accelerator missing from :data:`PEAKS`
    raises."""
    import jax

    info = get(key)
    if info is None or seconds_per_execution <= 0.0:
        return {}
    fps = info["flops"] / seconds_per_execution
    bps = info["bytes_accessed"] / seconds_per_execution
    dev = jax.devices()[0]
    on_chip = dev.platform != "cpu"
    util = fps / peak_flops(dev) if on_chip else None
    hbm_util = bps / peak_hbm_bytes_per_sec(dev) if on_chip else None
    if _tel._ENABLED:
        _tel.set_gauge(f"{prefix}.xla_flops_per_sec", round(fps, 3))
        _tel.set_gauge(f"{prefix}.xla_bytes_per_sec", round(bps, 3))
        _tel.set_gauge(f"{prefix}.xla_utilization",
                       round(util, 9) if util is not None else 0.0)
        _tel.set_gauge(f"{prefix}.xla_hbm_utilization",
                       round(hbm_util, 9) if hbm_util is not None else 0.0)
    # 9 decimals: smoke-scale models legitimately measure micro-GFLOPs
    # and micro-utilizations; coarser rounding would zero them out
    return {"xla_gflops_per_step": round(info["flops"] / 1e9, 9),
            "xla_gbytes_per_step": round(info["bytes_accessed"] / 1e9, 9),
            "xla_flops_per_sec": round(fps, 3),
            "xla_utilization": round(util, 9) if util is not None else None,
            "xla_hbm_utilization": (round(hbm_util, 9)
                                    if hbm_util is not None else None)}
