"""Kernel selection + fallback observability (docs/kernels.md).

TVM (PAPERS.md) frames the pattern this module implements: a dispatch
registry where every hand-written kernel is *selectable* and every
fallback is *observable*.  A Pallas kernel that silently degrades to the
jnp reference path is how perf regressions hide — PERF.md round 4's
"O(T^2) fallback on the chip" failure mode — so every decision point
reports:

  * ``kernels.dispatches[.<name>]`` telemetry counters tick when a Pallas
    (or interpret-mode) kernel body is actually used;
  * ``kernels.fallbacks[.<name>]`` counters tick when a kernel was
    *eligible by mode* but the call degraded to the reference path, and a
    once-per-(kernel, reason) warning names WHY (shape not tile-able,
    mask form, platform, optimizer not fusible, traced mesh);
  * a ``kernels.dispatch`` trace instant (docs/tracing.md) records the
    decision with its mode/reason attributes.

Selection is mode-based (``MXNET_KERNELS``):

  * ``pallas``     — compiled Mosaic kernels; requires a TPU backend.
  * ``interpret``  — the same kernel bodies under the Pallas interpreter;
    runs on any backend (how CI validates the kernels without a chip).
  * ``off``        — reference paths only; fully silent (no fallback
    counters — *off* is a deliberate choice, not a degradation).

The default is ``pallas`` on a TPU backend and ``off`` elsewhere, so a
plain CPU run (tier-1, notebooks) behaves exactly as before this layer
existed.  Per-call overrides ride :func:`override` (a thread-local
context manager) or the explicit ``fused_opt=``/``kernels=`` arguments on
the public entry points.

Counters tick at *decision time*, which for kernels living inside jitted
code (the flash VJP, the arena optimizer) is trace time — once per jit
signature, not once per step.  That is exactly when the
pallas-vs-reference choice is made, so the counters answer "did this
executable get the kernel" rather than "how many steps ran it".
"""
from __future__ import annotations

import contextlib
import os
import threading
import warnings
from typing import Dict, Optional, Tuple

from .. import telemetry as _tel
from ..base import MXNetError
from ..trace import recorder as _tr

__all__ = ["MODES", "KERNELS", "mode", "override", "select", "fallback",
           "dispatched", "reset_warned", "batch_mesh", "mesh_ineligible",
           "shard_over_batch"]

MODES = ("pallas", "interpret", "off")

# name -> one-line description (docs/kernels.md carries the full matrix)
KERNELS: Dict[str, str] = {
    "flash_attention": "blockwise online-softmax attention forward",
    "flash_attention_bwd": "flash-attention backward (dq + dk/dv kernels)",
    "flash_attention_decode": "single-query/chunk attention vs a KV cache",
    "opt_arena": "flat-arena fused optimizer update (sgd/momentum/adam)",
    "bn_act": "single-pass batch-norm statistics + scale/shift + act",
}

_TLS = threading.local()
_WARNED = set()
_WARN_LOCK = threading.Lock()


def _backend() -> str:
    import jax

    return jax.default_backend()


def mode() -> str:
    """Resolve the active kernel mode: thread-local :func:`override` wins,
    then ``MXNET_KERNELS``, then the platform default (``pallas`` on TPU,
    ``off`` elsewhere — a CPU run without explicit opt-in never pays the
    interpreter)."""
    ov = getattr(_TLS, "override", None)
    if ov is not None:
        return ov
    env = os.environ.get("MXNET_KERNELS")
    if env is not None:
        env = env.strip().lower()
        if env not in MODES:
            raise MXNetError(
                f"MXNET_KERNELS={env!r} unknown; choose from {MODES}")
        return env
    return "pallas" if _backend() == "tpu" else "off"


@contextlib.contextmanager
def override(m: Optional[str]):
    """Per-call mode override (thread-local); ``None`` restores env
    resolution inside the scope."""
    if m is not None and m not in MODES:
        raise MXNetError(f"kernel mode {m!r} unknown; choose from {MODES}")
    prev = getattr(_TLS, "override", None)
    _TLS.override = m
    try:
        yield
    finally:
        _TLS.override = prev


def select(name: str, mode_override: Optional[str] = None) -> Optional[str]:
    """Mode-level selection for kernel ``name``: returns ``"pallas"`` /
    ``"interpret"`` when the kernel body should run, else ``None``.

    ``off`` is silent; ``pallas`` on a non-TPU backend is an observable
    fallback (reason ``platform:<backend>``).  Shape/mask/optimizer
    eligibility is the call site's job — report misses via
    :func:`fallback` so the reason names the actual constraint."""
    if name not in KERNELS:
        raise MXNetError(f"unknown kernel {name!r}; registry has "
                         f"{sorted(KERNELS)}")
    m = mode_override if mode_override is not None else mode()
    if m == "off":
        return None
    if m == "interpret":
        return "interpret"
    backend = _backend()
    if backend != "tpu":
        fallback(name, f"platform:{backend}")
        return None
    return "pallas"


@contextlib.contextmanager
def batch_mesh(mesh, axis: str):
    """Declare, while a step is TRACED, that activations' leading (batch)
    dim is sharded over ``axis`` of ``mesh`` (thread-local; the trainer
    enters it around its forward+backward trace).

    Why it exists: under a multi-device GSPMD ``jit`` the TPU compiler
    refuses every Pallas kernel, whatever its operands' shardings —
    "Mosaic kernels cannot be automatically partitioned. Please wrap the
    call in a shard_map."  A kernel call site therefore asks
    :func:`mesh_ineligible` (a counted decision before the call) and
    wraps its ``pallas_call`` with :func:`shard_over_batch`."""
    prev = getattr(_TLS, "batch_mesh", None)
    _TLS.batch_mesh = (mesh, axis) \
        if mesh is not None and mesh.size > 1 else None
    try:
        yield
    finally:
        _TLS.batch_mesh = prev


def mesh_ineligible(batch: Optional[int]) -> Optional[str]:
    """Why a batch-major kernel cannot run under the traced mesh, else
    ``None``.  ``batch=None``: the kernel has no per-shard form (it
    reduces over the batch, or nobody wrote one) — any mesh rules it
    out."""
    bm = getattr(_TLS, "batch_mesh", None)
    if bm is None:
        return None
    mesh, axis = bm
    if batch is None:
        return f"traced under a {mesh.size}-device mesh (no per-shard form)"
    other = [a for a, n in mesh.shape.items() if a != axis and n > 1]
    if other or axis not in mesh.shape:
        return (f"mesh axes {other} besides the batch axis {axis!r}: the "
                "kernel would need partitioning over them too")
    if batch % mesh.shape[axis]:
        return (f"batch {batch} not divisible by the {mesh.shape[axis]} "
                f"{axis!r} shards")
    return None


def shard_over_batch(fn):
    """``fn`` as it must be called under the traced mesh: itself when no
    multi-device mesh is declared, else a full-manual ``shard_map`` that
    hands each device its batch shard — every array argument and result
    is batch-major (dim 0); a ``None`` argument passes through."""
    bm = getattr(_TLS, "batch_mesh", None)
    if bm is None:
        return fn
    import jax
    from jax.sharding import PartitionSpec as P

    mesh, axis = bm
    return jax.shard_map(fn, mesh=mesh, in_specs=P(axis),
                         out_specs=P(axis), check_vma=False)


def fallback(name: str, reason: str):
    """Record an observable degradation: kernel ``name`` was eligible by
    mode but the call runs the reference path for ``reason``.  Ticks
    ``kernels.fallbacks`` + ``kernels.fallbacks.<name>`` and warns once
    per (kernel, reason) — silent reference-path fallback is how perf
    regressions hide (docs/kernels.md)."""
    if _tel._ENABLED:
        _tel.inc("kernels.fallbacks")
        _tel.inc(f"kernels.fallbacks.{name}")
    if _tr._ENABLED:
        _tr.instant("kernels.dispatch", kernel=name, mode="fallback",
                    reason=reason)
    key = (name, reason)
    with _WARN_LOCK:
        if key in _WARNED:
            return
        _WARNED.add(key)
    warnings.warn(
        f"kernels: {name} fell back to the reference path ({reason}); "
        "set MXNET_KERNELS=off to silence, or see docs/kernels.md for "
        "the eligibility matrix", RuntimeWarning, stacklevel=3)


def dispatched(name: str, kmode: str, **form):
    """Record that the kernel body for ``name`` was selected (``kmode`` in
    pallas/interpret) — the positive counterpart of :func:`fallback`.
    ``form``: the program form the call site chose from its static shapes
    (the training attention's ``hg``/``bq``/``bk`` and, on the backward,
    ``one_program``) — attributes of the trace instant, and the gauges
    ``kernels.form.<name>.<key>`` (the last executable's)."""
    if _tel._ENABLED:
        _tel.inc("kernels.dispatches")
        _tel.inc(f"kernels.dispatches.{name}")
        for key, value in form.items():
            _tel.set_gauge(f"kernels.form.{name}.{key}", int(value))
    if _tr._ENABLED:
        _tr.instant("kernels.dispatch", kernel=name, mode=kmode, **form)


def reset_warned():
    """Clear the once-per-reason warning dedup (tests)."""
    with _WARN_LOCK:
        _WARNED.clear()


def pick_block(n: int,
               preferred: Tuple[int, ...] = (512, 256, 128, 64, 32, 16, 8)
               ) -> int:
    """Largest ``preferred`` block size dividing ``n`` (0 = not
    tile-able).  The one divisor picker every kernel family shares —
    retune the preference list here, not per kernel."""
    for b in preferred:
        if n % b == 0:
            return b
    return 0


def operand_dtype(*arrays):
    """What a kernel hands the MXU: the widest of the arrays' own dtypes
    (bf16 x bf16 for a bf16 model — one pass, where an f32 product is
    emulated with several), accumulated in f32 by
    ``preferred_element_type``."""
    import functools

    import jax.numpy as jnp

    return functools.reduce(jnp.promote_types, (a.dtype for a in arrays))


def tpu_compiler_params(dimension_semantics: Tuple[str, ...]):
    """Mosaic compiler params for a kernel's grid: which axes may be
    split across cores (``parallel``) and which carry an accumulation
    (``arbitrary``)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)
