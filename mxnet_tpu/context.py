"""Device contexts: ``mx.cpu()`` / ``mx.tpu(i)`` / ``mx.gpu(i)``.

TPU-native analogue of the reference Context (include/mxnet/base.h:95-118,
Context::Create/CPU/GPU at base.h:394-416). A Context names a logical device;
it resolves lazily to a concrete ``jax.Device``. ``mx.gpu`` is accepted as an
alias for the accelerator so reference scripts keep running, but the
first-class accelerator here is the TPU.

Unlike the reference there is no per-device stream/thread pool to manage:
XLA/PJRT owns async dispatch (SURVEY.md §7 design stance).
"""
from __future__ import annotations

import threading
from typing import List, Optional

from .base import MXNetError

__all__ = ["Context", "cpu", "tpu", "gpu", "cpu_pinned", "current_context",
           "num_tpus", "num_gpus", "device"]

_DEVTYPE_ALIASES = {
    "cpu": "cpu",
    "cpu_pinned": "cpu",   # pinned memory is meaningless under PJRT; alias to cpu
    "cpu_shared": "cpu",
    "tpu": "tpu",
    "gpu": "tpu",          # compat alias: reference scripts say gpu; we run TPU-first
}


class Context:
    """A logical device handle.

    Lazily binds to a ``jax.Device``; comparisons and hashing use the
    (device_type, device_id) pair like the reference's (dev_mask, dev_id).
    """

    _default_stack = threading.local()

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):  # copy-construction, ref ctx.py
            device_type, device_id = (device_type.device_type,
                                      device_type.device_id)
        if device_type not in _DEVTYPE_ALIASES:
            raise MXNetError(f"unknown device type '{device_type}'")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- resolution ---------------------------------------------------------
    @property
    def kind(self) -> str:
        """Canonical backend kind ('cpu' or 'tpu')."""
        return _DEVTYPE_ALIASES[self.device_type]

    def jax_device(self):
        """Resolve to a concrete PROCESS-LOCAL jax.Device (multi-process:
        jax.devices() enumerates the whole job; only local ones are
        addressable).  A ``tpu``/``gpu`` context names one accelerator
        chip: it raises when there is none, or when the id is past the
        chips this process holds — it never stands for a CPU device or
        wraps onto another chip."""
        import jax

        if self.kind == "tpu":
            devs = _accelerator_devices()
            if not 0 <= self.device_id < len(devs):
                raise MXNetError(
                    f"{self!r} names accelerator {self.device_id}, but this "
                    f"process holds {len(devs)} accelerator device(s) "
                    f"(jax backend {jax.default_backend()!r}); use mx.cpu() "
                    "to run on the host")
            return devs[self.device_id]
        # the host backend always exists; cpu ids are logical (reference
        # scripts number cpu contexts freely), so they wrap
        devs = jax.local_devices(backend="cpu")
        return devs[self.device_id % len(devs)]

    # -- protocol -----------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.kind == other.kind
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.kind, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        stack = getattr(Context._default_stack, "stack", None)
        if stack is None:
            stack = Context._default_stack.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default_stack.stack.pop()


def _accelerator_devices() -> List:
    import jax

    return [d for d in jax.local_devices() if d.platform != "cpu"]


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compat alias — reference scripts use mx.gpu(); maps to the accelerator."""
    return Context("gpu", device_id)


def device(dev: str, device_id: int = 0) -> Context:
    return Context(dev, device_id)


def num_tpus() -> int:
    return len(_accelerator_devices())


def num_gpus() -> int:
    """Compat shim (ref: mx.context.num_gpus); counts accelerator chips."""
    return num_tpus()


def current_context() -> Context:
    """Innermost ``with ctx:`` scope, else default device.

    Default is the accelerator when one is attached, mirroring nothing in the
    reference (whose default is cpu) but matching TPU-first intent; set
    MXNET_DEFAULT_CONTEXT=cpu to force cpu.
    """
    stack = getattr(Context._default_stack, "stack", None)
    if stack:
        return stack[-1]
    from .base import get_env

    forced = get_env("MXNET_DEFAULT_CONTEXT", None, str)
    if forced:
        name, _, idx = forced.partition(":")
        return Context(name, int(idx or 0))
    return tpu(0) if num_tpus() > 0 else cpu(0)
