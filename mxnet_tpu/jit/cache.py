"""Persistent XLA compilation cache (mx.jit.cache).

Every fresh process re-pays the XLA compile of its model (seconds on a
CPU host, a minute or more for a whole train step on the chip).  JAX
ships an on-disk compilation cache (serialized executables keyed by a
hash of the HLO + compile options + jaxlib version + the cache PATH);
this module owns its lifecycle for the framework so a second process of
the same model skips XLA entirely.  The directory has exactly two
possible locations, and only one of them is chosen from outside:

  * ``JAX_COMPILATION_CACHE_DIR`` set  -> that directory, and no other
    is ever set in code (jax's own thresholds stay the operator's too);
  * not set -> ``<checkout>/.jax_cache`` — a FIXED path next to the
    package (the path is part of the cache key: a home directory, a
    ``mkdtemp``, a pid or a time in it never hits).

  * ``MXNET_COMPILE_CACHE=0``     disable the persistent cache
  * ``MXNET_COMPILE_CACHE_MIN_COMPILE_SECS``  for the in-checkout cache,
    only persist executables whose compile took at least this long
    (default 0.0: persist all — disk is cheap, recompile stalls are not)

Initialization is **lazy**: nothing touches jax config until the first
``_CachedOp`` / ``make_train_step`` compile calls :func:`ensure_cache`.
A cache directory that cannot be created is REPORTED (a RuntimeWarning
naming the directory and the error) before the process carries on
uncached — never swallowed.

jax memoizes "cache disabled" at the first compile of the process
(``compilation_cache._cache_checked``), and eager-op dispatch compiles
tiny programs long before the first hybridize; :func:`ensure_cache`
therefore calls ``compilation_cache.reset_cache()`` after pointing the
config at the directory, so the next compile re-reads the config.

Telemetry: a ``jax.monitoring`` listener ticks
``hybridize.persistent_cache_hits`` whenever an executable is served
from disk instead of compiled — together with
``hybridize.cache_misses`` this splits every miss into *cold compile*
(misses - persistent hits) vs *persistent hit* (trace + deserialize,
no XLA).  ``hybridize.compile_seconds`` keeps timing both, so the
cache's win is visible as the timer's total collapsing while the
counter still ticks.
"""
from __future__ import annotations

import os
import re
import threading
import warnings
from typing import Optional

from .. import telemetry as _tel
from ..base import get_env

__all__ = ["cache_dir", "enabled", "ensure_cache", "is_active", "reset"]

_LOCK = threading.Lock()
_STATE = {"initialized": False, "active_dir": None, "listener": False}

_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def enabled() -> bool:
    """Whether the persistent cache is enabled (``MXNET_COMPILE_CACHE``)."""
    return bool(get_env("MXNET_COMPILE_CACHE", 1, int))


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """Resolved cache directory (not created until :func:`ensure_cache`):
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def is_active() -> bool:
    """True once :func:`ensure_cache` has armed the cache this process."""
    return _STATE["initialized"] and _STATE["active_dir"] is not None


def _on_event(name: str, **kwargs):
    if name == _HIT_EVENT and _STATE["active_dir"] is not None:
        _tel.inc("hybridize.persistent_cache_hits")


def _install_listener():
    if _STATE["listener"]:
        return
    import jax

    jax.monitoring.register_event_listener(_on_event)
    _STATE["listener"] = True


def ensure_cache() -> Optional[str]:
    """Arm the persistent compilation cache (idempotent, thread-safe).

    Returns the directory in effect, or ``None`` when disabled (or when
    the directory could not be created — that case warns).  Called by
    ``_CachedOp`` and ``make_train_step`` right before their first
    ``jax.jit`` is built; safe to call eagerly (e.g. from tools).
    """
    with _LOCK:
        if _STATE["initialized"]:
            return _STATE["active_dir"]
        _STATE["initialized"] = True
        if not enabled():
            return None
        import jax
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc,
        )

        d = cache_dir()
        external = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
        if not external:
            try:
                os.makedirs(d, exist_ok=True)
            except OSError as e:
                warnings.warn(
                    f"mx.jit.cache: cannot create the compile cache "
                    f"directory {d!r} ({e}); this process compiles "
                    "uncached — set JAX_COMPILATION_CACHE_DIR to a "
                    "writable directory", RuntimeWarning, stacklevel=2)
                return None
        if jax.config.jax_compilation_cache_dir != d:
            jax.config.update("jax_compilation_cache_dir", d)
            if not external:
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs",
                    get_env("MXNET_COMPILE_CACHE_MIN_COMPILE_SECS", 0.0,
                            float))
                jax.config.update(
                    "jax_persistent_cache_min_entry_size_bytes", -1)
            # eager dispatch compiled tiny programs before we got here and
            # jax memoized "no cache" at that first compile — reset so the
            # next compile re-reads the config and opens the directory
            _cc.reset_cache()
        if not jax.config.jax_hlo_source_file_canonicalization_regex:
            # the key must not follow the checkout directory: jax keeps
            # its own source metadata out of the key, but a Pallas
            # kernel's Mosaic payload embeds source locations as absolute
            # paths — make paths under the checkout relative (PERF.md,
            # PR 23: 0 hits from a second checkout of the same code)
            jax.config.update("jax_hlo_source_file_canonicalization_regex",
                              re.escape(_CHECKOUT + os.sep))
        _STATE["active_dir"] = d
        _install_listener()
        return d


def reset():
    """Forget this process's init state (tests).  Does not clear disk."""
    with _LOCK:
        _STATE["initialized"] = False
        _STATE["active_dir"] = None
