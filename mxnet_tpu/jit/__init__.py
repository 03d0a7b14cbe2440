"""mx.jit — compile-cost control: persistent cache, bucketing, warmup.

XLA compilation is the dominant fixed cost of the TPU path (a minute
or more for a whole train step), and any variable-shape
workload re-pays it mid-run.  This package attacks compile cost on
three coordinated fronts (docs/jit.md):

* :mod:`~mxnet_tpu.jit.cache` — persistent on-disk compilation cache
  (``JAX_COMPILATION_CACHE_DIR`` when set, else
  ``<checkout>/.jax_cache``): a second process of the same model skips XLA compilation entirely.
  Armed lazily at the first ``_CachedOp`` / ``make_train_step``
  compile; ``MXNET_COMPILE_CACHE=0`` disables.
* :class:`ShapeBucketer` — pad variable shapes up to a bounded bucket
  set (explicit / pow2 / linear policies) with validity masks, at both
  seams: ``DataLoader(bucket_spec=...)`` (host-side, before prefetch)
  and ``net.hybridize(bucketer=...)`` (eager callers; outputs sliced
  back transparently).  A shape storm becomes at most ``len(buckets)``
  compiles.
* AOT warmup — ``HybridBlock.warmup(...)`` and
  ``ShardedTrainer.compile(batch)`` compile every bucket up front
  (optionally on a background thread overlapping data-pipeline start)
  so the first real step runs at steady-state speed.
"""
from . import bucketing
from . import cache
from .bucketing import ShapeBucketer
from .cache import cache_dir, enabled as persistent_cache_enabled, \
    ensure_cache, is_active as persistent_cache_active

__all__ = ["bucketing", "cache", "ShapeBucketer", "cache_dir",
           "ensure_cache", "persistent_cache_enabled",
           "persistent_cache_active"]
