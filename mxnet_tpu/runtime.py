"""Runtime feature detection (ref: python/mxnet/runtime.py + src/libinfo.cc).

The reference exposes compile-time feature bits (CUDA, MKLDNN, ...);
here features reflect the live JAX/PJRT environment.
"""
from __future__ import annotations

from typing import List


class Feature:
    def __init__(self, name: str, enabled: bool):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def feature_list() -> List[Feature]:
    import jax

    feats = []
    try:
        platforms = {d.platform for d in jax.devices()}
    except RuntimeError:
        platforms = set()
    feats.append(Feature("TPU", any(p not in ("cpu",) for p in platforms)))
    feats.append(Feature("CPU", True))
    feats.append(Feature("CUDA", False))   # by design: zero CUDA calls
    feats.append(Feature("XLA", True))
    feats.append(Feature("PALLAS", True))
    feats.append(Feature("BF16", True))
    feats.append(Feature("INT64_TENSOR_SIZE", True))
    feats.append(Feature("DIST", jax.process_count() > 1))
    feats.append(Feature("SHARD_MAP", True))   # jax.shard_map
    return feats


class Features(dict):
    def __init__(self):
        super().__init__([(f.name, f) for f in feature_list()])

    def is_enabled(self, name: str) -> bool:
        f = self.get(name.upper())
        return bool(f and f.enabled)


def libinfo_features():
    return feature_list()
