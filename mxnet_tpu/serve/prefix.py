"""Prefix-trie KV-cache reuse across decode requests (docs/serving.md,
"Prefix cache").

System-prompt-heavy traffic re-prefills the same leading tokens for
every request.  Causality makes that work reusable: a transformer KV
page at position ``p`` depends only on tokens ``<= p``, so the cache
pages of a shared prompt *prefix* are identical across requests and can
be copied instead of recomputed.  This module keeps those pages in a
trie keyed on BLOCK-ALIGNED token chunks (``block`` tokens per node —
aligned to the attention kv block granularity so a hit's page window
tiles the flash-decode kernel's skip logic):

* :meth:`PrefixCache.lookup` walks the trie over a prompt's full
  blocks and returns the longest retained prefix — capped one token
  short of the prompt, because the *next-token logits* still need at
  least one real forward;
* :meth:`PrefixCache.materialize` scatters the matched nodes' pages
  into a fresh row cache at the requested capacity bucket via
  :func:`mxnet_tpu.parallel.layout.scatter_into` — the same
  slice-mapping the checkpoint reshard reader uses, with trie nodes as
  the source layout;
* :meth:`PrefixCache.insert` retains the full blocks of a finished
  prefill (host copies, sliced straight off the returned row cache's
  page axis) — existing nodes are skipped, identical by causality.

Eviction is LRU over CHILDLESS nodes (an interior node's pages stay
reachable only through its children, so leaves go first), driven by a
byte budget: ``MXNET_PREFIX_CACHE_BYTES`` (default 64 MiB; 0 disables
retention entirely).  Capacity-independent caches (the LSTM carrier:
one recurrent state, no per-position pages) cannot be sliced by prefix,
so the decode tier disables the cache for those models.

Telemetry (docs/telemetry.md): ``serve.cache_hits`` /
``serve.cache_misses`` / ``serve.cache_evictions`` counters,
``serve.cache_hit_tokens`` (prefill tokens skipped), and the
``serve.cache_bytes`` gauge.  Trace: the decode tier records a
``serve.prefix_hit`` instant per hit.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as onp

from .. import telemetry as _tel
from ..analysis import thread_check as _tchk
from ..base import MXNetError, get_env
from ..ndarray.ndarray import NDArray
from ..parallel import layout as _layout

__all__ = ["PrefixCache"]


class _Node:
    """One trie node: ``block`` tokens' worth of KV pages, per layer a
    tuple of host ``(1, H, block, d)`` arrays, one per cache leaf (the
    transformer's payload leaf is K‖V on the last axis)."""

    __slots__ = ("key", "parent", "children", "pages", "nbytes", "tick")

    def __init__(self, key, parent, pages, nbytes, tick):
        self.key = key
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.pages = pages
        self.nbytes = nbytes
        self.tick = tick


class PrefixCache:
    """Block-aligned prefix trie over prompt token ids (module
    docstring).  All methods are thread-safe: N prefill workers look
    up/insert concurrently under one named lock."""

    def __init__(self, block: int = 8, max_bytes: Optional[int] = None,
                 name: str = "default"):
        if block < 1:
            raise MXNetError(f"prefix block must be >= 1, got {block}")
        self.block = int(block)
        self.max_bytes = int(
            get_env("MXNET_PREFIX_CACHE_BYTES", 64 << 20, int)
            if max_bytes is None else max_bytes)
        self.name = name
        self._lock = _tchk.lock(f"serve.prefix.{name}")
        self._children: Dict[Tuple[int, ...], _Node] = {}  # root level
        self._nodes: List[_Node] = []
        self._bytes = 0
        self._tick = 0
        self._hits = 0
        self._misses = 0
        self._evictions = 0

    # ------------------------------------------------------------ lookup
    def lookup(self, tokens: Sequence[int]
               ) -> Tuple[int, List[_Node]]:
        """Longest retained block-aligned prefix of ``tokens``: returns
        ``(matched_len, nodes)`` with ``matched_len`` a multiple of
        ``block`` and strictly less than ``len(tokens)`` (at least one
        token is always left to forward — its logits seed generation).
        Ticks ``serve.cache_{hits,misses}``; touches the matched chain's
        LRU clocks."""
        toks = [int(t) for t in tokens]
        max_blocks = max(0, (len(toks) - 1)) // self.block
        chain: List[_Node] = []
        with self._lock:
            self._tick += 1
            level = self._children
            for i in range(max_blocks):
                key = tuple(toks[i * self.block:(i + 1) * self.block])
                node = level.get(key)
                if node is None:
                    break
                node.tick = self._tick
                chain.append(node)
                level = node.children
            matched = len(chain) * self.block
            if matched:
                self._hits += 1
            else:
                self._misses += 1
        if _tel._ENABLED:
            if matched:
                _tel.inc("serve.cache_hits")
                _tel.inc("serve.cache_hit_tokens", matched)
            else:
                _tel.inc("serve.cache_misses")
        return matched, chain

    # ------------------------------------------------------- materialize
    def materialize(self, chain: Sequence[_Node], capacity: int):
        """Assemble the matched chain into a fresh row cache at
        ``capacity``: per layer a tuple of zeroed ``(1, H, capacity, d)``
        leaves with each node's pages scattered at its block offset — node
        boxes are the source layout, the capacity bucket the target box
        (:func:`~mxnet_tpu.parallel.layout.scatter_into`).  Returns the
        NDArray cache tree the LM forward consumes."""
        if not chain:
            raise MXNetError("materialize() needs a non-empty match chain")
        matched = len(chain) * self.block
        if matched > capacity:
            raise MXNetError(
                f"matched prefix ({matched} tokens) exceeds capacity "
                f"bucket {capacity}")
        out = []
        for layer, pair in enumerate(chain[0].pages):
            bufs = []
            for kv in range(len(pair)):
                template = chain[0].pages[layer][kv]
                _b, h, _blk, dh = template.shape
                buf = onp.zeros((1, h, capacity, dh), template.dtype)
                tbox = ((0, 1), (0, h), (0, capacity), (0, dh))
                # the chain tiles [0, matched) contiguously: one
                # concatenated source box per leaf, not one per node
                sbox = ((0, 1), (0, h), (0, matched), (0, dh))
                _layout.scatter_into(
                    buf, tbox, sbox,
                    onp.concatenate(
                        [n.pages[layer][kv] for n in chain], axis=2))
                bufs.append(NDArray(jnp.asarray(buf)))
            out.append(tuple(bufs))
        return tuple(out)

    # ------------------------------------------------------------ insert
    def insert(self, tokens: Sequence[int], cache, valid_len: int) -> int:
        """Retain the full blocks of a finished prefill: ``cache`` is
        the LM's returned row cache tree (per layer a tuple of 4-D page
        leaves ``(1, H, C, d)``, capacity on axis 2), valid through
        ``valid_len`` positions.  Pages are host-copied per block; nodes
        already present are skipped (identical by causality).  Returns
        the number of NEW nodes, after evicting LRU childless nodes down
        to the byte budget."""
        if self.max_bytes <= 0:
            return 0
        toks = [int(t) for t in tokens]
        n_blocks = min(len(toks), int(valid_len)) // self.block
        if n_blocks == 0:
            return 0
        # host-fetch each leaf once, slice per block below
        leaves = [[onp.asarray(l._data if isinstance(l, NDArray) else l)
                   for l in pair] for pair in cache]
        if any(a.ndim != 4 for pair in leaves for a in pair):
            raise MXNetError(
                "prefix cache needs (1, H, C, d) page-layout leaves — "
                "capacity-independent caches cannot be prefix-sliced")
        created = 0
        with self._lock:
            self._tick += 1
            level = self._children
            parent: Optional[_Node] = None
            for i in range(n_blocks):
                key = tuple(toks[i * self.block:(i + 1) * self.block])
                node = level.get(key)
                if node is None:
                    pages = tuple(
                        tuple(onp.ascontiguousarray(
                            a[:, :, i * self.block:(i + 1) * self.block, :])
                            for a in pair)
                        for pair in leaves)
                    nbytes = sum(a.nbytes for pair in pages for a in pair)
                    node = _Node(key, parent, pages, nbytes, self._tick)
                    level[key] = node
                    self._nodes.append(node)
                    self._bytes += nbytes
                    created += 1
                else:
                    node.tick = self._tick
                parent = node
                level = node.children
            evicted = self._evict_locked()
        if _tel._ENABLED:
            if evicted:
                _tel.inc("serve.cache_evictions", evicted)
            _tel.set_gauge("serve.cache_bytes", self._bytes)
        return created

    def _evict_locked(self) -> int:
        """Drop LRU childless nodes until the byte budget holds."""
        evicted = 0
        while self._bytes > self.max_bytes:
            victim = None
            for node in self._nodes:
                if node.children:
                    continue
                if victim is None or node.tick < victim.tick:
                    victim = node
            if victim is None:
                break
            siblings = (victim.parent.children if victim.parent is not None
                        else self._children)
            siblings.pop(victim.key, None)
            self._nodes.remove(victim)
            self._bytes -= victim.nbytes
            self._evictions += 1
            evicted += 1
        return evicted

    # ------------------------------------------------------------- stats
    def stats(self) -> dict:
        with self._lock:
            total = self._hits + self._misses
            return {"nodes": len(self._nodes), "bytes": self._bytes,
                    "max_bytes": self.max_bytes, "block": self.block,
                    "hits": self._hits, "misses": self._misses,
                    "evictions": self._evictions,
                    "hit_rate": (self._hits / total) if total else 0.0}

    def clear(self):
        with self._lock:
            self._children.clear()
            self._nodes.clear()
            self._bytes = 0
        if _tel._ENABLED:
            _tel.set_gauge("serve.cache_bytes", 0)
