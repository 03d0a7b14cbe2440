"""Collective wrappers — the NCCL/ps-lite API analogue over XLA.

Ref mapping (SURVEY.md §2.3): ncclAllReduce/tree-reduce (src/kvstore/comm.h,
comm_tree.h, gpu_topology.h) → lax.psum over a mesh axis; ps-lite ZPush/ZPull
→ nothing (SPMD replaces the server). These helpers are valid *inside*
shard_map/pjit-traced functions; the hand-built PCIe spanning trees of the
reference are replaced by XLA's ICI routing.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
from jax import lax

from .. import telemetry as _tel

__all__ = ["all_reduce", "all_gather", "reduce_scatter", "ppermute",
           "ring_all_gather", "broadcast_from", "barrier", "axis_index",
           "axis_size"]

AxisName = Union[str, Sequence[str]]


def _note(op: str, x):
    """Per-collective call + byte accounting.  These helpers run inside
    shard_map/pjit TRACES, so the counters tick once per (re)trace, not
    once per executed step — they answer "which collectives does this
    graph contain and how big are they", the input the sharding PRs
    (PAPERS: cross-replica weight-update sharding) steer by."""
    if not _tel._ENABLED:
        return
    try:
        n = 1
        for d in x.shape:
            n *= int(d)
        nbytes = n * x.dtype.itemsize
    except (AttributeError, TypeError):
        nbytes = 0
    _tel.inc(f"collectives.{op}_calls")
    _tel.inc(f"collectives.{op}_bytes", nbytes)


def all_reduce(x, axis_name: AxisName = "dp", op: str = "sum"):
    """≈ ncclAllReduce (src/kvstore/kvstore_nccl.h)."""
    _note("all_reduce", x)
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown all_reduce op {op}")


def all_gather(x, axis_name: AxisName = "dp", axis: int = 0, tiled: bool = True):
    _note("all_gather", x)
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: AxisName = "dp", axis: int = 0):
    _note("reduce_scatter", x)
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def ppermute(x, perm, axis_name: AxisName = "sp"):
    """Neighbor exchange — the ring-attention building block."""
    _note("ppermute", x)
    return lax.ppermute(x, axis_name, perm)


def ring_all_gather(x, axis_name: str = "dp", axis: int = 0):
    """AllGather decomposed into ``size-1`` neighbor hops (ppermute ring),
    per "Memory-efficient array redistribution through portable collective
    communication" (PAPERS.md): each hop moves ONE shard-sized buffer, so
    peak per-hop bytes stay ``total/size`` instead of the full gather, and
    no blocking ``all-gather`` op ever appears in the executable — the
    form the X007 lint contract (``async_required``) accepts on backends
    without async collective pairs.  Valid inside shard_map; returns the
    concatenation of every member's ``x`` along ``axis``, identical on
    all members."""
    _note("ring_all_gather", x)
    size = axis_size(axis_name)
    if size == 1:
        return x
    idx = lax.axis_index(axis_name)
    shape = list(x.shape)
    out = jax.numpy.zeros([size] + shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, idx, 0)
    perm = [(i, (i + 1) % size) for i in range(size)]
    recv = x
    for h in range(1, size):
        recv = lax.ppermute(recv, axis_name, perm)
        out = lax.dynamic_update_index_in_dim(
            out, recv, (idx - h) % size, 0)
    # (size, ..., d_axis, ...) -> concat along `axis`
    out = jax.numpy.moveaxis(out, 0, axis)
    shape[axis] *= size
    return out.reshape(shape)


def broadcast_from(x, axis_name: AxisName = "dp", src: int = 0):
    """≈ KVStore broadcast (comm.h Broadcast): take src's value everywhere."""
    _note("broadcast_from", x)
    idx = lax.axis_index(axis_name)
    masked = jax.numpy.where(idx == src, x, jax.numpy.zeros_like(x))
    return lax.psum(masked, axis_name)


def barrier(axis_name: AxisName = "dp"):
    """Synchronization fence (≈ engine WaitForAll across ranks)."""
    if _tel._ENABLED:
        _tel.inc("collectives.barrier_calls")
    return lax.psum(jax.numpy.ones(()), axis_name)


def axis_index(axis_name: AxisName = "dp"):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str = "dp"):
    return lax.axis_size(axis_name)
