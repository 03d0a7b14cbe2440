"""Flat-arena fused optimizer update — one Pallas kernel per step.

Stack-based optimizer fusion (per-step ``jnp.stack`` copies of every
parameter group) cost more compile time and memory traffic than the
fused kernel saved.  This module is the design that sidesteps that:

  * parameters are **never packed** — the weight-decay/clip fold and the
    final ``w + delta`` application are per-leaf elementwise ops XLA
    fuses into the backward and the slice reads;
  * optimizer **state lives as one flat arena per slot** (momentum arena,
    adam m/v arenas), created once and donated through the step — no
    per-step re-pack, ever;
  * gradients are raveled into one arena (the single concatenate in the
    step HLO), and ONE ``pallas_call`` runs the optimizer math for every
    parameter at once — O(1) kernels per step instead of O(#params)
    kernel replays or O(#shapes) vmap groups.

The kernel is purely elementwise, which is what makes arbitrary leaf
boundaries (and ZeRO-1 shard boundaries — the arena shards evenly over
``dp`` regardless of where leaves fall) safe: sgd / momentum(+nesterov) /
adam touch each element independently.  Norm-based optimizers (LAMB,
LARS) need per-tensor reductions and stay on the per-param adapter.

Zero padding (arena tail, ZeRO-1 alignment) is inert: zero grads keep
zero state and produce zero delta for every supported variant — the same
invariant the PR-6 zero1 padding relies on.

Math matches the imperative kernels in ``optimizer/__init__.py``
(``_sgd_kernel`` / ``_adam_kernel``) operation-for-operation, so
sgd/momentum parity with the per-param adapter is few-ULP and adam-family
parity is at worst reassociation-level (fusion order), asserted in
tests/test_kernels.py.
"""
from __future__ import annotations

import functools
import math
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import registry as _registry

__all__ = ["ArenaLayout", "build_layout", "bucket_layouts", "arena_update",
           "VARIANT_STATES", "LANES"]

LANES = 128          # TPU lane width: the arena is viewed as (rows, 128)
_BLOCK_ROWS = 64     # rows per kernel block -> 8192 elements per program
_MIN_ROWS = 8        # f32 sublane tile: the smallest block a segment takes

# state arenas per variant (momentum arena; adam m/v arenas)
VARIANT_STATES = {"sgd": 0, "momentum": 1, "adam": 2}


class ArenaLayout(NamedTuple):
    """Per-leaf offsets into the flat arena.

    ``padded`` is the arena length: total rounded up so it (a) views as
    whole ``(rows, LANES)`` blocks of ``_BLOCK_ROWS`` rows and (b) shards
    evenly over ``shard_multiple`` (the ZeRO-1 ``dp`` degree) into
    segments of whole ``(_MIN_ROWS, LANES)`` tiles."""

    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    total: int
    padded: int


def build_layout(shapes: Sequence[Tuple[int, ...]],
                 shard_multiple: int = 1) -> ArenaLayout:
    offsets, sizes = [], []
    off = 0
    for s in shapes:
        n = 1
        for d in s:
            n *= int(d)
        offsets.append(off)
        sizes.append(n)
        off += n
    # under a mesh the kernel runs per device on its own segment (Mosaic
    # kernels are not auto-partitioned, registry.batch_mesh), so every
    # one of the ``shard_multiple`` segments is whole sublane tiles
    m = math.lcm(_BLOCK_ROWS * LANES, _MIN_ROWS * LANES * shard_multiple)
    padded = max(m, -(-off // m) * m)
    return ArenaLayout(tuple(offsets), tuple(sizes),
                       tuple(tuple(int(d) for d in s) for s in shapes),
                       off, padded)


def bucket_layouts(shapes: Sequence[Tuple[int, ...]],
                   bucket_bytes: int, shard_multiple: int = 1,
                   itemsize: int = 4
                   ) -> Tuple[Tuple[Tuple[int, ...], ...],
                              Tuple[ArenaLayout, ...]]:
    """Partition leaves into size-bounded buckets, one ``ArenaLayout``
    per bucket — the grad-flush grouping of the collective/compute
    overlap path (docs/sharding.md "Latency hiding").

    Leaves are walked in REVERSE declaration order: backward produces the
    LAST layers' gradients first, so reverse-order buckets close (and
    their collective chains issue) while earlier layers' backward is
    still running.  A bucket closes when adding the next leaf would push
    it past ``bucket_bytes`` (a single over-sized leaf gets its own
    bucket).  Returns ``(buckets, layouts)`` where ``buckets[b]`` is the
    tuple of ORIGINAL leaf indices in bucket ``b`` and ``layouts[b]`` is
    its arena layout (padded to the ``shard_multiple`` / block grid like
    any arena, so bucket arenas stay kernel- and ZeRO-shard-ready)."""
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got "
                         f"{bucket_bytes}")
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    for i in reversed(range(len(shapes))):
        n = 1
        for d in shapes[i]:
            n *= int(d)
        b = n * itemsize
        if cur and cur_bytes + b > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += b
    if cur:
        buckets.append(cur)
    layouts = tuple(build_layout([shapes[i] for i in bk],
                                 shard_multiple=shard_multiple)
                    for bk in buckets)
    return tuple(tuple(bk) for bk in buckets), layouts


def _arena_kernel(sc_ref, g_ref, *rest, variant: str, momentum: float,
                  nesterov: bool, beta1: float, beta2: float, eps: float):
    """Elementwise optimizer math over one (block_rows, LANES) tile.

    ``sc_ref`` (SMEM) carries the traced scalars: lr, and for adam the
    bias-correction denominators (1-b1^t, 1-b2^t) — computed outside so
    the op sequence matches ``_adam_kernel`` exactly.  Weight decay and
    gradient clipping are folded into ``g`` per-leaf BEFORE packing (they
    read the parameter value, which never enters the arena)."""
    lr = sc_ref[0, 0]
    g = g_ref[...]
    if variant == "sgd":
        (d_ref,) = rest
        d_ref[...] = -(lr * g)
    elif variant == "momentum":
        m_ref, d_ref, m_out = rest
        m = momentum * m_ref[...] - lr * g
        m_out[...] = m
        d_ref[...] = momentum * m - lr * g if nesterov else m
    elif variant == "adam":
        m_ref, v_ref, d_ref, m_out, v_out = rest
        c1 = sc_ref[0, 1]          # 1 - beta1**t
        c2 = sc_ref[0, 2]          # 1 - beta2**t
        m = beta1 * m_ref[...] + (1 - beta1) * g
        v = beta2 * v_ref[...] + (1 - beta2) * jnp.square(g)
        mhat = m / c1
        vhat = v / c2
        m_out[...] = m
        v_out[...] = v
        d_ref[...] = -(lr * mhat / (jnp.sqrt(vhat) + eps))
    else:  # pragma: no cover - guarded by VARIANT_STATES at the adapter
        raise ValueError(f"unknown arena variant {variant!r}")


def arena_update(variant: str, garena, states: List, lr, t, *,
                 momentum: float = 0.0, nesterov: bool = False,
                 beta1: float = 0.9, beta2: float = 0.999,
                 eps: float = 1e-8, interpret: bool = False):
    """Run the fused update: ``(delta_arena, new_state_arenas)``.

    ``garena``/``states`` are flat f32 arrays of the layout's ``padded``
    length (wd/clip already folded into the gradient per-leaf); ``lr`` and
    ``t`` are traced scalars.  State arenas are aliased input→output
    (donated in place on TPU).  The caller applies ``w + delta`` per leaf.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_state = VARIANT_STATES[variant]
    if len(states) != n_state:
        raise ValueError(f"variant {variant!r} expects {n_state} state "
                         f"arenas, got {len(states)}")
    padded = garena.shape[0]
    rows = padded // LANES
    # a whole arena is whole _BLOCK_ROWS blocks; a device's segment of a
    # sharded one may only be whole _MIN_ROWS tiles — take the largest
    # block that divides it
    block_rows = _registry.pick_block(rows, (_BLOCK_ROWS, 32, 16, _MIN_ROWS))
    if padded % LANES or not block_rows:
        raise ValueError(f"arena length {padded} is not a whole number of "
                         f"({_MIN_ROWS}, {LANES}) tiles — use build_layout")
    lr = jnp.asarray(lr, jnp.float32)
    if variant == "adam":
        tf = jnp.asarray(t, jnp.float32)
        scalars = jnp.stack([lr, 1.0 - jnp.float32(beta1) ** tf,
                             1.0 - jnp.float32(beta2) ** tf])
    else:
        scalars = jnp.stack([lr, jnp.float32(0), jnp.float32(0)])
    scalars = scalars.reshape(1, 3)

    g2 = garena.reshape(rows, LANES)
    st2 = [s.reshape(rows, LANES) for s in states]

    blk = pl.BlockSpec((block_rows, LANES), lambda r: (r, 0))
    sc_spec = pl.BlockSpec((1, 3), lambda r: (0, 0),
                           memory_space=pltpu.SMEM)
    f32 = jax.ShapeDtypeStruct((rows, LANES), jnp.float32)
    kernel = functools.partial(
        _arena_kernel, variant=variant, momentum=float(momentum),
        nesterov=bool(nesterov), beta1=float(beta1), beta2=float(beta2),
        eps=float(eps))
    # alias state inputs onto state outputs (outputs are [delta, *states]):
    # the persistent arenas update in place instead of allocating fresh
    # HBM every step — the "donated state arena" in the ISSUE design
    aliases = {2 + i: 1 + i for i in range(n_state)}
    out = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[sc_spec, blk] + [blk] * n_state,
        out_specs=[blk] * (1 + n_state),
        out_shape=[f32] * (1 + n_state),
        input_output_aliases=aliases,
        compiler_params=_registry.tpu_compiler_params(("arbitrary",)),
        interpret=interpret,
        name="opt_arena",
    )(scalars, g2, *st2)
    delta = out[0].reshape(padded)
    new_states = [o.reshape(padded) for o in out[1:]]
    return delta, new_states
