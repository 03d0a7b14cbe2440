"""SPMD training: pjit train-step builder + ShardedTrainer.

This is the TPU-native replacement for the reference's distributed training
stack (Trainer.step → KVStore push/pull → NCCL/ps-lite, SURVEY.md §3.4):
one jitted SPMD step over a Mesh — batch sharded on 'dp', parameters
replicated (DP), sharded per rules ('fsdp'/'tp'), XLA emits the gradient
AllReduce over ICI that KVStoreNCCL hand-coded. The gluon net's forward is
lifted functionally with the same state-swap + mutation-capture protocol as
HybridBlock's cached op, so BatchNorm stats and the RNG advance correctly.
"""
from __future__ import annotations

import functools
import os as _os
import time as _time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import engine as _engine
from .. import telemetry as _tel
from ..analysis import xla_lint as _xlint
from ..trace import recorder as _tr
from ..base import MXNetError
from ..gluon import block as _blk
from ..jit import cache as _jit_cache
from ..ndarray.ndarray import NDArray, _mutation_scope
from .. import autograd as _autograd

__all__ = ["shard_params", "make_train_step", "ShardedTrainer",
           "fsdp_spec_fn", "replicated_spec_fn", "mp_spec_fn"]

PARTITIONS = ("replicated", "zero1")


def _prod(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def replicated_spec_fn(name: str, shape) -> P:
    """Pure DP: every parameter replicated (ref KVStore broadcast model)."""
    return P()


def fsdp_spec_fn(axis: str = "dp", min_size: int = 2 ** 16):
    """ZeRO-3 style: shard the largest dim of big params over ``axis``
    (capability beyond the reference — SURVEY.md §5 gap list)."""

    def fn(name: str, shape) -> P:
        if not shape or _prod(shape) < min_size:
            return P()
        big = max(range(len(shape)), key=lambda i: shape[i])
        spec = [None] * len(shape)
        spec[big] = axis
        return P(*spec)

    return fn


def mp_spec_fn(axis: str = "mp", min_size: int = 2 ** 12,
               row_patterns: Tuple[str, ...] = ("proj", "ffn2", "ffn_2",
                                                "out", "down")):
    """Megatron-style tensor model parallelism over mesh axis ``axis``.

    Dense weights are ``(out_units, in_units)``: the default is
    column-parallel (shard the output dim — QKV projections, FFN-up), and
    weights whose name matches a ``row_patterns`` substring are
    row-parallel (shard the input dim — attention output projection,
    FFN-down), so a column→row pair contracts over the sharded hidden dim
    and XLA inserts ONE activation psum per pair instead of gathering
    weights. 1-D params (biases, norms) and small weights stay replicated.
    Dims the mesh axis cannot divide are replicated by ``shard_params``'s
    divisibility sanitizer, so this spec_fn is safe on any net."""

    def fn(name: str, shape) -> P:
        if len(shape) < 2 or _prod(shape) < min_size:
            return P()
        j = 1 if any(p in name for p in row_patterns) else 0
        spec = [None] * len(shape)
        spec[j] = axis
        return P(*spec)

    return fn


def _axis_size(mesh: Mesh, name) -> int:
    """Device count behind one PartitionSpec entry (str or tuple of str)."""
    names = name if isinstance(name, tuple) else (name,)
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size


def _sanitize_spec(mesh: Mesh, spec: P, shape) -> P:
    """Drop spec entries the array's dims cannot divide evenly.

    jax (0.4.x) rejects uneven ``device_put`` placements outright, so a
    heuristic spec_fn (mp/fsdp) meeting an odd-shaped param must degrade
    to replication on that dim instead of crashing trainer construction."""
    entries = tuple(spec)[:len(shape)]
    out = []
    for i, s in enumerate(entries):
        if s is not None and shape[i] % _axis_size(mesh, s):
            s = None
        out.append(s)
    return P(*out)


def shard_params(net, mesh: Mesh, spec_fn: Callable = replicated_spec_fn):
    """Place a gluon net's parameters onto the mesh per spec_fn.

    Returns (names, param_arrays, specs). Specs are sanitized against the
    mesh (non-divisible dims replicate, see _sanitize_spec)."""
    params = {n: p for n, p in net.collect_params().items() if p._data is not None}
    names = sorted(params)
    specs = []
    vals = []
    # under the trace guard: placing params while a background warmup
    # trace has them swapped to tracers would device_put a tracer
    with _blk.trace_guard():
        for n in names:
            v = params[n].data()._data
            spec = _sanitize_spec(mesh, spec_fn(n, v.shape), v.shape)
            sharded = jax.device_put(v, NamedSharding(mesh, spec))
            params[n].data()._set_data(sharded)
            specs.append(spec)
            vals.append(sharded)
    return names, vals, specs


# -- ZeRO-1 sharded weight update ---------------------------------------------
#
# "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
# Training" (PAPERS.md): with parameters replicated over the data axis, the
# optimizer update is redundantly identical on every replica — the update
# FLOPs and the optimizer state can be divided across 'dp' with no change
# to the math.  Expressed in GSPMD annotations: the gradient is
# with_sharding_constraint'd onto a dp-sharded layout (XLA turns the grad
# AllReduce into ReduceScatter), the optimizer state LIVES dp-sharded
# (NamedSharding at init — the memory win), the update computes
# shard-locally, and the output constraint back to the replicated param
# placement becomes the AllGather ("Memory-efficient array redistribution",
# PAPERS.md, gives the decomposition).
#
# jax 0.4.x only places evenly divisible shards, so each leaf picks one
# free dim and PADS it up to a multiple of dp inside the step (zeros —
# padding is invisible to every registry optimizer: elementwise kernels
# update zeros to zeros, and LAMB/LARS per-tensor norms ignore zero tails).
# Params keep their true shape at the step boundary; only the persistent
# optimizer-state leaves are stored padded.


class Zero1Info(NamedTuple):
    """Per-trainable-param ZeRO-1 placement: shard ``axis``-th dim (padded
    ``size``→``padded``) with ``sharding``; None ⇒ param opted out."""

    axis: int
    size: int
    padded: int
    sharding: NamedSharding


def _zero1_infos(mesh: Mesh, dp_axis: str, tspecs: List[P], pvals,
                 min_size: Optional[int] = None) -> List[Optional[Zero1Info]]:
    """Choose the ZeRO-1 shard dim per trainable param.

    Prefers the free (un-sharded) dim with the least padding waste;
    params already sharded over ``dp_axis`` (fsdp) keep their placement
    (the ZeRO property already holds), and params below ``min_size``
    elements (MXNET_ZERO1_MIN_SIZE, default 2048) stay replicated — an
    all-gather per tiny bias costs more latency than it saves memory."""
    if dp_axis not in mesh.shape:
        raise MXNetError(f"partition='zero1' needs a {dp_axis!r} mesh axis; "
                         f"mesh has {tuple(mesh.axis_names)}")
    if min_size is None:
        min_size = int(_os.environ.get("MXNET_ZERO1_MIN_SIZE", "2048"))
    dp = mesh.shape[dp_axis]
    infos: List[Optional[Zero1Info]] = []
    for spec, p in zip(tspecs, pvals):
        entries = list(tuple(spec)) + [None] * (p.ndim - len(tuple(spec)))
        used = set()
        for s in entries:
            if s is not None:
                used.update(s if isinstance(s, tuple) else (s,))
        if p.ndim == 0 or dp_axis in used or _prod(p.shape) < min_size:
            infos.append(None)
            continue
        free = [j for j in range(p.ndim) if entries[j] is None]
        if not free:
            infos.append(None)
            continue
        # least relative padding waste: minimize ceil(d/dp)*dp / d
        j = min(free, key=lambda k: (-(-p.shape[k] // dp) * dp) / p.shape[k])
        padded = -(-p.shape[j] // dp) * dp
        entries[j] = dp_axis
        infos.append(Zero1Info(j, p.shape[j], padded,
                               NamedSharding(mesh, P(*entries))))
    return infos


def _pad_dim(v, axis: int, target: int):
    """Zero-pad ``axis`` up to ``target`` (identity when already there)."""
    if v.shape[axis] == target:
        return v
    pads = [(0, 0)] * v.ndim
    pads[axis] = (0, target - v.shape[axis])
    return jnp.pad(v, pads)


def _layout_mismatch_error(detail):
    """Optimizer-state layouts (per-param vs flat-arena, leaf arity,
    leaf rank) never reshard silently — shared by both restore paths
    (``load_states`` and the slice-wise ``load_state_shards``)."""
    return MXNetError(
        f"checkpoint optimizer state does not match this "
        f"trainer's layout ({detail}): it was saved under a "
        "different optimizer layout (per-param vs flat-arena) or "
        "optimizer — rebuild the trainer with the matching "
        "fused_opt / MXNET_KERNELS setting (docs/kernels.md)")


def _functional_apply(net, names: List[str], training: bool):
    """Lift net.forward to fn(param_vals, rng_key_val, *inputs) →
    (outputs..., new_rng, mutated_state...). Same protocol as
    gluon.block._CachedOp."""
    from ..random import key_holder

    params = net.collect_params()
    # state capture under the trace guard: a concurrent background
    # warmup trace (gluon.block) has these arrays swapped to tracers
    with _blk.trace_guard():
        arrs = [params[n].data() for n in names] + [key_holder()]
    holder: Dict[str, Any] = {}

    def fn(pvals, *xs):
        saved = [(a, a._data) for a in arrs]
        ms = _mutation_scope()
        try:
            with _autograd.pause(train_mode=training), ms:
                for a, v in zip(arrs, pvals):
                    a._data = v
                out = net.forward(*[NDArray(x) for x in xs])
            outs = out if isinstance(out, tuple) else (out,)
            state_ids = {id(a) for a in arrs}
            mutated = [(a, a._data) for (a, prev) in ms.mutated.values()
                       if id(a) in state_ids or not isinstance(prev, jax.core.Tracer)]
            holder["mutated_refs"] = [a for a, _ in mutated]
            holder["n_out"] = len(outs)
            return tuple(o._data for o in outs), tuple(v for _, v in mutated)
        finally:
            for a, v in saved:
                a._data = v
            for a, prev in ms.mutated.values():
                if not isinstance(prev, jax.core.Tracer):
                    a._data = prev

    return fn, arrs, holder


def _functional_apply_stages(net, names: List[str], stages, training: bool):
    """Per-stage functional forwards for the pipeline ('pp') axis: one fn
    per ``PipelineStage``, all sharing ``_functional_apply``'s state-swap
    protocol — ``stage_fns[k](all_param_vals, x)`` applies stage k's
    blocks in declaration order and returns the raw output array.

    Pipeline stages must be MUTATION-FREE: a BatchNorm running-stat or
    RNG-key advance would fire once per (micro-batch × schedule tick),
    outside the step's state accounting — enforced at trace time so the
    first compile fails loudly instead of training silently-wrong
    statistics."""
    from ..random import key_holder

    params = net.collect_params()
    with _blk.trace_guard():
        arrs = [params[n].data() for n in names] + [key_holder()]
    holder: Dict[str, Any] = {"mutated_refs": [], "n_out": 1}

    def make(k, blocks):
        def fn(pvals, x):
            saved = [(a, a._data) for a in arrs]
            ms = _mutation_scope()
            try:
                with _autograd.pause(train_mode=training), ms:
                    for a, v in zip(arrs, pvals):
                        a._data = v
                    h = NDArray(x)
                    for b in blocks:
                        h = b.forward(h)
                if ms.mutated:
                    raise MXNetError(
                        f"pipeline stage {k} mutated {len(ms.mutated)} "
                        "state array(s): the 'pp' axis needs "
                        "mutation-free forwards (BatchNorm running "
                        "stats / RNG draws update outside the GPipe "
                        "schedule — docs/sharding.md 'Pipeline axis')")
                return h._data
            finally:
                for a, v in saved:
                    a._data = v
                for a, prev in ms.mutated.values():
                    if not isinstance(prev, jax.core.Tracer):
                        a._data = prev

        return fn

    return [make(k, st.blocks) for k, st in enumerate(stages)], arrs, holder


# -- traced optimizer adapter (reuses the full 20-optimizer registry) --------
#
# Every imperative optimizer follows one shape: host bookkeeping
# (_update_count / _get_lr) + a pure jitted kernel over raw arrays behind
# NDArray handles (optimizer/__init__.py). Inside the pjit step we replay
# update() with lr and the update count t supplied as TRACED values (the
# kernels take them as regular arguments, so nothing bakes in), and thread
# the optimizer state through the step as flat raw-array lists.


class _TracedCounts(dict):
    """Stands in for Optimizer._index_update_count during tracing: every
    index reads the traced step counter."""

    def __init__(self, t):
        super().__init__()
        self._t = t

    def __getitem__(self, key):
        return self._t

    def setdefault(self, key, default=None):
        return self._t


# optimizers whose update() keeps host-side per-step state or data-dependent
# Python control flow — unreplayable inside a trace (nadam's m_schedule
# running product, lbsgd's warmup branch on t, sgld's host math.sqrt(lr) +
# per-call RNG draw). They stay available on the eager gluon.Trainer path.
_UNTRACEABLE_OPTIMIZERS = {"nadam", "lbsgd", "sgld"}


def _make_opt(optimizer, learning_rate, weight_decay, momentum, **extra):
    from .. import optimizer as opt_mod

    if isinstance(optimizer, opt_mod.Optimizer):
        opt = optimizer
    else:
        kwargs = dict(learning_rate=learning_rate, wd=weight_decay, **extra)
        if optimizer in ("sgd", "nag", "signum"):
            kwargs["momentum"] = momentum
        opt = opt_mod.create(optimizer, **kwargs)
    name = type(opt).__name__.lower()
    if name in _UNTRACEABLE_OPTIMIZERS:
        raise MXNetError(
            f"optimizer '{name}' keeps host-side per-step state or "
            "data-dependent control flow and cannot replay inside the "
            "jitted SPMD step; use it with gluon.Trainer (eager)")
    return opt


class _OptAdapter:
    """Functional bridge: init_state(pvals) → flat state leaves;
    update(pvals, grads, leaves, lr, t) → (new_pvals, new_leaves)."""

    def __init__(self, optimizer):
        self.opt = optimizer
        self._tree = None  # per-param state structure template

    @staticmethod
    def _flatten(state):
        if state is None:
            return []
        if isinstance(state, NDArray):
            return [state._data]
        if isinstance(state, (tuple, list)):
            out = []
            for s in state:
                out.extend(_OptAdapter._flatten(s))
            return out
        raise MXNetError(f"unsupported optimizer state leaf {type(state)}")

    @staticmethod
    def _rebuild(template, leaves_iter):
        if template is None:
            return None
        if isinstance(template, NDArray):
            return NDArray(next(leaves_iter))
        return tuple(_OptAdapter._rebuild(t, leaves_iter) for t in template)

    def init_state(self, pvals) -> List[Any]:
        self._tree = [self.opt.create_state(i, NDArray(p))
                      for i, p in enumerate(pvals)]
        leaves: List[Any] = []
        self.leaf_param_ix: List[int] = []  # leaf → owning param (sharding)
        # optimizers may alias one buffer across slots (Adam's (m, v) share
        # a zeros array; DCASGD's prev-weight IS the param array) — both
        # step args are donated, so every leaf needs a distinct buffer
        seen = {id(p) for p in pvals}
        for i, s in enumerate(self._tree):
            ls = self._flatten(s)
            for leaf in ls:
                if id(leaf) in seen:
                    leaf = jnp.array(leaf, copy=True)
                seen.add(id(leaf))
                leaves.append(leaf)
            self.leaf_param_ix.extend([i] * len(ls))
        return leaves

    def _traced_opt(self, lr, t):
        import copy

        opt = copy.copy(self.opt)
        opt.rescale_grad = 1.0  # scaling handled by the step
        opt.lr_scheduler = None
        opt.lr = lr                       # traced scalar
        opt._index_update_count = _TracedCounts(t)
        opt.num_update = 0                # only read host-side; unused here
        opt._update_count = lambda *a, **k: None
        return opt

    def _update_one(self, opt, i, p, g, st):
        w = NDArray(p)
        opt.update(i, w, NDArray(g.astype(p.dtype)), st)
        return w._data.astype(p.dtype), st

    def update(self, pvals, grads, leaves, lr, t):
        opt = self._traced_opt(lr, t)
        it = iter(leaves)
        new_p, new_leaves = [], []
        for i, (p, g) in enumerate(zip(pvals, grads)):
            st = self._rebuild(self._tree[i], it)
            np_, st = self._update_one(opt, i, p, g, st)
            new_p.append(np_)
            new_leaves.extend(self._flatten(st))
        return new_p, new_leaves


class _ArenaOptAdapter(_OptAdapter):
    """Flat-arena fused optimizer update — ONE Pallas kernel per step
    (mx.kernels.opt_arena, docs/kernels.md).

    Parameters are NEVER packed (no per-leaf ``jnp.stack``/concatenate of
    params in the step HLO — asserted by ``make kernels-smoke``).  The
    weight-decay/clip fold and the final ``w + delta`` application are
    per-leaf elementwise ops XLA fuses away; optimizer state lives as
    persistent flat arenas donated through the step; gradients ravel
    into one arena (the step's single concatenate) and one elementwise
    ``pallas_call`` runs the whole update.

    Supports the elementwise optimizers (sgd / momentum+nesterov / adam)
    with uniform lr/wd multipliers; norm-based or per-leaf-heterogeneous
    configurations stay on the per-param adapter (observable fallback).
    Under ``partition='zero1'`` the arenas shard evenly over ``dp`` —
    shard-local segments need no per-leaf padding because the update is
    elementwise, so leaf boundaries may fall anywhere."""

    def __init__(self, optimizer, kmode: str):
        super().__init__(optimizer)
        self._kmode = kmode
        self.layout = None
        self.arena_sharding = None   # set by ShardedTrainer under zero1
        self.mesh = None             # set by ShardedTrainer
        self._shard_multiple = 1     # dp degree the arena length aligns to
        name = type(optimizer).__name__
        if name in ("SGD", "NAG"):
            self.variant = "momentum" if getattr(optimizer, "momentum",
                                                 0.0) else "sgd"
            self._nesterov = name == "NAG"
        else:
            self.variant = "adam"
            self._nesterov = False

    @classmethod
    def supports(cls, opt) -> Tuple[bool, str]:
        """Whether ``opt`` can run as a flat-arena update, with the
        fallback reason when not.  Exact types only: subclasses (AdamW,
        Signum, ...) change the update math."""
        from ..optimizer import SGD, NAG, Adam

        if type(opt) not in (SGD, NAG, Adam):
            return False, (f"optimizer {type(opt).__name__} not "
                           "arena-fusible (elementwise sgd/momentum/adam "
                           "only)")
        if opt.lr_mult or opt.wd_mult:
            return False, "per-parameter lr/wd multipliers"
        for p in opt.param_dict.values():
            if getattr(p, "lr_mult", 1.0) != 1.0 or \
                    getattr(p, "wd_mult", 1.0) != 1.0:
                return False, "per-parameter lr/wd multipliers"
        return True, ""

    def init_state(self, pvals) -> List[Any]:
        from ..kernels import opt_arena as _oa

        for p in pvals:
            if jnp.dtype(p.dtype) != jnp.float32:
                raise MXNetError(
                    "arena optimizer update expects f32 parameters; got "
                    f"{p.dtype} (use fused_opt='off')")
        self.layout = _oa.build_layout(
            [tuple(p.shape) for p in pvals],
            shard_multiple=self._shard_multiple)
        n = _oa.VARIANT_STATES[self.variant]
        # arena leaves own no single param (leaf_param_ix is per-leaf in
        # the base adapters); ShardedTrainer special-cases the placement
        self.leaf_param_ix = [-1] * n
        self._tree = None
        return [jnp.zeros((self.layout.padded,), jnp.float32)
                for _ in range(n)]

    def update(self, pvals, grads, leaves, lr, t):
        from ..kernels import opt_arena as _oa
        from ..kernels import registry as _kreg

        opt = self.opt
        wd = float(opt.wd)
        clip = float(opt.clip_gradient) if opt.clip_gradient is not None \
            else -1.0
        lay = self.layout
        # per-leaf elementwise fold (reads the param value, which never
        # enters the arena): same op order as _sgd_kernel/_adam_kernel
        gs = []
        for p, g in zip(pvals, grads):
            g = g.astype(jnp.float32)
            if clip > 0:
                g = jnp.clip(g, -abs(clip), abs(clip))
            if wd:
                g = g + wd * p
            gs.append(g.ravel())
        garena = gs[0] if len(gs) == 1 else jnp.concatenate(gs)
        if lay.padded != lay.total:
            garena = jnp.pad(garena, (0, lay.padded - lay.total))
        if self.arena_sharding is not None:
            # zero1: pin the grad arena dp-sharded — the constraint turns
            # the gradient AllReduce into ReduceScatter ahead of the
            # shard-local kernel (same move as the per-leaf zero1 path)
            garena = jax.lax.with_sharding_constraint(
                garena, self.arena_sharding)
        kw = {}
        if self.variant == "momentum":
            kw = dict(momentum=float(opt.momentum),
                      nesterov=self._nesterov)
        elif self.variant == "adam":
            kw = dict(beta1=float(opt.beta1), beta2=float(opt.beta2),
                      eps=float(opt.epsilon))
        update = functools.partial(
            _oa.arena_update, self.variant,
            interpret=self._kmode == "interpret", **kw)
        if self.mesh is not None and self.mesh.size > 1:
            # Mosaic kernels are not auto-partitioned (kernels/registry.py
            # :batch_mesh): run the elementwise update per device on its
            # zero1 segment, or on the whole replicated arena
            seg = self.arena_sharding.spec if self.arena_sharding \
                is not None else P()
            update = jax.shard_map(
                update, mesh=self.mesh, in_specs=(seg, seg, P(), P()),
                out_specs=seg, check_vma=False)
        delta, new_leaves = update(garena, list(leaves), lr, t)
        _kreg.dispatched("opt_arena", self._kmode)
        new_p = [p + jax.lax.slice_in_dim(delta, off, off + size)
                 .reshape(shape)
                 for p, off, size, shape in
                 zip(pvals, lay.offsets, lay.sizes, lay.shapes)]
        return new_p, new_leaves


class _OverlapOptAdapter(_OptAdapter):
    """Bucketed collective/compute-overlap update under
    ``partition='zero1'`` (``overlap=True``; docs/sharding.md "Latency
    hiding").

    Gradients flush in REVERSE parameter order into size-bounded bucket
    arenas (``MXNET_OVERLAP_BUCKET_BYTES``, default 4 MiB; one
    ``ArenaLayout`` per bucket from ``mx.kernels.opt_arena
    .bucket_layouts`` — the PR-8 layout machinery), so the collective
    chain for the last layers' bucket issues while backward for the
    earlier layers is still running ("Automatic Cross-Replica Sharding
    of Weight Update in Data-Parallel Training", PAPERS.md).  Per
    bucket: the reduced grad arena is sliced to the device's ``dp``
    shard inside a manual shard_map, the registry optimizer's imperative
    kernel replays on the flat shard segment (elementwise ⇒ leaf and
    shard boundaries may fall anywhere — the flat-arena invariant), and
    the updated segment returns through a ppermute RING gather
    (``collectives.ring_all_gather``): per-hop buffers stay shard-sized
    ("Memory-efficient array redistribution", PAPERS.md) and the
    executable contains NO blocking reduce-scatter/all-gather — the
    X007 ``async_required`` lint contract, checkable even on backends
    that never emit ``-start/-done`` async pairs (XLA:CPU).

    Optimizer state lives as per-bucket dp-sharded flat arenas (the
    ZeRO-1 memory win, unchanged).  The same registry kernel replays
    elementwise on the reduced gradients, so given IDENTICAL gradients
    the sgd / momentum update is bit-exact against the per-leaf path
    (asserted in tests/test_trainer_overlap.py); full trajectories
    differ from classic zero1 only by gradient-reduction order
    (all-reduce here vs reduce-scatter there — ULP-level), gated at the
    SPMD tolerance by ``tools/spmd_smoke.py``."""

    def __init__(self, optimizer, bucket_bytes: Optional[int] = None):
        super().__init__(optimizer)
        if bucket_bytes is None:
            bucket_bytes = int(_os.environ.get(
                "MXNET_OVERLAP_BUCKET_BYTES", str(4 << 20)))
        self.bucket_bytes = int(bucket_bytes)
        self._shard_multiple = 1     # dp degree; set by ShardedTrainer
        self.mesh: Optional[Mesh] = None
        self.dp_axis = "dp"
        self.buckets: Tuple[Tuple[int, ...], ...] = ()
        self.layouts: Tuple[Any, ...] = ()
        self.leaf_layouts: List[Any] = []

    @classmethod
    def supports(cls, opt) -> Tuple[bool, str]:
        """Same fusibility set as the flat arena (elementwise
        sgd/momentum/adam with uniform multipliers): norm-based
        optimizers read per-tensor reductions that flat shard segments
        destroy."""
        return _ArenaOptAdapter.supports(opt)

    def init_state(self, pvals) -> List[Any]:
        from ..kernels import opt_arena as _oa

        for p in pvals:
            if jnp.dtype(p.dtype) != jnp.float32:
                raise MXNetError(
                    "overlap bucketed update expects f32 parameters; "
                    f"got {p.dtype} (drop overlap=True)")
        self.buckets, self.layouts = _oa.bucket_layouts(
            [tuple(p.shape) for p in pvals], self.bucket_bytes,
            shard_multiple=self._shard_multiple)
        self._btree: List[Any] = []
        self._bucket_nleaves: List[int] = []
        self.leaf_layouts = []
        leaves: List[Any] = []
        for b, lay in enumerate(self.layouts):
            tmpl = self.opt.create_state(
                b, NDArray(jnp.zeros((lay.padded,), jnp.float32)))
            self._btree.append(tmpl)
            ls = self._flatten(tmpl)
            self._bucket_nleaves.append(len(ls))
            for _ in ls:
                leaves.append(jnp.zeros((lay.padded,), jnp.float32))
                self.leaf_layouts.append(lay)
        self.leaf_param_ix = [-1] * len(leaves)
        self._tree = None
        return leaves

    def update(self, pvals, grads, leaves, lr, t):
        from jax import shard_map

        from . import collectives as _coll

        if self.mesh is None or self.dp_axis not in self.mesh.shape:
            raise MXNetError(
                "overlap adapter is unconfigured — ShardedTrainer sets "
                "mesh/dp_axis before the first trace (overlap=True needs "
                "ShardedTrainer, not a bare make_train_step)")
        ax = self.dp_axis
        new_p: List[Any] = [None] * len(pvals)
        new_leaves: List[Any] = []
        it = iter(leaves)
        for b, (idxs, lay) in enumerate(zip(self.buckets, self.layouts)):
            bl = [next(it) for _ in range(self._bucket_nleaves[b])]
            ps = [pvals[i].ravel() for i in idxs]
            gs = [grads[i].astype(jnp.float32).ravel() for i in idxs]
            parena = ps[0] if len(ps) == 1 else jnp.concatenate(ps)
            garena = gs[0] if len(gs) == 1 else jnp.concatenate(gs)
            if lay.padded != lay.total:
                parena = jnp.pad(parena, (0, lay.padded - lay.total))
                garena = jnp.pad(garena, (0, lay.padded - lay.total))
            # pin the arenas REPLICATED at the manual-region boundary:
            # otherwise GSPMD back-propagates the P(dp) in_spec through
            # the concat into the param leaves and re-GATHERS them at
            # every forward use — blocking all-gathers that X007's
            # async_required contract forbids.  Grads are replicated
            # after the dp all-reduce, so the constraint costs nothing.
            rep = NamedSharding(self.mesh, P())
            parena = jax.lax.with_sharding_constraint(parena, rep)
            garena = jax.lax.with_sharding_constraint(garena, rep)

            def seg_update(p_seg, g_seg, lr_, t_, *state_segs, _b=b):
                # shard-local replay of the registry kernel on this
                # device's flat segment; the padded tail is inert zeros
                # (zero grad keeps zero state, zero delta) — the PR-6
                # zero1 invariant
                opt = self._traced_opt(lr_, t_)
                st = self._rebuild(self._btree[_b], iter(state_segs))
                w = NDArray(p_seg)
                opt.update(_b, w, NDArray(g_seg), st)
                gathered = _coll.ring_all_gather(w._data, ax)
                return (gathered,) + tuple(self._flatten(st))

            n_st = self._bucket_nleaves[b]
            out = shard_map(
                seg_update, mesh=self.mesh,
                in_specs=(P(ax), P(ax), P(), P()) + (P(ax),) * n_st,
                out_specs=(P(),) + (P(ax),) * n_st,
                check_vma=False)(parena, garena, lr, t, *bl)
            new_leaves.extend(out[1:])
            for i, off, size, shape in zip(idxs, lay.offsets, lay.sizes,
                                           lay.shapes):
                new_p[i] = jax.lax.slice_in_dim(
                    out[0], off, off + size).reshape(shape)
        return new_p, new_leaves


def _pick_adapter(opt, fused_opt: Optional[str], all_f32: bool = True):
    """Adapter selection (docs/kernels.md): the flat arena when the
    kernels layer is active (``MXNET_KERNELS``), the optimizer is
    arena-fusible and every parameter is f32, else the per-parameter
    adapter.  ``fused_opt`` is the per-call override — ``"arena"``
    requires the arena (raises when unavailable), ``"off"`` pins the
    per-parameter adapter.  Every auto-path ineligibility — unfusible
    optimizer, per-leaf multipliers, non-f32 params — is an observable
    fallback, never an error."""
    from ..kernels import registry as _kreg

    if fused_opt not in (None, "arena", "off"):
        raise MXNetError(f"fused_opt={fused_opt!r} unknown; use None, "
                         "'arena' or 'off'")
    if fused_opt in (None, "arena"):
        kmode = _kreg.select("opt_arena")
        ok, reason = _ArenaOptAdapter.supports(opt)
        if ok and not all_f32:
            ok, reason = False, ("non-f32 parameters (the f32 arena "
                                 "would silently change update numerics)")
        if kmode and ok:
            return _ArenaOptAdapter(opt, kmode)
        if fused_opt == "arena":
            raise MXNetError(
                "fused_opt='arena' requested but unavailable: "
                + (reason or "kernels layer inactive (MXNET_KERNELS, "
                             "platform — see docs/kernels.md)"))
        if kmode and not ok:
            _kreg.fallback("opt_arena", reason)
    return _OptAdapter(opt)


def all_finite(grads):
    """Fused finiteness scan over a gradient list — the reference's
    all_finite op (src/operator/all_finite.cc) that drives dynamic loss
    scaling."""
    flags = [jnp.isfinite(jnp.sum(g.astype(jnp.float32))) for g in grads]
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_and(out, f)
    return out


def make_train_step(net, loss_fn, names: List[str],
                    optimizer="sgd", learning_rate: float = 0.01,
                    weight_decay: float = 0.0, momentum: float = 0.9,
                    donate: bool = True, compute_dtype=None,
                    loss_scale_growth_interval: int = 2000,
                    shardings_box=None,
                    partition: str = "replicated",
                    fused_opt: Optional[str] = None,
                    overlap: bool = False,
                    pipeline: Optional[Dict[str, Any]] = None,
                    loss_scaling: Any = "auto"):
    """Build the jitted SPMD train machinery. Returns
    (step, grad_fn, apply_fn, adapter, holder):

    step(tvals, avals, rng, opt_state, t, lr, scale_state, x, y)
        -> (tvals', mutated_state, opt_state', scale_state', loss)

    ``tvals`` are trainable parameter values (grad_req != 'null'); ``avals``
    are auxiliary state (BatchNorm running stats etc.) which is never
    differentiated or optimizer-updated — its new values come back through
    ``mutated_state``, exactly like the reference's aux-state split.
    ``lr`` is a traced scalar (LR schedules never recompile) and the
    optimizer can be ANY registry optimizer or Optimizer instance — its
    imperative update() replays inside the trace with traced lr/t
    (_OptAdapter).

    ``loss_scaling`` selects dynamic loss scaling (ref
    python/mxnet/amp/loss_scaler.py + all_finite op): ``"auto"`` enables
    it exactly for fp16 compute (bf16 carries fp32-range exponents and
    needs none by default), ``True``/``False`` force it on/off for any
    low-precision policy.  When active the loss is multiplied by
    scale_state[0] before the backward, gradients unscaled, and on
    overflow the update is skipped (per-leaf select), the scale halves
    and ``scale_state[2]`` (skipped-step count) ticks; after
    ``loss_scale_growth_interval`` clean steps the scale doubles.
    Unscaled steps run with the scale pinned at 1.

    bf16 without scaling is the AMP fast path: gradients LEAVE the
    backward in bf16 and ride the dp reduction at half the AllReduce
    bytes; every optimizer adapter casts them to f32 at update entry, so
    the master-weight update math is untouched (docs/precision.md).

    grad_fn/apply_fn split the step for gradient accumulation (micro-batch
    grads summed host-side between applies).

    Shardings are carried by the committed input arrays (shard_params /
    device_put in the caller); XLA inserts the gradient reduction over 'dp'
    (params replicated / sharded on non-dp axes ⇒ psum over ICI), replacing
    the reference's KVStore push/pull (trainer.py:363).

    ``partition`` selects the weight-update layout: ``"replicated"`` (every
    replica runs the full update — the reference model) or ``"zero1"``
    (reduce-scatter grads → shard-local update → all-gather params; the
    concrete per-param placements arrive via ``shardings_box["zero1"]`` /
    ``["opt_state"]``, filled by ShardedTrainer before the first trace —
    see the ZeRO-1 block comment above).

    ``fused_opt`` selects the optimizer-update implementation: ``None``
    auto-picks the flat-arena Pallas kernel when the kernels layer is
    active (``MXNET_KERNELS``, docs/kernels.md), ``"arena"`` requires it,
    ``"off"`` keeps the per-param replay.

    ``overlap=True`` (zero1 only) replaces the reduce-scatter/all-gather
    weight update with the bucketed overlappable form
    (``_OverlapOptAdapter``): grads flush in reverse order into
    size-bounded bucket arenas, each bucket updates shard-locally inside
    a manual shard_map and returns through a ppermute ring gather — no
    blocking collective in the executable (lint rule X007,
    docs/sharding.md "Latency hiding").  Unlike ``fused_opt``'s
    observable fallback, an unsupported configuration RAISES: overlap is
    an explicit opt-in whose silent absence would void the lint budget.

    ``pipeline`` (dict with ``stages``/``mesh``/``batch_axis``; built by
    ShardedTrainer from a 'pp' mesh axis) switches the forward to the
    GPipe schedule: ``x``/``y`` arrive micro-STACKED ``(m, B, ...)`` and
    the whole window is one executable — loss and backward stay outside
    the shard_map in GSPMD-land, which transposes the schedule for the
    VJP."""
    if partition not in PARTITIONS:
        raise MXNetError(f"partition={partition!r} unknown; "
                         f"choose from {PARTITIONS}")
    if partition == "zero1" and shardings_box is None:
        raise MXNetError(
            "partition='zero1' needs a shardings_box dict carrying the "
            "per-param placements (ShardedTrainer fills ['zero1'] / "
            "['opt_state'] before the first trace); without one the update "
            "would silently run fully replicated")
    if pipeline is not None:
        fn = None
        stage_fns, arrs, holder = _functional_apply_stages(
            net, names, pipeline["stages"], training=True)
    else:
        fn, arrs, holder = _functional_apply(net, names, training=True)
    params = net.collect_params()
    train_ix = [i for i, n in enumerate(names) if params[n].grad_req != "null"]
    aux_ix = [i for i, n in enumerate(names) if params[n].grad_req == "null"]
    holder["train_ix"], holder["aux_ix"] = train_ix, aux_ix
    with _blk.trace_guard():
        all_f32 = all(jnp.dtype(arrs[i]._data.dtype) == jnp.float32
                      for i in train_ix)
    opt = _make_opt(optimizer, learning_rate, weight_decay, momentum)
    if overlap:
        if partition != "zero1":
            raise MXNetError(
                "overlap=True is the zero1 latency-hiding path; it needs "
                "partition='zero1' (docs/sharding.md 'Latency hiding')")
        if fused_opt == "arena":
            raise MXNetError(
                "overlap=True supersedes fused_opt='arena': the bucketed "
                "flush IS the arena machinery, one layout per bucket — "
                "drop fused_opt")
        ok, reason = _OverlapOptAdapter.supports(opt)
        if ok and not all_f32:
            ok, reason = False, "non-f32 parameters"
        if not ok:
            # overlap is an explicit opt-in backed by a lint budget
            # (X007 async_required): a silent fallback would pass the
            # training run and fail the budget later, so raise here
            raise MXNetError(f"overlap=True unavailable: {reason} "
                             "(docs/sharding.md 'Latency hiding')")
        adapter = _OverlapOptAdapter(opt)
    else:
        adapter = _pick_adapter(opt, fused_opt, all_f32=all_f32)
    if loss_scaling not in ("auto", True, False):
        raise MXNetError(f"loss_scaling={loss_scaling!r} unknown; use "
                         "'auto', True or False")
    if loss_scaling == "auto":
        dynamic_scaling = compute_dtype is not None and \
            jnp.dtype(compute_dtype) == jnp.float16
    else:
        dynamic_scaling = bool(loss_scaling)
        if dynamic_scaling and compute_dtype is None:
            raise MXNetError(
                "loss_scaling=True without a compute_dtype: f32 steps "
                "cannot overflow, scaling would only mask a config bug")
    # bf16 AMP fast path: no scaling needed, so gradients stay bf16
    # through the dp reduction (half the AllReduce bytes) and are cast
    # to f32 at the optimizer-update entry (every adapter casts on its
    # own — master params stay f32)
    bf16_grads = (compute_dtype is not None
                  and jnp.dtype(compute_dtype) == jnp.bfloat16
                  and not dynamic_scaling)

    def assemble(tvals, avals, key_val):
        allv: List[Any] = [None] * (len(names) + 1)
        for i, v in zip(train_ix, tvals):
            allv[i] = v
        for i, v in zip(aux_ix, avals):
            allv[i] = v
        allv[-1] = key_val
        return allv

    def pp_forward(allv, xs):
        """GPipe forward over the 'pp' mesh axis (docs/sharding.md
        "Pipeline axis") inside ONE full-manual shard_map: params enter
        replicated (in_spec P() — GSPMD gathers any mp-sharded storage
        at the boundary), the batch splits over the data axis, and the
        schedule runs m+pp−1 ticks of collective-permute + per-rank
        stage compute with activations on a flat padded carrier
        (heterogeneous stage shapes).  check_vma=False because manual
        replication claims (psum'd bank, identical mp compute) aren't
        provable by the rep checker."""
        from jax import shard_map

        from . import pipeline as _pl

        pmesh = pipeline["mesh"]
        dp_axis = pipeline["batch_axis"]
        s = pmesh.shape["pp"]
        dpn = pmesh.shape.get(dp_axis, 1)
        m, bg = int(xs.shape[0]), int(xs.shape[1])
        if bg % dpn:
            raise MXNetError(f"pipeline micro-batch of {bg} does not "
                             f"divide the {dp_axis!r} axis ({dpn})")
        bl = bg // dpn
        micro = jax.ShapeDtypeStruct((bl,) + tuple(xs.shape[2:]), xs.dtype)
        bshapes = [micro]
        for k in range(s):
            bshapes.append(jax.eval_shape(
                lambda a, _k=k: stage_fns[_k](allv, a), bshapes[-1]))
        widths = [int(_prod(sd.shape[1:])) for sd in bshapes]
        cw = max(widths[1:])             # flat carrier width
        w_out = widths[-1]
        out_tail = tuple(bshapes[-1].shape[1:])

        def inner(*vals):
            av_l, x_l = list(vals[:-1]), vals[-1]

            def call(k, a):
                y = stage_fns[k](av_l, a)
                yf = y.reshape((y.shape[0], -1))
                if yf.shape[1] < cw:
                    yf = jnp.pad(yf, ((0, 0), (0, cw - yf.shape[1])))
                return yf

            calls = [(lambda a: call(0, a))] + \
                    [(lambda a, _k=k: call(
                        _k, a[:, :widths[_k]].reshape(
                            (a.shape[0],) + tuple(bshapes[_k].shape[1:]))))
                     for k in range(1, s)]
            flat = _pl.pipeline_apply_stages(calls, x_l, cw, w_out)
            return flat.reshape((m, bl) + out_tail)

        specs_in = tuple(P() for _ in allv) + (P(None, dp_axis),)
        return shard_map(inner, mesh=pmesh, in_specs=specs_in,
                         out_specs=P(None, dp_axis),
                         check_vma=False)(*allv, xs)

    def loss_of(tvals, avals, key_val, scale, x, y):
        xs = x if isinstance(x, (tuple, list)) else (x,)
        if compute_dtype is not None:
            # AMP: forward runs in compute_dtype on the MXU, master params
            # stay fp32 in the optimizer (ref python/mxnet/amp)
            cast = lambda v: (v.astype(compute_dtype)  # noqa: E731
                              if jnp.issubdtype(v.dtype, jnp.floating)
                              else v)
            tv = [cast(v) for v in tvals]
            av = [cast(v) for v in avals]
            xs = tuple(cast(v) for v in xs)
        else:
            tv, av = tvals, avals
        if pipeline is not None:
            if len(xs) != 1:
                raise MXNetError("pipeline ('pp') steps take a single "
                                 "array input, not a tuple batch")
            # x/y are micro-STACKED (m, B, ...); the window loss is the
            # mean over every sample, identical to averaging per-micro
            # grads (the grad-accum contract)
            preds = pp_forward(assemble(tv, av, key_val), xs[0])
            pflat = preds.reshape((-1,) + tuple(preds.shape[2:]))
            yflat = y.reshape((-1,) + tuple(y.shape[2:]))
            loss = jnp.mean(loss_fn(pflat, yflat)).astype(jnp.float32)
            return (loss * scale if dynamic_scaling else loss), (loss, ())
        outs, mutated = fn(assemble(tv, av, key_val), *xs)
        pred = outs[0] if len(outs) == 1 else tuple(outs)
        loss = jnp.mean(loss_fn(pred, y)).astype(jnp.float32)
        return (loss * scale if dynamic_scaling else loss), (loss, mutated)

    def compute_grads(tvals, avals, key_val, scale, x, y):
        from ..kernels import registry as _kreg

        # Pallas kernels traced here must know the mesh their batch is
        # sharded over: the TPU compiler does not partition them
        # (kernels/registry.py:batch_mesh).  The pp path runs inside its
        # own full-manual shard_map, where kernels need no second wrap.
        mesh, axis = (shardings_box or {}).get("batch_mesh", (None, None))
        with _kreg.batch_mesh(None if pipeline else mesh, axis):
            (_, (loss, mutated)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(tvals, avals, key_val, scale, x, y)
        if compute_dtype is not None:
            # mutated aux state (BN stats) came out of the low-precision
            # forward; keep the persistent copies fp32
            mutated = [m.astype(jnp.float32)
                       if jnp.issubdtype(m.dtype, jnp.floating) else m
                       for m in mutated]
        if dynamic_scaling:
            grads = [g.astype(jnp.float32) / scale for g in grads]
        elif not bf16_grads:
            grads = [g.astype(jnp.float32) for g in grads]
        # zero1: pin each gradient onto its dp-sharded layout (padded dim,
        # Zero1Info) — the constraint turns XLA's gradient AllReduce into
        # ReduceScatter, so no replica ever materializes the full gradient
        z1 = (shardings_box or {}).get("zero1")
        if z1:
            wsc = jax.lax.with_sharding_constraint
            grads = [g if i is None
                     else wsc(_pad_dim(g, i.axis, i.padded), i.sharding)
                     for g, i in zip(grads, z1)]
        return grads, mutated, loss

    def run_update(tvals, grads, opt_state, lr, t):
        """adapter.update, in the selected partition layout.  zero1 pads
        param+grad onto the state's dp-sharded layout (zeros are inert
        for every registry optimizer, incl. LAMB/LARS per-tensor norms),
        updates shard-locally, and slices the params back to true shape —
        adapter-agnostic."""
        if partition == "zero1" and "zero1" not in shardings_box:
            # trace-time check: the box is legitimately empty at build
            # time (ShardedTrainer fills it after make_train_step
            # returns), but by the first trace the placements must exist
            raise MXNetError(
                "partition='zero1' but shardings_box['zero1'] was never "
                "filled — the update would silently run fully replicated "
                "(use ShardedTrainer, or fill the box before tracing)")
        z1 = (shardings_box or {}).get("zero1")
        if not z1 or all(i is None for i in z1):
            return adapter.update(tvals, grads, opt_state, lr, t)
        wsc = jax.lax.with_sharding_constraint
        pp, gg = [], []
        for p, g, i in zip(tvals, grads, z1):
            if i is not None:
                p = wsc(_pad_dim(p, i.axis, i.padded), i.sharding)
                g = wsc(_pad_dim(g, i.axis, i.padded), i.sharding)
            pp.append(p)
            gg.append(g)
        new_p, new_state = adapter.update(pp, gg, opt_state, lr, t)
        new_p = [jax.lax.slice_in_dim(v, 0, i.size, axis=i.axis)
                 if i is not None and i.padded != i.size else v
                 for v, i in zip(new_p, z1)]
        return new_p, new_state

    def apply_update(tvals, opt_state, t, lr, scale_state, grads):
        scale, good, skipped = scale_state
        new_p, new_state = run_update(tvals, grads, opt_state, lr, t)
        if dynamic_scaling:
            ok = all_finite(grads)
            new_p = [jnp.where(ok, n, p) for n, p in zip(new_p, tvals)]
            new_state = [jnp.where(ok, n, s)
                         for n, s in zip(new_state, opt_state)]
            grown = good + 1 >= loss_scale_growth_interval
            new_scale = jnp.where(
                ok, jnp.where(grown, scale * 2.0, scale),
                jnp.maximum(scale * 0.5, 1.0))
            new_good = jnp.where(ok, jnp.where(grown, 0, good + 1), 0)
            scale_state = (new_scale, new_good,
                           jnp.where(ok, skipped, skipped + 1))
        # pin loop-carried state to its input placement: without output
        # constraints XLA may emit a different sharding for a small param
        # (observed: a [64] BN bias coming back 'tp'-sharded), making every
        # step pay a reshard when outputs feed the next step — and making
        # the AOT-compiled step (dryrun) reject its own outputs.
        # Under zero1 the param constraint IS the AllGather (sharded
        # update → replicated placement) and the state constraint keeps
        # the leaves dp-sharded.  shardings_box is filled by
        # ShardedTrainer AFTER this builder returns (the train/aux split
        # comes from the holder); the box is read here at TRACE time,
        # which happens strictly later.
        psh = (shardings_box or {}).get("params")
        if psh is not None:
            wsc = jax.lax.with_sharding_constraint
            new_p = [wsc(p, s) for p, s in zip(new_p, psh)]
            ssh = (shardings_box or {}).get("opt_state")
            if ssh is not None:
                new_state = [wsc(s, sh) for s, sh in zip(new_state, ssh)]
            else:
                # box without per-leaf placements (external callers):
                # state follows its owning param when same-shaped
                repl = NamedSharding(psh[0].mesh, P())
                new_state = [
                    wsc(s, psh[pi]) if s.shape == new_p[pi].shape
                    else wsc(s, repl)
                    for s, pi in zip(new_state, adapter.leaf_param_ix)]
        return new_p, new_state, scale_state

    def step(tvals, avals, key_val, opt_state, t, lr, scale_state, x, y):
        grads, mutated, loss = compute_grads(
            tvals, avals, key_val, scale_state[0], x, y)
        new_p, new_state, scale_state = apply_update(
            tvals, opt_state, t, lr, scale_state, grads)
        ash = (shardings_box or {}).get("aux")
        if ash is not None:
            wsc = jax.lax.with_sharding_constraint
            mutated = [wsc(m, s) for m, s in zip(mutated, ash)]
        return new_p, mutated, new_state, scale_state, loss

    # arm the persistent compilation cache before the step jits exist —
    # their (long) XLA compiles must be able to hit/fill the on-disk
    # cache so a second process of the same model skips XLA entirely
    cache_armed = _jit_cache.ensure_cache() is not None
    if donate and cache_armed and jax.default_backend() == "cpu":
        # XLA:CPU corrupts donated buffers when the executable comes
        # back DESERIALIZED from the persistent cache: the stored
        # input-output aliasing is mishandled, and a resumed trainer's
        # params silently fill with garbage on its second step
        # (reproduced on jax 0.4.37: save_states → load_states → step;
        # tests/test_jit.py::test_resume_with_persistent_cache_*).
        # TPU executables round-trip aliasing correctly, so only the
        # CPU backend trades donation's buffer reuse for correctness.
        donate = False
    # the X004 donation-aliasing lint reads the DECLARED donations from
    # the holder (post-CPU-adjustment) and checks them against the
    # executable's actual input_output_alias table (analysis/xla_lint)
    holder["donate_argnums"] = (0, 3) if donate else ()
    holder["apply_donate_argnums"] = (0, 1) if donate else ()
    jitted = jax.jit(step, donate_argnums=holder["donate_argnums"])
    grad_fn = jax.jit(compute_grads)
    apply_fn = jax.jit(apply_update,
                       donate_argnums=holder["apply_donate_argnums"])
    return jitted, grad_fn, apply_fn, adapter, holder


class ShardedTrainer:
    """End-to-end SPMD trainer for a gluon net over a Mesh.

    Capability summary vs reference: DP (≈ kvstore 'device'/'dist_sync'),
    plus fsdp/tp param sharding the reference lacks; any registry optimizer
    (the full 20, ref trainer.py's Optimizer integration); LR schedulers
    (traced lr — no recompiles); gradient accumulation; fp16 dynamic loss
    scaling in-step; checkpoint save/load restorable onto a different mesh
    (ref Trainer.save_states/load_states, trainer.py:482,511). Multi-host:
    build the mesh from jax.devices() after jax.distributed.initialize() —
    the same code runs, collectives ride ICI within a slice and DCN across
    (north-star requirement).

    ``partition`` selects the weight-update layout (docs/sharding.md):
    ``"replicated"`` (default; env override ``MXNET_PARTITION``) keeps the
    reference semantics, ``"zero1"`` shards the optimizer state and the
    update over the data axis (reduce-scatter grads → shard-local update →
    all-gather params) — same math, 1/dp the optimizer memory and update
    FLOPs per device.

    ``fused_opt`` picks the optimizer-update implementation
    (docs/kernels.md): ``None`` auto-selects the flat-arena Pallas kernel
    when the kernels layer is active and the optimizer is arena-fusible
    (sgd/momentum/adam, uniform multipliers), ``"arena"`` requires it,
    ``"off"`` pins the per-param replay.  Under zero1 the arenas shard
    over dp as flat segments.  Checkpoints reshard across mesh shapes
    and partitions: padding is stripped at save and re-sliced/re-padded
    to the target dp/mp factors at load (the slice-wise path is
    ``state_shards``/``load_state_shards``, docs/resilience.md
    "Manifest v2 + resharding").  The optimizer LAYOUT is recorded
    implicitly and never reshards: restoring across different
    ``fused_opt``/kernels configs (per-param vs flat-arena leaf arity
    or rank) raises."""

    def __init__(self, net, loss_fn, mesh: Optional[Mesh] = None,
                 optimizer="sgd", learning_rate: float = 0.01,
                 weight_decay: float = 0.0, momentum: float = 0.9,
                 spec_fn: Callable = replicated_spec_fn,
                 batch_spec: P = P("dp"), compute_dtype=None,
                 lr_scheduler=None, grad_accum: int = 1,
                 init_loss_scale: float = 2.0 ** 16,
                 max_inflight: Optional[int] = None,
                 partition: Optional[str] = None,
                 fused_opt: Optional[str] = None,
                 overlap: Optional[bool] = None,
                 loss_scaling: Any = "auto"):
        from .mesh import default_mesh

        if partition is None:
            partition = _os.environ.get("MXNET_PARTITION", "replicated")
        if partition not in PARTITIONS:
            raise MXNetError(f"partition={partition!r} unknown; "
                             f"choose from {PARTITIONS}")
        if overlap is None:
            overlap = _os.environ.get("MXNET_OVERLAP", "0").lower() \
                not in ("", "0", "false")
        self.overlap = bool(overlap)
        self.partition = partition
        #: the AMP policy dtype traced into the step (None = pure f32)
        self.compute_dtype = compute_dtype
        self.net = net
        self.mesh = mesh if mesh is not None else default_mesh()
        self._batch_spec = batch_spec
        self._dp_axis = self._data_axis_name()
        self.grad_accum = int(grad_accum)
        # pipeline ('pp') axis: partition the net into one stage per pp
        # rank; micro-batch count = grad_accum (the window IS the
        # schedule — docs/sharding.md "Pipeline axis")
        self._pp = self.mesh.shape.get("pp", 1)
        pipeline_info = None
        self._pp_stages = None
        if self._pp > 1:
            from .pipeline import split_stages

            self._pp_stages = split_stages(net, self._pp)
            pipeline_info = dict(stages=self._pp_stages, mesh=self.mesh,
                                 batch_axis=self._dp_axis)
        self.names, allvals, self.specs = shard_params(net, self.mesh, spec_fn)
        if any(any(e is not None for e in tuple(s)) for s in self.specs):
            # mp/fsdp-sharded params: the arena's grad pack would gather
            # every sharded gradient replicated, silently undoing the
            # tensor-MP memory/comms win — the arena stays a pure-DP tool
            from ..kernels import registry as _kreg

            if fused_opt == "arena":
                raise MXNetError(
                    "fused_opt='arena' cannot run with sharded parameters "
                    "(mp/fsdp spec_fn): packing their gradients into one "
                    "replicated arena would gather full-model grad bytes "
                    "per device — use the per-param adapter "
                    "(docs/kernels.md)")
            if fused_opt is None and _kreg.mode() != "off":
                _kreg.fallback(
                    "opt_arena", "params sharded over mesh axes "
                    "(mp/fsdp spec_fn): the grad-arena pack would gather "
                    "them replicated")
            fused_opt = "off"
        if self.overlap and any(any(e is not None for e in tuple(s))
                                for s in self.specs):
            raise MXNetError(
                "overlap=True cannot run with sharded parameters "
                "(mp/fsdp spec_fn): packing their gradients into bucket "
                "arenas would gather full-model grad bytes per device — "
                "use the per-leaf zero1 path (docs/sharding.md)")
        shardings_box = {}
        (self._step_fn, self._grad_fn, self._apply_fn, self._adapter,
         self._holder) = make_train_step(
            net, loss_fn, self.names, optimizer, learning_rate,
            weight_decay, momentum, compute_dtype=compute_dtype,
            shardings_box=shardings_box,
            partition=partition, fused_opt=fused_opt,
            overlap=self.overlap, pipeline=pipeline_info,
            loss_scaling=loss_scaling)
        self.pvals = [allvals[i] for i in self._holder["train_ix"]]
        self.avals = [allvals[i] for i in self._holder["aux_ix"]]
        # loop-carried outputs keep their input placements (read by the
        # step at trace time — see make_train_step)
        shardings_box["batch_mesh"] = (self.mesh, self._dp_axis)
        shardings_box["params"] = [
            NamedSharding(self.mesh, self.specs[i])
            for i in self._holder["train_ix"]]
        shardings_box["aux"] = [
            NamedSharding(self.mesh, self.specs[i])
            for i in self._holder["aux_ix"]]
        self._params = net.collect_params()
        self.train_names = [self.names[i] for i in self._holder["train_ix"]]
        self.aux_names = [self.names[i] for i in self._holder["aux_ix"]]
        tspecs = [self.specs[i] for i in self._holder["train_ix"]]
        # ZeRO-1 placement plan (None per param when replicated): the
        # sharded dim is chosen against the data axis named by batch_spec
        arena = isinstance(self._adapter, _ArenaOptAdapter)
        ovl = isinstance(self._adapter, _OverlapOptAdapter)
        if arena:
            self._adapter.mesh = self.mesh
        if ovl:
            # overlap: bucket arenas shard over dp inside the adapter's
            # own shard_map; the per-leaf Zero1Info machinery AND the
            # grad constraint stay disengaged (grads reduce via plain
            # AllReduce — allowed by the X007 budget; the blocking RS/AG
            # pair is what the overlap form eliminates)
            if self._dp_axis not in self.mesh.shape:
                raise MXNetError(
                    f"overlap=True needs a {self._dp_axis!r} mesh axis; "
                    f"mesh has {tuple(self.mesh.axis_names)}")
            self._zero1 = [None] * len(self.pvals)
            self._adapter._shard_multiple = self.mesh.shape[self._dp_axis]
            self._adapter.mesh = self.mesh
            self._adapter.dp_axis = self._dp_axis
        elif partition == "zero1" and arena:
            # flat-arena zero1: the 1-D state arenas shard evenly over dp
            # — shard-local SEGMENTS, no per-leaf padding (the update is
            # elementwise, so leaf boundaries may fall anywhere); the
            # per-leaf Zero1Info machinery stays disengaged (all None)
            if self._dp_axis not in self.mesh.shape:
                raise MXNetError(
                    f"partition='zero1' needs a {self._dp_axis!r} mesh "
                    f"axis; mesh has {tuple(self.mesh.axis_names)}")
            self._zero1 = [None] * len(self.pvals)
            self._adapter._shard_multiple = self.mesh.shape[self._dp_axis]
            self._adapter.arena_sharding = NamedSharding(
                self.mesh, P(self._dp_axis))
        elif partition == "zero1":
            self._zero1 = _zero1_infos(self.mesh, self._dp_axis, tspecs,
                                       self.pvals)
        else:
            self._zero1 = [None] * len(self.pvals)
        shardings_box["zero1"] = self._zero1
        # optimizer state: created on the zero1-padded layout (leaves whose
        # shard dim needs padding are STORED padded — the dp-sharded
        # placement is what divides optimizer memory across replicas),
        # replicated/fsdp leaves keep their parameter's placement
        init_vals = [p if i is None else _pad_dim(p, i.axis, i.padded)
                     for p, i in zip(self.pvals, self._zero1)]
        self.opt_state = self._adapter.init_state(init_vals)
        self._state_shardings: List[NamedSharding] = []
        self._leaf_unpad: List[Optional[Tuple[int, int]]] = []
        for li, (s, pi) in enumerate(zip(self.opt_state,
                                         self._adapter.leaf_param_ix)):
            if ovl:
                # per-bucket flat arenas, dp-sharded (the ZeRO-1 memory
                # win); checkpointed stripped to the bucket's true total
                # like the single-arena path below
                lay = self._adapter.leaf_layouts[li]
                self._state_shardings.append(
                    NamedSharding(self.mesh, P(self._dp_axis)))
                self._leaf_unpad.append(
                    (0, lay.total) if lay.padded != lay.total else None)
                continue
            if arena:
                # arena leaves span every param: dp-sharded under zero1,
                # replicated otherwise.  Stored padded (inert zeros), but
                # CHECKPOINTED stripped to layout.total — the pad width
                # depends on dp (lcm alignment), and save_states promises
                # restore onto ANY mesh shape; load_states re-pads toward
                # this trainer's padded length like any zero1 leaf
                lay = self._adapter.layout
                self._state_shardings.append(
                    self._adapter.arena_sharding
                    or NamedSharding(self.mesh, P()))
                self._leaf_unpad.append(
                    (0, lay.total) if lay.padded != lay.total else None)
                continue
            info = self._zero1[pi]
            if info is not None and s.shape == init_vals[pi].shape:
                self._state_shardings.append(info.sharding)
                self._leaf_unpad.append(
                    (info.axis, info.size) if info.padded != info.size
                    else None)
            elif s.shape == tuple(self.pvals[pi].shape):
                # momenta etc. share their parameter's placement (FSDP:
                # optimizer state shards with the param, the ZeRO property)
                self._state_shardings.append(
                    NamedSharding(self.mesh, tspecs[pi]))
                self._leaf_unpad.append(None)
            else:
                self._state_shardings.append(NamedSharding(self.mesh, P()))
                self._leaf_unpad.append(None)
        shardings_box["opt_state"] = self._state_shardings
        self.opt_state = [jax.device_put(s, sh) for s, sh in
                          zip(self.opt_state, self._state_shardings)]
        # construction-time storage shapes: load_states re-pads toward
        # THESE (not the live leaves, which a prior load's replicated
        # shape-mismatch fallback may have replaced)
        self._leaf_shapes = [tuple(s.shape) for s in self.opt_state]
        #: byte accounting of the last load_state_shards (manifest v2)
        #: restore — {bytes_read, sharded_full_bytes,
        #: sharded_max_rank_bytes, leaves_resharded}; None until then
        self.last_restore_stats: Optional[Dict[str, int]] = None
        self._t = 0
        # an Optimizer instance brings its own lr / scheduler — honor them
        # (its update() replays with the trainer-supplied traced lr)
        opt = self._adapter.opt
        self._lr = float(opt.lr) if optimizer is opt else learning_rate
        self.lr_scheduler = lr_scheduler if lr_scheduler is not None \
            else getattr(opt, "lr_scheduler", None)
        self._accum: Optional[List[Any]] = None
        self._micro = 0
        # pipeline window buffer: micro-batches collect host-side and the
        # whole window dispatches as one GPipe executable (_pp_step)
        self._pp_buf: List[Tuple[Any, Any]] = []
        self._pp_validated = False
        if loss_scaling == "auto":
            self._dynamic_scaling = compute_dtype is not None and \
                jnp.dtype(compute_dtype) == jnp.float16
        else:
            self._dynamic_scaling = bool(loss_scaling)
        # AOT-compiled step executables (compile()): (slot, batch signature
        # | None) -> jax compiled.  One executable PER batch signature per
        # slot (the mesh shape is fixed per trainer, so the key space is
        # per-(mesh-shape, batch-signature)); _step dispatches straight to
        # a matching executable — no trace, no XLA, no first-step stall.
        self._aot: Dict[Tuple[str, Optional[tuple]], Any] = {}
        self._scale_state = (
            jnp.float32(init_loss_scale if self._dynamic_scaling else 1.0),
            jnp.int32(0), jnp.int32(0))
        # amp scale telemetry cadence: reading the device-side scale
        # forces a host sync, so publish every N applied steps
        # (MXNET_AMP_TELEMETRY_EVERY, 0 disables — docs/precision.md)
        self._amp_tel_every = int(_os.environ.get(
            "MXNET_AMP_TELEMETRY_EVERY", "50"))
        # bounded in-flight dispatch (MXNET_MAX_INFLIGHT_STEPS, default 2):
        # step() rides JAX async dispatch, blocking only on the step-(t-K)
        # loss handle — the queue stays K deep, never unbounded or depth-1
        self._inflight = _engine.InflightQueue(max_inflight)
        from ..random import key_holder

        with _blk.trace_guard():
            self._key = key_holder()._data
        self._publish_layout_gauges()
        # J003 footgun hint: a big replicated optimizer state on a
        # multi-device mesh silently pays dp× memory + update FLOPs
        from ..analysis import spmd_hints

        n_params = sum(int(_prod(p.shape)) for p in self.pvals)
        # an optimizer WITHOUT state leaves (plain sgd) has nothing to
        # replicate — all([]) would fire the hint vacuously
        fully_repl = bool(self._state_shardings) and all(
            not any(e is not None for e in tuple(sh.spec))
            for sh in self._state_shardings)
        spmd_hints.on_trainer_init(
            type(net).__name__, mesh_devices=self.mesh.size,
            n_params=n_params, opt_state_replicated=fully_repl,
            partition=self.partition)

    def _data_axis_name(self) -> str:
        """The mesh axis the batch shards over: the first named entry of
        batch_spec (first element when a tuple), else 'dp' when the mesh
        has one, else the mesh's leading axis."""
        for s in tuple(self._batch_spec):
            if s is not None:
                return s[0] if isinstance(s, tuple) else s
        return "dp" if "dp" in self.mesh.shape else self.mesh.axis_names[0]

    # -- memory/comms telemetry (docs/sharding.md, docs/telemetry.md) -------
    def _publish_layout_gauges(self):
        """(Re-)publish the layout-derived gauges; the layouts can change
        after construction (load_states may fall back to replicated
        placements on shape mismatch)."""
        if _tel._ENABLED:
            _tel.set_gauge("trainer.opt_state_bytes_per_device",
                           self.opt_state_bytes_per_device)
            _tel.set_gauge("trainer.param_gather_bytes",
                           self.param_gather_bytes)
            if isinstance(self._adapter, _OverlapOptAdapter):
                _tel.set_gauge("trainer.overlap_bucket_count",
                               len(self._adapter.buckets))
            if self._pp > 1:
                from .pipeline import bubble_fraction

                _tel.set_gauge(
                    "trainer.pp_bubble_fraction",
                    bubble_fraction(self._pp, self.grad_accum))

    @property
    def opt_state_bytes_per_device(self) -> int:
        """Bytes of optimizer state resident on EACH device.  Replicated
        partition: the full state.  zero1: ≈ full/dp (plus padding and
        any sub-min-size leaves kept replicated) — the measurable ZeRO-1
        memory win."""
        total = 0
        for s in self.opt_state:
            try:
                shard = s.sharding.shard_shape(s.shape)
            except Exception:
                shard = s.shape
            total += int(_prod(shard)) * s.dtype.itemsize
        return total

    @property
    def param_gather_bytes(self) -> int:
        """Bytes each device RECEIVES in the per-step param all-gather
        (zero1: Σ padded_shard_bytes × (dp−1)/dp, where the shard is the
        device's portion of any mp/fsdp-sharded dims — the gather runs
        over dp only; replicated: 0 — no gather happens, every replica
        updated the full params)."""
        dp = self.mesh.shape.get(self._dp_axis, 1)
        if dp <= 1:
            return 0
        if isinstance(self._adapter, _OverlapOptAdapter):
            # overlap zero1: each bucket's updated arena returns through
            # the ppermute ring — dp−1 hops of one shard each, i.e. the
            # same (dp−1)/dp of the arena bytes an all-gather would move
            return sum(lay.padded * 4
                       for lay in self._adapter.layouts) * (dp - 1) // dp
        if isinstance(self._adapter, _ArenaOptAdapter):
            # arena zero1: the dp-sharded delta arena is gathered into the
            # replicated params each step — bill the arena bytes, not the
            # (disengaged, all-None) per-leaf Zero1Info plan
            if self._adapter.arena_sharding is None:
                return 0
            return self._adapter.layout.padded * 4 * (dp - 1) // dp
        total = 0
        for p, info in zip(self.pvals, self._zero1):
            if info is None:
                continue
            padded = int(_prod(p.shape)) // max(info.size, 1) \
                * info.padded
            # an mp-sharded param stays mp-sharded through the gather:
            # each device receives only its shard of the non-dp dims
            for k, e in enumerate(tuple(info.sharding.spec)):
                if e is not None and k != info.axis:
                    padded //= _axis_size(self.mesh, e)
            total += padded * p.dtype.itemsize * (dp - 1) // dp
        return total

    @property
    def collective_bytes_per_step(self) -> int:
        """Analytic per-device collective bytes of ONE step
        (docs/telemetry.md): the gradient reduction — ring AllReduce
        moves 2(dp−1)/dp of the grad bytes, ReduceScatter (classic
        zero1) half that — plus the param gather
        (:attr:`param_gather_bytes`).  A count from shapes: how long
        the collectives take, and how much of that is exposed, only a
        device trace on several chips can say."""
        dp = self.mesh.shape.get(self._dp_axis, 1)
        if dp <= 1:
            return 0
        gbytes = sum(int(_prod(p.shape)) * 4 for p in self.pvals)
        classic_z1 = (self.partition == "zero1"
                      and not isinstance(self._adapter, _OverlapOptAdapter))
        red = (1 if classic_z1 else 2) * gbytes * (dp - 1) // dp
        return red + self.param_gather_bytes

    # -- lr -----------------------------------------------------------------
    @property
    def learning_rate(self) -> float:
        if self.lr_scheduler is not None:
            return float(self.lr_scheduler(self._t))
        return self._lr

    def set_learning_rate(self, lr: float):
        if self.lr_scheduler is not None:
            # parity with Optimizer.set_learning_rate: _lr would be dead
            # (the property always consults the scheduler), so a silent
            # write here would let the caller believe the LR changed
            raise MXNetError(
                "LRScheduler of the trainer has already been defined; "
                "mutate the scheduler instead of calling set_learning_rate")
        self._lr = float(lr)

    @property
    def loss_scale(self) -> float:
        return float(self._scale_state[0])

    @property
    def skipped_steps(self) -> int:
        """Update steps skipped on non-finite gradients since
        construction (or the last checkpoint restore) — dynamic loss
        scaling only; 0 otherwise.  Reading it syncs on the last
        dispatched step."""
        return int(self._scale_state[2])

    def _publish_amp_gauges(self):
        """amp.loss_scale / amp.skipped_steps, every
        ``MXNET_AMP_TELEMETRY_EVERY`` applied steps (the read blocks on
        this step's scale_state, so it is gated to keep the async
        dispatch pipeline deep — docs/telemetry.md)."""
        if not (self._dynamic_scaling and _tel._ENABLED
                and self._amp_tel_every
                and self._t % self._amp_tel_every == 0):
            return
        _tel.set_gauge("amp.loss_scale", float(self._scale_state[0]))
        _tel.set_gauge("amp.skipped_steps", int(self._scale_state[2]))

    def _put(self, v):
        """Shard a batch value (or tuple tree of them) per batch_spec; the
        spec is truncated for lower-rank leaves. Benchmarks drive the raw
        step function with values placed by this same helper.

        Multi-process: each process passes its LOCAL portion of the global
        batch (the usual per-host data pipeline); the pieces are assembled
        into one global sharded array. device_put would instead demand the
        identical global value on every process."""
        if isinstance(v, (tuple, list)):
            return tuple(self._put(e) for e in v)
        if isinstance(v, NDArray):
            v = v._data
        spec = self._batch_spec
        if getattr(v, "ndim", 1) < len(spec):
            spec = P(*spec[:v.ndim])
        if any(s is not None for s in spec):
            # replicate SIZE-1 axes instead of sharding them — bucket
            # validity masks are size 1 on non-bucketed axes (e.g. a
            # (1, T) seq mask under batch_spec P('dp')), and a hard
            # error there would make every bucketed pipeline multi-chip
            # hostile.  Size-1 replication is exactly what the mask's
            # broadcast semantics want.  On a 2-D mesh, TRAILING dims
            # the spec shards over the model axis (activation sharding,
            # batch_spec P('dp','mp')) replicate too when the axis can't
            # divide them — a seq-len that doesn't divide mp is a data
            # property, not a config bug, and the old one-axis fallback
            # made every such batch a hard error.  The BATCH dim (the
            # first NAMED spec entry — index 1 for a time-major
            # P(None, 'dp'), matching _data_axis_name) still errors
            # loudly in device_put: a batch size that doesn't divide dp
            # IS a config bug, and silently replicating it would hide
            # 8x redundant compute.
            batch_ix = next(k for k, s in enumerate(spec) if s is not None)
            fixed = []
            for i, s in enumerate(spec):
                if s is not None and (
                        v.shape[i] == 1
                        or (i != batch_ix
                            and v.shape[i] % _axis_size(self.mesh, s))):
                    s = None
                fixed.append(s)
            spec = P(*fixed)
        sharding = NamedSharding(self.mesh, spec)
        if isinstance(v, jax.Array) and v.sharding == sharding:
            # already placed (the DevicePrefetcher path): no re-layout, no
            # host round-trip — the transfer was paid off the main thread
            return v
        if jax.process_count() > 1 and any(s is not None for s in spec):
            import numpy as onp

            return jax.make_array_from_process_local_data(
                sharding, onp.asarray(v))
        return jax.device_put(v, sharding)

    def device_put(self, batch):
        """Place a host batch (or tuple tree) onto the mesh per
        ``batch_spec`` — the placement hook ``DevicePrefetcher`` /
        ``DataLoader(prefetch_to_device=trainer)`` call so prefetched
        batches arrive pre-sharded and ``step`` skips its own put."""
        return self._put(batch)

    # -- pipeline ('pp') window plumbing (docs/sharding.md) ------------------
    def _put_window(self, v):
        """Place a micro-STACKED ``(m, B, ...)`` window: the micro axis
        replicated, the rest per batch_spec (a batch that doesn't divide
        dp errors loudly in device_put — a config bug, like _put)."""
        if isinstance(v, NDArray):
            v = v._data
        entries = (None,) + tuple(self._batch_spec)
        spec = P(*entries[:v.ndim]) if v.ndim < len(entries) \
            else P(*entries)
        return jax.device_put(v, NamedSharding(self.mesh, spec))

    def _pp_batch(self, batch):
        """A sample (x, y) micro-batch → the placed window compile()
        keys on (grad_accum identical micros stacked)."""
        import numpy as onp

        def host(v):
            return onp.asarray(v._data if isinstance(v, NDArray) else v)

        m = max(self.grad_accum, 1)
        return (self._put_window(onp.stack([host(batch[0])] * m)),
                self._put_window(onp.stack([host(batch[1])] * m)))

    def _pp_validate(self, x):
        """One-time numeric check that the stage split reproduces the
        net: ``split_stages`` partitions by registration order, which
        cannot be PROVEN to equal forward composition — a residual or
        branchy top-level net must fail here loudly instead of training
        a different function."""
        import numpy as onp

        if self._pp_validated:
            return
        with _blk.trace_guard():
            h = NDArray(jnp.asarray(
                x._data if isinstance(x, NDArray) else x))
            want = self.net.forward(h)
            got = h
            for st in self._pp_stages:
                for b in st.blocks:
                    got = b.forward(got)
            w = onp.asarray(want._data)
            g = onp.asarray(got._data)
        scale = max(float(onp.max(onp.abs(w))), 1e-6)
        rel = float(onp.max(onp.abs(w - g))) / scale
        if rel > 1e-5:
            raise MXNetError(
                f"pipeline stage split does not reproduce the net's "
                f"forward (rel err {rel:.2e}): the net's forward is not "
                "the fold of its registered children — restructure it "
                "as (Hybrid)Sequential chains or drop the 'pp' axis "
                "(docs/sharding.md 'Pipeline axis')")
        self._pp_validated = True

    def _pp_step(self, x, y) -> NDArray:
        """Pipeline step: micro-batches buffer host-side; the grad_accum-th
        call stacks them into one ``(m, B, ...)`` window and dispatches
        the whole GPipe schedule as ONE executable.  Buffered calls
        return a placeholder 0 loss; the window call returns the
        window-mean loss (the same accounting as grad-accum: k calls,
        one optimizer update)."""
        import numpy as onp

        if isinstance(x, (tuple, list)) or isinstance(y, (tuple, list)):
            raise MXNetError("pipeline ('pp') trainers take single-array "
                             "x/y batches (tuple batches unsupported)")
        self._pp_validate(x)

        def host(v):
            return onp.asarray(v._data if isinstance(v, NDArray) else v)

        self._pp_buf.append((host(x), host(y)))
        self._micro += 1
        if self._micro < self.grad_accum:
            with _blk.trace_guard():
                return NDArray(jnp.zeros((), jnp.float32))
        xs = onp.stack([b[0] for b in self._pp_buf])
        ys = onp.stack([b[1] for b in self._pp_buf])
        self._pp_buf, self._micro = [], 0
        xb, yb = self._put_window(xs), self._put_window(ys)
        self._t += 1
        lr = jnp.float32(self.learning_rate)
        aot = self._aot_fn("step", xb, yb) if self._aot else None
        with _tr.span("trainer.dispatch", aot=aot is not None,
                      pp=self._pp):
            if aot is not None:
                (self.pvals, mutated, self.opt_state,
                 self._scale_state, loss) = aot(
                    self.pvals, self.avals, self._key, self.opt_state,
                    self._t, lr, self._scale_state, xb, yb)
            else:
                (self.pvals, mutated, self.opt_state,
                 self._scale_state, loss) = self._jit_call(
                    self._step_fn, self.pvals, self.avals, self._key,
                    self.opt_state, self._t, lr, self._scale_state,
                    xb, yb)
        self._write_back(mutated)
        self._publish_amp_gauges()
        self._inflight.push(loss)
        return NDArray(loss)

    # -- AOT warmup (docs/jit.md) -------------------------------------------
    @staticmethod
    def _batch_sig(xb, yb) -> tuple:
        def leaf(v):
            if isinstance(v, (tuple, list)):
                return tuple(leaf(e) for e in v)
            return (tuple(v.shape), str(v.dtype))

        return (leaf(xb), leaf(yb))

    def _aot_fn(self, slot: str, xb=None, yb=None):
        # keyed per batch signature (None for the shape-free apply slot):
        # several compiled signatures coexist, unmatched shapes fall back
        # to the jit path
        sig = self._batch_sig(xb, yb) if xb is not None else None
        return self._aot.get((slot, sig))

    def compile(self, batch, background: bool = False):
        """AOT-compile the SPMD step for a sample ``(x, y)`` batch via
        ``jit.lower(...).compile()`` — the first real ``step()`` with
        matching batch shapes then dispatches straight to the stored
        executable: no trace, no XLA compile, steady-state speed from
        step one.  With the persistent cache armed (mx.jit.cache) the
        lowered compile itself is a disk hit on any later process.

        ``lower()`` only needs shapes, so ``batch`` can be the first
        real batch or zeros; nothing executes and no buffer is donated.
        With ``grad_accum > 1`` the grad and apply executables compile
        instead of the fused step.  ``background=True`` compiles on a
        daemon thread (overlap with data-pipeline start) and returns a
        :class:`~mxnet_tpu.gluon.block.WarmupHandle`; call ``wait()``
        before timing.  Returns the number of executables compiled."""
        from ..gluon.block import WarmupHandle

        if not isinstance(batch, (tuple, list)) or len(batch) != 2:
            raise MXNetError("compile() takes a sample (x, y) batch")
        if self._pp > 1:
            # pipeline: the executable consumes the micro-STACKED window
            # (one fused GPipe step per grad_accum window, no grad/apply
            # split) — key the AOT entry on the stacked signature
            xb, yb = self._pp_batch(batch)
        else:
            xb, yb = self._put(batch[0]), self._put(batch[1])
        lr = jnp.float32(self.learning_rate)

        def timed_compile(lowered, slot):
            t0 = _time.perf_counter()
            compiled = lowered.compile()
            if _tel._ENABLED:
                _tel.observe("hybridize.compile_seconds",
                             _time.perf_counter() - t0)
                _tel.inc("hybridize.warmup_compiles")
            if _tr._ENABLED:
                _tr.record_span("hybridize.compile", t0,
                                _time.perf_counter() - t0,
                                block=type(self.net).__name__, slot=slot)
            if _xlint.enabled():
                # X-rule pass over the newborn executable (one of the
                # three compile seams, docs/analysis.md); =raise
                # verdicts propagate, everything else is warn+count.
                # The lowered StableHLO pins X003's concatenate count
                # to the program-semantic number (the compiled CPU HLO
                # adds backend-chosen concatenates on top).
                _xlint.lint_trainer_executable(
                    self, compiled, slot, lowered_text=lowered.as_text())
            return compiled

        wid = _tr.next_id("warmup")

        def run():
            n = 0
            with _tr.correlate(warmup=wid), \
                    _tr.span("jit.warmup", timer="jit.warmup_seconds",
                             timer_on_error=True,
                             block=type(self.net).__name__):
                sig = self._batch_sig(xb, yb)
                if self.grad_accum <= 1 or self._pp > 1:
                    if self._aot_fn("step", xb, yb) is None:
                        # lower() traces the functional step (state swap
                        # — trace guard); compile() is pure XLA and runs
                        # outside the lock so stepping/readers overlap it
                        with _blk.trace_guard():
                            lowered = self._step_fn.lower(
                                self.pvals, self.avals, self._key,
                                self.opt_state, self._t + 1, lr,
                                self._scale_state, xb, yb)
                        self._aot[("step", sig)] = timed_compile(lowered,
                                                                 "step")
                        n += 1
                else:
                    if self._aot_fn("grad", xb, yb) is None:
                        with _blk.trace_guard():
                            lowered = self._grad_fn.lower(
                                self.pvals, self.avals, self._key,
                                self._scale_state[0], xb, yb)
                        self._aot[("grad", sig)] = timed_compile(lowered,
                                                                 "grad")
                        n += 1
                    if self._aot_fn("apply") is None:
                        with _blk.trace_guard():
                            lowered = self._apply_fn.lower(
                                self.pvals, self.opt_state, self._t + 1,
                                lr, self._scale_state,
                                self._grad_specs())
                        self._aot[("apply", None)] = timed_compile(
                            lowered, "apply")
                        n += 1
            return n

        if background:
            return WarmupHandle(run)
        return run()

    def _grad_specs(self):
        """ShapeDtypeStructs of the gradients ``apply_fn`` consumes:
        always fp32; under zero1 they leave grad_fn padded onto the
        dp-sharded layout (compute_grads), otherwise they carry the
        params' shapes and placements."""
        return [jax.ShapeDtypeStruct(p.shape, jnp.float32,
                                     sharding=p.sharding)
                if i is None else jax.ShapeDtypeStruct(
                    tuple(i.padded if a == i.axis else d
                          for a, d in enumerate(p.shape)),
                    jnp.float32, sharding=i.sharding)
                for p, i in zip(self.pvals, self._zero1)]

    def _write_back_params(self):
        params = self._params
        for n, v in zip(self.train_names, self.pvals):
            params[n].data()._set_data(v)

    def _write_back(self, mutated):
        params = self._params
        from ..random import key_holder

        # under the trace guard: a background warmup trace of this net
        # would otherwise hand us tracers for aux state / the RNG key,
        # and our _set_data writes would race its save/restore
        with _blk.trace_guard():
            self._write_back_params()
            refs = self._holder.get("mutated_refs", [])
            for a, v in zip(refs, mutated):
                a._set_data(v)
            self.avals = [params[n].data()._data for n in self.aux_names]
            self._key = key_holder()._data

    def step(self, x, y, block: bool = False):
        """One SPMD step.  By default the loss comes back as a LAZY
        scalar ``NDArray`` riding JAX async dispatch — no host sync per
        iteration; read it at gated points with ``loss.item()`` /
        ``float(loss)``.  In-flight depth is bounded by
        ``MXNET_MAX_INFLIGHT_STEPS`` (default 2): dispatching step t
        blocks on step t-K's loss handle, so the device queue stays K
        deep (docs/pipeline.md).  ``block=True`` restores the old
        synchronous contract (drain the pipeline, return ``float``).

        With grad_accum=k, every k-th call applies the averaged
        accumulated gradient (the k-1 other calls only accumulate — ref
        gradient-accumulation idiom over grad_req='add')."""
        # correlation: this dispatch belongs to step t+1 (grad-accum
        # micro-batches all belong to the upcoming apply); every span
        # recorded below — including on the prefetch thread via
        # capture(), and the InflightQueue's deferred wait — carries it
        sid = self._t + 1
        with _tr.correlate(step=sid), \
                _tr.span("trainer.step", timer="trainer.step_seconds",
                         timer_on_error=True):
            loss = self._step(x, y)
        if block:
            self.drain()
            return float(loss)
        return loss

    def drain(self):
        """Retire every in-flight step (block until the device queue is
        empty).  Call at checkpoint/eval boundaries; ``save_states`` and
        ``step(block=True)`` call it for you."""
        self._inflight.drain()

    @staticmethod
    def _jit_call(fn, *args):
        """Invoke a jitted step function; when its jit cache grows the
        call traced + XLA-compiled synchronously, so book that wall time
        under the same compile timer the hybridize cache uses — one
        metric answers "how much of this run was compilation" for both
        paths, including per-shape recompiles and the grad-accum fns.

        Runs under the global trace guard: a first call traces the
        functional step, which swaps shared Parameter ._data / the RNG
        key to tracers (_functional_apply), and that swap must not
        interleave with a background warmup trace or its readers."""
        if not (_tel._ENABLED or _tr._ENABLED):
            with _blk.trace_guard():
                return fn(*args)
        cache_size = getattr(fn, "_cache_size", None)
        if cache_size is None:  # jit internals changed: skip attribution
            with _blk.trace_guard():
                return fn(*args)
        n0 = cache_size()
        t0 = _time.perf_counter()
        with _blk.trace_guard():
            out = fn(*args)
        if cache_size() > n0:
            dur = _time.perf_counter() - t0
            if _tel._ENABLED:
                _tel.observe("hybridize.compile_seconds", dur)
            _tr.record_span("hybridize.compile", t0, dur, slot="trainer")
        return out

    def _step(self, x, y) -> NDArray:
        if self._pp > 1:
            return self._pp_step(x, y)
        xb, yb = self._put(x), self._put(y)
        if self.grad_accum <= 1:
            self._t += 1
            # lr AFTER the increment: update k uses scheduler(k), matching
            # the eager Optimizer path (optimizer/__init__.py _update_count
            # before _get_lr)
            lr = jnp.float32(self.learning_rate)
            aot = self._aot_fn("step", xb, yb) if self._aot else None
            with _tr.span("trainer.dispatch", aot=aot is not None):
                if aot is not None:
                    (self.pvals, mutated, self.opt_state,
                     self._scale_state, loss) = aot(
                        self.pvals, self.avals, self._key,
                        self.opt_state, self._t, lr,
                        self._scale_state, xb, yb)
                else:
                    (self.pvals, mutated, self.opt_state,
                     self._scale_state, loss) = self._jit_call(
                        self._step_fn, self.pvals, self.avals, self._key,
                        self.opt_state, self._t, lr,
                        self._scale_state, xb, yb)
            self._write_back(mutated)
            self._publish_amp_gauges()
            # the loss depends on the whole fwd+bwd+update, is never fed
            # back into a donating call, and is tiny — the one safe handle
            # to bound the dispatch queue on
            self._inflight.push(loss)
            return NDArray(loss)
        aot = self._aot_fn("grad", xb, yb) if self._aot else None
        with _tr.span("trainer.dispatch", aot=aot is not None,
                      micro=self._micro):
            if aot is not None:
                grads, mutated, loss = aot(
                    self.pvals, self.avals, self._key,
                    self._scale_state[0], xb, yb)
            else:
                grads, mutated, loss = self._jit_call(
                    self._grad_fn,
                    self.pvals, self.avals, self._key,
                    self._scale_state[0], xb, yb)
        # accumulate in f32 even when bf16 grads flow (bf16 window sums
        # would round; apply_fn's AOT signature consumes f32 grads) —
        # astype is a no-op for already-f32 grads
        self._accum = [g.astype(jnp.float32) for g in grads] \
            if self._accum is None else \
            [a + g for a, g in zip(self._accum, grads)]
        self._micro += 1
        self._write_back(mutated)
        if self._micro >= self.grad_accum:
            self._t += 1
            lr = jnp.float32(self.learning_rate)
            avg = [g / self.grad_accum for g in self._accum]
            aot = self._aot_fn("apply") if self._aot else None
            with _tr.span("trainer.apply_update", aot=aot is not None):
                if aot is not None:
                    (self.pvals, self.opt_state, self._scale_state) = aot(
                        self.pvals, self.opt_state, self._t, lr,
                        self._scale_state, avg)
                else:
                    (self.pvals, self.opt_state, self._scale_state) = \
                        self._jit_call(
                            self._apply_fn, self.pvals, self.opt_state,
                            self._t, lr, self._scale_state, avg)
            self._accum, self._micro = None, 0
            self._write_back_params()
            self._publish_amp_gauges()
        # micro-step losses chain to the last apply through pvals, so
        # bounding on them transitively bounds the applies too
        self._inflight.push(loss)
        return NDArray(loss)

    # -- checkpoint (ref Trainer.save_states/load_states) -------------------
    def save_states(self, fname: str):
        """Full training state → one .npz: params (train+aux), optimizer
        state leaves, RNG key, step count, loss scale. Arrays are gathered
        to host unsharded (zero1 leaves with their shard padding stripped),
        so the file restores onto ANY mesh shape and ANY partition."""
        import numpy as onp

        if self._micro != 0:
            # load_states resets the accumulator, so a checkpoint taken
            # mid-window would silently drop consumed micro-batches
            raise MXNetError(
                f"save_states called mid gradient-accumulation window "
                f"({self._micro}/{self.grad_accum} micro-batches pending); "
                f"step to a window boundary first")
        self.drain()  # retire in-flight steps before snapshotting state
        with _tr.span("ckpt.save_states", step=self._t):
            blob: Dict[str, Any] = {}
            for n, v in zip(self.train_names, self.pvals):
                blob[f"param/{n}"] = onp.asarray(v)
            for n, v in zip(self.aux_names, self.avals):
                blob[f"aux/{n}"] = onp.asarray(v)
            for i, s in enumerate(self.opt_state):
                a = onp.asarray(s)
                up = self._leaf_unpad[i]
                if up is not None:
                    ax, size = up
                    a = a[tuple(slice(size) if k == ax else slice(None)
                                for k in range(a.ndim))]
                blob[f"opt/{i}"] = a
            blob["meta/t"] = onp.asarray(self._t)
            blob["meta/key"] = onp.asarray(self._key)
            blob["meta/scale"] = onp.asarray(self._scale_state[0])
            blob["meta/good"] = onp.asarray(self._scale_state[1])
            blob["meta/skipped"] = onp.asarray(self._scale_state[2])
            from ..resilience.checkpoint import write_payload

            # atomic (tmp + fsync + os.replace, docs/resilience.md): a
            # preempted VM mid-write must not tear the only checkpoint
            write_payload(fname, lambda f: onp.savez(f, **blob))

    def load_states(self, fname: str):
        """Restore a save_states checkpoint onto THIS trainer's mesh: each
        array is re-placed per the trainer's sharding specs."""
        with _tr.span("ckpt.load_states"):
            self._load_states_impl(fname)

    def _load_states_impl(self, fname: str):
        import numpy as onp

        with onp.load(fname) as z:
            blob = {k: z[k] for k in z.files}
        spec_of = dict(zip(self.names, self.specs))

        def place(name, v):
            return jax.device_put(jnp.asarray(v), NamedSharding(
                self.mesh, spec_of.get(name, P())))

        for key in list(blob):
            if key.startswith("param/"):
                n = key[len("param/"):]
                if n not in self.train_names:
                    raise MXNetError(f"checkpoint param '{n}' unknown")
        self.pvals = [place(n, blob[f"param/{n}"]) for n in self.train_names]
        self.avals = [place(n, blob[f"aux/{n}"]) for n in self.aux_names]

        _layout_mismatch = _layout_mismatch_error

        n_blob = sum(1 for k in blob if k.startswith("opt/"))
        if n_blob != len(self.opt_state):
            # catches BOTH directions of a per-param<->arena mismatch for
            # multi-param nets (leaf counts differ) before any placement
            raise _layout_mismatch(
                f"{n_blob} saved leaves, {len(self.opt_state)} expected")

        def place_leaf(i):
            # checkpoints carry UNPADDED leaves (save_states strips the
            # zero1 shard padding), so they restore across partitions and
            # mesh shapes; re-pad onto THIS trainer's storage layout
            v = jnp.asarray(blob[f"opt/{i}"])
            up = self._leaf_unpad[i]
            if up is not None and v.shape[up[0]] < self._leaf_shapes[i][up[0]]:
                v = _pad_dim(v, up[0], self._leaf_shapes[i][up[0]])
            if v.shape == self._leaf_shapes[i]:
                return jax.device_put(v, self._state_shardings[i])
            if isinstance(self._adapter,
                          (_ArenaOptAdapter, _OverlapOptAdapter)):
                # a per-param-layout checkpoint CANNOT silently feed the
                # arena kernel (leaf 0 would be one param's momentum, not
                # the arena) — unlike the mesh-shape fallback below this
                # is a layout mismatch, not a placement one
                raise _layout_mismatch(
                    f"leaf {i} has shape {tuple(v.shape)}, expected arena "
                    f"shape {self._leaf_shapes[i]}")
            if v.ndim != len(self._leaf_shapes[i]):
                # the reverse direction: a flat (padded,) arena leaf must
                # not silently become one param's replicated momentum.
                # Legitimate cross-mesh/partition restores only change
                # SIZES (zero1 padding stripped at save), never rank
                raise _layout_mismatch(
                    f"leaf {i} has rank {v.ndim}, expected rank "
                    f"{len(self._leaf_shapes[i])}")
            return jax.device_put(v, NamedSharding(self.mesh, P()))

        self.opt_state = [place_leaf(i)
                          for i in range(len(self.opt_state))]
        self._t = int(blob["meta/t"])
        self._key = jnp.asarray(blob["meta/key"])
        self._scale_state = (jnp.float32(blob["meta/scale"]),
                             jnp.int32(blob["meta/good"]),
                             # absent in pre-precision-ladder checkpoints
                             jnp.int32(blob.get("meta/skipped", 0)))
        params = self._params
        for n, v in zip(self.train_names, self.pvals):
            params[n].data()._set_data(v)
        for n, v in zip(self.aux_names, self.avals):
            params[n].data()._set_data(v)
        from ..random import key_holder

        key_holder()._set_data(self._key)
        self._accum, self._micro = None, 0
        self._pp_buf = []
        self._publish_layout_gauges()

    # -- shard-wise checkpoints (manifest v2, resilience.reshard) ------------

    def _shard_leaves(self):
        """(key, value, clip_shape) triples in checkpoint order — the
        leaf enumeration shared by the shard-wise writer and reader.
        ``clip_shape`` strips the zero1/arena shard padding (same
        convention as ``save_states``) so slices live in dp-independent
        logical coordinates."""
        leaves = []
        for n, v in zip(self.train_names, self.pvals):
            leaves.append((f"param/{n}", v, None))
        for n, v in zip(self.aux_names, self.avals):
            leaves.append((f"aux/{n}", v, None))
        for i, s in enumerate(self.opt_state):
            up = self._leaf_unpad[i]
            clip = None
            if up is not None:
                shp = list(self._leaf_shapes[i])
                shp[up[0]] = up[1]
                clip = tuple(shp)
            leaves.append((f"opt/{i}", s, clip))
        return leaves

    def state_shards(self, dirname: str):
        """Write this trainer's full state shard-wise under ``dirname``
        (one ``shards.bin``): each leaf lands as the SOURCE sharding's
        slices — replicas deduplicated, zero1/arena padding clipped per
        slice, no full-leaf host gather for sharded leaves.  Returns
        the ``(leaves, meta)`` sections :class:`~..resilience.checkpoint
        .CheckpointManager` embeds in its manifest-v2 commit record."""
        import numpy as onp

        if self._micro != 0:
            raise MXNetError(
                f"state_shards called mid gradient-accumulation window "
                f"({self._micro}/{self.grad_accum} micro-batches "
                f"pending); step to a window boundary first")
        self.drain()
        from ..resilience import reshard as _reshard

        with _tr.span("ckpt.state_shards", step=self._t):
            leaves = _reshard.write_shards(dirname, self._shard_leaves())
        key = onp.asarray(self._key)
        meta = {"t": int(self._t),
                "key": key.tolist(), "key_dtype": key.dtype.name,
                "scale": float(self._scale_state[0]),
                "good": int(self._scale_state[1]),
                "skipped": int(self._scale_state[2])}
        return leaves, meta

    def _place_shardwise(self, rdr, rec, storage, sharding, stats):
        """Place one manifest-v2 leaf onto ``sharding``.  Partitioned
        targets assemble per-device shards from ONLY the source slices
        each shard intersects (the all-gather-free redistribution path
        — no rank materializes a full leaf it doesn't hold); replicated
        targets read the leaf once.  Zero-pads from the unpadded
        logical shape toward ``storage`` (this trainer's zero1/arena
        layout — the reshard-instead-of-raise semantics of
        docs/sharding.md)."""
        import numpy as onp

        from ..resilience import reshard as _reshard

        storage = tuple(int(d) for d in storage)
        src_boxes = {s.box for s in rec.slices}
        if getattr(sharding, "is_fully_replicated", True):
            v = rdr.read(rec.key)
            if v.shape != storage:
                out = onp.zeros(storage, v.dtype)
                out[tuple(slice(d) for d in v.shape)] = v
                v = out
            if src_boxes != {tuple((0, d) for d in rec.shape)}:
                stats["leaves_resharded"] += 1
            return jax.device_put(jnp.asarray(v), sharding)
        dmap = sharding.devices_indices_map(storage)
        pi = jax.process_index()
        arrs = []
        tgt_boxes = set()
        for d, idx in dmap.items():
            gbox = _reshard.box_of(idx, storage)
            cbox = _reshard.clip_box(gbox, rec.shape)
            if cbox is not None:
                tgt_boxes.add(cbox)
            # manifest-only accounting, per target device: what THIS
            # shard costs to read wherever its rank lives (on a pod each
            # process only reads its own devices' rows of this table)
            rb = stats["_rank_bytes"]
            rb[d.id] = rb.get(d.id, 0) + _reshard.plan_bytes(
                rec, [cbox] if cbox is not None else [])
            if d.process_index != pi:
                continue
            local = onp.zeros(tuple(b - a for a, b in gbox), rec.dtype)
            if cbox is not None:
                sub = rdr.read(rec.key, cbox)
                local[tuple(slice(c0 - g0, c1 - g0)
                            for (g0, _), (c0, c1)
                            in zip(gbox, cbox))] = sub
            arrs.append(jax.device_put(jnp.asarray(local), d))
        stats["sharded_full_bytes"] += _reshard.full_bytes(rec)
        if tgt_boxes != src_boxes:
            stats["leaves_resharded"] += 1
        return jax.make_array_from_single_device_arrays(
            storage, sharding, arrs)

    def load_state_shards(self, dirname: str, manifest: dict):
        """Restore a manifest-v2 (shard-wise) checkpoint onto THIS
        trainer's mesh: every leaf is re-sliced from the source
        sharding's slices straight onto the target sharding — source
        padding stripped at save, re-padded here to the target
        zero1/arena layout — reading only the slices the target shards
        intersect.  Leaf-count and leaf-rank mismatches (per-param vs
        flat-arena layouts) still raise loudly.  Restore accounting
        lands on ``self.last_restore_stats``; a cross-sharding restore
        ticks ``resilience.reshards``."""
        with _tr.span("ckpt.load_state_shards"):
            self._load_state_shards_impl(dirname, manifest)

    def _load_state_shards_impl(self, dirname: str, manifest: dict):
        import numpy as onp

        from ..resilience import reshard as _reshard

        leaves = _reshard.leaves_from_json(manifest["leaves"])
        try:
            meta = manifest["meta"]
            meta_t = int(meta["t"])
            meta_key = onp.asarray(meta["key"],
                                   dtype=meta.get("key_dtype", "uint32"))
            meta_scale = float(meta["scale"])
            meta_good = int(meta["good"])
            meta_skipped = int(meta.get("skipped", 0))
        except (KeyError, TypeError, ValueError) as e:
            raise MXNetError(
                f"manifest v2 'meta' section is malformed: {e}") from e
        by_key = {leaf.key: leaf for leaf in leaves}
        for leaf in leaves:
            if leaf.key.startswith("param/") and \
                    leaf.key[len("param/"):] not in self.train_names:
                raise MXNetError(
                    f"checkpoint param "
                    f"'{leaf.key[len('param/'):]}' unknown")
        n_blob = sum(1 for k in by_key if k.startswith("opt/"))
        if n_blob != len(self.opt_state):
            raise _layout_mismatch_error(
                f"{n_blob} saved leaves, {len(self.opt_state)} expected")
        spec_of = dict(zip(self.names, self.specs))
        stats = {"bytes_read": 0, "sharded_full_bytes": 0,
                 "sharded_max_rank_bytes": 0, "leaves_resharded": 0,
                 "_rank_bytes": {}}
        placed: Dict[str, Any] = {}
        with _reshard.ShardReader(dirname, leaves) as rdr:
            for key, cur, clip in self._shard_leaves():
                rec = by_key.get(key)
                if rec is None:
                    raise MXNetError(
                        f"checkpoint is missing leaf {key!r}")
                if key.startswith("opt/"):
                    i = int(key[len("opt/"):])
                    storage = self._leaf_shapes[i]
                    sharding = self._state_shardings[i]
                    logical = clip if clip is not None else storage
                    if len(rec.shape) != len(logical):
                        raise _layout_mismatch_error(
                            f"leaf {i} has rank {len(rec.shape)}, "
                            f"expected rank {len(logical)}")
                    if tuple(rec.shape) != tuple(logical):
                        raise _layout_mismatch_error(
                            f"leaf {i} has shape {tuple(rec.shape)}, "
                            f"expected unpadded shape {tuple(logical)}")
                else:
                    name = key.split("/", 1)[1]
                    sharding = NamedSharding(self.mesh,
                                             spec_of.get(name, P()))
                    storage = tuple(cur.shape)
                    if tuple(rec.shape) != storage:
                        raise MXNetError(
                            f"checkpoint leaf {key!r} has shape "
                            f"{tuple(rec.shape)}; this trainer expects "
                            f"{storage}")
                placed[key] = self._place_shardwise(
                    rdr, rec, storage, sharding, stats)
            stats["bytes_read"] = rdr.bytes_read
        # every leaf placed and meta validated — mutate atomically from
        # here (a failure above leaves the trainer untouched)
        self.pvals = [placed[f"param/{n}"] for n in self.train_names]
        self.avals = [placed[f"aux/{n}"] for n in self.aux_names]
        self.opt_state = [placed[f"opt/{i}"]
                          for i in range(len(self.opt_state))]
        self._t = meta_t
        self._key = jnp.asarray(meta_key)
        self._scale_state = (jnp.float32(meta_scale),
                             jnp.int32(meta_good),
                             jnp.int32(meta_skipped))
        params = self._params
        for n, v in zip(self.train_names, self.pvals):
            params[n].data()._set_data(v)
        for n, v in zip(self.aux_names, self.avals):
            params[n].data()._set_data(v)
        from ..random import key_holder

        key_holder()._set_data(self._key)
        self._accum, self._micro = None, 0
        self._pp_buf = []
        rank_bytes = stats.pop("_rank_bytes")
        stats["sharded_max_rank_bytes"] = max(rank_bytes.values(),
                                              default=0)
        if stats["leaves_resharded"]:
            _tel.inc("resilience.reshards")
        self.last_restore_stats = stats
        self._publish_layout_gauges()
