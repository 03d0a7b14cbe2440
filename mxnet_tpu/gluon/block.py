"""gluon.Block / HybridBlock — the layer system.

Ref: python/mxnet/gluon/block.py (Block:203, HybridBlock:998,
SymbolBlock:1716). TPU-native redesign of the hybridize machinery
(SURVEY.md §3.3): the reference traces ``forward`` once under
deferred-compute into an nnvm Symbol and replays it through CachedOp
(src/imperative/cached_op.cc:776) with its own memory planner and fusion
passes; here ``hybridize()`` swaps the call path to a ``jax.jit``-compiled
function of (parameters, rng key, inputs) — XLA is the pass pipeline. The
subtleties live in ``_CachedOp``:

  * parameters + the global RNG key are lifted to traced inputs, so random
    ops stay live across calls instead of baking one sample;
  * in-place NDArray mutations during the trace (BatchNorm moving stats,
    RNG advance, any user ``a[:] =``) are captured by the mutation-watcher
    protocol (ndarray._mutation_scope) and returned as extra jit outputs,
    then rebound eagerly — replacing the reference's mutable-graph
    semantics losslessly;
  * under ``autograd.record()``, the whole jitted call is recorded as ONE
    tape node via ops.dispatch.invoke — mirroring CachedOp's lazily-built
    backward graph (cached_op.cc:1016) with jax.vjp through the jit.

Deferred parameter init (ref block.py HybridBlock.infer_shape): layers
implement ``infer_shape(*args)``; ``__call__`` catches
DeferredInitializationError, infers, finishes init, retries — compositional
because each child handles its own.
"""
from __future__ import annotations

import time as _time
from typing import Any, Callable, Dict, List, Optional, Tuple

import threading

import jax
import jax.numpy as jnp

from .. import telemetry as _tel
from ..trace import recorder as _tr
from ..analysis import retrace as _retrace
from ..analysis import xla_lint as _xlint
from ..base import DeferredInitializationError, MXNetError
from ..context import Context, current_context
from ..jit import cache as _jit_cache
from ..jit.bucketing import ShapeBucketer
from ..ndarray.ndarray import NDArray, _mutation_scope
from ..ops.dispatch import invoke as _invoke
from .parameter import Constant, Parameter
from .. import autograd as _autograd

__all__ = ["Block", "HybridBlock", "SymbolBlock", "WarmupHandle",
           "pipeline_atoms"]


class _Static:
    """A leaf of a flattened argument tree that is no NDArray.  The trace
    bakes its value in, so it keys the signature, by its type and ``repr``
    (which every value has: a hash or an ``==`` would merge ``1`` with
    ``True`` and part NaN from itself)."""

    __slots__ = ("value", "_key")

    def __init__(self, value):
        self.value = value
        self._key = (type(value), repr(value))

    def __eq__(self, other):
        return isinstance(other, _Static) and self._key == other._key

    def __hash__(self):
        return hash(self._key)


def _flatten_into(o, leaves):
    if isinstance(o, NDArray):
        leaves.append(o)
        return ("@",)
    if o is None:
        return (None,)
    if isinstance(o, (list, tuple)):
        return (type(o).__name__, tuple([_flatten_into(x, leaves) for x in o]))
    if isinstance(o, dict):
        return ("dict", tuple([(k, _flatten_into(v, leaves))
                               for k, v in sorted(o.items())]))
    return ("#", _Static(o))  # static aux value


def _flatten_nd(obj):
    """Flatten nested (list/tuple/dict) structures of NDArrays into the
    leaves and a tree of tuples, which hashes: a hybridized call's key.  No
    closure that names itself: one would be a reference cycle holding
    ``leaves``, so every argument of every hybridized call -- a decode
    admission's whole row cache -- would outlive its last reference until
    the cycle collector next ran."""
    leaves: List[NDArray] = []
    return leaves, _flatten_into(obj, leaves)


def _unflatten_from(t, it, wrap):
    tag = t[0]
    if tag == "@":
        return wrap(next(it))
    if tag is None:
        return None
    if tag == "list":
        return [_unflatten_from(x, it, wrap) for x in t[1]]
    if tag == "tuple":
        return tuple([_unflatten_from(x, it, wrap) for x in t[1]])
    if tag == "dict":
        return {k: _unflatten_from(v, it, wrap) for k, v in t[1]}
    if tag == "#":
        return t[1].value
    return t[1]


def _unflatten_nd(tree, leaves, wrap=lambda v: v):
    return _unflatten_from(tree, iter(leaves), wrap)


class Block:
    """Base container (ref block.py:203). Attribute assignment registers
    children and Parameters, like the reference's Gluon 2.0 (no name_scope)."""

    def __init__(self, prefix=None, params=None):
        self._children: "Dict[str, Block]" = {}
        self._reg_params: "Dict[str, Parameter]" = {}
        self._forward_hooks: List[Callable] = []
        self._forward_pre_hooks: List[Callable] = []

    # -- registration -------------------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            existing = self.__dict__.get("_reg_params")
            if existing is not None:
                existing[name] = value
        super().__setattr__(name, value)

    def register_child(self, block: "Block", name: Optional[str] = None):
        self._children[name if name is not None else str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return _HookHandle(self._forward_hooks, hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return _HookHandle(self._forward_pre_hooks, hook)

    def _all_blocks(self):
        """This block + every descendant (any Block subclass)."""
        yield self
        for c in self._children.values():
            if isinstance(c, Block):
                yield from c._all_blocks()

    # -- parameter access ---------------------------------------------------
    def collect_params(self, select: Optional[str] = None) -> "Dict[str, Parameter]":
        """Structured-name → Parameter dict (ref block.py collect_params)."""
        out: Dict[str, Parameter] = {}

        def rec(block: Block, prefix: str):
            for pname, p in block._reg_params.items():
                full = prefix + pname
                p._structure_name = full
                out[full] = p
            for cname, c in block._children.items():
                rec(c, prefix + cname + ".")

        rec(self, "")
        if select is not None:
            import re

            pat = re.compile(select)
            out = {k: v for k, v in out.items() if pat.match(k)}
        return out

    @property
    def params(self):
        return self._reg_params

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False, device=None):
        """Initialize all parameters; ``init`` is the default for params
        without their own initializer (ref Block.initialize)."""
        from .. import initializer as _init_mod

        default = init if init is not None else _init_mod.Uniform()
        if isinstance(default, str):
            default = _init_mod.create(default)
        for p in self.collect_params().values():
            p.initialize(init=None, ctx=ctx or device, default_init=default,
                         force_reinit=force_reinit)
        return self

    def cast(self, dtype):
        for p in self.collect_params().values():
            p.cast(dtype)
        for c in self._children.values():
            c.cast(dtype)
        return self

    def zero_grad(self):
        for p in self.collect_params().values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self.collect_params().values():
            p.reset_ctx(ctx)

    reset_device = reset_ctx

    def apply(self, fn):
        for c in self._children.values():
            c.apply(fn)
        fn(self)
        return self

    def setattr(self, name, value):
        """Set an attr on all registered params (ref Block.setattr), e.g.
        net.setattr('grad_req', 'null')."""
        for p in self.collect_params().values():
            setattr(p, name, value)

    # -- save / load --------------------------------------------------------
    def save_parameters(self, filename: str, deduplicate: bool = False):
        """Ref block.py:341 — structured-name keyed weights file."""
        from ..ndarray.utils import save

        arg_dict = {name: p.data() for name, p in self.collect_params().items()
                    if p._data is not None}
        save(filename, arg_dict)

    def load_parameters(self, filename: str, ctx=None, allow_missing: bool = False,
                        ignore_extra: bool = False, cast_dtype: bool = False,
                        dtype_source: str = "current", device=None):
        """Ref block.py:379."""
        from ..ndarray.utils import load

        loaded = load(filename)
        params = self.collect_params()
        if not allow_missing:
            for name in params:
                if name not in loaded and params[name]._data is None and \
                        params[name]._deferred_init is None:
                    pass  # uninitialized-and-unsaved handled below
                if name not in loaded:
                    raise MXNetError(
                        f"Parameter '{name}' is missing in file '{filename}'. "
                        "Set allow_missing=True to ignore missing parameters.")
        for name, value in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise MXNetError(
                        f"Parameter '{name}' loaded from file '{filename}' is "
                        "not present in the Block. Set ignore_extra=True to ignore.")
                continue
            p = params[name]
            if cast_dtype:
                p.cast(value._data.dtype)
            p.set_data(value)
        return self

    def save(self, prefix):
        """Structured whole-model save (ref block.py:577)."""
        self.save_parameters(prefix + "-model.params")

    def load(self, prefix):
        self.load_parameters(prefix + "-model.params")

    # -- call path ----------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        try:
            out = self.forward(*args, **kwargs)
        except DeferredInitializationError:
            self._deferred_infer_and_init(*args, **kwargs)
            out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def _deferred_infer_and_init(self, *args, **kwargs):
        infer = getattr(self, "infer_shape", None)
        if infer is None:
            raise
        infer(*args, **kwargs)
        for p in self._reg_params.values():
            p._finish_deferred_init()

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def hybridize(self, active: bool = True, **kwargs):
        """On a plain Block: recurse (ref Block.hybridize)."""
        for c in self._children.values():
            c.hybridize(active, **kwargs)

    def summary(self, *inputs):
        lines = [f"{type(self).__name__}:"]
        for name, p in self.collect_params().items():
            lines.append(f"  {name:60s} {str(p.shape):20s} {p.dtype}")
        total = sum(int(jnp.prod(jnp.array(p.shape))) for p in self.collect_params().values()
                    if p.shape is not None)
        lines.append(f"  total parameters: {total}")
        print("\n".join(lines))

    def __repr__(self):
        mods = "\n".join(f"  ({k}): {type(v).__name__}" for k, v in self._children.items())
        return f"{type(self).__name__}(\n{mods}\n)" if mods else f"{type(self).__name__}()"


class _HookHandle:
    def __init__(self, lst, fn):
        self._lst, self._fn = lst, fn

    def detach(self):
        if self._fn in self._lst:
            self._lst.remove(self._fn)


# One process-wide lock for every state-swapping jit trace.  A trace
# temporarily swaps shared Parameter ._data and the global RNG key to
# tracers (raw() below; same protocol in parallel.trainer's
# _functional_apply), so with background AOT warmup in the picture TWO
# kinds of races exist: two traces interleaving their swaps, and an
# eager READER (a forward's state collection, ShardedTrainer capturing
# params/key) observing mid-trace tracers.  Both serialize on this
# RLock: traces hold it for their duration, readers take it briefly —
# a reader that would have captured a tracer instead blocks until the
# trace's finally-restore has run.  Reentrant, because a trace may
# nest state collection.
_TRACE_LOCK = threading.RLock()

# The hybridized call in flight on each thread: ``raw()`` in
# ``_CachedOp._new_holder`` marks it when jax traces the program for it;
# a trace that no call made (``eval_shape``, ``lower``, a warm-up's own
# execution) finds nothing to mark.
_DISPATCH = threading.local()


def trace_guard():
    """The global trace lock (docs/jit.md): wrap reads of live model
    state (``Parameter.data()``, the RNG key holder) that may run
    concurrently with a background ``warmup()`` trace."""
    return _TRACE_LOCK


def pipeline_atoms(block) -> "List[Block]":
    """Flatten ``block`` into the ordered unit list that pipeline-stage
    splitting partitions (``parallel.pipeline.split_stages``): direct
    children in registration order, with ``(Hybrid)Sequential``
    containers recursed into — their forward IS the children fold, so
    their atoms may legally land in different stages.  Any other
    composite child stays ONE atom (its forward may branch arbitrarily
    across its children).  Whether the top-level registration order
    itself composes to ``block``'s forward cannot be proven here;
    ``ShardedTrainer`` validates it numerically before the first
    pipelined step.  A block with no children is its own single atom."""
    from .nn.basic_layers import HybridSequential, Sequential

    def rec(b):
        if isinstance(b, (Sequential, HybridSequential)):
            out = []
            for c in b._children.values():
                out.extend(rec(c))
            return out
        return [b]

    atoms = []
    for c in block._children.values():
        atoms.extend(rec(c))
    return atoms if atoms else [block]


def _pad_args(bucketer: ShapeBucketer, args):
    """Pad NDArray leaves in ``args`` up to their bucket shapes
    (device-side ``jnp.pad``; the tiny pad program is cached per source
    shape and costs microseconds — the point is that the MODEL compiles
    at most once per bucket).  Returns ``(padded_args, unpad_fn)``;
    ``unpad_fn`` is ``None`` when nothing padded.

    ``unpad_fn`` slices output leaves back to the original sizes: for
    every axis this call padded, an output axis of exactly the padded
    size is cut back to the original.  That is the right inverse for
    batch/sequence axes that flow through the graph unchanged (every
    per-sample / causal-time architecture); disable via
    ``hybridize(bucketer=None)`` for models where an output dimension
    legitimately equals the bucket size.  When two input leaves pad the
    same axis to DIFFERENT (orig, padded) sizes (e.g. src/tgt sequences
    of different lengths), the mapping is ambiguous and that axis is
    left padded rather than sliced wrong — mask/slice such outputs
    yourself."""
    import jax.numpy as jnp

    padded_axes: Dict[int, set] = {}

    def pad_leaf(x: NDArray) -> NDArray:
        shape = tuple(x.shape)
        target = bucketer.bucket_shape(shape)
        if target == shape:
            return x
        widths = [(0, t - s) for s, t in zip(shape, target)]
        for a in bucketer.spec:
            if a < len(shape) and shape[a] != target[a]:
                padded_axes.setdefault(a, set()).add(
                    (shape[a], target[a]))
        return NDArray(jnp.pad(x._data, widths,
                               constant_values=bucketer.pad_value))

    def rec(o):
        if isinstance(o, NDArray):
            return pad_leaf(o)
        if isinstance(o, (list, tuple)):
            return type(o)(rec(v) for v in o)
        if isinstance(o, dict):
            return {k: rec(v) for k, v in o.items()}
        return o

    new_args = rec(args)
    # only unambiguous axes are invertible: one (orig, padded) pair
    cut_axes = {a: next(iter(pairs))
                for a, pairs in padded_axes.items() if len(pairs) == 1}
    if not cut_axes:
        return (new_args, None) if padded_axes else (args, None)

    def unpad(out):
        def cut(o):
            if isinstance(o, NDArray):
                shape = tuple(o.shape)
                sl = [slice(None)] * len(shape)
                hit = False
                for a, (orig, pad) in cut_axes.items():
                    if a < len(shape) and shape[a] == pad:
                        sl[a] = slice(0, orig)
                        hit = True
                return NDArray(o._data[tuple(sl)]) if hit else o
            if isinstance(o, (list, tuple)):
                return type(o)(cut(v) for v in o)
            if isinstance(o, dict):
                return {k: cut(v) for k, v in o.items()}
            return o

        return cut(out)

    return new_args, unpad


class _CachedOp:
    """jit-backed graph executor for one HybridBlock (≈ CachedOp,
    src/imperative/cached_op.cc). See module docstring for semantics.

    A call collects the raw arrays, calls the jitted program and wraps
    what comes back: jax's own cache decides hit or miss.  The call learns
    of a miss from ``raw()``, which runs only while jax traces and marks
    the call in flight on its thread, or from jax's dispatch cache for the
    program growing (a signature ``eval_shape`` or ``lower`` traced
    earlier compiles without a second trace).  Only then does it build the
    signature (``_sig_of``) that ``hybridize.cache_misses``, the retrace
    guard and ``warmup`` go by.  Lock discipline: ``raw()`` holds the
    global trace lock for its trace alone, and the XLA compile after it
    runs unlocked."""

    def __init__(self, block: "HybridBlock"):
        self.block = block
        self._holders: Dict[Any, dict] = {}
        # a trace temporarily swaps shared Parameter ._data to tracers
        # (raw() below) — two threads tracing at once would leak tracers
        # into each other, and so would an eager reader racing a
        # background warmup trace.  All traces share the module-global
        # _TRACE_LOCK (see trace_guard); compiled calls never take it
        # beyond the state collection.
        self._trace_lock = _TRACE_LOCK
        self._traced: set = set()
        self._calls = 0
        self._name = f"cached_op_{type(block).__name__}"
        # collect_params() is a recursive tree walk; doing it per forward
        # dominates small-model dispatch (VERDICT weak #5; ref CachedOp
        # computes its ref-counted input set once, cached_op.h:290). The
        # Parameter OBJECT list is structure-dependent only — cleared by
        # hybridize()/clear(); per-call work is just the ._data fetch.
        self._param_cache: Optional[List["Parameter"]] = None

    def clear(self):
        self._holders.clear()
        self._traced.clear()
        self._calls = 0
        self._param_cache = None

    def _note_trace(self, sig, n_calls: Optional[int] = None):
        """Record a newly traced signature and let the retrace guard
        (mx.analysis.retrace) flag unbounded signature growth — J001
        names the input slot whose shape keeps changing, J002 flags a
        shape-churn storm on blocks with no bucketer attached."""
        self._traced.add(sig)
        _retrace.on_trace(
            type(self.block).__name__, sig, self._traced, n_calls=n_calls,
            bucketed=getattr(self.block, "_bucketer", None) is not None)

    def _lint_compiled(self, jit_fn, raw_inputs, donated=()):
        """MXNET_XLA_LINT hook — executables born here (warmup or first
        call) get the X-rule pass (analysis/xla_lint).  The re-lower finds
        jax's trace (a new one would take the trace lock inside
        ``raw()``); the compile runs UNLOCKED — a disk hit when the
        persistent cache is armed, a real second compile otherwise (the
        opt-in flag buys that cost).  ``donated`` is the jit's flat
        donate_argnums (holder record) — X004 checks each against the
        executable's actual aliasing.  Lint failures other than the
        =raise verdict never break the compile path."""
        if not _xlint.enabled():
            return
        try:
            lowered = jit_fn.lower(*raw_inputs)
            compiled = lowered.compile()
        except Exception:  # pragma: no cover - lint is best-effort
            return
        label = getattr(self.block, "_xla_lint_label",
                        type(self.block).__name__)
        budget = getattr(self.block, "_xla_lint_budget", None)
        exe_donated: Tuple[int, ...] = ()
        if donated:
            # jit prunes unused leaves: map the flat donate_argnums onto
            # the executable's parameter numbering.  A donated leaf jit
            # pruned entirely is dead weight, not a live double buffer;
            # an unknowable map (None) must never guess indices.
            kept = _xlint._kept_param_map(compiled)
            if kept is not None:
                exe_donated = tuple(kept[i] for i in donated if i in kept)
        _xlint.report(_xlint.lint_compiled(
            compiled, name=f"hybridize:{label}", budget=budget,
            donated_params=exe_donated,
            lowered_text=lowered.as_text()))

    def _prepare(self, args, training: bool):
        """Resolve ``(key, jit_fn, inputs, holder)`` for ``args``,
        building the jit wrapper lazily (the compile itself happens at
        the first execution of a new input signature)."""
        from ..random import key_holder

        params = self._param_cache
        if params is None:
            params = self._param_cache = \
                list(self.block.collect_params().values())
        # state collection under the trace guard: a background warmup
        # trace has these same arrays swapped to tracers mid-trace, and
        # capturing one here would poison this call's inputs
        with _TRACE_LOCK:
            state: List[NDArray] = \
                [d for p in params if (d := p._data) is not None]
            state.append(key_holder())
        arg_leaves, arg_tree = _flatten_nd(args)
        key = (training, arg_tree, len(state))
        holder = self._holders.get(key)
        if holder is None:
            holder = self._new_holder(key, args, training)
        holder["state"] = state
        return key, holder["jit"], state + arg_leaves, holder

    def _new_holder(self, key, args, training: bool) -> dict:
        """The jitted program of one call structure ``key``, and what its
        traces record: the output tree, the state arrays a trace mutates,
        each argument shape set it was traced at (``symbolize()``)."""
        _, arg_tree, n_state = key
        # arm the persistent compilation cache before the first jit
        # of this block exists — the upcoming compile must already
        # be able to hit/fill the on-disk cache (mx.jit.cache)
        cache_armed = _jit_cache.ensure_cache() is not None
        donate_argnums = self._donate_argnums(args, n_state, training,
                                              cache_armed)
        holder = {"donate_argnums": donate_argnums, "arg_specs": []}
        block = self.block

        def raw(*vals):
            # runs only while jax traces: mark the call in flight on this
            # thread (none for eval_shape, lower or a warm-up), and hold
            # the trace lock through the swap of state to tracers
            with _TRACE_LOCK:
                if getattr(_DISPATCH, "traced", None) is False:
                    _DISPATCH.traced = True
                sarr = holder["state"]
                svals, avals = vals[:n_state], vals[n_state:]
                spec = tuple((v.shape, v.dtype) for v in avals)
                if spec not in holder["arg_specs"]:
                    holder["arg_specs"].append(spec)
                saved = [(a, a._data) for a in sarr]
                ms = _mutation_scope()
                try:
                    with _autograd.pause(train_mode=training), ms:
                        for a, v in zip(sarr, svals):
                            a._data = v
                        call_args = _unflatten_nd(arg_tree, avals,
                                                  wrap=NDArray)
                        out = block.forward(*call_args)
                    out_leaves, out_tree = _flatten_nd(out)
                    state_ids = {id(a) for a in sarr}
                    # keep mutations of pre-existing arrays: state arrays
                    # (their pre-trace value is the swapped-in tracer) and
                    # any array that existed before the trace
                    mutated = [
                        (a, a._data) for (a, prev) in ms.mutated.values()
                        if id(a) in state_ids
                        or not isinstance(prev, jax.core.Tracer)
                    ]
                    holder["out_tree"] = out_tree
                    holder["mutated_refs"] = [a for a, _ in mutated]
                    holder["n_out"] = len(out_leaves)
                    return tuple(o._data for o in out_leaves) + \
                        tuple(v for _, v in mutated)
                finally:
                    for a, v in saved:
                        a._data = v
                    for a, prev in ms.mutated.values():
                        if not isinstance(prev, jax.core.Tracer):
                            a._data = prev

        holder["jit"] = (jax.jit(raw, donate_argnums=donate_argnums)
                         if donate_argnums else jax.jit(raw))
        with self._trace_lock:
            return self._holders.setdefault(key, holder)

    def _donate_argnums(self, args, n_state: int, training: bool,
                        cache_armed: bool) -> Tuple[int, ...]:
        """Flat jit-arg indices to donate: the block's ``donate_args``
        (top-level forward-arg positions, set by ``hybridize()``) mapped
        onto the flat leaf numbering of the jitted signature (state
        arrays first, then the args' leaves in order).  Inference-only —
        a training graph re-reads its inputs on the backward pass.
        Dropped on the CPU backend when the persistent compile cache is
        armed: XLA:CPU executables deserialized from the cache corrupt
        donated buffers (same guard as parallel/trainer.py)."""
        donate = getattr(self.block, "_donate_args", None)
        if not donate or training:
            return ()
        if cache_armed and jax.default_backend() == "cpu":
            return ()
        idx: List[int] = []
        off = n_state
        for pos, a in enumerate(args):
            leaves, _ = _flatten_nd(a)
            if pos in donate:
                idx.extend(range(off, off + len(leaves)))
            off += len(leaves)
        return tuple(idx)

    @staticmethod
    def _sig_of(key, inputs) -> tuple:
        return (key, tuple((x.shape, str(x._data.dtype)) for x in inputs))

    def warmup(self, args, training: bool = False) -> bool:
        """AOT-compile the signature of ``args`` without touching model
        state.  The jitted fn is pure — parameter values ride in as
        inputs and mutations (BN stats, RNG advance) come back as extra
        outputs that only ``__call__`` rebinds — so executing it once on
        sample inputs and discarding the results compiles AND seeds the
        jit dispatch cache with zero side effects.  (A bare
        ``lower().compile()`` would leave the dispatch cache cold: the
        first real call would re-trace and reload the executable.)

        Lock discipline: ``raw()`` holds the global trace lock for the
        state-swapping trace alone; the XLA compile of a whole model takes
        minutes and runs unlocked, so a background warmup never stalls a
        concurrent step or forward.  Returns True when a new signature
        compiled."""
        bucketer = getattr(self.block, "_bucketer", None)
        if bucketer is not None:
            args, _ = _pad_args(bucketer, args)
        key, jit_fn, inputs, holder = self._prepare(args, training)
        sig = self._sig_of(key, inputs)
        if sig in self._traced:
            return False
        raw_inputs = [x._data for x in inputs]
        t0 = _time.perf_counter()
        jax.block_until_ready(jit_fn(*raw_inputs))
        dur = _time.perf_counter() - t0
        with self._trace_lock:
            if sig in self._traced:
                return False     # another thread compiled it first
            # n_calls omitted: warmup traces are deliberate, not churn
            self._note_trace(sig)
        if _tel._ENABLED:
            _tel.observe("hybridize.compile_seconds", dur)
            _tel.inc("hybridize.cache_misses")
            _tel.inc("hybridize.warmup_compiles")
        _tr.record_span("hybridize.compile", t0, dur,
                        block=type(self.block).__name__, warmup=True)
        self._lint_compiled(jit_fn, raw_inputs,
                            donated=holder["donate_argnums"])
        return True

    def out_avals(self, args, training: bool = False):
        """``jax.ShapeDtypeStruct`` of each output leaf for ``args``, in
        the order ``forward`` returns them.  Only the arguments' shapes and
        dtypes are read (an array a donation has deleted will do) and
        nothing runs or counts: a signature traced before is looked up."""
        _, jit_fn, inputs, holder = self._prepare(args, training)
        out = jit_fn.eval_shape(*(
            jax.ShapeDtypeStruct(x.shape, x._data.dtype) for x in inputs))
        return list(out[:holder["n_out"]])

    def __call__(self, args, kwargs):
        if kwargs:
            raise MXNetError("hybridized blocks do not support kwargs in forward")
        self._calls += 1
        bucketer = getattr(self.block, "_bucketer", None)
        unpad = None
        if bucketer is not None:
            args, unpad = _pad_args(bucketer, args)
        training = _autograd.is_training()
        key, jit_fn, inputs, holder = self._prepare(args, training)
        outer = getattr(_DISPATCH, "traced", None)
        _DISPATCH.traced = False
        entries = jit_fn._cache_size()
        t0 = _time.perf_counter()
        try:
            res = _invoke(jit_fn, inputs, name=self._name)
            traced = _DISPATCH.traced
        finally:
            _DISPATCH.traced = outer
        if traced or jit_fn._cache_size() != entries:
            self._on_miss(key, jit_fn, inputs, holder, t0)
        elif _tel._ENABLED:
            _tel.inc("hybridize.cache_hits")
        n_out = holder["n_out"]
        for a, v in zip(holder["mutated_refs"], res[n_out:]):
            a._set_data(v._data)
        n_state = key[2]
        if len(inputs) > n_state:
            # what symbolize() replays: the one shape set this structure
            # was traced at, else this call's own (metadata, no array)
            specs = holder["arg_specs"]
            self.block._last_args_spec = (key[1], specs[0] if len(specs) == 1
                                          else [(x.shape, x._data.dtype)
                                                for x in inputs[n_state:]])
        out = _unflatten_nd(holder["out_tree"], res[:n_out])
        if unpad is not None:
            out = unpad(out)
        return out

    def _on_miss(self, key, jit_fn, inputs, holder, t0: float):
        """A call that jax traced or compiled for: a miss if its signature
        is new (a thread that raced it to the same trace finds it is not,
        and counts a hit).  The compile span is recorded after the fact;
        the lint runs outside the trace lock, which must never be held
        through a compile (class lock discipline)."""
        dur = _time.perf_counter() - t0
        sig = self._sig_of(key, inputs)
        with self._trace_lock:
            new = sig not in self._traced
            if new:
                self._note_trace(sig, n_calls=self._calls)
        if not new:
            if _tel._ENABLED:
                _tel.inc("hybridize.cache_hits")
            return
        # first call for this signature pays trace + XLA compile — the
        # #1 silent cost on TPU; hybridize.compile_seconds is the timer
        # every perf investigation reads first (the span carries the same
        # wall time onto the timeline)
        if _tel._ENABLED:
            _tel.observe("hybridize.compile_seconds", dur)
            _tel.inc("hybridize.cache_misses")
        _tr.record_span("hybridize.compile", t0, dur,
                        block=type(self.block).__name__)
        self._lint_compiled(jit_fn, [x._data for x in inputs],
                            donated=holder["donate_argnums"])


class WarmupHandle:
    """Background AOT warmup in flight (``warmup(background=True)``) —
    compile overlaps data-pipeline start; ``wait()`` before timing."""

    def __init__(self, fn):
        self.result = None
        self.error: Optional[BaseException] = None
        # the spawning thread's correlation context rides onto the
        # warmup thread, so its compile spans stay attributed to the
        # owner (docs/tracing.md)
        self._corr = _tr.capture()
        self._thread = threading.Thread(target=self._run, args=(fn,),
                                        name="mx-jit-warmup", daemon=True)
        self._thread.start()

    def _run(self, fn):
        _tr.attach(self._corr)
        try:
            self.result = fn()
        except BaseException as e:  # noqa: BLE001 — rethrown at wait()
            self.error = e

    def done(self) -> bool:
        return not self._thread.is_alive()

    def wait(self, timeout: Optional[float] = None):
        """Join the warmup thread; rethrows its error, returns the
        number of signatures it compiled."""
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise MXNetError(f"warmup still running after {timeout}s")
        if self.error is not None:
            raise self.error
        return self.result


def _warmup_leaf(x) -> NDArray:
    """One warmup input leaf: NDArray/array passthrough, shape tuple or
    (shape, dtype) pair -> zeros.  Any other tuple recurses — a sample
    arg may be a nested state tree (the decode path's per-layer KV
    cache), whose structure must survive into the traced signature."""
    if isinstance(x, NDArray):
        return x
    if hasattr(x, "shape") and hasattr(x, "dtype"):  # numpy / jax array
        return NDArray(jnp.asarray(x))
    if isinstance(x, tuple) and x and all(isinstance(i, int) for i in x):
        return NDArray(jnp.zeros(x, jnp.float32))
    if isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple) \
            and all(isinstance(i, int) for i in x[0]) \
            and not isinstance(x[1], tuple):
        return NDArray(jnp.zeros(x[0], jnp.dtype(x[1])))
    if isinstance(x, tuple) and x:
        return tuple(_warmup_leaf(e) for e in x)
    raise MXNetError(
        f"warmup sample leaf must be an array, a shape tuple, a "
        f"(shape, dtype) pair, or a tuple tree of those; got {x!r}")


def _normalize_warmup_samples(samples) -> List[Tuple[NDArray, ...]]:
    """Normalize the ``warmup()`` argument to a list of args-tuples."""
    def one(s) -> Tuple[NDArray, ...]:
        if isinstance(s, tuple) and s and not all(
                isinstance(i, int) for i in s) and not (
                len(s) == 2 and isinstance(s[0], tuple)
                and all(isinstance(i, int) for i in s[0])
                and not isinstance(s[1], tuple)):
            return tuple(_warmup_leaf(e) for e in s)  # args tuple
        return (_warmup_leaf(s),)

    if isinstance(samples, list):
        return [one(s) for s in samples]
    return [one(samples)]


def _expand_sample(bucketer: ShapeBucketer,
                   sample: Tuple[NDArray, ...]) -> List[Tuple[NDArray, ...]]:
    """Every bucket combination for ``sample`` (zeros of the right spec):
    bounded policies enumerate the full grid, unbounded ones contribute
    the sample's own bucket — the AOT warmup coverage set."""
    ref = max((tuple(l.shape) for l in sample), key=len)
    out = []
    for shape in bucketer.expand(ref):
        combo = {a: shape[a] for a in bucketer.spec if a < len(shape)}
        leaves = []
        for l in sample:
            sh = list(l.shape)
            for a, size in combo.items():
                if a < len(sh):
                    sh[a] = size
            leaves.append(NDArray(jnp.zeros(tuple(sh), l._data.dtype)))
        out.append(tuple(leaves))
    return out


class HybridBlock(Block):
    """Block that can JIT its forward (ref block.py:998)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._cached_op: Optional[_CachedOp] = None
        self._warmed_up = False
        self._flags: Dict[str, Any] = {}
        self._bucketer: Optional[ShapeBucketer] = None
        self._donate_args: Optional[Tuple[int, ...]] = None

    def hybridize(self, active: bool = True, static_alloc: bool = False,
                  static_shape: bool = False, inline_limit: int = 2,
                  forward_bulk_size: Optional[int] = None,
                  backward_bulk_size: Optional[int] = None,
                  bucketer: Optional[ShapeBucketer] = None,
                  donate_args: Optional[Tuple[int, ...]] = None, **kwargs):
        """Ref block.py:1419. static_alloc/static_shape are implicit under
        XLA (all jit'd code is statically planned); flags kept for compat.

        ``bucketer`` (a :class:`mxnet_tpu.jit.ShapeBucketer` or a spec
        dict) bounds this block's jit-signature set: eager callers'
        inputs are padded up to the nearest bucket before dispatch and
        outputs sliced back, so drifting shapes compile at most
        ``len(buckets)`` programs instead of one per shape (docs/jit.md).
        The bucketer attaches to THIS block only — children are inlined
        into its single jitted graph.

        ``donate_args`` marks top-level forward-argument POSITIONS whose
        buffers XLA may reuse for the outputs (jax donate_argnums, with
        the position mapped over every leaf of a nested arg).  Built for
        functional-state loops — the decode path donates its KV cache so
        each step updates in place instead of holding old+new cache live
        (docs/serving.md).  Inference-only; after a call the passed-in
        donated arrays are DELETED, so the caller must rebind to the
        returned state, never reuse the old one.  xla_lint X004 verifies
        the aliasing actually happened."""
        self._active = active
        if isinstance(bucketer, dict):
            bucketer = ShapeBucketer(bucketer)
        self._bucketer = bucketer
        self._donate_args = tuple(donate_args) if donate_args else None
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape,
                           **kwargs)
        if self._cached_op is not None:
            self._cached_op.clear()
        self._warmed_up = False
        for c in self._children.values():
            if isinstance(c, HybridBlock):
                # children are inlined into this block's single jitted
                # graph; a per-child cache would only add call overhead and
                # jit-under-jit mutation-watcher hazards, so deactivate
                # theirs (call hybridize() on the child directly to compile
                # it standalone)
                c.hybridize(False, **kwargs)
            else:
                c.hybridize(active, **kwargs)
        return self

    def optimize_for(self, x, *args, backend=None, clear=False, **kwargs):
        """Ref block.py:1325 — backend partitioning is XLA's job here; this
        hybridizes and warms the cache on the given input."""
        self.hybridize(True, **kwargs)
        return self(x, *args)

    def warmup(self, samples, train_mode: bool = False,
               background: bool = False):
        """AOT-compile this hybridized block so the first real call runs
        at steady-state speed (docs/jit.md).

        ``samples`` is one sample or a list of samples; each sample is
        an args tuple of arrays/NDArrays, a single array, a shape tuple
        (zeros, float32), or a ``(shape, dtype)`` pair.  With a bucketer
        attached (``hybridize(bucketer=...)``), every sample expands
        over the bucketer's full bucket grid — bounded policies compile
        ALL buckets up front, so a variable-shape stream never compiles
        mid-run.  Signatures already compiled are skipped, so repeated
        warmups are free and a later ``__call__`` on a warmed signature
        adds zero ``hybridize.cache_misses``.

        ``train_mode=True`` compiles the training-mode graph (what runs
        under ``autograd.record()``).  ``background=True`` returns a
        :class:`WarmupHandle` immediately and compiles on a daemon
        thread — overlap it with data-pipeline start, ``wait()`` before
        timing.  Returns the number of newly compiled signatures."""
        if not self._active:
            raise MXNetError("warmup() requires hybridize() first")
        norm = _normalize_warmup_samples(samples)
        if not self._warmed_up:
            # eager pass on the first sample: completes deferred param
            # init + shape discovery, exactly like the first real call --
            # and for that alone: a block whose every parameter has its
            # array has nothing left to discover, and op by op a deep stack
            # is most of a minute on the chip (PERF.md section 6, PR 37)
            if any(p._data is None
                   for p in self.collect_params().values()):
                super().__call__(*norm[0])
            self._warmed_up = True
        if self._cached_op is None:
            self._cached_op = _CachedOp(self)
        if self._bucketer is not None:
            expanded: List[Tuple[NDArray, ...]] = []
            for s in norm:
                expanded.extend(_expand_sample(self._bucketer, s))
            norm = expanded
        cached_op = self._cached_op
        # every warmup run gets its own correlation id, so spans it
        # produces (even on the background thread) answer "which warmup
        # compiled this" — asserted in tests/test_trace.py
        wid = _tr.next_id("warmup")

        def run():
            n = 0
            with _tr.correlate(warmup=wid), \
                    _tr.span("jit.warmup", timer="jit.warmup_seconds",
                             timer_on_error=True,
                             block=type(self).__name__):
                for s in norm:
                    if cached_op.warmup(s, training=train_mode):
                        n += 1
            return n

        if background:
            return WarmupHandle(run)
        return run()

    def eval_shape(self, *args, train_mode: bool = False):
        """Shape and dtype (``jax.ShapeDtypeStruct``) of each output leaf
        the hybridized block gives ``args``, without running it; of the
        arguments only shapes and dtypes are read."""
        if not self._active:
            raise MXNetError("eval_shape() requires hybridize() first")
        if self._cached_op is None:
            self._cached_op = _CachedOp(self)
        return self._cached_op.out_avals(args, training=train_mode)

    def __call__(self, *args, **kwargs):
        if not (self._active and self._warmed_up):
            # an eager call records what symbolize() replays here; a
            # compiled one in _CachedOp.__call__, from its own flatten
            leaves, tree = _flatten_nd(args)
            if leaves:
                self._last_args_spec = (
                    tree, [(l.shape, l._data.dtype) for l in leaves])
            out = super().__call__(*args, **kwargs)
            if self._active:
                # the first call after hybridize() runs eagerly: completes
                # deferred init + shape discovery, exactly like the
                # reference's trace-on-first-call
                self._warmed_up = True
            return out
        if self._cached_op is None:
            self._cached_op = _CachedOp(self)
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self._cached_op(args, kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def export(self, path: str, epoch: int = 0, remove_amp_cast: bool = True):
        """Ref block.py:1514. Serializes compiled StableHLO + params (the
        executable artifact — see SymbolBlock) AND the nnvm-style
        ``{path}-symbol.json`` of the traced op graph for tooling/
        visualization parity with the reference's symbol-json."""
        import logging

        from .symbol_block import export_hybrid

        out = export_hybrid(self, path, epoch)
        try:
            self.symbolize().save(f"{path}-symbol.json")
        except Exception as e:  # stablehlo is the executable artifact;
            # the json graph is descriptive — degrade loudly, not silently
            logging.getLogger(__name__).warning(
                "export: could not write %s-symbol.json: %s", path, e)
        return out

    def symbolize(self, *args) -> "mxnet_tpu.symbol.Symbol":
        """Trace this block's forward into an mx.symbol.Symbol — the
        TPU-native producer of the reference's deferred-compute symbol
        (block.py:1135 _build_cache → GetDeferredComputeSymbol). Parameters
        appear as named variables; BN running stats are auxiliary states.
        With no args, replays the structure/shapes of the last real call.
        User forward hooks are suspended during the trace (it feeds
        synthetic zero inputs that must not leak into e.g. calibration)."""
        from .. import symbol as _sym
        from ..ndarray import NDArray
        from .. import numpy as _np

        if not args:
            spec = getattr(self, "_last_args_spec", None)
            if spec is None:
                raise MXNetError("symbolize() needs example inputs (or call "
                                 "the block once first)")
            tree, leaf_specs = spec
            leaves = [_np.zeros(s, dtype=d) for s, d in leaf_specs]
            args = _unflatten_nd(tree, leaves)
        params = {k: p.data() for k, p in self.collect_params().items()
                  if p._data is not None}
        aux = [k for k in params
               if k.rsplit(".", 1)[-1] in ("running_mean", "running_var")]
        leaves, tree = _flatten_nd(tuple(args))
        names = ["data" if i == 0 else f"data{i}" for i in range(len(leaves))]
        # trace eagerly (drop jit caching so every op dispatches through
        # invoke, the recorder) with hooks suspended everywhere
        saved = [(b, b._forward_hooks, b._forward_pre_hooks, b._active
                  if isinstance(b, HybridBlock) else None)
                 for b in self._all_blocks()]
        for b, *_ in saved:
            b._forward_hooks, b._forward_pre_hooks = [], []
            if isinstance(b, HybridBlock):
                b._active = False
        try:
            def run(*flat):
                structured = _unflatten_nd(tree, list(flat))
                return self(*structured)

            return _sym.trace(run, leaves, input_names=names, known=params,
                              aux=aux)
        finally:
            for b, fh, fph, act in saved:
                b._forward_hooks, b._forward_pre_hooks = fh, fph
                if act is not None:
                    b._active = act

    def infer_shape(self, *args):
        """Layers with deferred params override this (ref HybridBlock.infer_shape)."""
        raise MXNetError(
            f"{type(self).__name__} has deferred-init parameters but does not "
            "implement infer_shape")

    def forward(self, *args, **kwargs):
        raise NotImplementedError


class SymbolBlock(HybridBlock):
    """Runs an exported computation (ref block.py:1716). Construct via
    SymbolBlock.imports(path) — see gluon/symbol_block.py."""

    def __init__(self, outputs=None, inputs=None, params=None):
        super().__init__()
        self._exported = outputs  # jax.export.Exported or callable

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None, ctx=None):
        from .symbol_block import import_exported

        return import_exported(symbol_file, param_file, ctx,
                               input_names=input_names)

    def forward(self, *args):
        from ..ops.dispatch import invoke

        if self._exported is None:
            raise MXNetError("SymbolBlock has no graph; use SymbolBlock.imports")
        fn = self._exported
        return invoke(lambda *xs: fn(*xs), list(args), name="symbol_block")
