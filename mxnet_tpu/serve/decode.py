"""Token-level continuous batching for autoregressive decode
(docs/serving.md, "Decode lifecycle").

The batch tier (server.py) coalesces *single-shot* forwards; generative
decoding is a different scheduling problem — a request occupies the
model for hundreds of sequential steps, so batching at request
granularity would make every request wait for the longest one.  This
module schedules at TOKEN granularity over a fixed set of cache
*slots*:

  * the batch cache is one tree of ``(S, ...)`` buffers (``S`` slots);
    a request claims a free slot at ANY step boundary — prefill runs as
    a one-row forward, the slot writer splices the row cache into the
    batch, and the request rides the next decode step with everyone
    already in flight;
  * every leaf of that tree is of one of three KINDS (``cache_spec``
    below): ``"paged"`` — per-position pages, 4-D with the bucketed
    capacity on axis 2 (a transformer's K‖V leaf, a latent-attention
    row) — ``"window"`` — per-position pages on a ring, 4-D with the
    ring's rows on axis 2 whatever the capacity (a window layer's K‖V
    leaf: the row of position ``p`` is ``p mod R``) — or ``"state"`` —
    constant in the context (an LSTM's ``(h, c)``, a linear-attention
    layer's state matrix and conv tail).  One tree may hold several.
    The kind, not the leaf's rank or one flag for the whole tree,
    decides what grows (paged leaves only), what a cross-bucket move
    copies (the page window of a paged leaf, the whole row of a window
    or state leaf), which capacities the warm-up grid spans, and
    whether the prefix cache may serve the model (only a tree of paged
    leaves: a trie of pages cannot restore a recurrent state, nor the
    pages a ring has overwritten);
  * a request leaves on EOS / max-tokens and its slot frees
    IMMEDIATELY — the next queued request enters at the next step, not
    at a batch boundary;
  * the shared capacity axis ``C`` of the cache is bucketed
    (``capacity_buckets``): when any active row would outgrow ``C`` the
    whole batch zero-extends to the next bucket, stepping between
    pre-warmed executables instead of retracing (the BucketingModule
    idea applied to decode state, docs/jit.md).

Every executable the loop can hit — an admission's fresh row cache per
capacity, prefill per (prompt-bucket, capacity), decode step per
capacity, slot write per capacity, cache growth per bucket pair —
AOT-warms at :class:`DecodeEntry` construction, so steady-state serving
is zero-compile (``hybridize.cache_misses`` stays flat;
tools/decode_smoke.py gates it).  The LM's cache argument is DONATED
(``hybridize(donate_args=)``) so XLA updates it in place — without
aliasing, every step would hold old+new cache live and double decode
memory (xla_lint X004 is the gate).

**The token stays on the device, and the loop runs one step ahead.**
The step program (:class:`_DecodeStepper`) takes the ``argmax`` of its
own logits and the next step reads that ``(S,)`` array where it lies, so
a greedy step (``temperature=0``) hands the host ``S`` int32 ids and the
block's counts, never the logits.  ``_step`` dispatches step N+1 —
lengths and occupancy are counts the host holds — and only then waits
for step N's ids, emits its tokens and releases what ended: the device
runs N+1 meanwhile.  At most ONE step is in flight unread, and none is
run ahead of a step at whose end the host knows a slot frees (a full
count, a row to truncate): the admission that follows finds the device
free.  What the host cannot foresee — ``eos_id``, a cancel, a deadline —
it sees one step late: the slot has computed one step for nobody
(``serve.slot_steps_dropped``), the token is dropped, the request's
tokens are what they were.  A request that samples
(``mx.np.random.categorical`` at a temperature/top-k with a per-request
PRNG key, deterministic under a fixed ``seed``) needs its row of the
logits on the host: while one holds a slot nothing runs ahead, and its
token goes in through the step's host override, as an admission's first
token does.

**Disaggregated prefill/decode** (``prefill_workers > 0`` or
``MXNET_PREFILL_WORKERS``): prompt forwards move OFF the decode loop
onto a pool of ``mx-prefill-<model>-<i>`` threads.  Prefill is
compute-bound (a whole prompt's worth of FLOPs) while the decode step
is latency-bound (one token for every resident request) — inlining
prefill into the loop stalls every in-flight request for the duration
of each admission, which is exactly the TTFT tail the pool removes
(tools/disagg_smoke.py gates disagg p99 < unified p99).  A worker runs
the prompt bucket to completion at its own capacity bucket, samples the
first token, and ships a :class:`_Ready` — the finished ``(row_cache,
cache_len)`` — back to the loop, which claims a slot and moves the
cache across with :class:`_CacheMover`.  The move is an array
redistribution in the :mod:`mxnet_tpu.parallel.layout` sense: the
worker's capacity bucket and the batch's current bucket may differ, so
only the intersecting page window is copied (``ops.attention.
cache_page_copy``), never a full host gather.  The shipment crosses
the ``serve.prefill_transfer`` chaos seam BEFORE touching the batch
cache: an injected fault fails only that request's future and the loop
keeps serving.

**Prefix cache** (:class:`~mxnet_tpu.serve.prefix.PrefixCache`, on by
default with the pool for page-layout models): workers look shared
prompt prefixes up in a block-aligned trie, materialize retained KV
pages into the row cache, and forward only the remainder — hit
requests never enter ``serve.prefill_seconds``, their remainder runs
under ``serve.prefix_fill_seconds`` (that count split is the
"prefix hits skip prefill" gate).

Telemetry (docs/telemetry.md): ``serve.tokens``,
``serve.decode_step_seconds``, ``serve.prefill_seconds``,
``serve.prefill_chunks``, ``serve.stack_passes``,
``serve.prefix_fill_seconds``, ``serve.ttft_seconds``,
``serve.cache_move_seconds``, ``serve.decode_slots_active`` gauge,
``serve.decode_requests``, ``serve.cache_grows``, and the
``serve.cache_*`` prefix-trie set; TTFT splits into
``serve.queue_wait_seconds`` (submit until the loop reached the
request) and ``serve.first_token_seconds`` (``serve.cache_alloc_seconds``
+ ``serve.prefill_forward_seconds`` + the first sample), and a step
into ``serve.step_dispatch_seconds`` (the dispatch of the step ahead),
``serve.step_readback_seconds`` (the wait for the step behind and the
read of its ids and counts) and ``serve.sample_seconds`` (the emit
loop); ``serve.steps_run_ahead`` counts the steps dispatched while the
one before was unread.  Trace (docs/tracing.md): one span per
loop PHASE, never per slot or token, each on the profiler's clock too,
so a device trace says what the host did in every idle gap —
``serve.admit`` per admission around ``serve.prefill`` /
``serve.prefix_fill`` (``serve.first_token`` > ``serve.cache_alloc``,
``serve.prefill_chunk`` > ``serve.prefill_forward`` >
{``serve.prefill_dispatch``, ``serve.prefill_readback``}) and
``serve.cache_move``; per step
``serve.decode_step`` (occupancy/capacity attrs; ``serve.step_dispatch``,
``serve.step_readback``) then ``serve.sample``; ``serve.reply_wait`` at
a boundary that freed a slot with nothing queued; ``serve.idle_wait``
while no slot is occupied; a ``serve.prefix_hit`` instant per trie hit.
Every op of the step program is traced under the ``jax.named_scope``
``decode_step`` (:data:`STEP_SCOPE`).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from .. import telemetry as _tel
from ..analysis import thread_check as _tchk
from ..base import MXNetError, get_env
from ..gluon.block import HybridBlock
from ..gluon.model_zoo.decoder import (CACHE_PAGED, CACHE_STATE,
                                       CACHE_WINDOW)
from ..jit.bucketing import _Policy
from ..ndarray.ndarray import NDArray
from ..numpy_extension import call as _npx_call
from ..ops import attention as _att
from ..parallel import layout as _layout
from ..resilience import chaos as _chaos
from ..trace import recorder as _tr
from .coalescer import ClosedError, DeadlineError, RejectedError
from .prefix import PrefixCache

__all__ = ["DecodeEntry", "DecodeServer", "DecodeFuture", "TokenRangeError",
           "register_decode", "decode_server", "decode_submit", "generate",
           "shutdown_decode"]


class TokenRangeError(MXNetError):
    """A prompt token id outside ``[0, vocab_size)``.  Raised at submit
    (and mapped to HTTP 400 at the edge via ``status``) instead of
    letting the id reach the embedding gather — an out-of-range gather
    under jit FILLS the lookup with NaN on CPU, silently poisoning every
    logit downstream (docs/known_failures.md precedent, PR 18)."""

    status = 400


# How long the loop blocks at a step boundary, after a request's terminal
# event and with nothing queued, so that a caller in this process can answer
# the reply with its next request: submit() ends the wait at once, and the
# admission then finds the device free.  The loop's thread holds the
# interpreter lock from the terminal event to its look at the queue, and a
# woken caller cannot submit before it has had the lock; dispatching first
# puts its prefill behind a step and the one run ahead of it (XL: TTFT p50
# 28.9-29.9 ms for 18.9-19.3).  The wait only has to outlast the caller's
# wake-up: once it runs it keeps the lock until it has submitted (1.4 ms at
# the median in XL's closed loop).  1 ms handed over 262 boundaries of 262,
# 0.25 ms 130 of 133, a bare time.sleep(0) 93 in 100 (TTFT p95 51-55 ms for
# 33-37).  A boundary that nobody answers idles the device for it: 0.9-1.2%
# of an open queue's window at 0.7 of capacity (PERF.md section 6, PR 34).
_REPLY_GRACE_S = 1e-3

# the jax.named_scope of every op of the step program (docs/tracing.md)
STEP_SCOPE = "decode_step"


def _nd_i32(a) -> NDArray:
    return NDArray(jnp.asarray(a, jnp.int32))


def _quant_bytes_saved(cache) -> int:
    """HBM the int8 KV cache saves vs the same geometry held in f32:
    int8 payload pages save 3 bytes/element, their f32 scale pages
    count against the win as overhead.  0 for unquantized caches."""
    leaves = [leaf._data for pair in cache for leaf in pair]
    if not any(leaf.dtype == jnp.int8 for leaf in leaves):
        return 0
    saved = 0
    for leaf in leaves:
        saved += 3 * leaf.nbytes if leaf.dtype == jnp.int8 else -leaf.nbytes
    return saved


def _write_leaf(batch, row, slot):
    return _npx_call(
        lambda b, r, s: jax.lax.dynamic_update_slice(
            b, r.astype(b.dtype), (s,) + (0,) * (b.ndim - 1)),
        (batch, row, slot), {}, name="slot_write")


def _move_leaf(batch, row, slot, n_pages):
    return _npx_call(
        lambda b, r, s: _att.cache_page_copy(b, r, n_pages, dst_row=s),
        (batch, row, slot), {}, name="cache_move")


def cache_spec(block):
    """The kind of every leaf of ``block``'s cache, as a tree shaped like
    the cache: :data:`CACHE_PAGED`, :data:`CACHE_WINDOW` or
    :data:`CACHE_STATE`.

    What follows the capacity is read off ``begin_cache(1, 1)`` against
    ``begin_cache(1, 2)`` (two DISTINCT capacities: a bucket list may hold
    only one): a leaf that follows it on axis 2 of four, and nowhere else,
    is paged; anything else that follows it is an error at registration
    and not a corrupted cache later.  A leaf that does not follow it is
    state -- unless the block NAMES its kinds (``cache_kinds()``, a tree
    shaped like the cache), which is how a window leaf (a ring addressed
    by position, 4-D like a page layout, and yet constant in the capacity)
    is told from a state leaf; what the block names is checked against
    what the two capacities show."""
    def kind(lo, hi, named):
        lo, hi = tuple(lo.shape), tuple(hi.shape)
        if lo == hi:
            found = CACHE_STATE
        elif len(lo) == 4 and lo[:2] + lo[3:] == hi[:2] + hi[3:] \
                and (lo[2], hi[2]) == (1, 2):
            found = CACHE_PAGED
        else:
            raise MXNetError(
                f"cache leaf {lo} -> {hi} follows the capacity elsewhere "
                "than on axis 2 of a 4-D (B, H, C, d) page layout — see "
                "gluon/model_zoo/decoder.py for the contract")
        if named is None:
            return found
        if (named == CACHE_PAGED) != (found == CACHE_PAGED) \
                or named not in (CACHE_PAGED, CACHE_WINDOW, CACHE_STATE) \
                or (named == CACHE_WINDOW and len(lo) != 4):
            raise MXNetError(
                f"cache leaf {lo} -> {hi} is named {named!r} by the block's "
                f"cache_kinds() and reads as {found!r} at two capacities "
                "(a window leaf is 4-D and constant in the capacity)")
        return named

    lo, hi = block.begin_cache(1, 1), block.begin_cache(1, 2)
    named = block.cache_kinds() if hasattr(block, "cache_kinds") \
        else tuple((None,) * len(leaves) for leaves in lo)
    return tuple(tuple(kind(a, b, n) for a, b, n in zip(l, h, ns))
                 for l, h, ns in zip(lo, hi, named))


class _CacheMover(HybridBlock):
    """Ship a one-row cache into the batch cache at a TRACED slot
    index — one executable serves every slot (a static index would
    compile S programs).  ``spec`` (:func:`cache_spec`) picks each leaf's
    path:

    * a state or window leaf, and a paged leaf at matching capacity:
      whole-row splice, the original slot-writer;
    * a paged ``(1, H, Cs, d)`` leaf whose capacity differs from the
      batch's ``Cd``: copy only the intersecting page window —
      :func:`mxnet_tpu.parallel.layout.intersect_box` on the capacity
      axis, static per (src, dst) bucket pair, executed by
      ``ops.attention.cache_page_copy``.  This is what lets a prefill
      worker run at ITS bucket and still land in a batch that has
      grown (or not) independently, with no host gather.

    Param-less HybridBlock so its compiles land in
    ``hybridize.cache_misses`` (the zero-compile gate) and get linted;
    the batch cache is donated (position 0) so the move is in-place."""

    def __init__(self, spec, **kw):
        super().__init__(**kw)
        self._spec = spec

    def forward(self, batch_cache, row_cache, slot):
        def move(kind, b, r):
            if kind == CACHE_PAGED and b.shape[2] != r.shape[2]:
                win = _layout.intersect_box(
                    ((0, int(r.shape[2])),), ((0, int(b.shape[2])),))
                return _move_leaf(b, r, slot, win[0][1] - win[0][0])
            return _write_leaf(b, r, slot)

        return tuple(
            tuple(move(k, b, r) for k, b, r in zip(kinds, bpair, rpair))
            for kinds, bpair, rpair in zip(self._spec, batch_cache,
                                           row_cache))


class _CacheGrower(HybridBlock):
    """Zero-extend the capacity axis (axis 2) of the PAGED leaves to the
    next bucket; it is handed those leaves only (``DecodeEntry.grow``
    puts them back beside the window and state leaves, which growth does
    not touch).  The target rides in as the SHAPE of ``ref`` — baking
    it into a closure would collide signatures (the jit key is
    structural, the target must be shape-visible).  Built on
    dynamic_update_slice into a zeros buffer, not concatenate, so the
    decode models' X003 concat budgets stay untouched."""

    def forward(self, paged, ref):
        cap = ref.shape[0]

        def grow(leaf):
            return _npx_call(
                lambda x: jax.lax.dynamic_update_slice(
                    jnp.zeros(x.shape[:2] + (cap,) + x.shape[3:], x.dtype),
                    x, (0,) * x.ndim),
                (leaf,), {}, name="cache_grow")

        return tuple(grow(leaf) for leaf in paged)


class _CacheAllocator(HybridBlock):
    """The zero tree of ``begin_cache(1, capacity)`` as ONE program, one
    dispatch: an admission's fresh row cache (eagerly it is a dispatch a
    leaf).  The trace calls the model's own ``begin_cache``, so shapes,
    dtypes and leaf kinds stay defined there and nowhere else.  The
    capacity rides in as the SHAPE of ``ref``, as the grower's target
    does.  Every call returns new buffers: the LM donates its cache
    argument, so a tree handed out twice would be a deleted one the
    second time.  Param-less HybridBlock like its siblings (it holds the
    bound method, not the LM, which would make it a child)."""

    def __init__(self, begin_cache, **kw):
        super().__init__(**kw)
        self._begin_cache = begin_cache

    def forward(self, ref):
        return self._begin_cache(1, ref.shape[0])


class _DecodeStepper(HybridBlock):
    """One decode step of the whole slot batch as a program that feeds
    itself: it calls the LM unchanged on one token a slot and takes the
    ``argmax`` of the logits on the device, so that the token's way from
    one step to the next never crosses the host.  A slot's token is the
    step before's own ``ids`` -- the device array it is -- unless the host
    overrides it (an admission's first token, a token sampled at a
    temperature).  ``host`` is ONE ``(4, S)`` int32 upload a step: the
    override tokens, the override mask, the lengths and the occupied slots
    (the LM's ``n_tokens``, 1 or 0).  Returns ``(ids (S,) int32, logits,
    cache, *counts)``: the logits stay on the device unless a sampled
    request asks for its row; ``argmax`` over them with the first index at
    a tie is what numpy's gave on the host.

    The LM is held in a tuple and not as a child: ``hybridize()`` of a
    parent turns its children's own programs off, and the LM keeps its
    prefill grid.  Its parameters are this block's all the same
    (``collect_params``), so they ride in as arguments and are no
    constants of the program.  The cache is donated (position 2)."""

    def __init__(self, lm, **kw):
        super().__init__(**kw)
        self._lm = (lm,)

    def collect_params(self, select=None):
        return self._lm[0].collect_params(select)

    def hybridize(self, active=True, **kw):
        super().hybridize(active, **kw)
        # no eager first pass: the LM finds its deferred shapes in its own
        # first call (a prefill comes before any step), and a step run op
        # by op would hold a second slot cache
        self._warmed_up = True
        return self

    def forward(self, prev_ids, host, cache):
        # no other program's ops carry the scope: a device trace reads a
        # step's own time by it; the compiled program is the same without
        with jax.named_scope(STEP_SCOPE):
            tokens, lens, n_tokens = _npx_call(
                lambda p, h: (jnp.where(h[1] != 0, h[0], p)[:, None], h[2],
                              h[3]),
                (prev_ids, host), {}, name="step_inputs")
            logits, cache, *counts = self._lm[0].forward(tokens, cache, lens,
                                                         n_tokens)
            ids = _npx_call(
                lambda l: jnp.argmax(l[:, 0, :], axis=-1).astype(jnp.int32),
                (logits,), {}, name="step_argmax")
        return (ids, logits, cache, *counts)


# one row of a step's logits at a TRACED slot (a static index would compile
# a program a slot): what a request that samples at a temperature reads
_logits_row = jax.jit(lambda logits, slot: logits[slot, 0])


class _DecodeRequest:
    __slots__ = ("id", "model", "prompt", "max_new_tokens", "temperature",
                 "top_k", "key", "tokens", "truncated", "corr", "t0",
                 "on_token", "deadline", "cancelled", "finish_reason",
                 "_event", "_error")

    def __init__(self, rid, model, prompt, max_new_tokens, temperature,
                 top_k, seed, on_token=None, deadline=None):
        self.id = rid
        self.model = model
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.key = jax.random.PRNGKey(seed if seed is not None else rid)
        self.tokens: List[int] = []
        self.truncated = False
        self.corr = _tr.capture()
        self.t0 = time.perf_counter()       # submit time; TTFT anchor
        # streaming sink: called with each token id as it is sampled,
        # then once with None at terminal resolution (the edge tier's
        # per-step SSE feed, serve/edge.py)
        self.on_token = on_token
        # absolute time.monotonic() bound; the decode loop releases the
        # slot at the next step boundary once it passes
        self.deadline = deadline
        self.cancelled = False
        # "stop" | "length" | "deadline" | "cancelled" | "error"
        self.finish_reason: Optional[str] = None
        self._event = threading.Event()
        self._error: Optional[BaseException] = None

    def expired(self, now: Optional[float] = None) -> bool:
        return self.deadline is not None and \
            (now if now is not None else time.monotonic()) >= self.deadline


def _emit(req: _DecodeRequest, tok: Optional[int]):
    """Feed one token (or the ``None`` terminal) to the request's
    streaming sink.  A broken sink is dropped, never raised — the
    decode loop must keep serving the other slots."""
    cb = req.on_token
    if cb is None:
        return
    try:
        cb(tok)
    except Exception:  # noqa: BLE001 — sink bug, not a serving bug
        req.on_token = None


def _queue_waited(req: _DecodeRequest):
    """A worker reached ``req``: what it waited since ``submit()`` is the
    part of its TTFT that no span of its own covers."""
    if _tel._ENABLED:
        _tel.observe("serve.queue_wait_seconds",
                     time.perf_counter() - req.t0)


def _fail(req: _DecodeRequest, err: BaseException):
    """Resolve a request with an error (same wire contract as the batch
    tier: non-MXNetErrors surface wrapped) and fire the terminal
    streaming event."""
    req._error = err if isinstance(err, MXNetError) \
        else MXNetError(f"{type(err).__name__}: {err}")
    req._error.__cause__ = err
    req.finish_reason = "error"
    req._event.set()
    _emit(req, None)


class _Ready:
    """A pool-prefilled request in flight from prefill to decode: the
    finished one-row cache plus the geometry the decode loop needs to
    redistribute it into a slot (``src_cap`` = the worker's capacity
    bucket, ``min_capacity`` = the prompt bucket the batch must reach
    before the valid pages fit)."""

    __slots__ = ("req", "row_cache", "cache_len", "src_cap", "min_capacity")

    def __init__(self, req, row_cache, cache_len, src_cap, min_capacity):
        self.req = req
        self.row_cache = row_cache
        self.cache_len = cache_len
        self.src_cap = src_cap
        self.min_capacity = min_capacity


class _Flight(NamedTuple):
    """A decode step that was dispatched and is not read yet: its outputs
    still on the device (``ids``, ``logits``, ``counts``), who rode in it
    (``riders``: ``(slot, request)``, by which a slot released since is
    told), and the cache rows it had to read (``live``; ``in_window`` for
    a model with window layers), counted when it is read."""

    ids: NDArray
    logits: NDArray
    counts: list
    riders: List[Tuple[int, _DecodeRequest]]
    live: int
    in_window: Optional[int]


class DecodeFuture:
    """Handle returned by ``submit()``; ``result()`` blocks for the
    generated token ids."""

    __slots__ = ("_req",)

    def __init__(self, req: _DecodeRequest):
        self._req = req

    @property
    def id(self) -> int:
        return self._req.id

    @property
    def truncated(self) -> bool:
        """True when generation stopped because the cache ran out of
        capacity buckets (not EOS / max-tokens)."""
        return self._req.truncated

    @property
    def finish_reason(self) -> Optional[str]:
        """Why generation ended: ``"stop"`` (EOS), ``"length"``
        (max-tokens / truncation), ``"deadline"``, ``"cancelled"``,
        ``"error"`` — None while still running."""
        return self._req.finish_reason

    def tokens_so_far(self) -> List[int]:
        """Snapshot of the tokens generated so far (streaming peek)."""
        return list(self._req.tokens)

    def cancel(self):
        """Ask the decode loop to drop this request: the slot is
        released at the next step boundary, the future resolves with
        the partial tokens (``finish_reason == "cancelled"``), and a
        streaming sink gets its terminal event.  The edge tier calls
        this on client disconnect (docs/serving.md)."""
        self._req.cancelled = True

    def done(self) -> bool:
        return self._req._event.is_set()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self._req._event.wait(timeout):
            raise MXNetError(
                f"decode request {self._req.id} ({self._req.model}) still "
                f"pending after {timeout}s")
        if self._req._error is not None:
            raise self._req._error
        return self._req.tokens


class DecodeEntry:
    """One registered decode model: the LM plus its row-cache allocator,
    slot writer, cache grower, bucket grids, and the registration-time
    AOT warmup.

    ``block`` must expose the decode contract
    (gluon/model_zoo/decoder.py): ``begin_cache(batch, capacity)`` and
    ``forward(tokens, cache, cache_len, n_tokens) -> (logits,
    new_cache)``.  The entry re-hybridizes it with the cache donated.
    """

    def __init__(self, name: str, block, *, slots: int = 4,
                 prompt_buckets: Sequence[int] = (8, 16, 32),
                 capacity_buckets: Sequence[int] = (32, 64),
                 eos_id: Optional[int] = None, max_new_tokens: int = 32,
                 lint_budget: Optional[dict] = None, warmup: bool = True,
                 precision: Optional[str] = None):
        if not hasattr(block, "begin_cache"):
            raise MXNetError(
                f"decode model {name!r} has no begin_cache(batch, capacity) "
                "— see gluon/model_zoo/decoder.py for the contract")
        if slots < 1:
            raise MXNetError(f"slots must be >= 1, got {slots}")
        if precision not in (None, "int8"):
            raise MXNetError(
                f"decode model {name!r}: precision={precision!r} "
                "unsupported; None or 'int8'")
        if precision == "int8" and \
                getattr(block, "_cache_dtype", False) is False:
            raise MXNetError(
                f"decode model {name!r} has no quantizable KV cache "
                "(no cache_dtype contract — the LSTM carrier's recurrent "
                "state has no per-position pages to quantize); "
                "precision='int8' needs the transformer family")
        if precision == "int8":
            # flip BEFORE the capacity probe / warmup below: begin_cache
            # must build the (kv_q, k_scale, v_scale) page layout
            # for every executable in the grid (docs/precision.md)
            block._cache_dtype = "int8"
        self.precision = precision
        self.name = name
        self.block = block
        self.slots = int(slots)
        self.eos_id = eos_id
        self.max_new_tokens = int(max_new_tokens)
        self.prompt_policy = _Policy(list(prompt_buckets))
        self.capacity_policy = _Policy(list(capacity_buckets))
        self.prompt_buckets = tuple(self.prompt_policy.enumerate())
        self.capacity_buckets = tuple(self.capacity_policy.enumerate())
        if self.prompt_buckets[-1] > self.capacity_buckets[-1]:
            raise MXNetError(
                f"largest prompt bucket {self.prompt_buckets[-1]} exceeds "
                f"largest capacity bucket {self.capacity_buckets[-1]} — the "
                "prompt's KV rows must fit the cache")
        self.cache_spec = cache_spec(block)      # what each cache leaf is
        # passes one forward of the block makes over its layers (a looped
        # stack's ``loops``): what serve.stack_passes counts a forward
        self.stack_passes = int(getattr(block, "loops", 1))
        # the positions a window leaf's layer sees (None without one)
        self.window = int(block.attention_window) if any(
            k == CACHE_WINDOW for kinds in self.cache_spec for k in kinds) \
            else None

        block._xla_lint_label = f"serve.{name}"
        if lint_budget is not None:
            block._xla_lint_budget = lint_budget
        block.hybridize(donate_args=(1,))
        self.stepper = _DecodeStepper(block)
        self.stepper._xla_lint_label = f"serve.{name}.step"
        if lint_budget is not None:
            self.stepper._xla_lint_budget = lint_budget
        self.stepper.hybridize(donate_args=(2,))
        self.mover = _CacheMover(self.cache_spec)
        self.mover._xla_lint_label = f"serve.{name}.mover"
        self.mover.hybridize(donate_args=(0,))
        self.grower = _CacheGrower()
        self.grower._xla_lint_label = f"serve.{name}.grow"
        self.grower.hybridize()
        self.allocator = _CacheAllocator(block.begin_cache)
        self.allocator._xla_lint_label = f"serve.{name}.alloc"
        self.allocator.hybridize()
        # a capacity rides into the allocator and the grower as a SHAPE;
        # the references are kept (the warm-up makes them), so that no
        # admission pays an eager transfer for one
        self._cap_refs: Dict[int, NDArray] = {}
        if warmup:
            self.warmup()

    # ---------------------------------------------------------- warmup
    def warmup(self) -> int:
        """AOT-compile the full executable grid: the fresh row cache
        per capacity, prefill per (prompt-bucket <= capacity) pair,
        decode step + slot write per capacity, growth per consecutive
        bucket pair.  Donation deletes each sample's cache after its
        compile, so every sample gets a fresh tree.  Returns the number
        of newly compiled signatures."""
        s = self.slots
        caps = self.capacity_buckets if not self.capacity_static \
            else self.capacity_buckets[:1]
        lm_samples, step_samples = [], []
        idle = onp.zeros(s, onp.int32)
        for c in caps:
            for tp in self.prompt_buckets:
                if tp <= c:
                    lm_samples.append(
                        (_nd_i32(onp.zeros((1, tp))),
                         self.block.begin_cache(1, c),
                         _nd_i32(onp.zeros(1)), _nd_i32(onp.ones(1))))
            step_samples.append(
                (_nd_i32(idle), self._step_inputs(idle, idle, idle, idle + 1),
                 self.block.begin_cache(s, c)))
        n = self.block.warmup(lm_samples)
        n += self.stepper.warmup(step_samples)
        mover_samples = [
            (self.block.begin_cache(s, c), self.block.begin_cache(1, c),
             _nd_i32(0)) for c in caps]
        if not self.capacity_static:
            # cross-capacity moves: a prefill worker's bucket and the
            # batch's current bucket drift independently, so warm every
            # (src != dst) pair of the page-window executable too
            mover_samples += [
                (self.block.begin_cache(s, cd), self.block.begin_cache(1, cs),
                 _nd_i32(0))
                for cd in caps for cs in caps if cs != cd]
        n += self.mover.warmup(mover_samples)
        if not self.capacity_static and len(self.capacity_buckets) > 1:
            pairs = zip(self.capacity_buckets, self.capacity_buckets[1:])
            n += self.grower.warmup(
                [(self._paged(self.block.begin_cache(s, c_lo)),
                  self._cap_ref(c_hi)) for c_lo, c_hi in pairs])
        prefill_logits = {self.block.eval_shape(*sample)[0]
                          for sample in lm_samples}
        step_logits = {self.stepper.eval_shape(*sample)[1]
                       for sample in step_samples}
        # last, with every sample cache dropped and each output dropped as
        # its compile returns: the warm-up's peak (the mover's, above) is
        # behind, so the device's high-water mark stays what it was
        del lm_samples, step_samples, mover_samples
        n += self.allocator.warmup([(self._cap_ref(c),) for c in caps])
        self._warm_reads(prefill_logits, step_logits)
        return n

    def _warm_reads(self, prefill_logits, step_logits):
        """The eager slice that reads a prefill's last logits compiles once
        a logits SHAPE (a prompt bucket), and so does the one row of a
        step's logits that a sampled request reads: here, on zeros of
        those shapes, and not at the first admission of each bucket or the
        first sampled step, which may fall inside a measured window
        (``hybridize.cache_misses`` does not see an eager op's compile;
        jax's own compile events do).  The arguments are the logits'
        shapes and dtypes of the grid's prefill and step programs; the
        same expressions as :meth:`_forward_window` and
        :meth:`logits_row`.  A step's ``ids`` and counts are read whole:
        a transfer compiles nothing."""
        for logits in prefill_logits:
            onp.asarray(jnp.zeros(logits.shape, logits.dtype)[0, 0])
        for logits in step_logits:
            self.logits_row(NDArray(jnp.zeros(logits.shape, logits.dtype)), 0)

    def _cap_ref(self, capacity: int) -> NDArray:
        """The array whose shape tells the allocator and the grower their
        target: ``(capacity, 0)``, so that it holds no byte of device
        memory (XL's warm-up peaks 0.26 GB under the chip's limit)."""
        ref = self._cap_refs.get(capacity)
        if ref is None:
            ref = self._cap_refs[capacity] = _nd_i32(onp.zeros((capacity, 0)))
        return ref

    # ------------------------------------------------------- execution
    def prompt_chunks(self, n_prompt: int) -> List[Tuple[int, int, int]]:
        """``[(start, real tokens, bucket)]`` of a prompt's forward: one
        piece when it fits the largest prompt bucket; past it, whole
        chunks of that bucket and the rest in the smallest bucket that
        holds it — every piece one of the warmed (prompt bucket,
        capacity) programs, forwarded against the row cache the pieces
        before it filled."""
        top = self.prompt_buckets[-1]
        chunks, start = [], 0
        while n_prompt - start > top:
            chunks.append((start, top, top))
            start += top
        rest = n_prompt - start
        chunks.append((start, rest, self.prompt_policy.bucket(rest)))
        if len(chunks) > 1 and getattr(self.block,
                                       "prefill_needs_empty_cache", False):
            raise MXNetError(
                f"decode model {self.name!r}: a prompt of {n_prompt} tokens "
                f"is past the largest prompt bucket {top}, and a mixer of "
                f"{type(self.block).__name__} forwards T > 1 tokens from an "
                "EMPTY cache only, so the prompt cannot be served in "
                "chunks; add a larger prompt bucket")
        return chunks

    def prompt_rows(self, n_prompt: int) -> int:
        """Cache rows a prompt's forward writes (its last piece padded to
        its bucket): what a paged leaf's capacity must reach first."""
        start, _, bucket = self.prompt_chunks(n_prompt)[-1]
        rows = start + bucket
        if not self.capacity_static and rows > self.capacity_buckets[-1]:
            raise MXNetError(
                f"decode model {self.name!r}: a prompt of {n_prompt} tokens "
                f"writes {rows} cache rows, past the largest capacity "
                f"bucket {self.capacity_buckets[-1]}")
        return rows

    def prefill(self, tokens: onp.ndarray, true_len: int, capacity: int):
        """One-row prompt forward from an empty cache: returns
        ``(last_logits (V,) numpy, row_cache)`` — ``tokens`` already
        padded to a prompt bucket."""
        return self.prefill_window(tokens, self._fresh_row(capacity), 0,
                                   true_len)

    def _fresh_row(self, capacity: int):
        with _tr.span("serve.cache_alloc", timer="serve.cache_alloc_seconds",
                      capacity=capacity):
            return self.allocator(self._cap_ref(capacity))

    def prefill_prompt(self, prompt: Sequence[int], capacity: int):
        """A whole prompt from an empty cache, in the pieces
        :meth:`prompt_chunks` cuts it into, back to back: returns
        ``(last_logits (V,) numpy, row_cache)``.  Only the last piece's
        logits are read back, so the pieces before it queue on the device
        while the host dispatches the next."""
        cache = self._fresh_row(capacity)
        chunks = self.prompt_chunks(len(prompt))
        waiting = []                     # counts of the pieces not read yet
        for start, n_new, bucket in chunks:
            toks = onp.zeros((1, bucket), onp.int32)
            toks[0, :n_new] = prompt[start:start + n_new]
            with _tr.span("serve.prefill_chunk", start=start, tokens=n_new):
                last, cache, counts = self._forward_window(
                    toks, cache, start, n_new,
                    read=start + n_new == len(prompt))
                waiting.append(counts)
        if _tel._ENABLED:
            _tel.inc("serve.prefill_chunks", len(chunks))
            if any(waiting):
                with _tr.span("serve.prefill_readback"):
                    for counts in waiting:
                        self._count(counts)
        return last, cache

    def _forward_window(self, tokens, cache, cache_len: int, n_new: int,
                        read: bool = True):
        """One piece: ``(last_logits or None, cache, counts not yet
        counted)``.  Without ``read`` it is dispatched and nothing is read
        back."""
        bucket = int(tokens.shape[1])
        with _tr.span("serve.prefill_forward",
                      timer="serve.prefill_forward_seconds", tokens=n_new,
                      bucket=bucket):
            with _tr.span("serve.prefill_dispatch", tokens=n_new,
                          bucket=bucket):
                logits, cache, *counts = self.block(
                    _nd_i32(tokens), cache,
                    _nd_i32(onp.asarray([cache_len])),
                    _nd_i32(onp.asarray([n_new])))
            if _tel._ENABLED:
                _tel.inc("serve.prefill_tokens", n_new)
                _tel.inc("serve.stack_passes", self.stack_passes)
            if not read:
                return None, cache, counts
            # the wait for the piece, the eager slice and copy of its last
            # row of logits, the counts
            with _tr.span("serve.prefill_readback"):
                last = onp.asarray(logits._data[0, n_new - 1])
                if _tel._ENABLED:
                    self._count(counts)
            return last, cache, ()

    def prefill_window(self, tokens: onp.ndarray, cache, cache_len: int,
                       n_new: int):
        """Forward ``n_new`` real tokens (padded window ``tokens``
        ``(1, Tp)``) against a row cache whose first ``cache_len``
        positions are already valid — a prompt's later pieces and the
        prefix-hit remainder path.
        Same executable family as :meth:`prefill` (``cache_len`` /
        ``n_tokens`` are traced), so no extra warmup signatures."""
        return self._forward_window(tokens, cache, cache_len, n_new)[:2]

    @staticmethod
    def _step_inputs(pending, fresh, lens, active) -> NDArray:
        """What the host tells a step, as the ONE ``(4, S)`` int32 upload
        :class:`_DecodeStepper` takes."""
        return _nd_i32(onp.stack([pending, fresh, lens, active]))

    def step(self, prev_ids: NDArray, pending: onp.ndarray,
             fresh: onp.ndarray, lens: onp.ndarray, active: onp.ndarray,
             cache):
        """DISPATCH one decode step for the whole slot batch and read
        nothing: returns ``(ids (S,) int32, logits, new_cache, counts)``,
        device arrays all, ``ids`` the ``argmax`` of each slot's logits.
        The token of slot ``i`` is ``pending[i]`` where ``fresh[i]``, else
        ``prev_ids[i]`` -- the ``ids`` of the step before, still on the
        device, read or not.  ``active`` (S,) marks the occupied slots and
        rides in as ``n_tokens`` (1 or 0): a model with recurrent state
        leaves a free slot's state alone and counts no routing for it.
        ``counts`` is the block's small per-call counts, if it returns any
        (:meth:`_count` turns them into telemetry once they are read)."""
        ids, logits, cache, *counts = self.stepper(
            prev_ids, self._step_inputs(pending, fresh, lens, active), cache)
        return ids, logits, cache, counts

    @staticmethod
    def read(x: NDArray) -> onp.ndarray:
        """A device array on the host.  Every pull of a decode step goes
        through here: its ``(S,)`` ids (the wait for the device), the
        block's counts, a sampled slot's one row of logits."""
        return onp.asarray(x._data)

    def logits_row(self, logits: NDArray, slot: int) -> onp.ndarray:
        """Slot ``slot``'s row ``(V,)`` of a step's logits on the host:
        what a request that samples at a temperature needs, one row and
        not ``S``."""
        return self.read(NDArray(_logits_row(logits._data, onp.int32(slot))))

    def _count(self, counts):
        """Telemetry from the small counts a block may return as a third
        value (read AFTER the logits or the ids, so the device wait is not
        moved)."""
        if counts:
            for name, n in self.block.step_counters(
                    self.read(counts[0])).items():
                _tel.inc(name, n)

    @property
    def capacity_static(self) -> bool:
        """No paged leaf (the LSTM carrier: recurrent state IS the
        history; a stack of window layers alone): growth is a no-op."""
        return not any(k == CACHE_PAGED for kinds in self.cache_spec
                       for k in kinds)

    def move(self, cache, row_cache, slot: int):
        """Ship ``row_cache`` into batch ``slot`` — whole-row splice at
        matching capacity, page-window copy across buckets (the
        redistribution consumer, docs/sharding.md)."""
        return self.mover(cache, row_cache, _nd_i32(slot))

    # back-compat name from the equal-capacity slot-writer era
    insert = move

    def _paged(self, cache):
        """The paged leaves of ``cache``, flat, in tree order."""
        return tuple(leaf for kinds, leaves in zip(self.cache_spec, cache)
                     for k, leaf in zip(kinds, leaves) if k == CACHE_PAGED)

    def grow(self, cache, new_capacity: int):
        """The paged leaves zero-extended to ``new_capacity``; the window
        and state leaves are the same arrays as before."""
        grown = iter(self.grower(self._paged(cache),
                                 self._cap_ref(new_capacity)))
        return tuple(
            tuple(next(grown) if k == CACHE_PAGED else leaf
                  for k, leaf in zip(kinds, leaves))
            for kinds, leaves in zip(self.cache_spec, cache))

    def cache_bytes(self, cache) -> Dict[str, int]:
        """Bytes of ``cache`` by leaf kind."""
        out = {CACHE_STATE: 0, CACHE_WINDOW: 0, CACHE_PAGED: 0}
        for kinds, leaves in zip(self.cache_spec, cache):
            for k, leaf in zip(kinds, leaves):
                out[k] += int(leaf._data.nbytes)
        return out


class DecodeServer:
    """The token-level scheduler: a worker thread owning the slot batch.

    All device state (cache tree, per-slot host bookkeeping) is touched
    by the decode worker only; ``submit`` just enqueues under the
    condition variable.  ``close()`` drains accepted requests before
    joining.

    With ``prefill_workers > 0`` (default ``MXNET_PREFILL_WORKERS``,
    0 = unified) the server is DISAGGREGATED: submits land on the
    prefill queue, ``mx-prefill-<model>-<i>`` threads run prompt
    forwards to completion (consulting ``prefix_cache`` — a
    :class:`~mxnet_tpu.serve.prefix.PrefixCache`, ``None`` auto-creates
    one for page-layout models, ``False`` disables), and finished
    shipments re-enter the decode queue as :class:`_Ready` items.  One
    condition variable guards both queues plus the in-flight prefill
    count, so close() can drain exactly: the loop exits only when
    closed AND both queues are empty AND no prefill is mid-flight AND
    every slot has resolved."""

    def __init__(self, entry: DecodeEntry, queue_max: Optional[int] = None,
                 prefill_workers: Optional[int] = None, prefix_cache=None):
        self.entry = entry
        self._queue_max = queue_max if queue_max is not None \
            else get_env("MXNET_SERVE_QUEUE_MAX", 1024, int)
        self._prefill_workers = int(
            prefill_workers if prefill_workers is not None
            else get_env("MXNET_PREFILL_WORKERS", 0, int))
        if self._prefill_workers < 0:
            raise MXNetError(
                f"prefill_workers must be >= 0, got {self._prefill_workers}")
        def state():         # names of the unpaged leaves, when it matters
            return [f"layer {i} leaf {j} ({k})"
                    for i, kinds in enumerate(entry.cache_spec)
                    for j, k in enumerate(kinds) if k != CACHE_PAGED]

        if prefix_cache is None:
            self.prefix = PrefixCache(name=entry.name) \
                if self._prefill_workers > 0 and not state() else None
        elif prefix_cache is True:
            self.prefix = PrefixCache(name=entry.name)
        elif prefix_cache is False:
            self.prefix = None
        else:
            self.prefix = prefix_cache
        if self.prefix is not None and state():
            raise MXNetError(
                f"decode model {entry.name!r} keeps leaves in its cache "
                f"that are not pages ({', '.join(state()[:4])}"
                f"{', ...' if len(state()) > 4 else ''}) — a trie "
                "of pages cannot restore a recurrent state, nor the pages "
                "before a window that a ring has overwritten, so the prefix "
                "cache cannot serve it; pass prefix_cache=False")
        self._q: deque = deque()
        self._pq: deque = deque()
        self._prefill_busy = 0
        self._cv = _tchk.condition("serve.decode")
        self._closed = False
        self._seq = 0
        # worker-owned state
        self._cap_i = 0
        self._cache = None
        self._active: List[Optional[_DecodeRequest]] = [None] * entry.slots
        # a slot's next token comes from the device (the last dispatched
        # step's ids) unless the host marks its own as fresh
        self._pending = onp.zeros(entry.slots, onp.int32)
        self._fresh = onp.zeros(entry.slots, onp.int32)
        self._ids = None
        # valid cache rows a slot, AFTER every step dispatched so far
        self._lens = onp.zeros(entry.slots, onp.int32)
        self._flight: Optional[_Flight] = None  # the one step not yet read
        self._freed = False     # a slot was released since the last look
        self._steps = 0
        # a window layer reads min(live, window) rows of a slot in a step
        self._window = entry.window
        self._thread = threading.Thread(
            target=self._loop, name=f"mx-decode-worker-{entry.name}",
            daemon=True)
        self._thread.start()
        self._prefill_threads = [
            threading.Thread(target=self._prefill_loop,
                             name=f"mx-prefill-{entry.name}-{i}", daemon=True)
            for i in range(self._prefill_workers)]
        for t in self._prefill_threads:
            t.start()

    # ------------------------------------------------------------- API
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0,
               seed: Optional[int] = None, on_token=None,
               deadline: Optional[float] = None) -> DecodeFuture:
        """``on_token`` (optional) is called with every sampled token id
        as generation proceeds, then once with ``None`` at terminal
        resolution — the streaming feed.  ``deadline`` (optional,
        seconds from now) bounds the request end to end: an expired
        request releases its slot at the next step boundary and its
        future raises :class:`DeadlineError` (already-expired submits
        shed immediately with the same 503-path :class:`RejectedError`
        contract as a full queue)."""
        prompt = [int(t) for t in onp.asarray(prompt).reshape(-1)]
        if not prompt:
            raise MXNetError("decode prompt must be non-empty")
        vocab = getattr(self.entry.block, "_vocab_size", None)
        if vocab is not None:
            bad = [t for t in prompt if t < 0 or t >= vocab]
            if bad:
                raise TokenRangeError(
                    f"decode prompt for {self.entry.name!r} has token ids "
                    f"outside [0, {vocab}): {bad[:8]} — an out-of-range "
                    "embedding gather fills the lookup with NaN under jit, "
                    "poisoning the logits silently")
        if deadline is not None and deadline <= 0:
            if _tel._ENABLED:
                _tel.inc("serve.rejected")
            raise RejectedError(
                f"decode request deadline {deadline!r}s already expired "
                "at submit; shed")
        with self._cv:
            if self._closed:
                raise ClosedError(
                    f"decode server {self.entry.name!r} is closed")
            if len(self._q) + len(self._pq) >= self._queue_max:
                if _tel._ENABLED:
                    _tel.inc("serve.rejected")
                raise RejectedError(
                    f"decode queue full ({self._queue_max}); shed load "
                    "upstream or raise MXNET_SERVE_QUEUE_MAX")
            self._seq += 1
            req = _DecodeRequest(
                self._seq, self.entry.name, prompt,
                max_new_tokens if max_new_tokens is not None
                else self.entry.max_new_tokens,
                temperature, top_k, seed, on_token=on_token,
                deadline=None if deadline is None
                else time.monotonic() + deadline)
            (self._pq if self._prefill_workers else self._q).append(req)
            self._cv.notify_all()
        if _tel._ENABLED:
            _tel.inc("serve.decode_submitted")
        return DecodeFuture(req)

    def generate(self, prompt, timeout: Optional[float] = None,
                 **kw) -> List[int]:
        """Blocking convenience: submit + result."""
        return self.submit(prompt, **kw).result(timeout)

    def close(self, timeout: float = 60.0):
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        deadline = time.monotonic() + timeout
        for t in self._prefill_threads:
            t.join(max(0.0, deadline - time.monotonic()))
        self._thread.join(max(0.0, deadline - time.monotonic()))
        if self._thread.is_alive() \
                or any(t.is_alive() for t in self._prefill_threads):
            raise MXNetError(
                f"decode server {self.entry.name!r} failed to drain within "
                f"{timeout}s")

    @property
    def alive(self) -> bool:
        """Liveness for the ``/readyz`` decode-loop check (docs/obs.md):
        the worker thread is running, or the server was closed cleanly.
        False only when the loop DIED with work possibly pending."""
        return self._thread.is_alive() or self._closed

    # ---------------------------------------------------------- worker
    def _occupancy(self) -> int:
        return sum(1 for r in self._active if r is not None)

    def _nothing_to_do(self) -> bool:
        """Under ``_cv``: no request queued, no slot occupied, no step in
        flight, and not yet closed-and-drained — the loop has to wait."""
        return not self._q and self._occupancy() == 0 \
            and self._flight is None \
            and not (self._closed and not self._pq
                     and self._prefill_busy == 0)

    def _loop(self):
        e = self.entry
        self._cache = e.block.begin_cache(e.slots, e.capacity_buckets[0])
        self._ids = _nd_i32(onp.zeros(e.slots, onp.int32))
        self._cache_gauges()
        while True:
            admitted: List = []
            with self._cv:
                if self._nothing_to_do():
                    with _tr.span("serve.idle_wait"):
                        while self._nothing_to_do():
                            self._cv.wait(0.1)
                elif self._freed and not self._q and not self._closed:
                    with _tr.span("serve.reply_wait"):
                        self._cv.wait(_REPLY_GRACE_S)
                self._freed = False
                if self._closed and not self._q and not self._pq \
                        and self._prefill_busy == 0 \
                        and self._occupancy() == 0 and self._flight is None:
                    return
                free = self._active.count(None)
                while self._q and len(admitted) < free:
                    admitted.append(self._q.popleft())
            for item in admitted:
                shipped = isinstance(item, _Ready)
                req = item.req if shipped else item
                if not shipped:
                    _queue_waited(req)
                try:
                    with _tr.correlate(serve_decode=req.id), \
                            _tr.span("serve.admit", request=req.id,
                                     slot=self._active.index(None)):
                        if shipped:
                            self._admit_ready(item)
                        else:
                            self._admit(item)
                except BaseException as err:  # noqa: BLE001 — to future
                    _fail(req, err)
            self._reap()
            if self._flight is None:
                # with a step in flight its successor's capacity was seen
                # to when it was dispatched (_may_run_ahead)
                if self._occupancy() == 0:
                    continue
                self._ensure_capacity()
                if self._occupancy() == 0:
                    continue
            self._step()
            self._reap()

    def _dead_on_arrival(self, req: _DecodeRequest) -> bool:
        """Cancelled/expired before claiming a slot: resolve without
        touching the batch (the slot stays free)."""
        if req.cancelled:
            req.finish_reason = "cancelled"
        elif req.expired():
            req.finish_reason = "deadline"
            req._error = DeadlineError(
                f"decode request {req.id} ({req.model}) deadline expired "
                "before admission")
            if _tel._ENABLED:
                _tel.inc("serve.deadline_exceeded")
        else:
            return False
        self._resolve(req)
        return True

    def _admit(self, req: _DecodeRequest):
        """Slot claim -> prefill -> splice into the running batch (under
        the loop's ``serve.admit`` span and ``serve_decode``
        correlation)."""
        if self._dead_on_arrival(req):
            return
        e = self.entry
        caps = e.capacity_buckets
        slot = self._active.index(None)
        t = len(req.prompt)
        rows = e.prompt_rows(t)             # raises on over-long prompts
        while not e.capacity_static and caps[self._cap_i] < rows:
            self._grow()
        with _tr.span("serve.prefill", timer="serve.prefill_seconds",
                      request=req.id, tokens=t, slot=slot):
            # the client has its token where serve.first_token ends; the
            # move after it stalls the other slots, not this request
            with _tr.span("serve.first_token",
                          timer="serve.first_token_seconds", request=req.id):
                last_logits, row_cache = e.prefill_prompt(
                    req.prompt, caps[self._cap_i])
                first = self._sample(req, last_logits)
                req.tokens.append(first)
                _emit(req, first)
            if _tel._ENABLED:
                _tel.inc("serve.tokens")
                _tel.observe("serve.ttft_seconds",
                             time.perf_counter() - req.t0)
            if (e.eos_id is not None and first == e.eos_id) \
                    or req.max_new_tokens <= 1:
                self._resolve(req)
                return
            with _tr.span("serve.cache_move",
                          timer="serve.cache_move_seconds", request=req.id,
                          slot=slot):
                self._cache = e.move(self._cache, row_cache, slot)
        self._seat(slot, req, t)

    def _seat(self, slot: int, req: _DecodeRequest, cache_len: int):
        """``req`` rides from the next step on: ``cache_len`` valid rows,
        and its last token handed in from the host (it was sampled there
        from the prefill's logits)."""
        self._lens[slot] = cache_len
        self._pending[slot] = req.tokens[-1]
        self._fresh[slot] = 1
        self._active[slot] = req
        if _tel._ENABLED:
            _tel.set_gauge("serve.decode_slots_active", self._occupancy())

    def _admit_ready(self, ready: _Ready):
        """Claim a slot for a pool-prefilled request and redistribute
        its row cache into the batch.  The ``serve.prefill_transfer``
        chaos seam fires BEFORE the move, so an injected transfer fault
        leaves the batch cache untouched: only this request's future
        fails, the slot stays free, and the loop keeps serving."""
        e = self.entry
        req = ready.req
        if self._dead_on_arrival(req):
            return
        caps = e.capacity_buckets
        slot = self._active.index(None)
        while not e.capacity_static and caps[self._cap_i] < ready.min_capacity:
            self._grow()
        if _chaos.active():
            kind = _chaos.draw("serve.prefill_transfer")
            if kind == "delay":
                time.sleep(get_env("MXNET_FAULT_DELAY", 0.05, float))
            elif kind is not None:
                raise _chaos.ChaosError(
                    "injected fault at 'serve.prefill_transfer' "
                    f"(request {req.id})")
        with _tr.span("serve.cache_move", timer="serve.cache_move_seconds",
                      request=req.id, slot=slot, tokens=ready.cache_len,
                      src_capacity=ready.src_cap,
                      dst_capacity=caps[self._cap_i]):
            self._cache = e.move(self._cache, ready.row_cache, slot)
        ready.row_cache = None
        self._seat(slot, req, ready.cache_len)

    # ---------------------------------------------------- prefill pool
    def _prefill_loop(self):
        while True:
            with self._cv:
                while not self._closed and not self._pq:
                    self._cv.wait(0.1)
                if not self._pq:            # closed and drained
                    return
                req = self._pq.popleft()
                self._prefill_busy += 1
            _queue_waited(req)
            ready = None
            try:
                ready = self._run_prefill(req)
            except BaseException as err:  # noqa: BLE001 — to future
                _fail(req, err)
            with self._cv:
                self._prefill_busy -= 1
                if ready is not None:
                    self._q.append(ready)
                self._cv.notify_all()

    def _run_prefill(self, req: _DecodeRequest) -> Optional[_Ready]:
        """One request's prompt forward on the pool: prefix-trie lookup,
        cold prefill or prefix-remainder forward, trie retention, first
        token.  Returns the shipment for the decode loop, or None when
        generation already finished (EOS / one-token budget)."""
        if self._dead_on_arrival(req):
            return None
        e = self.entry
        caps = e.capacity_buckets
        t = len(req.prompt)
        tp = e.prompt_rows(t)               # raises on over-long prompts
        matched, chain = 0, []
        if self.prefix is not None:
            matched, chain = self.prefix.lookup(req.prompt)
            if t - matched > e.prompt_buckets[-1]:
                matched, chain = 0, []      # a remainder is one piece
        if e.capacity_static:
            src_cap = caps[0]
        elif matched:
            # the remainder window appends at `matched`, so the row
            # needs matched + bucket(remainder) pages, which can exceed
            # the cold bucket; an unfittable hit degrades to a miss
            rem_bucket = e.prompt_policy.bucket(t - matched)
            need = max(tp, matched + rem_bucket)
            src_cap = next((c for c in caps if c >= need), None)
            if src_cap is None:
                matched, chain = 0, []
        if not matched and not e.capacity_static:
            src_cap = next(c for c in caps if c >= tp)
        with _tr.correlate(serve_decode=req.id):
            with _tr.span("serve.first_token",
                          timer="serve.first_token_seconds", request=req.id):
                if matched:
                    cache = self.prefix.materialize(chain, src_cap)
                    rem = t - matched
                    toks = onp.zeros((1, rem_bucket), onp.int32)
                    toks[0, :rem] = req.prompt[matched:]
                    with _tr.span("serve.prefix_fill",
                                  timer="serve.prefix_fill_seconds",
                                  request=req.id, tokens=rem, cached=matched):
                        last_logits, row_cache = e.prefill_window(
                            toks, cache, matched, rem)
                    if _tr._ENABLED:
                        _tr.instant("serve.prefix_hit", request=req.id,
                                    cached_tokens=matched, forwarded=rem)
                else:
                    with _tr.span("serve.prefill",
                                  timer="serve.prefill_seconds",
                                  request=req.id, tokens=t):
                        last_logits, row_cache = e.prefill_prompt(
                            req.prompt, src_cap)
                if self.prefix is not None:
                    self.prefix.insert(req.prompt, row_cache, t)
                first = self._sample(req, last_logits)
                req.tokens.append(first)
                _emit(req, first)
            if _tel._ENABLED:
                _tel.inc("serve.tokens")
                _tel.observe("serve.ttft_seconds",
                             time.perf_counter() - req.t0)
            if (e.eos_id is not None and first == e.eos_id) \
                    or req.max_new_tokens <= 1:
                self._resolve(req)
                return None
        return _Ready(req, row_cache, t, src_cap, tp)

    def _ensure_capacity(self):
        """Grow the batch before a step whose append would overflow; at
        the last bucket, force-finish the full rows (truncated)."""
        e = self.entry
        if e.capacity_static:
            return
        caps = e.capacity_buckets
        need = max(int(self._lens[i]) for i, r in enumerate(self._active)
                   if r is not None)
        if need < caps[self._cap_i]:
            return
        if self._cap_i + 1 < len(caps):
            self._grow()
            return
        for i, r in enumerate(self._active):
            if r is not None and int(self._lens[i]) >= caps[self._cap_i]:
                r.truncated = True
                self._release(i)

    def _grow(self):
        e = self.entry
        new_cap = e.capacity_buckets[self._cap_i + 1]
        with _tr.span("serve.cache_grow", capacity=new_cap):
            self._cache = e.grow(self._cache, new_cap)
        self._cap_i += 1
        if _tel._ENABLED:
            _tel.inc("serve.cache_grows")
        self._cache_gauges()

    def _cache_gauges(self):
        if _tel._ENABLED:
            _tel.set_gauge("serve.cache_quant_bytes_saved",
                           _quant_bytes_saved(self._cache))
            held = self.entry.cache_bytes(self._cache)
            _tel.set_gauge("serve.cache_state_bytes", held[CACHE_STATE])
            _tel.set_gauge("serve.cache_window_bytes", held[CACHE_WINDOW])
            _tel.set_gauge("serve.cache_paged_bytes", held[CACHE_PAGED])

    def _dispatch(self) -> _Flight:
        """Hand the next step to the device and count its rows into the
        lengths: every occupied slot rides, on the device's own token
        unless the host's is fresh."""
        e = self.entry
        riders = [(i, r) for i, r in enumerate(self._active) if r is not None]
        active = onp.asarray([r is not None for r in self._active], onp.int32)
        # cache rows this step's attention has to read: what was valid
        # before it plus the row it appends, over the occupied slots (a
        # free slot's length is 0); a window layer reads min(that, window)
        live = int(self._lens.sum()) + len(riders)
        in_window = None if self._window is None else \
            int((onp.minimum(self._lens + 1, self._window) * active).sum())
        ids, logits, self._cache, counts = e.step(
            self._ids, self._pending, self._fresh, self._lens, active,
            self._cache)
        self._ids = ids
        self._fresh[:] = 0
        self._lens += active
        return _Flight(ids, logits, counts, riders, live, in_window)

    def _may_run_ahead(self, flight: _Flight) -> bool:
        """Whether the step after ``flight`` may be dispatched before
        ``flight`` is read — by what the host knows now.  Not while a
        request samples at a temperature (its token is made on the host
        from this step's logits); not when a slot is known to free at this
        step's end (its request's count is full: the admission that
        follows must find the device free, and the client its terminal
        event); not when the cache has to grow, or a row to be truncated,
        before the next append.  An ``eos_id``, a cancel and a deadline
        the host cannot foresee: those are seen one step late."""
        e = self.entry
        cap = None if e.capacity_static else e.capacity_buckets[self._cap_i]
        for i, req in enumerate(self._active):
            if req is not None and (
                    req.temperature > 0.0
                    or (cap is not None and self._lens[i] >= cap)):
                return False
        riding = [req for i, req in flight.riders if self._active[i] is req]
        return bool(riding) and not any(
            len(req.tokens) + 1 >= req.max_new_tokens for req in riding)

    def _step(self):
        """Finish one decode step: dispatch it if none is in flight,
        dispatch its successor before reading it where that may be
        (:meth:`_may_run_ahead`: the device then runs N+1 while the host
        reads, emits and releases N), then read its ``ids`` and counts
        and emit its tokens.  At most ONE step is ever in flight unread;
        the synchronous order is this one with nothing run ahead."""
        e = self.entry
        self._steps += 1
        flight = self._flight
        occupancy = self._occupancy() if flight is None \
            else len(flight.riders)
        with _tr.span("serve.decode_step", timer="serve.decode_step_seconds",
                      step=self._steps, occupancy=occupancy,
                      capacity=e.capacity_buckets[self._cap_i]):
            # the dispatch of the step ahead (of this one too, with none
            # in flight; of nothing, where this one is not run ahead of)
            with _tr.span("serve.step_dispatch",
                          timer="serve.step_dispatch_seconds"):
                if flight is None:
                    flight = self._dispatch()
                self._flight = self._dispatch() \
                    if self._may_run_ahead(flight) else None
            # the wait for the step behind and the read of its (S,) ids
            # and counts: no logits
            with _tr.span("serve.step_readback",
                          timer="serve.step_readback_seconds"):
                ids = e.read(flight.ids)
                if _tel._ENABLED:
                    e._count(flight.counts)
        newly = dropped = 0
        # every on_token of a decode step fires in here
        with _tr.span("serve.sample", timer="serve.sample_seconds",
                      slots=occupancy):
            for i, req in flight.riders:
                if self._active[i] is not req:
                    dropped += 1    # ended since: a step for nobody
                    continue
                if req.temperature <= 0.0:
                    tok = int(ids[i])
                else:
                    tok = self._sample(req, e.logits_row(flight.logits, i))
                    self._pending[i], self._fresh[i] = tok, 1
                req.tokens.append(tok)
                _emit(req, tok)
                newly += 1
                if (e.eos_id is not None and tok == e.eos_id) \
                        or len(req.tokens) >= req.max_new_tokens:
                    self._release(i)
        if _tel._ENABLED:
            _tel.inc("serve.tokens", newly)
            _tel.inc("serve.step_live_positions", flight.live)
            _tel.inc("serve.stack_passes", e.stack_passes)
            if flight.in_window is not None:
                _tel.inc("serve.step_window_positions", flight.in_window)
            if self._flight is not None:
                _tel.inc("serve.steps_run_ahead")
            if dropped:
                _tel.inc("serve.slot_steps_dropped", dropped)

    def _reap(self):
        """Release any slot whose request was cancelled or whose
        deadline expired mid-stream: the slot frees at THIS step
        boundary (the next admit can claim it), the future resolves
        with the partial tokens (cancel) or :class:`DeadlineError`
        (deadline), and the streaming sink gets its terminal event —
        the satellite-3 contract (tests/test_edge.py)."""
        now = time.monotonic()
        for i, req in enumerate(self._active):
            if req is None:
                continue
            if req.cancelled:
                req.finish_reason = "cancelled"
            elif req.expired(now):
                req.finish_reason = "deadline"
                req._error = DeadlineError(
                    f"decode request {req.id} ({req.model}) deadline "
                    f"expired after {len(req.tokens)} token(s); slot "
                    "released")
                if _tel._ENABLED:
                    _tel.inc("serve.deadline_exceeded")
            else:
                continue
            self._release(i)

    def _sample(self, req: _DecodeRequest, logits_row: onp.ndarray) -> int:
        if req.temperature <= 0.0:
            return int(onp.argmax(logits_row))
        from ..numpy import random as _rng
        key = jax.random.fold_in(req.key, len(req.tokens))
        return int(_rng.categorical(key, jnp.asarray(logits_row),
                                    temperature=req.temperature,
                                    top_k=req.top_k))

    def _release(self, slot: int):
        req = self._active[slot]
        self._active[slot] = None
        self._freed = True
        self._lens[slot] = 0
        self._pending[slot] = self._fresh[slot] = 0
        self._resolve(req)
        if _tel._ENABLED:
            _tel.set_gauge("serve.decode_slots_active", self._occupancy())

    def _resolve(self, req: _DecodeRequest):
        if req.finish_reason is None:
            req.finish_reason = "length" if req.truncated \
                or len(req.tokens) >= req.max_new_tokens else "stop"
        req._event.set()
        _emit(req, None)                    # terminal streaming event
        if _tel._ENABLED:
            _tel.inc("serve.decode_requests")
            if req.finish_reason == "cancelled":
                _tel.inc("serve.cancelled")


# ----------------------------------------------------- module-level API
_DECODE: Dict[str, DecodeServer] = {}
_DLOCK = _tchk.lock("serve.decode_registry")


def register_decode(name: str, block, **cfg) -> DecodeEntry:
    """Register ``block`` for decode serving under ``name``: builds the
    :class:`DecodeEntry` (AOT-warming the executable grid) and starts
    its :class:`DecodeServer`.  Server-level knobs (``prefill_workers``,
    ``prefix_cache``, ``queue_max``) pass through to the server; the
    rest configure the entry — ``precision="int8"`` switches the
    model's KV cache to int8 pages with per-position scales
    (~2x the servable slots at the same cache budget,
    docs/precision.md).  Re-registering a name drains and replaces the
    old server."""
    srv_kw = {k: cfg.pop(k)
              for k in ("prefill_workers", "prefix_cache", "queue_max")
              if k in cfg}
    entry = DecodeEntry(name, block, **cfg)
    server = DecodeServer(entry, **srv_kw)
    with _DLOCK:
        old = _DECODE.pop(name, None)
        _DECODE[name] = server
    if old is not None:
        old.close(30.0)
    return entry


def decode_server(name: str) -> DecodeServer:
    with _DLOCK:
        try:
            return _DECODE[name]
        except KeyError:
            raise MXNetError(
                f"no decode model {name!r}; registered: "
                f"{sorted(_DECODE)}") from None


def servers() -> Dict[str, DecodeServer]:
    """Snapshot of the live decode servers by name (read-only copy —
    the ``/readyz`` decode-loop liveness check iterates this)."""
    with _DLOCK:
        return dict(_DECODE)


def decode_submit(name: str, prompt, **kw) -> DecodeFuture:
    """Enqueue one generation request (non-blocking)."""
    return decode_server(name).submit(prompt, **kw)


def generate(name: str, prompt, timeout: Optional[float] = None,
             **kw) -> List[int]:
    """Blocking generation on the named decode server."""
    return decode_server(name).generate(prompt, timeout=timeout, **kw)


def shutdown_decode(timeout: float = 60.0):
    """Drain and stop every decode server."""
    with _DLOCK:
        servers = list(_DECODE.values())
        _DECODE.clear()
    for s in servers:
        s.close(timeout)
