"""Inside the admission and the reply wait, and one decode step's device
time -- from the run's own ``.xplane.pb``, through ``lib/host_spans.py``.

``host_spans.SERVE_BUCKETS`` stops at ``admit``: every child of
``serve.admit`` falls to it, and the loop's reply wait to
``unattributed``.  ``ADMIT_BUCKETS`` splits both further by the spans the
program opens around each host mechanism of an admission (PR 39,
``mxnet_tpu/serve/decode.py``): the zero tree's dispatch
(``serve.cache_alloc``), a prompt piece's dispatch
(``serve.prefill_dispatch``), the wait for and read of its last logits and
counts (``serve.prefill_readback``), the mover's dispatch
(``serve.cache_move``), and the loop's wait for a reply at a freed slot
(``serve.reply_wait``).  The four admission spans nest in ``serve.admit``
on the loop's thread (every serving cell runs ``prefill_workers`` 0) and
the reply wait in no bucketed span, so with the innermost-span rule of
``split_idle``

    admit_alloc + admit_dispatch + admit_readback + admit_move + admit
        = the coarse split's admit
    reply_wait + unattributed = the coarse split's unattributed

and every share is over the same whole as the coarse five's.

``program_seconds`` is the device time of the program whose ops are
traced under one ``jax.named_scope``: the step program's under
``decode_step``.  An op the compiler put in -- a copy, the wait for an
asynchronous one (``%copy-done``, ``%slice-done``: the prefetch of a
weight matrix) -- carries no jax-side name, so it is the step's where the
named ops on either side of it on the device are.

A program without these spans or the scope (the parent of PR 39) reads as
None: the metric is left out of the line, nothing raises.
"""
from __future__ import annotations

import reduce_trace as rt
from lib import host_spans, op_names

ADMIT_BUCKETS = dict(host_spans.SERVE_BUCKETS, **{
    "serve.cache_alloc": "admit_alloc",
    "serve.prefill_dispatch": "admit_dispatch",
    "serve.prefill_readback": "admit_readback",
    "serve.cache_move": "admit_move",
    "serve.reply_wait": "reply_wait"})
# what a program without the split lacks (serve.cache_alloc and
# serve.cache_move are older: PR 32, PR 26)
SPLIT_SPANS = frozenset({"serve.prefill_dispatch", "serve.prefill_readback",
                         "serve.reply_wait"})
STEP_SCOPE = "decode_step"


# -- pure: intervals in, seconds out -----------------------------------------

def program_seconds(events, scope):
    """Seconds covered by the program traced under ``scope``, of the
    (jax name, start, end) device events: those whose jax-side name holds
    the scope (``op_names.scopes_of``), and the nameless ones ("") between
    two of them with no event of another name between.  A union."""
    hit = {}
    keep, pending, inside = [], [], False
    for name, s, e in sorted(events, key=lambda ev: ev[1]):
        if not name:
            if inside:
                pending.append((s, e))
            continue
        if name not in hit:
            hit[name] = scope in op_names.scopes_of(name)
        inside = hit[name]
        if inside:
            keep += pending
            keep.append((s, e))
        pending = []
    return rt.union_length(keep)


def steps_ending_in(spans, window):
    """How many ``serve.decode_step`` spans end inside ``window``: a step's
    span ends once its ids are on the host, after its program ran."""
    lo, hi = window
    return sum(1 for name, _, e in spans
               if name == host_spans.SERVE_STEP_SPAN and lo <= e <= hi)


# -- what the readers in layer_metrics/ call ---------------------------------

def admit_idle_share(ctx, bucket):
    """Percent of device-0 idle time under ``bucket`` of ``ADMIT_BUCKETS``
    (or "unattributed").  0.0 when its spans covered no idle time; None
    when the trace holds none of ``SPLIT_SPANS``."""
    data = host_spans.load(ctx)
    if data is None or not any(n in SPLIT_SPANS for n, _, _ in data["spans"]):
        return None
    if "admit_idle" not in data:
        data["admit_idle"] = host_spans.shares(host_spans.split_idle(
            data["busy"], data["window"], data["spans"], ADMIT_BUCKETS))
    return None if data["admit_idle"] is None else data["admit_idle"][bucket]


def step_ms(ctx):
    """Device-0 milliseconds of one decode step: the step program's seconds
    in the slice over the ``serve.decode_step`` spans that end in it (one
    step of error at each edge).  None when no op carries the scope or no
    step ended."""
    data = host_spans.load(ctx)
    if data is None:
        return None
    steps = steps_ending_in(data["spans"], data["window"])
    if not steps:
        return None
    if "named" not in data:
        data["named"] = op_names.named_events(
            data["path"], f"{rt.DEVICE_PLANE_PREFIX}0", rt.OP_LINE)
    seconds = program_seconds(data["named"], STEP_SCOPE)
    return 1e3 * seconds / steps if seconds > 0.0 else None
