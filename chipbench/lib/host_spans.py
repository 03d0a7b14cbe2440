"""What the host did while the device was idle, and which device ops carry
a name the program gave them -- from the run's own ``.xplane.pb``.

``mx.trace`` spans hold a ``jax.profiler.TraceAnnotation`` (PR 27,
``mxnet_tpu/trace/recorder.py``), so while the profiler is on they land on
the trace's host plane (``/host:CPU``; one line per thread, every line
named after the process, so spans are found BY NAME) on the same clock as
device 0's ``XLA Ops`` line.  Two reductions, both of them pure functions
of intervals that the tests drive on hand-made input:

* ``split_idle``: device-0 idle time (the slice less the union of the op
  intervals, exactly ``reduce_trace``'s idle) is cut at every span boundary
  and each piece goes to the INNERMOST span covering it -- the span that
  started last, on whichever thread -- or, when that span's name is in no
  bucket, to the nearest enclosing span that is; a piece under no bucketed
  span is ``unattributed``.  The buckets always sum to the idle time.
* ``scoped_seconds``: the time device 0 spent in ops that jax traced under
  a ``jax.named_scope`` or as a Pallas kernel with a ``name=`` -- either is
  a path element of the op's jax-side name, which ``lib/op_names.py`` digs
  out of the trace -- as the union of their intervals, so nested events
  count once.  (A Pallas ``name=`` is also the HLO instruction's own name,
  but wrapped in the transformations it was traced under:
  ``%flash_decode.48``, ``%jvp_flash_fwd_.20``,
  ``%transpose_jvp_flash_bwd_dq__.3`` on a v5e trace.  The jax-side name
  keeps the structure: ``jit(step)/transpose(jvp(flash_bwd_dq))/pallas_call``.)

``run.py`` does not pass the trace's path, so ``load`` finds the newest
``.xplane.pb`` under ``chipbench/out/<cell>/`` the way ``run.py`` does and
keeps what it read in the ``ctx`` that every reader of the run is handed.  A program without the spans (the parent of
PR 27), a trace without a host plane or a cell that never ran an op all
read as None: the metric is left out of the line, nothing raises.
"""
from __future__ import annotations

import os

import reduce_trace as rt
from lib import op_names

HOST_PLANE_PREFIX = "/host:"
UNATTRIBUTED = "unattributed"
OUT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "out")

# span name -> bucket of device.idle_in_<bucket>.serve.  serve.admit's
# children (serve.prefill, serve.first_token, serve.cache_alloc,
# serve.prefill_forward, serve.cache_move, serve.cache_grow) reach it as
# their nearest bucketed ancestor; serve.decode_step and serve.idle_wait
# are in no bucket on purpose: what they cover beyond their children is
# unattributed.
SERVE_BUCKETS = {"serve.step_readback": "readback",
                 "serve.sample": "sample",
                 "serve.step_dispatch": "dispatch",
                 "serve.admit": "admit"}
SERVE_STEP_SPAN = "serve.decode_step"
# the spans that are read; a span of another subsystem nested in one of
# these would fall to its bucketed ancestor anyway
SPAN_PREFIX = "serve."


# -- pure: intervals in, seconds out -----------------------------------------

def idle_intervals(busy, window):
    """The parts of ``window`` (start, end) that no (start, end) of ``busy``
    covers, in order."""
    lo, hi = window
    out, cur = [], lo
    for s, e in sorted(busy):
        if e <= cur:
            continue
        if s >= hi:
            break
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def innermost_timeline(spans, buckets):
    """Flatten (name, start, end) spans into non-overlapping, ordered
    (start, end, bucket) pieces: each instant belongs to the bucket of the
    innermost span covering it (latest start, then earliest end), or of the
    nearest enclosing span whose name is in ``buckets``.  Instants under no
    bucketed span are left out."""
    points = []
    for i, (name, s, e) in enumerate(spans):
        if e > s:
            points.append((s, 1, i))
            points.append((e, 0, i))      # ends sort before starts at a tie
    points.sort()
    active, out, prev = {}, [], None

    def bucket_now():
        # innermost first: latest start, and of two that started together
        # the one that ends first
        for i in sorted(active, key=lambda j: (-spans[j][1], spans[j][2])):
            b = buckets.get(spans[i][0])
            if b is not None:
                return b
        return None

    for t, opening, i in points:
        if prev is not None and t > prev and active:
            b = bucket_now()
            if b is not None:
                if out and out[-1][2] == b and out[-1][1] == prev:
                    out[-1] = (out[-1][0], t, b)
                else:
                    out.append((prev, t, b))
        if opening:
            active[i] = True
        else:
            active.pop(i, None)
        prev = t
    return out


def split_idle(busy, window, spans, buckets):
    """{bucket: idle seconds, ..., "unattributed": idle seconds} of the
    device's idle time inside ``window``; every bucket of ``buckets`` is a
    key, and the values sum to the idle time.  Spans reaching over the
    window's edge are clipped to it."""
    lo, hi = window
    clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in spans]
    pieces = innermost_timeline(clipped, buckets)
    out = {b: 0.0 for b in set(buckets.values())}
    total, j = 0.0, 0
    for s, e in idle_intervals(busy, window):
        total += e - s
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            ps, pe, b = pieces[k]
            out[b] += min(e, pe) - max(s, ps)
            k += 1
    out[UNATTRIBUTED] = max(0.0, total - sum(out.values()))
    return out


def shares(seconds):
    """Percent of the whole for each key; None when the whole is nothing."""
    total = sum(seconds.values())
    if total <= 0.0:
        return None
    return {k: 100.0 * v / total for k, v in seconds.items()}


def steps_on_one_clock(busy, steps):
    """Of the ``steps`` (start, end) spans, how many have the first device
    op that starts at or after their start begin before their end:
    (inside, counted).  Steps after the last device op are not counted."""
    import bisect

    starts = sorted(s for s, _ in busy)
    inside = counted = 0
    for s, e in steps:
        i = bisect.bisect_left(starts, s)
        if i == len(starts):
            continue
        counted += 1
        inside += starts[i] <= e
    return inside, counted


def scoped_seconds(events, scopes):
    """Seconds covered by the (jax name, start, end) device events traced
    under one of ``scopes``: a ``jax.named_scope`` or a Pallas ``name=`` that
    is a path element of the op's jax-side name, under whatever
    transformations (``lib/op_names.py:scopes_of``).  A union, so an event
    nested in another counts once; a Pallas call has no children, so for
    kernels this is their self time."""
    want = frozenset(scopes)
    hit = {}                  # a step's ops share a few thousand names
    for name, _, _ in events:
        if name not in hit:
            hit[name] = bool(want & op_names.scopes_of(name))
    return rt.union_length([(s, e) for name, s, e in events if hit[name]])


# -- the run's trace ---------------------------------------------------------

def newest_xplane(cell):
    found = []
    for d, _, files in os.walk(os.path.join(OUT_DIR, cell)):
        found += [os.path.join(d, f) for f in files
                  if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def read_file(path):
    """{"path", "window", "busy" [(s, e)], "spans" [(name, s, e)]} of one
    recorded trace, seconds on the trace's clock; None when device 0 ran
    nothing."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    ops = rt.device_events(profile, 0)
    ops = [(s, e) for _, s, e in ops if e > s]
    if not ops:
        return None
    window = rt.trace_window(profile)
    # the slice reduce_trace's idle share is a share of
    window = (min(window[0], min(s for s, _ in ops)),
              max(window[1], max(e for _, e in ops)))
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith(HOST_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9))
    return {"path": path, "window": window, "busy": ops, "spans": spans}


def load(ctx):
    """The traced run's own trace, read once per run: ``run.py`` hands every
    reader the same ``ctx``, which keeps it.  None when there is none to
    read."""
    if "xplane" not in ctx:
        path = newest_xplane(ctx["cell"]["name"])
        ctx["xplane"] = None if path is None else read_file(path)
    return ctx["xplane"]


# -- what the readers in layer_metrics/ call ---------------------------------

def serve_idle_share(ctx, bucket):
    """Percent of device-0 idle time under ``bucket`` (``SERVE_BUCKETS``'
    values or "unattributed").  0.0 when the bucket's spans covered no idle
    time; None when the trace holds none of the program's serving spans."""
    data = load(ctx)
    if data is None or not any(n in SERVE_BUCKETS for n, _, _ in data["spans"]):
        return None
    if "serve_idle" not in data:
        data["serve_idle"] = shares(split_idle(
            data["busy"], data["window"], data["spans"], SERVE_BUCKETS))
    return None if data["serve_idle"] is None else data["serve_idle"][bucket]


def scope_share(ctx, scopes):
    """Percent of device-0 busy time in ops traced under one of ``scopes``
    (named scopes or Pallas kernel names); None when no op carries any."""
    data = load(ctx)
    if data is None or not ctx["trace"]:
        return None
    if "named" not in data:
        data["named"] = op_names.named_events(
            data["path"], f"{rt.DEVICE_PLANE_PREFIX}0", rt.OP_LINE)
    seconds = scoped_seconds(data["named"], scopes)
    return 100.0 * seconds / ctx["trace"]["busy_s"] if seconds > 0.0 else None
