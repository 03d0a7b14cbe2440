"""From a ``jax.profiler`` trace (``.xplane.pb``) to numbers.

The smallest sound reduction (PERF.md section 3, layer "device"):

* **busy** = the union of the intervals in which an operation ran on the
  device's op line, so overlapping and nested events count once;
* **slice** = the time the profiler was on, on the profiler's own clock:
  from the first start to the last end of ANY event on ANY plane and line
  of the trace (the host's threads, the device's ``Steps`` and ``XLA
  Modules`` lines, its ops), so a device left idle at either edge of the
  slice counts as idle.  On a v5e trace the host lines cover the 3 s the
  profiler was on to a few ms and the device lines run ~45 ms past them.
  Host time (``perf_counter``) is never used;
* **idle share** = 1 - busy / slice;
* **op table** = SELF time per op name (a parent's duration less the
  children nested inside it), so the table sums to busy and a ``while``
  does not count its body twice;
* **gaps** = the longest idle intervals, each named by the op that ended
  before it and the op that started after it.  What the HOST was doing in
  a gap cannot be told from here (no host span is on this clock yet).

Planes are found by name ``/device:TPU:<i>``, the op line by ``XLA Ops``
(looked at by hand on a v5e trace, PR 26).  There an event's name is the
whole HLO instruction (``%fusion.6 = bf16[...] fusion(...), kind=...``, one
to two thousand characters), and a Pallas kernel is a ``custom-call`` whose
text holds ``custom_call_target="tpu_custom_call"`` under the name of the
jax function it was traced in (``%jvp__.12``), not under a name of its own.
So ``short_name`` keeps the instruction's name, drops its numeric suffix
(the 24 ``%add_subtract_fusion.N`` of one step are one row) and tags a
Pallas kernel ``[tpu_custom_call]``; it leaves an already short name alone.  ``reduce_events`` is pure and
is what the tests exercise on hand-made intervals; ``reduce_file`` reads
the recorded trace with ``jax.profiler.ProfileData`` and nothing else.
"""
from __future__ import annotations

import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
PALLAS_TAG = "[tpu_custom_call]"
_PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def short_name(text):
    """``%add_subtract_fusion.21 = (f32[768,3072]...) fusion(...)`` ->
    ``%add_subtract_fusion``; a Pallas kernel keeps a tag."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    head = re.sub(r"\.\d+$", "", head)
    return f"{head} {PALLAS_TAG}" if _PALLAS_TARGET in rest else head


def union_length(intervals):
    """Total length covered by (start, end) intervals, overlaps once."""
    busy, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy


def self_times(events):
    """{name: (self seconds, count)} for (name, start, end) events of one
    line: an event nested inside another is taken out of its parent."""
    table = {}
    stack = []                    # [name, start, end, child seconds]

    def close(item):
        name, s, e, child = item
        tot, n = table.get(name, (0.0, 0))
        table[name] = (tot + max(0.0, (e - s) - child), n + 1)
        if stack:
            stack[-1][3] += e - s

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][2]:
            close(stack.pop())
        if stack and e > stack[-1][2]:
            e = stack[-1][2]      # partial overlap: clip to the parent
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return table


def idle_gaps(events):
    """Idle intervals between consecutive busy stretches, longest first:
    [("after <op> | before <op>", seconds), ...]."""
    gaps, cur_end, cur_name = [], None, None
    for name, s, e in sorted(events, key=lambda ev: ev[1]):
        if cur_end is not None and s > cur_end:
            gaps.append((f"after {cur_name} | before {name}", s - cur_end))
        if cur_end is None or e > cur_end:
            cur_end, cur_name = e, name
    return sorted(gaps, key=lambda g: -g[1])


def reduce_events(events, window=None):
    """All numbers of one device's op line; events are (name, start_s,
    end_s).  ``window`` is the (start_s, end_s) the profiler was on; without
    one the slice is first op start to last op end.  Empty input reduces to
    None (nothing ran: the caller refuses the run)."""
    events = [ev for ev in events if ev[2] > ev[1]]
    if not events:
        return None
    lo = min(ev[1] for ev in events)
    hi = max(ev[2] for ev in events)
    if window is not None:
        lo, hi = min(lo, window[0]), max(hi, window[1])
    busy = union_length([(s, e) for _, s, e in events])
    table = self_times(events)
    ops = sorted(((n, t, c) for n, (t, c) in table.items()),
                 key=lambda r: -r[1])
    return {"busy_s": busy, "slice_s": hi - lo,
            "idle_share": 1.0 - busy / (hi - lo),
            "ops": ops, "gaps": idle_gaps(events)}


def device_events(profile, index):
    """(name, start_s, end_s) of the op line of device ``index``."""
    want = f"{DEVICE_PLANE_PREFIX}{index}"
    for plane in profile.planes:
        if plane.name != want:
            continue
        for line in plane.lines:
            if line.name == OP_LINE:
                return [(short_name(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
    return []


def trace_window(profile):
    """(start_s, end_s) of the whole trace: first start to last end over
    every event of every plane and line."""
    lo = hi = None
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                lo = s if lo is None or s < lo else lo
                hi = e if hi is None or e > hi else hi
    return None if lo is None else (lo * 1e-9, hi * 1e-9)


def reduce_file(path, n_devices=1):
    """Reduce a recorded trace.  Device 0 gives the idle share, the op
    table and the gaps; ``busy_s_mean`` averages busy time over the chips
    used (what the result line's ``device.busy_s`` asks for)."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    window = trace_window(profile)
    per_dev = [reduce_events(device_events(profile, i), window)
               for i in range(n_devices)]
    if per_dev[0] is None:
        raise RuntimeError(
            f"no operation on {DEVICE_PLANE_PREFIX}0 / {OP_LINE!r} in "
            f"{path}; planes: {[p.name for p in profile.planes]}")
    out = dict(per_dev[0])
    ran = [d for d in per_dev if d is not None]
    out["busy_s_mean"] = sum(d["busy_s"] for d in ran) / len(ran)
    out["top_ops"] = [[n, t] for n, t, _ in out["ops"][:10]]
    out["top_gaps"] = [[n, t] for n, t in out["gaps"][:5]]
    return out


def share_of_busy(trace, matches):
    """Percent of device-0 busy time in ops whose name ``matches(name)``;
    0 when no such op ran."""
    return 100.0 * sum(t for n, t, _ in trace["ops"]
                       if matches(n)) / trace["busy_s"]
