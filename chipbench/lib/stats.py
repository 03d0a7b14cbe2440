"""Percentiles and utilization arithmetic, on plain Python numbers."""
import math


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between closest
    ranks (numpy's default).  ``inf`` entries -- requests that failed and so
    missed every limit -- sort last and make the tail ``inf`` once it
    reaches them.  An empty list has no percentile: None."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or math.isinf(xs[lo]) or math.isinf(xs[hi]):
        return xs[hi]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mfu_percent(flops_per_sample, samples_per_s_per_chip, peak_flops_per_s):
    """Model FLOP/s utilization of one chip, in percent: the operations the
    forward and backward passes REQUIRE per sample (recomputation not
    counted) times samples per second per chip, over the chip's peak."""
    return 100.0 * flops_per_sample * samples_per_s_per_chip / peak_flops_per_s


def timer_delta(snaps, name):
    """(count, total seconds) a telemetry timer gained between two
    ``telemetry.snapshot()``s.  count/total are exact; the reservoir
    percentiles beside them are not, and are never read."""
    before, after = (s.get(name, {}) for s in snaps)
    return (after.get("count", 0) - before.get("count", 0),
            after.get("total", 0.0) - before.get("total", 0.0))


def counter_delta(snaps, name):
    before, after = (s.get(name, {}) for s in snaps)
    return after.get("value", 0) - before.get("value", 0)


def timer_mean_ms(snaps, name):
    """Mean milliseconds per observation of a timer over the window, or
    None when it saw none."""
    n, total = timer_delta(snaps, name)
    return 1e3 * total / n if n > 0 else None
