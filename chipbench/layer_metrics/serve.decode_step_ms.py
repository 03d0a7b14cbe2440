"""Mean host-clock time of one decode step: the dispatch of the step AHEAD and
the wait for and read of this step's ``(slots,)`` ids and counts.  With a
step running ahead it is the larger of the host's work a step and the
device's, not their sum."""
from lib.stats import timer_mean_ms


def read(ctx):
    return timer_mean_ms(ctx["telemetry"], "serve.decode_step_seconds")
