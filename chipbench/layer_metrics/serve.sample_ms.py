"""Mean time of ``serve.sample``, the per-slot emit loop of one decode step
(append, ``on_token``, release; the step program takes the ``argmax`` on the
device, so a greedy slot samples nothing here)."""
from lib.stats import timer_mean_ms


def read(ctx):
    return timer_mean_ms(ctx["telemetry"], "serve.sample_seconds")
