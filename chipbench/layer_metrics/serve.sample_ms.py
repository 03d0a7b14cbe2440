"""Mean time of ``serve.sample``, the per-slot sampling loop of one decode
step (sample, append, ``on_token``, release)."""
from lib.stats import timer_mean_ms


def read(ctx):
    return timer_mean_ms(ctx["telemetry"], "serve.sample_seconds")
