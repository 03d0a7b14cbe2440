"""Mean time of ``serve.cache_alloc``: an admission's fresh one-row cache,
before its prompt forward and while every other slot stalls."""
from lib.stats import timer_mean_ms


def read(ctx):
    return timer_mean_ms(ctx["telemetry"], "serve.cache_alloc_seconds")
