"""Mean time of ``serve.cache_move``: the row cache's move into its slot,
after the client has its first token and while every other slot stalls."""
from lib.stats import timer_mean_ms


def read(ctx):
    return timer_mean_ms(ctx["telemetry"], "serve.cache_move_seconds")
