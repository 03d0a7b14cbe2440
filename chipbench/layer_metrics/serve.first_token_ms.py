"""Mean time of ``serve.first_token``: row-cache allocation, prompt forward,
first sample and its ``on_token`` -- the request's own part of its TTFT."""
from lib.stats import timer_mean_ms


def read(ctx):
    return timer_mean_ms(ctx["telemetry"], "serve.first_token_seconds")
