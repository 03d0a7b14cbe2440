"""Mean time from ``submit()`` until a worker reached the request (the part
of TTFT before the request's own work starts)."""
from lib.stats import timer_mean_ms


def read(ctx):
    return timer_mean_ms(ctx["telemetry"], "serve.queue_wait_seconds")
