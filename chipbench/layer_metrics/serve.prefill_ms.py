"""Mean host-clock time of one prefill: forward, first sample, cache move."""
from lib.stats import timer_mean_ms


def read(ctx):
    return timer_mean_ms(ctx["telemetry"], "serve.prefill_seconds")
