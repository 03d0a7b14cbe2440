"""Mean host-clock time of one decode step (the timer ends after the logits
reached the host, so it includes the device)."""
from lib.stats import timer_mean_ms


def read(ctx):
    return timer_mean_ms(ctx["telemetry"], "serve.decode_step_seconds")
