"""Tokens each decode step yields: the ``serve.tokens`` counter's gain less
one per prefill (a prefill emits the request's first token), over the number
of decode steps (``serve.decode_step_seconds`` count)."""
from lib.stats import counter_delta, timer_delta


def read(ctx):
    steps, _ = timer_delta(ctx["telemetry"], "serve.decode_step_seconds")
    prefills, _ = timer_delta(ctx["telemetry"], "serve.prefill_seconds")
    if steps <= 0:
        return None
    tokens = counter_delta(ctx["telemetry"], "serve.tokens")
    return (tokens - prefills) / steps
