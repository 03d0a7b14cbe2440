"""Share of the window the decode loop spent in prefills.  With
``prefill_workers=0`` a prefill runs inside the decode loop, so this is
time in which every slot stalls."""
from lib.stats import timer_delta


def read(ctx):
    n, total = timer_delta(ctx["telemetry"], "serve.prefill_seconds")
    return 100.0 * total / ctx["window_s"] if n > 0 else None
