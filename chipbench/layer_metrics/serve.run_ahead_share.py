"""Percent of the window's decode steps that were dispatched while the step
before was still unread (counter ``serve.steps_run_ahead``), over the number
of decode steps (``serve.decode_step_seconds`` count): how much of the loop
overlaps the host's read, emit and release with the device's next step.  The
rest are the steps that follow an admission or a freed slot, the step after
every run of steps as long as the program allows in a row, and every step
taken while a request that samples at a temperature holds a slot.  Nothing
where the program has no such counter."""
from lib import roofline
from lib.stats import timer_delta


def read(ctx):
    ahead = roofline.counted(ctx, "serve.steps_run_ahead")
    if ahead is None:
        return None
    steps, _ = timer_delta(ctx["telemetry"], "serve.decode_step_seconds")
    return 100.0 * ahead / steps if steps > 0 else None
