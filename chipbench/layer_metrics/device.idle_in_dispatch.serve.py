"""Share of device-0 idle time under ``serve.step_dispatch``: the eager int32
inputs of a step and the call that hands the step program to the device.
Innermost span wins; the five ``device.idle_*`` shares sum to 100."""
from lib.host_spans import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "dispatch")
