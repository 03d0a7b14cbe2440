"""Share of device-0 idle time under ``serve.step_dispatch``: the one int32
upload and the call that hands the device the step AHEAD (this step too when
none was in flight; nothing where a step is not run ahead of).  Innermost
span wins; the five ``device.idle_*`` shares sum to 100."""
from lib.host_spans import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "dispatch")
