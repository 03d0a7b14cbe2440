"""Share of device-0 idle time under ``serve.sample``: the per-slot sampling
loop, where every ``on_token`` of a decode step fires.  Innermost span wins;
the five ``device.idle_*`` shares sum to 100."""
from lib.host_spans import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "sample")
