"""Share of device-0 idle time under ``serve.admit`` and its children: a
request's cache allocation, prompt forward, first sample and the move into
its slot.  Innermost span wins; the five ``device.idle_*`` shares sum to 100."""
from lib.host_spans import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "admit")
