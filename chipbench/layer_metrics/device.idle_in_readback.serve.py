"""Share of device-0 idle time under ``serve.step_readback``: the wait for the
step BEHIND the one in flight and the read of its ``(slots,)`` ids and counts
(no logits reach the host).  Innermost span wins;
the five ``device.idle_*`` shares sum to 100."""
from lib.host_spans import serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, "readback")
