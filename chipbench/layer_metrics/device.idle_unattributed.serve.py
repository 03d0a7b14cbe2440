"""Share of device-0 idle time under none of the decode loop's phase spans:
loop bookkeeping, ``_reap``, the interpreter between two phases."""
from lib.host_spans import UNATTRIBUTED, serve_idle_share


def read(ctx):
    return serve_idle_share(ctx, UNATTRIBUTED)
