"""Share of device-0 busy time in the flash-attention forward kernel
(``pallas_call(name="flash_fwd")``)."""
from lib.host_spans import scope_share


def read(ctx):
    return scope_share(ctx, ("flash_fwd",))
