"""Share of device-0 busy time in the decode-attention kernel
(``pallas_call(name="flash_decode")``), decode steps and prefill chunks
alike."""
from lib.host_spans import scope_share


def read(ctx):
    return scope_share(ctx, ("flash_decode",))
