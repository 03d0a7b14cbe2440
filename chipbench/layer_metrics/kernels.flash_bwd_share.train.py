"""Share of device-0 busy time in the two flash-attention backward kernels
(``pallas_call(name="flash_bwd_dq")`` and ``name="flash_bwd_dkv"``)."""
from lib.host_spans import scope_share


def read(ctx):
    return scope_share(ctx, ("flash_bwd_dq", "flash_bwd_dkv"))
