"""Share of device-0 busy time in ops under ``jax.named_scope("kda_chunk")``
(``ops/kda.py:kda_chunk``): the chunk-parallel KDA of a prompt's prefill."""
from lib.host_spans import scope_share


def read(ctx):
    return scope_share(ctx, ("kda_chunk",))
