"""Share of device-0 busy time in ops under ``jax.named_scope("kda_step")``
(``ops/kda.py:kda_step``): the one-token KDA recurrence of the decode step,
20 layers x slots x heads states read and written."""
from lib.host_spans import scope_share


def read(ctx):
    return scope_share(ctx, ("kda_step",))
