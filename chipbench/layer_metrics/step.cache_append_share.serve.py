"""Share of device-0 busy time in ops under ``jax.named_scope("cache_append")``
(``ops/attention.py:cache_append``): the KV cache's per-step append."""
from lib.host_spans import scope_share


def read(ctx):
    return scope_share(ctx, ("cache_append",))
