"""Share of device-0 busy time in ops under ``jax.named_scope("attn_full")``
(``gluon/model_zoo/mellum.py:GQAMixer``): the full-attention layers'
append and ``flash_decode`` against the K‖V leaf that follows the capacity,
decode steps and prefill chunks alike."""
from lib.host_spans import scope_share


def read(ctx):
    return scope_share(ctx, ("attn_full",))
