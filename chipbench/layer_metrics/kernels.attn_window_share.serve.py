"""Share of device-0 busy time in ops under
``jax.named_scope("attn_window")`` (``gluon/model_zoo/mellum.py:GQAMixer``):
the window layers' append and ``flash_decode`` against their ring, decode
steps and prefill chunks alike."""
from lib.host_spans import scope_share


def read(ctx):
    return scope_share(ctx, ("attn_window",))
