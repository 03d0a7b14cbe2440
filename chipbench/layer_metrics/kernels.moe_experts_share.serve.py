"""Share of device-0 busy time in the routed experts
(``parallel/moe.py:held_experts_ffn``), decode steps and prefills alike:
what runs under ``jax.named_scope("moe_experts")`` and the grouped
products' own custom calls (``lib/roofline.py:MOE_EXPERTS_SCOPES``)."""
from lib.host_spans import scope_share
from lib.roofline import MOE_EXPERTS_SCOPES


def read(ctx):
    return scope_share(ctx, MOE_EXPERTS_SCOPES)
