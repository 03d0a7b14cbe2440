"""``moe_experts``' share of its roofline (memory-bound at a token or two
an expert): the weights of the held experts that were HIT
(``serve.moe_experts_hit`` x ``models/<builder>.moe_experts_bytes``), not of
all the held, over ``hbm_bytes_per_s``, against the device seconds of the
scope and of the grouped products' own custom calls
(``lib/roofline.py:MOE_EXPERTS_SCOPES``).  Swings between traced runs of
one tree as the slice's share of admissions does (``lib/roofline.py``)."""
from lib import roofline


def read(ctx):
    hit = roofline.counted(ctx, "serve.moe_experts_hit")
    fn = roofline.builder_fn(ctx, "moe_experts_bytes")
    if hit is None or fn is None:
        return None
    return roofline.share(ctx, roofline.MOE_EXPERTS_SCOPES, fn(ctx["config"], hit)
                          / ctx["peaks"]["hbm_bytes_per_s"])
