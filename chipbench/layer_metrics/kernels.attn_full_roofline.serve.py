"""``attn_full``'s share of its roofline (memory-bound): the live cache rows
every full-attention layer must read in a decode step
(``serve.step_live_positions`` x ``models/<builder>.attn_full_bytes``) over
``hbm_bytes_per_s``, against the device seconds under
``jax.named_scope("attn_full")``.  The prefill chunks and the append run
under the same scope: their time lies in the denominator and their bytes
are left out of the numerator, so the share reads low, never high.  Swings
between traced runs of one tree as every share of ``lib/roofline.py``."""
from lib import roofline


def read(ctx):
    rows = roofline.counted(ctx, "serve.step_live_positions")
    fn = roofline.builder_fn(ctx, "attn_full_bytes")
    if rows is None or fn is None:
        return None
    return roofline.share(ctx, ("attn_full",), fn(ctx["config"], rows)
                          / ctx["peaks"]["hbm_bytes_per_s"])
