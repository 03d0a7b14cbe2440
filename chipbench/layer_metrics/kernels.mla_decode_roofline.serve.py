"""``mla_decode``'s share of its roofline (memory-bound): the live latent
rows every MLA layer must read in a decode step
(``serve.step_live_positions`` x ``models/<builder>.mla_decode_bytes``) over
``hbm_bytes_per_s``, against the device seconds under
``jax.named_scope("mla_decode")`` (``ops/mla.py:mla_absorbed``; the absorbed
form does not ride ``flash_decode``).  Swings by a quarter between traced
runs of one tree (``lib/roofline.py``)."""
from lib import roofline


def read(ctx):
    rows = roofline.counted(ctx, "serve.step_live_positions")
    fn = roofline.builder_fn(ctx, "mla_decode_bytes")
    if rows is None or fn is None:
        return None
    return roofline.share(ctx, ("mla_decode",), fn(ctx["config"], rows)
                          / ctx["peaks"]["hbm_bytes_per_s"])
