"""``kda_step``'s share of its roofline (memory-bound): the state bytes a
step must read and write for the slots that were OCCUPIED
(``models/<builder>.kda_step_bytes`` of the tokens the window's decode steps
delivered: a free slot's state need not move, though the program steps all
of them) over ``hbm_bytes_per_s``, against the scope's device seconds.
Swings by a quarter between traced runs of one tree (``lib/roofline.py``)."""
from lib import roofline


def read(ctx):
    fn = roofline.builder_fn(ctx, "kda_step_bytes")
    if fn is None:
        return None
    return roofline.share(
        ctx, ("kda_step",), fn(ctx["config"], roofline.decode_tokens(ctx))
        / ctx["peaks"]["hbm_bytes_per_s"])
