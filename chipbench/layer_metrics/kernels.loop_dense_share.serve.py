"""Share of device-0 busy time in ops under ``jax.named_scope("loop_dense")``
(``gluon/model_zoo/mixer_lm.py``: a looped stack's cells name it): the
seven matrix products of a layer -- q, k, v and o in ``mellum.GQAMixer``,
gate, up and down in ``GatedFFN``, with the norm before them and the SiLU
gate between them -- in every pass of every decode step and prefill piece.
The mixer's norms, rotary, attention, the post-norms and the head are
outside it.  The products read operands that asynchronous copies brought
into fast memory under other ops, so this is their compute and not the
matrices' stream (``kernels.loop_dense_roofline.serve``)."""
from lib.host_spans import scope_share


def read(ctx):
    return scope_share(ctx, ("loop_dense",))
