"""``kda_chunk``'s share of its roofline (compute-bound): the operations of
the chunk-parallel recurrence for the prompts' true tokens
(``serve.prefill_tokens`` x ``models/<builder>.kda_chunk_flops``) over
``bf16_flops_per_s`` -- the chip's only stated peak; the program computes
this scope in float32, which the MXU runs in several bf16 passes -- against
the scope's device seconds.  A ~3 s slice holds one to five prefills, so
this one swings fourfold between traced runs of one tree (0.33 to 1.33;
``lib/roofline.py``)."""
from lib import roofline


def read(ctx):
    tokens = roofline.counted(ctx, "serve.prefill_tokens")
    fn = roofline.builder_fn(ctx, "kda_chunk_flops")
    if tokens is None or fn is None:
        return None
    return roofline.share(ctx, ("kda_chunk",), fn(ctx["config"], tokens)
                          / ctx["peaks"]["bf16_flops_per_s"])
