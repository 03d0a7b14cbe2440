"""Device-0 milliseconds of one decode step: the busy time of the step
program (its ops carry the ``jax.named_scope`` ``decode_step``; the
compiler's nameless copies between them count with them) over the
``serve.decode_step`` spans that end in the slice.  With a step run ahead
``serve.decode_step_ms`` is the larger of the host's time and this."""
from lib.admit_spans import step_ms


def read(ctx):
    return step_ms(ctx)
