"""Share of device-0 idle time under ``serve.prefill_readback``: the wait
for the last prompt piece, the eager slice and copy of its last row of
logits, and the read of the pieces' counts.  One of the four parts of
``device.idle_in_admit.serve`` (``lib/admit_spans.py``)."""
from lib.admit_spans import admit_idle_share


def read(ctx):
    return admit_idle_share(ctx, "admit_readback")
