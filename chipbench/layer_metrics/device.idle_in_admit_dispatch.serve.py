"""Share of device-0 idle time under ``serve.prefill_dispatch``: the call of
the LM's prefill program on one prompt piece, over its weight leaves.  One
of the four parts of ``device.idle_in_admit.serve``
(``lib/admit_spans.py``)."""
from lib.admit_spans import admit_idle_share


def read(ctx):
    return admit_idle_share(ctx, "admit_dispatch")
