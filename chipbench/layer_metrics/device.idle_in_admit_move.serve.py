"""Share of device-0 idle time under ``serve.cache_move``: the dispatch of
the program that moves the admitted row cache into its slot.  One of the
four parts of ``device.idle_in_admit.serve`` (``lib/admit_spans.py``)."""
from lib.admit_spans import admit_idle_share


def read(ctx):
    return admit_idle_share(ctx, "admit_move")
