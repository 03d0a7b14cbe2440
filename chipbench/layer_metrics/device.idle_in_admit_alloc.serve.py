"""Share of device-0 idle time under ``serve.cache_alloc``: the dispatch of
an admission's zero-tree program.  One of the four parts of
``device.idle_in_admit.serve`` (``lib/admit_spans.py``)."""
from lib.admit_spans import admit_idle_share


def read(ctx):
    return admit_idle_share(ctx, "admit_alloc")
