"""Share of device-0 idle time under ``serve.reply_wait``: the loop's wait
of up to ``_REPLY_GRACE_S`` at a boundary that freed a slot with nothing
queued.  A part of ``device.idle_unattributed.serve``
(``lib/admit_spans.py``)."""
from lib.admit_spans import admit_idle_share


def read(ctx):
    return admit_idle_share(ctx, "reply_wait")
