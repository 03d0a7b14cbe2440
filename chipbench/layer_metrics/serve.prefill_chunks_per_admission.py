"""Forwards an admission's prompt took: the counter ``serve.prefill_chunks``
(one a ``(prompt bucket, capacity)`` program that ``DecodeEntry.
prefill_prompt`` ran) over the window's admissions (the count of
``serve.prefill_seconds``).  1.0 where every prompt fits the largest prompt
bucket; a prompt of ``n`` tokens past it takes ``ceil(n / bucket)``."""
from lib import roofline
from lib.stats import timer_delta


def read(ctx):
    chunks = roofline.counted(ctx, "serve.prefill_chunks")
    if chunks is None:
        return None
    admissions, _ = timer_delta(ctx["telemetry"], "serve.prefill_seconds")
    return chunks / admissions if admissions > 0 else None
