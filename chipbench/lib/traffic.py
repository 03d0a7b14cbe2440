"""The one general traffic generator.  A traffic mix is a data file of
parameters (``chipbench/traffic/<name>.json``); this module turns its
``lengths`` block into requests.

Lengths are drawn from the file's distributions by plain sampling, and the
only source of randomness is ``--seed``: the same seed gives the same
requests, another seed gives other requests of the same distribution (so a
change tuned on the seeds it was written with is also judged on requests it
has not seen).  Steadiness comes from the number of requests a window
holds, not from the generator.
"""
import math

import numpy as onp


def draw_length(spec, rs):
    """One length from ``{"dist", ..., "min", "max"}``, clipped to
    [min, max]: ``lognormal`` (``median``, ``sigma``) or ``uniform``."""
    if spec["dist"] == "lognormal":
        v = rs.lognormal(math.log(spec["median"]), spec["sigma"])
    elif spec["dist"] == "uniform":
        v = rs.randint(spec["min"], spec["max"] + 1)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return min(max(int(round(v)), spec["min"]), spec["max"])


def request_stream(lengths, vocab_size, seed):
    """An endless stream of (prompt token ids, max_new_tokens) drawn from
    ``seed``: lengths from the file's distributions, token ids uniform in
    [1, vocab)."""
    rs = onp.random.RandomState(seed % (2 ** 32))
    while True:
        n_prompt = draw_length(lengths["prompt"], rs)
        n_out = draw_length(lengths["output"], rs)
        yield rs.randint(1, vocab_size, size=n_prompt).astype("int32"), n_out
