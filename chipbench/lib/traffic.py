"""The one general traffic generator.  A traffic mix is a data file of
parameters (``chipbench/traffic/<name>.json``); this module turns its
``lengths`` block into requests.  The only source of randomness is
``--seed``: the same seed gives the same requests.

Two ways to draw, chosen by the file and never by a name:

* **plain sampling** (no ``deck`` key): each length is sampled from its
  distribution, so another seed gives other requests of the same
  distribution and does another amount of work; steadiness comes only from
  the number of requests a window holds.
* **a deck** (``"deck": {"cards": N, "hand": H}``, PR 36): every seed is
  dealt the SAME N prompt lengths and the SAME N output lengths -- the N
  mid-quantiles of each distribution -- in another order and pairing, over
  and over.  Each run of H requests holds one length from each of H equal
  strata of either distribution, so any stretch of the stream does nearly
  the same work whatever the seed, and what is left of a cell's spread is
  the run's own noise.  For a mix whose few requests a window have heavy
  tails (a closed loop's rate follows the sum of its prompts).
"""
import math
from statistics import NormalDist

import numpy as onp


def _clip(v, spec):
    return min(max(int(round(v)), spec["min"]), spec["max"])


def draw_length(spec, rs):
    """One length from ``{"dist", ..., "min", "max"}``, clipped to
    [min, max]: ``lognormal`` (``median``, ``sigma``) or ``uniform``."""
    if spec["dist"] == "lognormal":
        v = rs.lognormal(math.log(spec["median"]), spec["sigma"])
    elif spec["dist"] == "uniform":
        v = rs.randint(spec["min"], spec["max"] + 1)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return _clip(v, spec)


def quantile_lengths(spec, n):
    """The ``n`` mid-quantiles ((i + 1/2) / n) of a length distribution,
    ascending and clipped like ``draw_length``'s: what ``n`` plain draws
    tend to, with no luck in it."""
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "lognormal":
        inv = NormalDist().inv_cdf
        vs = [spec["median"] * math.exp(spec["sigma"] * inv(q)) for q in qs]
    elif spec["dist"] == "uniform":
        vs = [math.floor(spec["min"] + q * (spec["max"] + 1 - spec["min"]))
              for q in qs]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [_clip(v, spec) for v in vs]


def deal(deck, lengths, rs):
    """(prompt length, output length) for ever, dealt from the deck: the
    ``cards`` mid-quantiles of either distribution fall into ``hand``
    strata of ``cards / hand`` neighbours; a hand takes one length a
    stratum, shuffled, prompts and outputs apart, and after ``cards /
    hand`` hands every length has been dealt once and the next deck
    begins.  ``rs`` sets which neighbour a hand gets and every order."""
    cards, hand = deck["cards"], deck["hand"]
    if hand < 1 or cards % hand:
        raise ValueError(f"a deck of {cards} cards is no whole number of "
                         f"hands of {hand}")
    per = cards // hand
    columns = [quantile_lengths(lengths[k], cards)
               for k in ("prompt", "output")]
    while True:
        turn = [[rs.permutation(per) for _ in range(hand)] for _ in columns]
        for h in range(per):
            yield from zip(*(
                rs.permutation([col[s * per + t[s][h]] for s in range(hand)])
                for col, t in zip(columns, turn)))


def request_stream(lengths, vocab_size, seed):
    """An endless stream of (prompt token ids, max_new_tokens) drawn from
    ``seed``: lengths sampled from the file's distributions, or dealt from
    its deck where it has one; token ids uniform in [1, vocab)."""
    rs = onp.random.RandomState(seed % (2 ** 32))
    dealt = deal(lengths["deck"], lengths, rs) if "deck" in lengths else None
    while True:
        if dealt is None:
            n_prompt = draw_length(lengths["prompt"], rs)
            n_out = draw_length(lengths["output"], rs)
        else:
            n_prompt, n_out = (int(n) for n in next(dealt))
        yield rs.randint(1, vocab_size, size=n_prompt).astype("int32"), n_out
