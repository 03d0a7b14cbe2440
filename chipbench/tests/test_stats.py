import math

import pytest

from lib import peaks, stats
from lib.traffic import deal, draw_length, quantile_lengths, request_stream


def test_percentile_interpolates_like_numpy():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 95) == pytest.approx(4.8)
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 95) is None


def test_percentile_failed_requests_are_misses():
    xs = [1.0] * 18 + [math.inf, math.inf]
    assert stats.percentile(xs, 50) == 1.0
    assert math.isinf(stats.percentile(xs, 95))
    assert stats.percentile([1.0] * 99 + [math.inf], 95) == 1.0


def test_mfu_arithmetic():
    # 98.5 GFLOP a sample at 1000 samples/s is half of 197 TFLOP/s
    assert stats.mfu_percent(98.5e9, 1000.0, 197e12) == pytest.approx(50.0)


def test_bert_base_flops_from_shapes():
    import json
    import os

    import run as R

    here = os.path.dirname(R.__file__)
    cfg = json.load(open(os.path.join(here, "configs", "bert-base.json")))
    trf = json.load(open(os.path.join(here, "traffic", "pretrain-s128.json")))
    model = R.load_module("models", "bert_pretrain")
    # by hand: per token and layer 2*(4*768^2 + 2*768*3072) + 4*128*768,
    # 12 layers, 128 tokens; pooler + NSP; 20 x (transform + decoder); x 3
    per_tok = 2 * (4 * 768 ** 2 + 2 * 768 * 3072) + 4 * 128 * 768
    fwd = 128 * 12 * per_tok + 2 * 768 ** 2 + 4 * 768 \
        + 20 * (2 * 768 ** 2 + 2 * 768 * 30522)
    assert model.flops_per_sample(cfg, trf) == 3.0 * fwd
    assert 69e9 < 3.0 * fwd < 71e9


def test_timer_and_counter_deltas():
    a = {"t": {"count": 2, "total": 1.0}, "c": {"value": 5}}
    b = {"t": {"count": 5, "total": 2.5}, "c": {"value": 9}, "new": {"value": 3}}
    assert stats.timer_delta((a, b), "t") == (3, 1.5)
    assert stats.counter_delta((a, b), "c") == 4
    assert stats.counter_delta((a, b), "new") == 3
    assert stats.timer_delta((a, b), "absent") == (0, 0.0)


def test_unknown_device_kind_is_an_error():
    assert peaks.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9000")
    with pytest.raises(KeyError):
        peaks.peak_for("_source")


LENGTHS = {"prompt": {"dist": "lognormal", "median": 124, "sigma": 0.8,
                      "min": 16, "max": 512},
           "output": {"dist": "uniform", "min": 16, "max": 256}}


def test_a_seed_gives_the_same_requests_and_another_seed_others():
    def sizes(seed, n=400):
        s = request_stream(LENGTHS, 50257, seed)
        reqs = [next(s) for _ in range(n)]
        assert all(1 <= int(p.min()) and int(p.max()) < 50257 for p, _ in reqs)
        return [(len(p), k) for p, k in reqs]

    a, b = sizes(2 ** 31 + 11), sizes(1)
    assert a == sizes(2 ** 31 + 11) and a != b
    for got in (a, b):
        assert all(16 <= p <= 512 and 16 <= k <= 256 for p, k in got)
        prompts = sorted(p for p, _ in got)
        # plain sampling: the sample median is near the distribution's
        assert 105 <= prompts[len(prompts) // 2] <= 145
        assert 120 <= sum(k for _, k in got) / len(got) <= 152


def test_unknown_length_distribution_is_an_error():
    with pytest.raises(ValueError):
        draw_length({"dist": "zipf", "min": 1, "max": 2}, None)
    with pytest.raises(ValueError):
        quantile_lengths({"dist": "zipf", "min": 1, "max": 2}, 4)


def test_mid_quantiles_are_the_distribution_without_the_luck():
    prompts = quantile_lengths(LENGTHS["prompt"], 64)
    assert prompts == sorted(prompts) and len(prompts) == 64
    assert 16 <= prompts[0] and prompts[-1] <= 512
    assert 120 <= prompts[32] <= 128            # the median, 124
    outs = quantile_lengths(LENGTHS["output"], 241)
    assert outs == list(range(16, 257))         # one of each, 16..256
    assert quantile_lengths(LENGTHS["output"], 2) == [76, 196]
    # clipped like a plain draw: the tail past ``max`` piles up on it
    clipped = quantile_lengths(dict(LENGTHS["prompt"], max=200), 8)
    assert clipped[-1] == clipped[-2] == 200 and clipped[0] < 60


DECK = dict(LENGTHS, deck={"cards": 32, "hand": 8})


def dealt(seed, n, lengths=DECK):
    s = request_stream(lengths, 50257, seed)
    reqs = [next(s) for _ in range(n)]
    assert all(1 <= int(p.min()) and int(p.max()) < 50257 for p, _ in reqs)
    assert all(type(k) is int for _, k in reqs)     # max_new_tokens
    return [(len(p), k) for p, k in reqs]


def test_a_deck_deals_every_seed_the_same_lengths_in_another_order():
    want_p = quantile_lengths(LENGTHS["prompt"], 32)
    want_k = quantile_lengths(LENGTHS["output"], 32)
    a, b = dealt(2 ** 31 + 11, 96), dealt(1, 96)
    assert a == dealt(2 ** 31 + 11, 96) and a != b
    for got in (a, b):
        for d in range(0, 96, 32):              # deck after deck
            assert sorted(p for p, _ in got[d:d + 32]) == want_p
            assert sorted(k for _, k in got[d:d + 32]) == want_k
        assert got[:32] != got[32:64]           # each deck shuffled anew
    # the pairing is the seed's too, not one fixed set of requests
    assert sorted(a[:32]) != sorted(b[:32])


def test_a_hand_holds_one_length_of_each_stratum():
    want_p = quantile_lengths(LENGTHS["prompt"], 32)
    want_k = quantile_lengths(LENGTHS["output"], 32)
    strata_p = [set(want_p[s * 4:s * 4 + 4]) for s in range(8)]
    strata_k = [set(want_k[s * 4:s * 4 + 4]) for s in range(8)]
    sums = []
    for seed in (3, 2 ** 31 + 5, 77):
        got = dealt(seed, 64)
        for h in range(0, 64, 8):
            hand = got[h:h + 8]
            for col, strata in ((0, strata_p), (1, strata_k)):
                vals = sorted(r[col] for r in hand)
                assert all(v in st for v, st in zip(vals, strata))
            sums.append(sum(p for p, _ in hand))
    # so any stretch does nearly the same work: the hands' prompt totals
    # lie within a few percent where eight plain draws differ by tens
    assert (max(sums) - min(sums)) / (sum(sums) / len(sums)) < 0.2
    plain = dealt(3, 64, LENGTHS)
    psums = [sum(p for p, _ in plain[h:h + 8]) for h in range(0, 64, 8)]
    assert (max(psums) - min(psums)) / (sum(psums) / len(psums)) > 0.3


def test_a_deck_of_one_hand_is_a_plain_shuffle_and_a_ragged_one_an_error():
    one = dict(LENGTHS, deck={"cards": 8, "hand": 8})
    got = dealt(5, 16, one)
    assert sorted(p for p, _ in got[:8]) == quantile_lengths(
        LENGTHS["prompt"], 8)
    for bad in ({"cards": 30, "hand": 8}, {"cards": 8, "hand": 0}):
        with pytest.raises(ValueError):
            next(deal(bad, LENGTHS, None))


def test_the_mellum_mix_is_dealt_and_the_other_mixes_are_sampled():
    import glob
    import json
    import os

    from conftest import ROOT

    decks = {}
    for path in glob.glob(os.path.join(ROOT, "chipbench", "traffic",
                                       "*.json")):
        t = json.load(open(path))
        if "lengths" in t:
            decks[os.path.basename(path)] = t["lengths"].get("deck")
    assert decks.pop("ide-mixed-closed.json") == {"cards": 64, "hand": 16}
    assert decks and all(d is None for d in decks.values())
    t = json.load(open(os.path.join(ROOT, "chipbench", "traffic",
                                    "ide-mixed-closed.json")))
    prompts = quantile_lengths(t["lengths"]["prompt"], 64)
    # every chunk count an admission can take is in every deck
    assert {-(-p // 512) for p in prompts} == set(range(1, 15))
    assert prompts.count(7040) == 5 and min(prompts) >= 128
