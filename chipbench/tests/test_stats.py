import math

import pytest

from lib import peaks, stats
from lib.traffic import draw_length, request_stream


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
