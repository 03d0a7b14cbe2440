"""The comparison that decides ``correct`` for serving must fail on a model
that is wrong in a way a serving bug would be: a position shifted by one."""
import json
import os

import numpy as onp

import run as R
from lib.checks import greedy_agrees

FX = os.path.join(os.path.dirname(__file__), "fixtures")


def test_shifted_position_fails_the_logit_comparison():
    config = json.load(open(os.path.join(FX, "tiny-lm.json")))
    model = R.load_module("models", "transformer_lm")
    ref = R.load_module("references", "transformer_lm")
    lm = model.build(config, seed=3)
    params = {k: p.data()._data for k, p in lm.collect_params().items()}
    rs = onp.random.RandomState(0)
    seq = rs.randint(1, config["vocab_size"], size=40)
    good = onp.asarray(ref.logits(params, config, seq))
    n_prompt = 10
    chosen = good[n_prompt - 1:-1].argmax(-1)      # a greedy continuation
    ok, worst, exact = greedy_agrees(good, n_prompt, chosen, ref.LOGIT_RTOL)
    assert ok and worst == 0.0 and exact == len(chosen)
    import jax.numpy as jnp

    moved = dict(params, position_weight=jnp.roll(params["position_weight"],
                                                  -1, axis=0))
    shifted = onp.asarray(ref.logits(moved, config, seq))
    ok, worst, _ = greedy_agrees(shifted, n_prompt, chosen, ref.LOGIT_RTOL)
    assert not ok and worst > ref.LOGIT_RTOL


def test_short_or_non_finite_rows_fail():
    ref = onp.zeros((8, 5), "float32")
    assert not greedy_agrees(ref, 6, [0, 0, 0, 0], 0.05)[0]     # too few rows
    ref[3, 2] = onp.nan
    assert not greedy_agrees(ref, 2, [0, 0, 0], 0.05)[0]
