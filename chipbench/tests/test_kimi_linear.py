"""The ``kimi_linear`` builder, reference and readers at a tiny size on the
CPU: the closed-loop driver serves the fixture through ``run.main`` and its
check against the plain reference holds, and the control with the
recurrent state in bf16 comes out not correct by that same check; the byte
and operation functions
count what the docstrings say; the new readers leave their metric out, and
do not raise, on a program or a builder that lacks what they read."""
import json
import os

import run as R
from test_drivers import FX, run_fixture


def test_closed_loop_serves_the_tiny_kimi_fixture(monkeypatch, capsys):
    out, notes = run_fixture(monkeypatch, capsys, "tiny-kimi", "tiny-closed",
                             "serve")
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["worst_gap_of_max_ref"] <= notes["rtol"]
               for c in notes["reference"])


def test_a_bf16_state_is_not_correct_by_the_cell_s_own_check():
    """The reference's greedy tokens through ``lib/checks.greedy_agrees``,
    as ``serve_closed.verify`` calls it: correct with the program's
    recurrence under test (float32 state), not correct with the scan's
    state kept in bf16 -- by ``STATE_RTOL``, while ``LOGIT_RTOL`` alone
    would have passed those logits."""
    import functools

    import jax.numpy as jnp
    import numpy as onp

    from lib.checks import greedy_agrees

    cfg = json.load(open(os.path.join(FX, "tiny-kimi.json")))
    lm = R.load_module("models", "kimi_linear").build(cfg, 5)
    ref = R.load_module("references", "kimi_linear")
    params = {k: p.data()._data for k, p in lm.collect_params().items()}
    seq = onp.random.RandomState(5).randint(1, cfg["vocab_size"], size=90)
    sound = onp.asarray(ref.logits(params, cfg, seq))
    chosen = sound.argmax(-1)[29:]
    assert greedy_agrees(sound, 30, chosen, ref.LOGIT_RTOL)[0]
    bf16 = functools.partial(ref.scan_recurrence, state_dtype=jnp.bfloat16)
    judged = ref.logits(params, cfg, seq, recurrence=bf16)
    ok, worst, _ = greedy_agrees(judged, 30, chosen, ref.LOGIT_RTOL)
    assert not ok and worst == float("inf")
    lower = onp.asarray(ref.logits(params, cfg, seq, recurrence=None,
                                   state_dtype=jnp.bfloat16))
    assert greedy_agrees(sound, 30, lower.argmax(-1)[29:],
                         ref.LOGIT_RTOL)[0]


def test_byte_and_operation_functions_follow_the_shapes():
    model = R.load_module("models", "kimi_linear")
    cfg = json.load(open(os.path.join(
        os.path.dirname(R.HERE), "chipbench", "configs",
        "kimi-linear-48b-a3b.json")))
    assert model.moe_layers(cfg) == 26
    # 20 layers x 32 heads x 128 x 128 f32, read and written: 83.9 MB a slot
    assert model.kda_step_bytes(cfg, 1) == 2 * 20 * 32 * 128 * 128 * 4
    assert model.moe_experts_bytes(cfg, 1) == 3 * 2304 * 1024 * 2
    assert model.mla_decode_bytes(cfg, 1) == 7 * 576 * 2
    assert model.kda_chunk_flops(cfg, 1) == 32 * 20 * (
        4 * 64 * 128 + 64 * 256 + 6 * 128 * 128 + 2 * 64 * 128)


def test_new_readers_read_nothing_without_their_sources():
    bench = json.load(open(os.path.join(R.ROOT, "BENCHMARK.json")))
    cell = "kimi-linear-48b-a3b.reason-closed"
    mine = [m["name"] for m in bench["per_layer"]
            if cell in m["workloads"] and m["name"].split(".")[1].startswith(
                ("kda_", "moe_", "mla_"))]
    assert {"kernels.kda_step_roofline.serve", "kernels.mla_decode_roofline"
            ".serve", "serve.moe_held_picks_per_token"} <= set(mine)
    ctx = {"cell": {"name": "no-such-cell"}, "config": {}, "trace": None,
           "traffic": {"server": {"slots": 2}}, "telemetry": ({}, {}),
           "model": object(), "window_s": 1.0, "peaks": {}}
    for name in mine:
        assert R.load_module("layer_metrics", name).read(ctx) is None


def test_roofline_share_on_the_recorded_serving_trace():
    """``lib/roofline.share`` on a real v5e trace, under a scope that trace
    holds (``flash_decode``): the least time as a share of the window over
    the scope's share of the slice."""
    import pytest

    import reduce_trace as rt
    from lib import host_spans as hs
    from lib import roofline

    fixture = os.path.join(FX, "serve_steps.xplane.pb")
    if not os.path.exists(fixture):
        pytest.skip("no recorded serving trace in fixtures/")
    ctx = {"trace": rt.reduce_file(fixture), "window_s": 10.0,
           "xplane": hs.read_file(fixture),
           "telemetry": ({"c": {"value": 2}}, {"c": {"value": 9}})}
    seconds = roofline.scope_seconds(ctx, ("flash_decode",))
    assert 0.0 < seconds < ctx["trace"]["busy_s"]
    assert roofline.scope_seconds(ctx, ("kda_step",)) is None
    want = 100.0 * (0.5 / 10.0) / (seconds / ctx["trace"]["slice_s"])
    assert roofline.share(ctx, ("flash_decode",), 0.5) == pytest.approx(want)
    assert roofline.share(ctx, ("kda_step",), 0.5) is None
    assert roofline.counted(ctx, "c") == 7
    assert roofline.counted(ctx, "absent") is None
