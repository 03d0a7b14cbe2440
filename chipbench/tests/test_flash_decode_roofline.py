"""``kernels.flash_decode_roofline.serve`` on a hand-made ``ctx``: the
arithmetic on the recorded serving trace (which holds the kernel's name),
and nothing — no raise — without a trace, without the counter or for a
configuration that is no transformer."""
import os

import pytest

import reduce_trace as rt
import run as R
from lib import host_spans as hs
from lib import roofline
from test_drivers import FX

NAME = "kernels.flash_decode_roofline.serve"
XL = {"n_layer": 48, "n_embd": 1600, "n_head": 25, "dtype": "bfloat16"}


def _ctx(**over):
    ctx = {"cell": {"name": "no-such-cell"}, "config": XL, "trace": None,
           "window_s": 10.0,
           "peaks": {"hbm_bytes_per_s": 819e9},
           "telemetry": ({"serve.step_live_positions": {"value": 1000}},
                         {"serve.step_live_positions": {"value": 4521000}})}
    ctx.update(over)
    return ctx


def test_a_cache_position_is_k_and_v_of_every_head_and_layer():
    reader = R.load_module("layer_metrics", NAME)
    # 48 layers x 25 heads x 128 lanes (K‖V at head size 64) x 2 B
    assert reader.position_bytes(XL) == 48 * 25 * 128 * 2 == 307200
    assert reader.position_bytes(dict(XL, dtype="float32")) == 614400


def test_share_is_live_bytes_over_the_kernels_seconds():
    fixture = os.path.join(FX, "serve_steps.xplane.pb")
    if not os.path.exists(fixture):
        pytest.skip("no recorded serving trace in fixtures/")
    reader = R.load_module("layer_metrics", NAME)
    ctx = _ctx(trace=rt.reduce_file(fixture), xplane=hs.read_file(fixture))
    seconds = roofline.scope_seconds(ctx, ("flash_decode",))
    least = 4520000 * 307200 / 819e9            # 1.695 s of the 10 s window
    want = 100.0 * (least / 10.0) / (seconds / ctx["trace"]["slice_s"])
    assert reader.read(ctx) == pytest.approx(want) and want > 0.0


@pytest.mark.parametrize("over", [
    {},                                              # an untraced run
    {"telemetry": ({}, {})},                         # a program without it
    {"config": {"hidden_size": 2304}},               # no transformer_lm
], ids=["no-trace", "no-counter", "other-config"])
def test_reads_nothing_without_its_sources(over):
    assert R.load_module("layer_metrics", NAME).read(_ctx(**over)) is None
