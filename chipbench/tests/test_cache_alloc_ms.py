"""``serve.cache_alloc_ms`` on hand-made telemetry: the timer's mean over
the window in milliseconds, and nothing — no raise — from a program or a
window that never observed it."""
import pytest

import run as R

NAME = "serve.cache_alloc_ms"
TIMER = "serve.cache_alloc_seconds"


def _read(before, after):
    return R.load_module("layer_metrics", NAME).read(
        {"telemetry": (before, after)})


def test_mean_of_the_windows_allocations_in_ms():
    # 12 allocations before the window (warm-up, ramp) are not the window's
    before = {TIMER: {"count": 12, "total": 0.5}}
    after = {TIMER: {"count": 412, "total": 0.5 + 400 * 0.0012}}
    assert _read(before, after) == pytest.approx(1.2)
    # a timer first seen inside the window counts from zero
    assert _read({}, {TIMER: {"count": 4, "total": 0.128}}) \
        == pytest.approx(32.0)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                        # a program without it
    ({TIMER: {"count": 3, "total": 0.1}},) * 2,      # no admission in window
    ({}, {"serve.cache_move_seconds": {"count": 5, "total": 0.01}}),
], ids=["no-timer", "no-admission", "another-timer"])
def test_reads_nothing_without_its_timer(before, after):
    assert _read(before, after) is None
