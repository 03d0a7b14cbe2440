import json
import os

import pytest

from lib import host_spans as hs

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
FIXTURE = os.path.join(FIXTURES, "serve_steps.xplane.pb")
BUCKETS = {"step.readback": "readback", "step.sample": "sample",
           "admit": "admit"}


def test_idle_is_the_window_less_the_union_of_the_ops():
    busy = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (9.5, 12.0)]
    assert hs.idle_intervals(busy, (0.0, 10.0)) == [
        (0.0, 1.0), (3.0, 5.0), (6.0, 9.5)]
    assert hs.idle_intervals([], (2.0, 4.0)) == [(2.0, 4.0)]
    assert hs.idle_intervals([(0.0, 9.0)], (2.0, 4.0)) == []


def test_the_innermost_span_wins_and_shares_sum_to_100():
    #  busy   |##|      |####|          |#|
    #         0  1      4    6          9 10
    #  step   [----------------)  sample [6.5, 8)
    #  readback   [2, 3.5)
    busy = [(0.0, 1.0), (4.0, 6.0), (9.0, 10.0)]
    spans = [("step", 0.0, 6.0), ("step.readback", 2.0, 3.5),
             ("step.sample", 6.5, 8.0)]
    got = hs.split_idle(busy, (0.0, 10.0), spans, BUCKETS)
    # idle: [1, 4) and [6, 9).  readback takes [2, 3.5) although "step"
    # covers it too; "step" is in no bucket, so [1, 2) and [3.5, 4) are
    # unattributed, as are [6, 6.5) and [8, 9) under no span at all
    assert got == {"readback": 1.5, "sample": 1.5, "admit": 0.0,
                   "unattributed": 3.0}
    share = hs.shares(got)
    assert sum(share.values()) == pytest.approx(100.0)
    assert share["readback"] == pytest.approx(25.0)
    assert share["admit"] == 0.0


def test_a_child_in_no_bucket_goes_to_its_nearest_bucketed_ancestor():
    busy = [(0.0, 1.0), (9.0, 10.0)]
    spans = [("admit", 1.0, 8.0), ("prefill", 2.0, 7.0),
             ("forward", 3.0, 4.0), ("step.sample", 5.0, 6.0)]
    got = hs.split_idle(busy, (0.0, 10.0), spans, BUCKETS)
    # forward -> prefill -> admit; the sample span nested in the admission
    # is a bucket of its own and wins over its ancestor
    assert got == {"admit": 6.0, "sample": 1.0, "readback": 0.0,
                   "unattributed": 1.0}


def test_no_span_means_all_unattributed():
    got = hs.split_idle([(1.0, 2.0)], (0.0, 4.0), [], BUCKETS)
    assert got == {"readback": 0.0, "sample": 0.0, "admit": 0.0,
                   "unattributed": 3.0}
    assert hs.shares(got)["unattributed"] == 100.0
    # a device that never idled has no idle time to split
    assert hs.shares(hs.split_idle([(0.0, 4.0)], (0.0, 4.0), [],
                                   BUCKETS)) is None


def test_a_span_reaching_over_the_slices_edge_is_clipped():
    busy = [(2.0, 3.0)]
    spans = [("step.readback", -5.0, 1.0), ("admit", 3.5, 50.0)]
    got = hs.split_idle(busy, (0.0, 4.0), spans, BUCKETS)
    assert got == {"readback": 1.0, "admit": 0.5, "sample": 0.0,
                   "unattributed": 1.5}


def test_spans_of_two_threads_the_one_that_started_last_wins():
    # a pool thread's admission runs across the worker's step phases
    busy = [(0.0, 1.0), (5.0, 6.0)]
    spans = [("admit", 0.5, 5.5), ("step.readback", 2.0, 3.0)]
    got = hs.split_idle(busy, (0.0, 6.0), spans, BUCKETS)
    assert got["readback"] == 1.0 and got["admit"] == 3.0
    assert got["unattributed"] == 0.0


def test_the_first_op_of_a_step_starts_inside_its_span():
    busy = [(1.2, 1.9), (1.9, 2.5), (3.4, 3.9), (7.0, 7.5)]
    steps = [(1.0, 2.0), (3.0, 3.3), (6.9, 8.0), (9.0, 9.5)]
    # step 2's first op starts after the span closed; step 4 lies past the
    # last op and is not counted
    assert hs.steps_on_one_clock(busy, steps) == (2, 3)


def test_scopes_of_a_jax_side_name():
    from lib.op_names import scopes_of

    assert scopes_of("jit(raw)/cache_append/vmap(vmap())/scatter:") == {
        "cache_append", "", "scatter"}
    assert scopes_of("jit(step)/transpose(jvp(flash_bwd_dq))/pallas_call:") \
        == {"flash_bwd_dq", "pallas_call"}
    assert "flash_fwd" in scopes_of("jit(step)/jvp(flash_fwd)/pallas_call:")
    # the function a jit names is no scope, a fused op lists all its names
    assert "cache_append" not in scopes_of("jit(cache_append)/scatter:")
    assert scopes_of("jit(raw)/transpose:;jit(raw)/layer/reshape:") == {
        "transpose", "layer", "reshape"}
    assert scopes_of("") == {""}


def test_scoped_seconds_counts_nested_events_once():
    append = "jit(raw)/cache_append/vmap(vmap())/scatter:"
    events = [(append, 0.0, 4.0), (append, 1.0, 2.0),
              ("jit(raw)/not_cache_append/dot_general:", 4.0, 9.0),
              ("", 9.0, 10.0), (append, 10.0, 11.0),
              ("jit(raw)/flash_decode/pallas_call:", 11.0, 13.0)]
    assert hs.scoped_seconds(events, ("cache_append",)) == 5.0
    assert hs.scoped_seconds(events, ("flash_decode", "cache_append")) == 7.0
    assert hs.scoped_seconds(events, ("flash",)) == 0.0


def _ctx(cell="no-such-cell"):
    return {"cell": {"name": cell}, "trace": None}


def test_readers_return_nothing_where_there_is_nothing_to_read():
    ctx = _ctx()
    assert hs.load(ctx) is None
    assert hs.serve_idle_share(ctx, "readback") is None
    assert hs.scope_share(ctx, ("cache_append",)) is None
    assert hs.scope_share(ctx, ("flash_fwd",)) is None


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded serving trace in fixtures/")
def test_recorded_v5e_serving_trace_splits_to_fixed_numbers():
    import reduce_trace as rt

    with open(os.path.join(FIXTURES, "serve_steps.expected.json")) as f:
        want = json.load(f)
    data = hs.read_file(FIXTURE)
    names = sorted({n for n, _, _ in data["spans"]})
    assert names == want["span_names"]
    seconds = hs.split_idle(data["busy"], data["window"], data["spans"],
                            hs.SERVE_BUCKETS)
    share = hs.shares(seconds)
    assert sum(share.values()) == pytest.approx(100.0)
    assert set(share) == set(want["idle_share_by_span"])
    for bucket, value in want["idle_share_by_span"].items():
        assert share[bucket] == pytest.approx(value, abs=1e-6), bucket
        assert seconds[bucket] == pytest.approx(
            want["idle_seconds"][bucket], abs=1e-9), bucket
    # the idle that is split is reduce_trace's idle, to the nanosecond
    trace = rt.reduce_file(FIXTURE)
    assert trace["busy_s"] == pytest.approx(want["busy_s"], abs=1e-9)
    assert trace["slice_s"] == pytest.approx(want["slice_s"], abs=1e-9)
    assert sum(seconds.values()) == pytest.approx(
        trace["slice_s"] - trace["busy_s"], abs=1e-9)
    steps = [(s, e) for n, s, e in data["spans"] if n == hs.SERVE_STEP_SPAN]
    assert list(hs.steps_on_one_clock(data["busy"], steps)) == \
        want["steps_on_one_clock"]
    from lib import op_names

    named = op_names.named_events(FIXTURE, "/device:TPU:0", rt.OP_LINE)
    for scopes, key in ((("flash_decode",), "flash_decode_share"),
                        (("cache_append",), "cache_append_share")):
        assert 100.0 * hs.scoped_seconds(named, scopes) / trace["busy_s"] \
            == pytest.approx(want[key], abs=1e-6), key
    # a Pallas call has no children: its union is the op table's self time
    assert want["flash_decode_share"] == pytest.approx(
        rt.share_of_busy(trace, lambda n: rt.PALLAS_TAG in n), abs=1e-3)
    assert op_names.named_events(FIXTURE, "/device:TPU:9", rt.OP_LINE) == []
