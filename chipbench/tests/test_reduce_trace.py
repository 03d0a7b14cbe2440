import os

import pytest

import reduce_trace as rt

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "bert_steps.xplane.pb")


def test_overlapping_and_nested_intervals_count_once():
    assert rt.union_length([(0, 2), (1, 3), (5, 6), (5.2, 5.4)]) == 4.0
    assert rt.union_length([]) == 0.0


def test_self_time_takes_children_out_of_the_parent():
    events = [("while", 0.0, 10.0), ("fusion.1", 1.0, 3.0),
              ("fusion.2", 3.0, 4.0), ("fusion.1", 6.0, 7.0),
              ("copy", 12.0, 13.0)]
    table = rt.self_times(events)
    assert table["while"] == (6.0, 1)
    assert table["fusion.1"] == (3.0, 2)
    assert table["fusion.2"] == (1.0, 1)
    assert table["copy"] == (1.0, 1)
    assert sum(t for t, _ in table.values()) == rt.union_length(
        [(s, e) for _, s, e in events])


def test_reduce_events_idle_share_ops_and_gaps():
    events = [("a", 0.0, 1.0), ("b", 1.0, 2.0), ("a", 4.0, 5.0),
              ("c", 5.5, 6.0), ("zero", 3.0, 3.0)]
    r = rt.reduce_events(events)
    assert r["busy_s"] == 3.5 and r["slice_s"] == 6.0
    assert r["idle_share"] == pytest.approx(1 - 3.5 / 6.0)
    assert r["ops"][0] == ("a", 2.0, 2)
    assert r["gaps"] == [("after b | before a", 2.0),
                         ("after a | before c", 0.5)]
    assert rt.reduce_events([]) is None


def test_a_device_idle_at_the_edges_of_the_profiled_window_is_idle():
    events = [("a", 1.0, 2.0), ("b", 2.5, 3.0)]
    r = rt.reduce_events(events, window=(0.0, 4.0))
    assert r["busy_s"] == 1.5 and r["slice_s"] == 4.0
    assert r["idle_share"] == pytest.approx(1 - 1.5 / 4.0)
    # a window that ends before the last op never cuts an op off
    r = rt.reduce_events(events, window=(1.5, 2.0))
    assert r["busy_s"] == 1.5 and r["slice_s"] == 2.0


def test_share_of_busy_is_zero_for_what_did_not_run():
    r = rt.reduce_events([("flash_fwd", 0.0, 1.0), ("fusion", 1.0, 4.0)])
    assert rt.share_of_busy(r, lambda n: "flash" in n) == 25.0
    assert rt.share_of_busy(r, lambda n: "decode" in n) == 0.0


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded chip trace in fixtures/")
def test_recorded_v5e_trace_reduces_to_fixed_numbers():
    import json

    want = json.load(open(FIXTURE.replace(".xplane.pb", ".expected.json")))
    got = rt.reduce_file(FIXTURE)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["slice_s"] == pytest.approx(want["slice_s"], rel=1e-9)
    assert got["idle_share"] == pytest.approx(want["idle_share"], rel=1e-9)
    assert [n for n, _ in got["top_ops"]] == want["top_op_names"]
    assert [n for n, _ in got["top_gaps"]] == want["top_gap_names"]
    assert sum(t for _, t, _ in got["ops"]) == pytest.approx(got["busy_s"],
                                                             rel=1e-6)
    assert 0.0 < got["busy_s"] <= got["slice_s"]
    assert rt.share_of_busy(got, lambda n: rt.PALLAS_TAG in n) == \
        pytest.approx(want["pallas_share_percent"], rel=1e-9)


def test_short_name_drops_the_text_and_the_suffix_and_tags_pallas():
    assert rt.short_name(
        "%add_subtract_fusion.21 = (f32[768,3072]{1,0}) fusion(f32[] %x), "
        "kind=kOutput") == "%add_subtract_fusion"
    assert rt.short_name(
        '%jvp__.12 = (bf16[384,128,64]) custom-call(s32[384] %r), '
        'custom_call_target="tpu_custom_call"') == "%jvp__ [tpu_custom_call]"
    assert rt.short_name(
        '%custom-call.16 = f32[8] custom-call(f32[4] %a), '
        'custom_call_target="ConcatBitcast"') == "%custom-call"
    for already in ("%jvp__ [tpu_custom_call]", "%fusion"):
        assert rt.short_name(already) == already
