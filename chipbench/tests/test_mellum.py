"""The ``mellum`` builder, reference and readers at a tiny size on the CPU:
the closed-loop driver serves the fixture -- prompts in chunks, rings lapped
-- through ``run.main`` and its check against the plain reference holds; a
router in bf16 or a window one position too long comes out not correct by
that same check; the byte functions count what the docstrings say; the new
readers leave their metric out, and do not raise, on a program or a builder
that lacks what they read."""
import json
import os

import pytest

import run as R
from test_drivers import FX, run_fixture


def test_closed_loop_serves_the_tiny_mellum_fixture_in_chunks(monkeypatch,
                                                              capsys):
    out, notes = run_fixture(monkeypatch, capsys, "tiny-mellum",
                             "tiny-chunked", "serve")
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["worst_gap_of_max_ref"] <= notes["rtol"]
               for c in notes["reference"])
    assert max(c["prompt"] for c in notes["reference"]) > 16    # chunked


@pytest.mark.parametrize("control", [
    {"router": "bf16_router"}, {"attention": "wide_window_attention"}],
    ids=["bf16_router", "wide_window"])
def test_a_control_is_not_correct_by_the_cell_s_own_check(control):
    """The reference's greedy tokens through ``lib/checks.greedy_agrees``,
    as ``serve_closed.verify`` calls it: correct with the program's router
    and attention path under test; not correct with the router's operands
    in bf16 (``ROUTE_RTOL``) nor with a window one position too long
    (``ATTN_RTOL``), while ``LOGIT_RTOL`` alone would have passed."""
    import numpy as onp

    from lib.checks import greedy_agrees

    cfg = json.load(open(os.path.join(FX, "tiny-mellum.json")))
    lm = R.load_module("models", "mellum").build(cfg, 5)
    ref = R.load_module("references", "mellum")
    params = {k: p.data()._data for k, p in lm.collect_params().items()}
    seq = onp.random.RandomState(5).randint(1, cfg["vocab_size"], size=90)
    sound = onp.asarray(ref.logits(params, cfg, seq))
    chosen = sound.argmax(-1)[29:]
    assert greedy_agrees(sound, 30, chosen, ref.LOGIT_RTOL)[0]
    judged = ref.logits(params, cfg, seq, **{
        k: getattr(ref, v) for k, v in control.items()})
    ok, worst, _ = greedy_agrees(judged, 30, chosen, ref.LOGIT_RTOL)
    assert not ok and worst == float("inf")


def test_byte_functions_follow_the_shapes():
    model = R.load_module("models", "mellum")
    cfg = json.load(open(os.path.join(
        os.path.dirname(R.HERE), "chipbench", "configs",
        "mellum2-12b-a2.5b.json")))
    assert model.moe_layers(cfg) == 28
    assert model.moe_experts_bytes(cfg, 1) == 3 * 2304 * 896 * 2
    # K and V of 4 heads x 128 in bf16: 2048 B a layer and position
    assert model.attn_full_bytes(cfg, 1) == 7 * 2048
    assert model.attn_window_bytes(cfg, 1) == 21 * 2048


def test_the_configuration_keeps_every_published_number():
    cfg = json.load(open(os.path.join(
        os.path.dirname(R.HERE), "chipbench", "configs",
        "mellum2-12b-a2.5b.json")))
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(l) for l in open(catalog)
               if "Mellum2-12B-A2.5B" in l)
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {"num_experts", "vocab_size"}
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}


def test_new_readers_read_nothing_without_their_sources():
    bench = json.load(open(os.path.join(R.ROOT, "BENCHMARK.json")))
    cell = "mellum2-12b-a2.5b.ide-mixed-closed"
    mine = [m["name"] for m in bench["per_layer"] if m["workloads"] == [cell]]
    assert len(mine) == 5
    ctx = {"cell": {"name": "no-such-cell"}, "config": {}, "trace": None,
           "traffic": {"server": {"slots": 2}}, "telemetry": ({}, {}),
           "model": object(), "window_s": 1.0, "peaks": {}}
    for name in mine:
        assert R.load_module("layer_metrics", name).read(ctx) is None
