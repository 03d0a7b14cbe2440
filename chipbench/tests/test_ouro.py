"""The ``ouro`` builder, reference and readers at a tiny size on the CPU:
the closed-loop driver serves the fixture (three layers run three times,
prompts in chunks) through ``run.main`` and its check against the plain
reference holds; each wrong model of the reference comes out not correct by
that same check; the configuration is the catalog's row key for key; the
byte functions count what the docstrings say; the new readers read their
sources, and leave their metric out, without raising, on a program, a trace
or a builder that lacks what they read; the shapes the reference checks the
served programs at are the traffic file's."""
import json
import os

import pytest

import run as R
from test_drivers import FX, run_fixture

# the repo's one tiny Ouro configuration (tier-1's tests/test_ouro.py reads
# the same file), named from this directory's fixtures as run_fixture wants
TINY = os.path.relpath(os.path.join(R.ROOT, "tests", "fixtures", "tiny-ouro"),
                       FX)
CELL = "ouro-2.6b.math-closed"
NEW = ["kernels.loop_dense_share.serve", "device.weight_stream_share.serve"]


def _published():
    return json.load(open(os.path.join(R.HERE, "configs", "ouro-2.6b.json")))


def test_closed_loop_serves_the_tiny_ouro_fixture(monkeypatch, capsys):
    out, notes = run_fixture(monkeypatch, capsys, TINY, "tiny-chunked",
                             "serve")
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["worst_gap_of_max_ref"] <= notes["rtol"]
               for c in notes["reference"])
    assert max(c["prompt"] for c in notes["reference"]) > 16    # chunked


@pytest.mark.parametrize("fault", ["no_post_norm", "no_pass_norm",
                                   "one_pass_short", "neighbour_cache"])
def test_a_wrong_model_is_not_correct_by_the_cell_s_own_check(fault):
    """The reference's wrong models' greedy tokens through
    ``lib/checks.greedy_agrees`` (``LOGIT_RTOL``), judged as ``run.py``
    judges: the builder keeps the block it built (``lib/served.py``) and
    the reference finds it by the configuration object."""
    import numpy as onp

    from lib.checks import greedy_agrees

    cfg = json.load(open(os.path.join(FX, TINY + ".json")))
    lm = R.load_module("models", "ouro").build(cfg, 5)
    ref = R.load_module("references", "ouro")
    params = {k: p.data()._data for k, p in lm.collect_params().items()}
    seq = onp.random.RandomState(5).randint(1, cfg["vocab_size"], size=90)
    sound = onp.asarray(ref.logits(params, cfg, seq))      # served: the kept
    assert greedy_agrees(sound, 30, sound.argmax(-1)[29:], ref.LOGIT_RTOL)[0]
    wrong = onp.asarray(ref.logits(params, cfg, seq, fault=fault,
                                   served=None))
    assert not greedy_agrees(sound, 30, wrong.argmax(-1)[29:],
                             ref.LOGIT_RTOL)[0]


def test_a_reference_without_its_served_block_does_not_decide():
    """No block kept for the configuration object: an error by name, not a
    run judged on the logits alone."""
    ref = R.load_module("references", "ouro")
    cfg = json.load(open(os.path.join(FX, TINY + ".json")))
    with pytest.raises(LookupError, match="lib.served.keep"):
        ref.logits({}, cfg, [1, 2, 3])


def test_the_served_shapes_are_the_traffic_file_s():
    """``deployment.served`` -- the shapes the reference puts the served
    programs beside the chain of their cells at -- is the server block of
    the cell's traffic file (and the fixture's, of the fixture's)."""
    for cfg, traffic in (
            (_published(), os.path.join(R.HERE, "traffic", "math-closed.json")),
            (json.load(open(os.path.join(FX, TINY + ".json"))),
             os.path.join(FX, "tiny-chunked.json"))):
        server = json.load(open(traffic))["server"]
        assert cfg["deployment"]["served"] == {
            "slots": server["slots"],
            "capacity": server["capacity_buckets"][0],
            "prompt_buckets": server["prompt_buckets"]}
        assert len(server["capacity_buckets"]) == 1


def test_the_configuration_is_the_catalog_row_key_for_key():
    cfg = _published()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(l) for l in open(catalog) if '"Ouro-2.6B"' in l)
    assert {k: cfg[k] for k in row["config"]} == row["config"]
    assert cfg["reduced"] == [] and "published" not in cfg
    assert cfg["source"].startswith(row["source_url"])
    bench = json.load(open(os.path.join(R.ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert entry["source"] == row["source_url"] and entry["reduced"] == []
    for key in ("sandwich_norm", "pass_norm", "exit_gate", "attention_bias",
                "qk_norm"):
        assert isinstance(cfg["assumed"][key], bool)
        assert len(cfg["assumed"][key + "_why"]) > 40


def test_byte_functions_follow_the_shapes():
    model, cfg = R.load_module("models", "ouro"), _published()
    # K and V of 16 heads x 128 in bf16, 48 layers, four passes a position
    assert model.attn_full_bytes(cfg, 1) == 4 * 48 * 8192 == 1572864
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert model.loop_dense_bytes(cfg, 1) == 48 * layer * 2 == 4932501504
    assert model.loop_dense_bytes(cfg, 4) == 4 * 4932501504


def _ctx(**over):
    ctx = {"cell": {"name": "no-such-cell"}, "config": _published(),
           "trace": None, "traffic": {"server": {"slots": 2}},
           "telemetry": ({}, {}), "model": object(), "window_s": 1.0,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    ctx.update(over)
    return ctx


def test_new_readers_list_this_cell_alone():
    bench = json.load(open(os.path.join(R.ROOT, "BENCHMARK.json")))
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == NEW
    assert {m["moves"] for m in mine} == {"serve.tokens_per_s"}


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_without_their_sources(name):
    """No counter (the parent's program), no trace or scope, no builder
    function: None each time, nothing raises."""
    read = R.load_module("layer_metrics", name).read
    counted = ({"serve.stack_passes": {"value": 0}},
               {"serve.stack_passes": {"value": 400}})
    assert read(_ctx()) is None
    assert read(_ctx(telemetry=counted)) is None        # no steps, no trace
    assert read(_ctx(telemetry=counted,
                     model=R.load_module("models", "ouro"))) is None


def test_the_weight_stream_share_is_bytes_over_busy_time():
    """400 passes of 4.93 GB at 819 GB/s are 2.409 s of a 4 s window; a
    device busy 0.9 of the slice reads 66.9%; whatever scope ran."""
    read = R.load_module("layer_metrics", NEW[1]).read
    counted = ({"serve.stack_passes": {"value": 0}},
               {"serve.stack_passes": {"value": 400}})
    got = read(_ctx(telemetry=counted, window_s=4.0,
                    model=R.load_module("models", "ouro"),
                    trace={"busy_s": 2.7, "slice_s": 3.0}))
    assert got == pytest.approx(100 * (400 * 4932501504 / 819e9 / 4.0) / 0.9)
