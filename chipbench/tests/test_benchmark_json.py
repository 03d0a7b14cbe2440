import json
import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells_of(metric, b):
    return set(metric.get("workloads", [w["name"] for w in b["workloads"]]))


def test_names_and_units_are_in_the_allowed_characters():
    b = bench()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in b[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_every_moves_names_an_end_to_end_metric_of_the_same_cells():
    b = bench()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e, m
        assert cells_of(m, b) <= cells_of(e2e[m["moves"]], b), m["name"]


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    b = bench()
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)
    for w in b["workloads"]:
        mine = [m["name"] for m in b["end_to_end"] if w["name"] in cells_of(m, b)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w["name"] in cells_of(m, b) for m in b["per_layer"])


def test_every_name_resolves_to_a_file_of_its_own():
    b = bench()
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)
    for c in b["configs"]:
        assert c["file"].startswith("chipbench/")
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"] and "assumed" in cfg
        for kind in ("models", "references"):
            assert os.path.exists(os.path.join(BENCH, kind,
                                               cfg["builder"] + ".py"))
    for w in b["workloads"]:
        trf = json.load(open(os.path.join(BENCH, "traffic",
                                          w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           trf["driver"] + ".py"))
        assert trf.get("mesh", {"dp": 1})["dp"] == w["chips"]
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
