"""opperf harness: catalog resolution, timing structure, output formats.

Mirrors the reference's expectation that benchmark/opperf is runnable
against the live op registry (ref benchmark/opperf/README.md usage).
"""
import json
import os
import subprocess
import sys

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.opperf.op_catalog import build_catalog  # noqa: E402
from benchmark.opperf import opperf  # noqa: E402


def test_catalog_resolves_against_registry():
    cat = build_catalog(mx)
    assert set(cat) >= {"unary", "binary_broadcast", "reduction",
                        "gemm_linalg", "nn_conv", "nn_basic", "random"}
    total = sum(len(t) for t in cat.values())
    assert total >= 130
    missing = [f"{c}/{n}" for c, t in cat.items()
               for n, (fn, _, _) in t.items() if fn is None]
    assert not missing, f"catalog names absent from registry: {missing}"


def test_run_benchmarks_structure():
    res = opperf.run_benchmarks(categories=["unary"], ops=["exp", "sqrt"],
                                warmup=1, runs=2, verbose=False)
    assert set(res) == {"unary"}
    ops = {r["operator"] for r in res["unary"]}
    assert ops == {"exp", "sqrt"}
    for r in res["unary"]:
        assert r["avg_forward_time_ms"] > 0
        assert r["avg_backward_time_ms"] >= 0  # differentiable unary


def test_nondifferentiable_has_no_backward():
    res = opperf.run_benchmarks(categories=["comparison"], ops=["equal"],
                                warmup=1, runs=2, verbose=False)
    assert "avg_backward_time_ms" not in res["comparison"][0]


def test_markdown_output():
    res = {"unary": [{"operator": "exp", "avg_forward_time_ms": 0.5,
                      "avg_backward_time_ms": 1.0}],
           "skipped": ["x/y"]}
    md = opperf.to_markdown(res)
    assert "## unary" in md and "| exp | 0.5 | 1.0 |" in md
    assert "skipped: x/y" in md


def test_cli_json(tmp_path):
    out = tmp_path / "r.json"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "opperf",
                                      "opperf.py"),
         "--categories", "reduction", "--ops", "sum,mean",
         "--warmup", "1", "--runs", "2", "-q", "-o", str(out)],
        check=True, env=env, cwd=REPO)
    res = json.loads(out.read_text())
    assert {r["operator"] for r in res["reduction"]} == {"sum", "mean"}
