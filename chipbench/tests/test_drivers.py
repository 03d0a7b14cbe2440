"""Each driver runs a tiny fixture cell on the CPU through ``run.main`` and
its last line parses with the contract's keys.  The TPU requirement is
lifted HERE, by the test, never by a flag of run.py."""
import json
import os
import subprocess
import sys

import pytest

import run as R
from conftest import ROOT

FX = os.path.join(os.path.dirname(__file__), "fixtures")
E2E = {"train": ["train.samples_per_s_per_chip", "setup_s"],
       "serve": ["serve.tokens_per_s", "serve.ttft_p50_ms",
                 "serve.gap_p50_ms", "serve.gap_p95_ms", "setup_s"]}


def run_fixture(monkeypatch, capsys, config, traffic, kind, chips=1):
    import jax

    def resolve(bench, workload):
        cell = {"name": workload, "chips": chips}
        cfg = json.load(open(os.path.join(FX, config + ".json")))
        trf = json.load(open(os.path.join(FX, traffic + ".json")))
        e2e = [m for m in bench["end_to_end"] if m["name"] in E2E[kind]]
        return cell, cfg, trf, e2e, []

    monkeypatch.setattr(R, "resolve", resolve)
    monkeypatch.setattr(R, "require_device",
                        lambda chips: jax.devices()[:chips])
    from lib import peaks
    monkeypatch.setattr(peaks, "peak_for",
                        lambda kind, f=peaks.peak_for: f("TPU v5 lite"))
    rc = R.main(["--workload", "fixture", "--seed", str(2 ** 31 + 3),
                 "--seconds", "1.5", "--trace", "0"])
    lines = capsys.readouterr().out.strip().splitlines()
    out, notes = json.loads(lines[-1]), json.loads(lines[-2])["notes"]
    assert rc == 0 and out["correct"] is True
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == set(E2E[kind])
    for m in out["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    assert out["device"]["count"] == chips
    return out, notes


def test_train_stream_on_the_tiny_bert(monkeypatch, capsys):
    run_fixture(monkeypatch, capsys, "tiny-bert", "tiny-pretrain", "train")


def test_train_stream_zero1_on_four_virtual_devices(monkeypatch, capsys):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=4")
    run_fixture(monkeypatch, capsys, "tiny-bert", "tiny-pretrain-dp4",
                "train", chips=4)


def test_serve_closed_on_the_tiny_lm(monkeypatch, capsys):
    _, notes = run_fixture(monkeypatch, capsys, "tiny-lm", "tiny-closed",
                           "serve")
    assert all(c["worst_gap_of_max_ref"] <= notes["rtol"]
               for c in notes["reference"])


def test_run_exits_non_zero_without_a_tpu_before_building_anything():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "bert-base.pretrain-s128", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""
