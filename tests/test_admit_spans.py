"""The benchmark's split of the admission and its decode step's device time
(``chipbench/lib/admit_spans.py`` and the six readers of PR 39), on
hand-made intervals: the finer buckets sum to the coarse ones, every reader
reads None on a program without the spans or the scope, and a step's
device time is its program's ops, the compiler's nameless ones between
them included.  Seconds here are made up; nothing is a speed."""
from __future__ import annotations

import importlib
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
READERS = ("device.idle_in_admit_alloc.serve",
           "device.idle_in_admit_dispatch.serve",
           "device.idle_in_admit_readback.serve",
           "device.idle_in_admit_move.serve",
           "device.idle_in_reply_wait.serve",
           "device.step_ms.serve",
           # the coarse two the split refines
           "device.idle_in_admit.serve",
           "device.idle_unattributed.serve")


@pytest.fixture(scope="module")
def bench():
    """``lib.admit_spans``, ``lib.host_spans`` and the readers, by path:
    ``chipbench/`` is no package and its modules import each other as
    ``run.py`` runs them (``lib.*``, ``reduce_trace``), so its directory is
    on ``sys.path`` while they load, and neither it nor they stay there."""
    path, modules = list(sys.path), set(sys.modules)
    sys.path.insert(0, BENCH)
    try:
        admit = importlib.import_module("lib.admit_spans")
        host = importlib.import_module("lib.host_spans")
        readers = {}
        for name in READERS:
            spec = importlib.util.spec_from_file_location(
                "chipbench_reader_" + name.replace(".", "_"),
                os.path.join(BENCH, "layer_metrics", name + ".py"))
            readers[name] = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(readers[name])
    finally:
        sys.path[:] = path
        for name in set(sys.modules) - modules:
            if name in ("lib", "reduce_trace") or name.startswith("lib."):
                del sys.modules[name]
    return admit, host, readers


STEP = "jit(raw)/decode_step/dot_general:"
PREFILL = "jit(raw)/dot_general:"

# one admission in a loop that steps, on one thread: serve.admit holds the
# four parts of an admission and some of its own; a reply wait after it, in
# no admission; a step, its two parts, a sample
SPANS = [("serve.admit", 0.10, 0.50),
         ("serve.prefill", 0.10, 0.46),
         ("serve.first_token", 0.10, 0.40),
         ("serve.cache_alloc", 0.10, 0.14),
         ("serve.prefill_chunk", 0.15, 0.36),
         ("serve.prefill_forward", 0.15, 0.36),
         ("serve.prefill_dispatch", 0.15, 0.22),
         ("serve.prefill_readback", 0.26, 0.36),
         ("serve.cache_move", 0.41, 0.45),
         ("serve.reply_wait", 0.52, 0.56),
         ("serve.decode_step", 0.58, 0.80),
         ("serve.step_dispatch", 0.58, 0.62),
         ("serve.step_readback", 0.62, 0.80),
         ("serve.sample", 0.80, 0.83)]
# device 0 busy in pieces that leave idle time under every bucket
BUSY = [(0.00, 0.05), (0.12, 0.13), (0.18, 0.20), (0.30, 0.33),
        (0.42, 0.43), (0.47, 0.48), (0.53, 0.54), (0.60, 0.75),
        (0.90, 0.95)]
WINDOW = (0.0, 1.0)


def _ctx(spans, named=()):
    """What ``host_spans.load`` keeps for a run, made by hand."""
    return {"cell": {"name": "hand-made"}, "trace": {"busy_s": 1.0},
            "xplane": {"path": None, "window": WINDOW, "busy": list(BUSY),
                       "spans": list(spans), "named": list(named)}}


def test_the_fine_split_sums_to_the_coarse_one(bench):
    admit, host, _ = bench
    coarse = host.split_idle(BUSY, WINDOW, SPANS, host.SERVE_BUCKETS)
    fine = host.split_idle(BUSY, WINDOW, SPANS, admit.ADMIT_BUCKETS)
    parts = ("admit_alloc", "admit_dispatch", "admit_readback", "admit_move")
    for b in parts + ("reply_wait",):
        assert fine[b] > 0.0, b
    assert fine["admit"] > 0.0           # serve.admit's own, outside the four
    assert sum(fine[b] for b in parts) + fine["admit"] == \
        pytest.approx(coarse["admit"])
    assert fine["reply_wait"] + fine[host.UNATTRIBUTED] == \
        pytest.approx(coarse[host.UNATTRIBUTED])
    for b in ("readback", "dispatch", "sample"):
        assert fine[b] == pytest.approx(coarse[b])
    assert sum(fine.values()) == pytest.approx(sum(coarse.values()))
    # by hand: idle under serve.cache_alloc is 0.10-0.12 and 0.13-0.14
    assert fine["admit_alloc"] == pytest.approx(0.03)
    assert fine["reply_wait"] == pytest.approx(0.03)


def test_the_readers_share_one_whole_with_the_coarse_five(bench):
    _, _, readers = bench
    ctx = _ctx(SPANS)
    read = {name: mod.read(ctx) for name, mod in readers.items()}
    parts = sum(read[f"device.idle_in_admit_{p}.serve"]
                for p in ("alloc", "dispatch", "readback", "move"))
    assert 0.0 < parts < read["device.idle_in_admit.serve"]
    assert 0.0 < read["device.idle_in_reply_wait.serve"] < \
        read["device.idle_unattributed.serve"]
    idle = 1.0 - sum(e - s for s, e in BUSY)
    assert read["device.idle_in_reply_wait.serve"] == \
        pytest.approx(100.0 * 0.03 / idle)


@pytest.mark.parametrize("reader", READERS[:6])
def test_every_reader_reads_none_without_the_spans_or_the_scope(bench,
                                                                reader):
    """The parent's program (PR 38) opens serve.cache_alloc and
    serve.cache_move but none of the spans new in PR 39, and its step
    program's ops carry no ``decode_step``; a run with no trace at all
    reads None too."""
    _, _, readers = bench
    new = {"serve.prefill_dispatch", "serve.prefill_readback",
           "serve.reply_wait"}
    parent = [sp for sp in SPANS if sp[0] not in new]
    unscoped = [(PREFILL, 0.60, 0.75)]
    assert readers[reader].read(_ctx(parent, unscoped)) is None
    ctx = _ctx(parent, unscoped)
    ctx["xplane"] = None
    assert readers[reader].read(ctx) is None


def test_a_step_is_its_program_s_ops_and_the_nameless_ones_between(bench):
    """Two steps end in the window (a third after it is not counted); the
    step program's time is its named ops and the nameless ones between two
    of them (an asynchronous copy's wait), not the nameless op between a
    step and the prefill program nor the prefill's own."""
    admit, _, readers = bench
    named = [(STEP, 0.600, 0.610),
             ("", 0.610, 0.612),               # the step's: between two
             (STEP, 0.612, 0.620),
             ("", 0.620, 0.621),               # step | prefill: neither's
             (PREFILL, 0.621, 0.640),
             ("", 0.640, 0.641),               # prefill | step: neither's
             (STEP, 0.641, 0.650),
             (STEP, 0.645, 0.648)]             # nested: counted once
    assert admit.program_seconds(named, "decode_step") == \
        pytest.approx(0.010 + 0.002 + 0.008 + 0.009)
    spans = [("serve.decode_step", 0.55, 0.62),
             ("serve.decode_step", 0.62, 0.66),
             ("serve.decode_step", 0.95, 1.05),   # ends after the slice
             ("serve.sample", 0.66, 0.67)]
    assert admit.steps_ending_in(spans, WINDOW) == 2
    got = readers["device.step_ms.serve"].read(_ctx(spans, named))
    assert got == pytest.approx(1e3 * 0.029 / 2)
