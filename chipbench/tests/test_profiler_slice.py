"""``run.ProfilerSlice``: where the slice lies in the window, what its stop
waits for and what a failure says.  The profiler's session is a stub whose
``stop()`` sleeps; every constant is scaled down so no case takes a second."""
import os
import shutil
import time

import pytest

import run as R


class StubSession:
    """Stands in for ``jaxlib``'s ``ProfilerSession``: records when it was
    made and stopped, and its stop takes ``stop_s`` seconds."""

    made = []

    def __init__(self, options, stop_s=0.0, fail=None):
        self.t_made, self.t_stopped = time.perf_counter(), None
        self.stop_s, self.fail = stop_s, fail
        StubSession.made.append(self)

    def stop(self):
        self.t_stopped = time.perf_counter()
        if self.fail is not None:
            raise self.fail
        time.sleep(self.stop_s)
        return b"x" * 1234


@pytest.fixture
def slice_of(monkeypatch, tmp_path):
    """``slice_of(window_s, stop_s=..., fail=...)`` -> (profiler, log lines)
    with TRACE_SLICE_S scaled to 0.1 s and the stub for a session."""
    from jax._src.lib import _profiler

    monkeypatch.setattr(R, "TRACE_SLICE_S", 0.1)
    StubSession.made = []

    def make(window_s, stop_s=0.0, fail=None):
        monkeypatch.setattr(
            _profiler, "ProfilerSession",
            lambda options: StubSession(options, stop_s, fail))
        lines = []
        prof = R.ProfilerSlice(str(tmp_path), "cell", window_s,
                               lambda *a: lines.append(" ".join(map(str, a))))
        return prof, lines
    return make


def finish(prof, timeout=5.0):
    prof.join(timeout)
    assert not prof.is_alive()


def test_slice_is_the_windows_last_part_timed_from_its_opening(slice_of):
    prof, _ = slice_of(0.4)
    time.sleep(0.15)                # built early: the opening counts, not this
    prof.open_window()
    time.sleep(0.4)
    t_close = time.perf_counter()
    prof.close_window()
    finish(prof)
    (sess,) = StubSession.made
    assert prof.error is None
    assert sess.t_made - prof.t_open == pytest.approx(0.3, abs=0.05)
    assert sess.t_stopped >= t_close                # never before the close
    assert sess.t_stopped - t_close < 0.05
    assert prof.trace_bytes == 1234
    assert prof.path.endswith("cell.xplane.pb")
    assert os.path.getsize(prof.path) == 1234


def test_a_window_shorter_than_the_slice_is_profiled_whole(slice_of):
    prof, _ = slice_of(0.04)
    assert prof.delay == 0.0 and prof.length == 0.04
    prof.open_window()
    time.sleep(0.04)
    prof.close_window()
    finish(prof)
    assert StubSession.made[0].t_made - prof.t_open < 0.03


def test_a_driver_that_never_closes_gets_a_bounded_slice(slice_of):
    prof, _ = slice_of(0.1)
    prof.open_window()
    finish(prof)                                    # no close_window()
    sess = StubSession.made[0]
    assert sess.t_stopped - sess.t_made == pytest.approx(0.2, abs=0.05)


def test_a_long_stop_is_waited_for_and_the_wait_is_logged(slice_of,
                                                          monkeypatch):
    # the old harness gave up 0.15 (scaled) after the slice; this stop takes 0.5
    monkeypatch.setattr(R, "PROFILER_WAIT_S", 5.0)
    monkeypatch.setattr(R, "PROFILER_LOG_EVERY_S", 0.1)
    prof, lines = slice_of(0.1, stop_s=0.5)
    prof.open_window()
    time.sleep(0.1)
    prof.close_window()
    prof.wait()                                     # returns: the run goes on
    assert not prof.is_alive() and prof.error is None
    assert prof.stop_s == pytest.approx(0.5, abs=0.1)
    assert sum("still converting" in l for l in lines) >= 3
    assert any("stop returned" in l and "1234 bytes" in l for l in lines)


def test_past_the_bound_the_exit_says_how_long_it_waited_for_what(
        slice_of, monkeypatch):
    monkeypatch.setattr(R, "PROFILER_WAIT_S", 0.2)
    monkeypatch.setattr(R, "PROFILER_LOG_EVERY_S", 0.05)
    prof, _ = slice_of(0.05, stop_s=0.8)
    prof.open_window()
    prof.close_window()
    with pytest.raises(SystemExit) as e:
        prof.wait()
    msg = str(e.value)
    assert "profiler slice failed" in msg and "None" not in msg
    assert "0s after stop was called" in msg and "the wait is 0.2s" in msg
    assert "the slice 0.05s" in msg and prof.path in msg
    assert not os.path.exists(prof.path)
    finish(prof)


def test_an_exception_in_the_thread_fails_the_run_by_name(slice_of):
    prof, _ = slice_of(0.05, fail=RuntimeError("tracer is gone"))
    prof.open_window()
    prof.close_window()
    with pytest.raises(SystemExit) as e:
        prof.wait()
    assert "RuntimeError" in str(e.value) and "tracer is gone" in str(e.value)


def test_a_traced_run_reads_its_counters_before_the_stop(monkeypatch, capsys):
    """``run.main --trace 1`` on the tiny serving fixture, the real profiler
    on the CPU: the close's telemetry snapshot comes before the stop, the
    trace lies where the readers look, and the notes line says what the
    stop cost."""
    import json

    import jax

    import reduce_trace
    from lib import host_spans, peaks
    from mxnet_tpu import telemetry as tel

    fx = os.path.join(os.path.dirname(__file__), "fixtures")

    def resolve(bench, workload):
        spans = [m for m in bench["per_layer"]
                 if m["source"] in ("program_span", "program_counter")
                 and "gpt2-xl.batch-closed" in m["workloads"]]
        return ({"name": workload, "chips": 1},
                json.load(open(os.path.join(fx, "tiny-lm.json"))),
                json.load(open(os.path.join(fx, "tiny-closed.json"))), [], spans)

    events = []
    snapshot, end_trace = tel.snapshot, R.ProfilerSlice.end_trace

    def stamped_snapshot():
        events.append("snapshot")
        return snapshot()

    def stamped_end(self, session):
        events.append("stop")
        return end_trace(self, session)

    monkeypatch.setattr(R, "resolve", resolve)
    monkeypatch.setattr(R, "require_device", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(peaks, "peak_for",
                        lambda kind, f=peaks.peak_for: f("TPU v5 lite"))
    monkeypatch.setattr(tel, "snapshot", stamped_snapshot)
    monkeypatch.setattr(R.ProfilerSlice, "end_trace", stamped_end)
    monkeypatch.setattr(R, "TRACE_SLICE_S", 0.5)
    # a CPU trace has no /device:TPU plane to reduce
    monkeypatch.setattr(reduce_trace, "reduce_file", lambda path, n_devices: {
        "busy_s_mean": 0.25, "slice_s": 0.5, "top_ops": [], "top_gaps": []})
    rc = R.main(["--workload", "traced-fixture", "--seed", str(2 ** 31 + 7),
                 "--seconds", "1.5", "--trace", "1"])
    lines = capsys.readouterr().out.strip().splitlines()
    out, notes = json.loads(lines[-1]), json.loads(lines[-2])
    assert rc == 0 and out["correct"] is True
    assert events == ["snapshot", "snapshot", "stop"]   # open, close, stop
    assert {"serve.decode_step_ms", "serve.cache_alloc_ms",
            "serve.tokens_per_step"} <= set(out["metrics"])
    assert out["device"]["busy_s"] == 0.25 and out["device"]["window_s"] == 0.5
    assert notes["trace_mb"] > 0 and notes["trace_stop_s"] > 0
    assert notes["close_to_result_s"] >= notes["trace_stop_s"]
    path = host_spans.newest_xplane("traced-fixture")    # the readers' look
    assert path == os.path.join(R.HERE, "out", "traced-fixture",
                                "traced-fixture.xplane.pb")
    assert os.path.getsize(path) == int(notes["trace_mb"] * 1e6)
    shutil.rmtree(os.path.dirname(path))
