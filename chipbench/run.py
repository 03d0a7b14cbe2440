"""chipbench/run.py -- one run of one benchmark cell, on the chip.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It requires a TPU and the cell's chip count before it builds
anything, sets up (build, initialise, warm-up/compile), measures for
``--seconds``, checks the outputs against the plain reference, and prints
ONE JSON object
as the last line of stdout: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, when traced, ``breakdown``.

``--trace 0`` prints the cell's end-to-end metrics with the profiler off.
``--trace 1`` prints its per-layer metrics: telemetry deltas over the
window, XLA compiles counted inside it, and the window's last ~3 s under
``jax.profiler``, reduced by ``reduce_trace.py``.  The profiler's stop is
called as the window closes, after the counters are read, and converts on
its own thread while the drain and the reference check run (README.md).

Everything that belongs to one configuration, traffic mix, driver, model
builder, reference or per-layer metric is a file found BY NAME from
``BENCHMARK.json`` (see README.md).  This file branches on no name.
"""
from __future__ import annotations

T_PROCESS = __import__("time").perf_counter()     # set-up starts here

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SLICE_S = 3.0
PROFILER_WAIT_S = 600.0     # for the stop's conversion: ~0.4 s a MB of trace
PROFILER_LOG_EVERY_S = 30.0


def log(*a):
    """To stderr, stamped with the seconds since the process started, so
    the log says where set-up went."""
    print(f"[{time.perf_counter() - T_PROCESS:6.1f}s]", *a, file=sys.stderr,
          flush=True)


def read_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench, workload):
    """The cell's entry, its configuration and traffic files, and the
    end-to-end and per-layer metrics ``BENCHMARK.json`` lists for it."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = read_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = read_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return cell, config, traffic, mine(bench["end_to_end"]), \
        mine(bench["per_layer"])


def require_device(chips):
    """The accelerator, or exit non-zero before anything is built."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench needs a TPU; jax found "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"this cell needs {chips} chips; jax found "
                         f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Every XLA backend compile of the process, from jax's own monitoring
    events (copy of chip_smoke.py:CompileCounter)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class ProfilerSlice(threading.Thread):
    """Profile the LAST ~TRACE_SLICE_S seconds of the window (all of a
    shorter one), on a thread of its own so the load never waits for the
    profiler.  ``open_window()`` is the instant the slice's start is timed
    from; ``close_window()`` (from ``on_close``, after the counters are
    read) has ``stop`` called.  The stop converts the trace at ~0.4 s a MB
    (v5e host: 42 s for 104 MB) and leaves the process slower to its end
    (PERF.md section 6, PRs 32 and 36), so nothing the window measures may
    come after it, and ``wait()`` is called only once the run has nothing
    else left to do."""

    def __init__(self, out_dir, name, window_s, log):
        super().__init__(name="chipbench-profiler", daemon=True)
        self.path = os.path.join(out_dir, name + ".xplane.pb")
        self.log = log
        self.delay = max(0.0, window_s - TRACE_SLICE_S)
        self.length = min(TRACE_SLICE_S, window_s)
        self.closed = threading.Event()
        self.error = None
        self.t_open = self.t_close = self.t_stop = None
        self.stop_s = self.trace_bytes = None

    def open_window(self):
        self.t_open = time.perf_counter()
        self.start()

    def close_window(self):
        self.t_close = time.perf_counter()
        self.closed.set()

    def run(self):
        try:
            time.sleep(max(0.0, self.t_open + self.delay
                           - time.perf_counter()))
            self.log(f"[profiler] start called, {self.delay:g}s into the "
                     "window")
            session = self.begin_trace()
            self.log("[profiler] start returned")
            # a driver that closes late gets a longer slice, not an endless one
            self.closed.wait(2.0 * self.length)
            self.t_stop = time.perf_counter()
            self.log("[profiler] stop called")
            self.trace_bytes = self.end_trace(session)
            self.stop_s = time.perf_counter() - self.t_stop
            self.log(f"[profiler] stop returned after {self.stop_s:.1f}s, "
                     f"{self.trace_bytes} bytes of .xplane.pb")
        except Exception as e:  # noqa: BLE001 - reported, fails the run
            self.error = e

    def begin_trace(self):
        # written for jax / jaxlib 0.9.0: the session itself and not
        # jax.profiler.start_trace / stop_trace, whose stop_and_export also
        # writes a trace.json.gz that nothing here reads -- 108 s of the 150 s
        # a 104 MB trace took to stop (PERF.md section 6, PR 36)
        import jax
        from jax._src.lib import _profiler

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        return _profiler.ProfilerSession(opts)

    def end_trace(self, session):
        """Stop the session and write the XSpace it returns (in memory
        until then: a wait has no size to log) where the readers look for
        it; the bytes written."""
        data = session.stop()
        with open(self.path, "wb") as f:
            f.write(data)
        return len(data)

    def wait(self):
        """Until the thread has ended, however long the conversion takes up
        to PROFILER_WAIT_S from the stop's call, with a line every
        PROFILER_LOG_EVERY_S; a failure says what happened."""
        while self.is_alive():
            since, what = ((self.t_open, "the window opened (stop not called)")
                           if self.t_stop is None
                           else (self.t_stop, "stop was called"))
            waited = time.perf_counter() - since
            if waited >= PROFILER_WAIT_S:
                raise SystemExit(
                    f"profiler slice failed: still running {waited:.0f}s "
                    f"after {what} (the wait is {PROFILER_WAIT_S:g}s, the "
                    f"slice {self.length:g}s; nothing written to {self.path})")
            self.join(min(PROFILER_LOG_EVERY_S, PROFILER_WAIT_S - waited))
            if self.is_alive():
                self.log(f"[profiler] still converting, "
                         f"{time.perf_counter() - since:.0f}s after {what}")
        if self.error is not None:
            raise SystemExit(f"profiler slice failed: {self.error!r}")


def held_bytes(dev):
    """Bytes the chip holds right now.  The TPU allocator keeps XLA's
    program temporaries (activations, scratch) under ``bytes_reserved``,
    apart from the buffers under ``bytes_in_use``; the two are disjoint
    (``bytes_limit`` less both is the largest free block), so memory held
    is their sum, and ``peak_bytes_in_use`` alone misses the temporaries."""
    st = dev.memory_stats() or {}
    return max(st.get("peak_bytes_in_use", 0),
               st.get("bytes_in_use", 0) + st.get("bytes_reserved", 0))


def device_block(devs):
    """The device as jax reports it; the peak is read on the fullest chip
    at the window's end, while the cell's state is still live."""
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": int(max(held_bytes(d) for d in devs))}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic, e2e, per_layer = resolve(bench, args.workload)
    devs = require_device(cell["chips"])
    log(f"[chipbench] {len(devs)} x {devs[0].device_kind} ready")

    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    from lib import peaks as peaks_mod
    import reduce_trace
    from mxnet_tpu import telemetry as tel

    peaks = peaks_mod.peak_for(devs[0].device_kind)
    compiles = CompileCounter()
    driver = load_module("drivers", traffic["driver"])
    model = load_module("models", config["builder"])
    reference = load_module("references", config["builder"])
    log("[chipbench] program imported")

    run = driver.prepare(config=config, traffic=traffic, model=model,
                         reference=reference, devices=devs, seed=args.seed,
                         log=log)
    profiler = None
    out_dir = os.path.join(HERE, "out", args.workload)
    if args.trace:
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir, exist_ok=True)
        profiler = ProfilerSlice(out_dir, args.workload, args.seconds, log)
    snap0, compiles0 = tel.snapshot(), compiles.n
    setup_s = time.perf_counter() - T_PROCESS
    log(f"[chipbench] set-up done, window of {args.seconds:g}s opens")
    if profiler is not None:
        profiler.open_window()
    closed = []             # the driver calls on_close as the window closes

    def on_close():
        closed.append((tel.snapshot(), compiles.n))
        if profiler is not None:
            profiler.close_window()     # the counters are read: stop now

    result = driver.measure(run, args.seconds, on_close)
    snap1, compiles1 = closed[0]
    device = device_block(devs)                  # before the reference runs
    log(f"[chipbench] memory_stats of chip 0: {devs[0].memory_stats()}")
    correct, notes = driver.verify(run, result, log=log)
    if profiler is not None:
        profiler.wait()         # last: the stop converted under the above

    out = {"correct": bool(correct), "attempted": int(result["attempted"]),
           "failed": int(result["failed"]), "metrics": {}, "device": device}
    if not args.trace:
        values = dict(result["metrics"], setup_s=setup_s)
        for m in e2e:
            out["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                         "unit": m["unit"]}
    else:
        trace = reduce_trace.reduce_file(profiler.path, n_devices=len(devs))
        ctx = {"cell": cell, "config": config, "traffic": traffic,
               "result": result, "window_s": result["window_s"],
               "telemetry": (snap0, snap1),
               "compiles_in_window": compiles1 - compiles0,
               "trace": trace, "peaks": peaks, "model": model}
        for m in per_layer:
            value = load_module("layer_metrics", m["name"]).read(ctx)
            # None = nothing to read; a tail that reached a failed request
            # is +inf, which JSON cannot carry: such a run is not correct
            if value is not None and math.isfinite(value):
                out["metrics"][m["name"]] = {"value": float(value),
                                             "unit": m["unit"]}
        device["busy_s"] = trace["busy_s_mean"]
        device["window_s"] = trace["slice_s"]
        out["breakdown"] = {"device_ops": trace["top_ops"],
                            "idle_gaps": trace["top_gaps"]}
    # what a reader wants beside the result (medians, sample counts, the
    # reference gaps) goes on an EARLIER line; the last line is the result
    line = {"notes": notes, "workload": args.workload, "seed": args.seed,
            "trace": args.trace, "window_s": result["window_s"],
            "setup_s": setup_s, "compiles_in_window": compiles1 - compiles0}
    if profiler is not None:
        line.update(trace_mb=profiler.trace_bytes / 1e6,
                    trace_stop_s=profiler.stop_s,
                    close_to_result_s=time.perf_counter() - profiler.t_close)
    print(json.dumps(line), flush=True)
    print(json.dumps(out), flush=True)
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())
