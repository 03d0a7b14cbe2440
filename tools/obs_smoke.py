"""Observability smoke gate (`make obs-smoke`).

The acceptance run for mx.obs (docs/obs.md).  Serves LeNet through the
continuous-batching tier with the metrics endpoint armed, then FAILS
(exit 1) unless:

  * a second thread scraping ``/metrics`` + ``/statusz`` MID-LOAD gets
    nothing but 200s (exposition never blocks on the serving path);
  * at quiesce, the windowed histogram's lifetime count equals the
    telemetry timer's count for ``serve.e2e_seconds`` — every observe
    fed both sides, none was dropped or doubled;
  * two REAL worker processes (``--worker`` mode: own registry, own
    ephemeral endpoint) aggregate into one fleet view whose merged
    histogram count is exactly the sum of the workers' counts, and a
    dead URL in the same scrape makes the view partial instead of
    raising;
  * ``/readyz`` answers 200 on the warmed, healthy replica.

The obs-on over obs-off serve wall time (min-of-4 alternated
``obs.set_enabled`` passes) is reported, not gated: a CPU timing is no
speed.

Writes ``obs_smoke.json`` (gitignored).  Serial — single-core box,
never run concurrently with tier-1 (ROADMAP note).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# runnable as `python tools/obs_smoke.py` from a source checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_REQS = 64
OVERHEAD_REQS = 256
WORKER_REQS = 12


def build_registry():
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.serve.registry import Registry

    reg = Registry()
    mx.random.seed(0)
    lenet = mx.gluon.model_zoo.get_model("lenet")
    lenet.initialize(mx.init.Xavier())
    lenet(mx.np.zeros((1, 1, 28, 28)))
    reg.register("lenet", lenet, bucketer={0: [4, 16]},
                 sample=onp.zeros((1, 28, 28), "float32"))
    return reg


def _requests(n, seed=7):
    import numpy as onp

    rs = onp.random.RandomState(seed)
    return [rs.rand(1, 28, 28).astype("float32") for _ in range(n)]


def _serve_batch(server, reqs):
    futs = [server.submit("lenet", r) for r in reqs]
    for f in futs:
        f.result(timeout=60.0)


def worker_main() -> int:
    """Subprocess mode: serve WORKER_REQS requests with the endpoint
    up, print one READY line, hold until stdin closes."""
    from mxnet_tpu import obs
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.serve.server import Server

    srv_http = obs.serve_metrics(0)
    reg = build_registry()
    with Server(registry=reg) as server:
        _serve_batch(server, _requests(WORKER_REQS, seed=os.getpid()))
        count = tel.snapshot()["serve.e2e_seconds"]["count"]
        print(f"READY {srv_http.url} {count}", flush=True)
        sys.stdin.readline()  # parent closes the pipe when done
    return 0


def _scrape(url, path="/metrics", timeout=10.0):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        return r.status, r.read().decode()


def main() -> int:
    import mxnet_tpu as mx  # noqa: F401 — full package (registers obs)
    from mxnet_tpu import obs
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.obs.histogram import histograms
    from mxnet_tpu.serve.server import Server

    if not obs.enabled():
        print("obs-smoke: MXNET_OBS=0 — nothing to verify; run with obs "
              "enabled", file=sys.stderr)
        return 1
    checks = {}
    srv_http = obs.serve_metrics(0)
    reg = build_registry()

    with Server(registry=reg) as server:
        # -- mid-load scrape from a second thread -----------------------
        codes = []

        def scrape_loop():
            for _ in range(6):
                codes.append(_scrape(srv_http.url)[0])
                codes.append(_scrape(srv_http.url, "/statusz")[0])

        t = threading.Thread(target=scrape_loop, name="smoke-scraper")
        t.start()
        _serve_batch(server, _requests(N_REQS))
        t.join(60.0)
        checks["midload_scrapes"] = len(codes)
        checks["midload_all_200"] = bool(codes) and \
            all(c == 200 for c in codes) and not t.is_alive()

        # -- histogram count == telemetry timer count -------------------
        tel_count = tel.snapshot()["serve.e2e_seconds"]["count"]
        hist = histograms().get("serve.e2e_seconds")
        hist_count = hist.count if hist else -1
        checks["telemetry_count"] = tel_count
        checks["histogram_count"] = hist_count
        checks["counts_match"] = tel_count == hist_count == N_REQS

        # -- readiness on the warmed healthy replica --------------------
        code, body = _scrape(srv_http.url, "/readyz")
        checks["readyz"] = code
        checks["readyz_ok"] = code == 200 and \
            json.loads(body)["ready"] is True

        # -- overhead: obs ON vs OFF, min of 4 alternated passes --------
        reqs = _requests(OVERHEAD_REQS, seed=11)
        _serve_batch(server, reqs)  # settle residual warmup
        on_walls, off_walls = [], []
        for _ in range(4):
            obs.set_enabled(True)
            t0 = time.perf_counter()
            _serve_batch(server, reqs)
            on_walls.append(time.perf_counter() - t0)
            obs.set_enabled(False)
            t0 = time.perf_counter()
            _serve_batch(server, reqs)
            off_walls.append(time.perf_counter() - t0)
        obs.set_enabled(True)
        ratio = min(on_walls) / min(off_walls)
        checks["overhead_ratio"] = round(ratio, 4)
        checks["wall_on_secs"] = round(min(on_walls), 4)
        checks["wall_off_secs"] = round(min(off_walls), 4)

    # -- fleet aggregation over two real worker processes -------------------
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TELEMETRY="1",
               MXNET_OBS="1")
    workers = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        env=env) for _ in range(2)]
    urls, counts = [], []
    try:
        for w in workers:
            deadline = time.time() + 300
            line = ""
            while time.time() < deadline:
                line = w.stdout.readline()
                if line.startswith("READY "):
                    break
            _, url, count = line.split()
            urls.append(url)
            counts.append(int(count))
        fleet = obs.aggregate(urls)
        merged = fleet.histogram("serve.e2e_seconds").count
        checks["worker_counts"] = counts
        checks["fleet_merged_count"] = merged
        checks["fleet_merge_exact"] = merged == sum(counts) and \
            not fleet.partial
        checks["fleet_p99_ms"] = round(
            fleet.percentile("serve.e2e_seconds", 0.99) * 1e3, 3)
        # one dead URL in the same sweep: partial view, no exception
        dead = obs.aggregate(urls + ["http://127.0.0.1:9"], timeout=1.0)
        checks["fleet_partial_flagged"] = dead.partial and \
            len(dead.dead_workers) == 1 and \
            dead.histogram("serve.e2e_seconds").count == sum(counts)
        fleet_doc = fleet.to_dict()
    finally:
        for w in workers:
            try:
                w.stdin.close()
                w.wait(30)
            except Exception:
                w.kill()

    # runtime lock witness (Makefile arms MXNET_THREAD_CHECK=raise):
    # any inversion/long-hold in the obs/serve path fails the gate
    from mxnet_tpu.analysis import thread_check as tchk
    tc_diags = tchk.diagnostics() if tchk.enabled() else []
    checks["thread_check_armed"] = tchk.enabled()
    checks["thread_check_findings"] = len(tc_diags)

    ok = (checks["midload_all_200"]
          and checks["counts_match"]
          and checks["readyz_ok"]
          and checks["fleet_merge_exact"]
          and checks["fleet_partial_flagged"]
          and not tc_diags)

    out_path = os.environ.get("MXNET_OBS_SMOKE_JSON") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "obs_smoke.json")
    with open(out_path, "w") as f:
        json.dump({"reqs": N_REQS, "ok": ok, "checks": checks,
                   "fleet": fleet_doc,
                   "telemetry": tel.snapshot()}, f, indent=2,
                  sort_keys=True, default=str)
        f.write("\n")

    print(f"obs-smoke: {N_REQS} requests -> {out_path}")
    print(f"  mid-load scrapes (all 200)   {checks['midload_scrapes']} "
          f"-> {checks['midload_all_200']}")
    print(f"  hist == telemetry count      {checks['histogram_count']} "
          f"== {checks['telemetry_count']}")
    print(f"  overhead (on/off)            {checks['overhead_ratio']} "
          f"({checks['wall_on_secs']}s / {checks['wall_off_secs']}s)")
    print(f"  fleet merge exact            {checks['fleet_merge_exact']} "
          f"({counts} -> {checks['fleet_merged_count']})")
    print(f"  dead worker flagged          "
          f"{checks['fleet_partial_flagged']}")
    if not ok:
        print("obs-smoke: FAILED — an observability seam regressed "
              "(docs/obs.md)", file=sys.stderr)
        return 1
    print("obs-smoke: OK — exposition and merge exactness held")
    return 0


if __name__ == "__main__":
    sys.exit(worker_main() if "--worker" in sys.argv[1:] else main())
