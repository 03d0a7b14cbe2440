"""Elastic-fleet smoke gate (`make fleet-smoke`).

Proves the network edge + replica fleet end to end on CPU
(docs/serving.md "Network edge + fleet") — the acceptance gates of
ISSUE 19, checked without a chip:

  * **Fleet load**: a multi-client open-loop HTTP load against the
    router with every ADMITTED request answered (shed-before-admit 503s
    are allowed and counted — they are the contract, not a loss).
    Sequential and concurrent RPS are reported, not gated: a CPU
    timing is no speed.
  * **Kill a replica under load**: SIGKILL one replica mid-load; the
    supervisor must detect, retire, and respawn it with ZERO
    admitted-request loss (the router retries idempotent predicts on a
    sibling), the detection->ready recovery time is recorded, and the
    respawn must replay the shared persistent compile cache
    (``JAX_COMPILATION_CACHE_DIR``): persistent-cache hits > 0 in its
    READY announcement; cold and warm build seconds are reported.
  * **Streaming parity**: a streamed ``/v1/generate`` through the
    router delivers tokens INCREMENTALLY (first chunk strictly before
    the last token's chunk) and bit-exactly equal to an in-process
    greedy ``generate`` of the same model/seed.
  * **Zero post-warmup compiles, every replica**: each replica's
    ``/statusz`` compile-miss count at the end must equal the count in
    its READY announcement.
  * **Chaos-hardened dispatch**: with ``fleet.dispatch:error:0.5``
    installed, every predict still succeeds (bounded sibling retry +
    backoff) and ``fleet.dispatch_retries`` ticks.
  * **Thread hygiene**: MXNET_THREAD_CHECK=raise stays clean (Makefile
    recipe arms it) and no ``mx-*`` thread survives ``Fleet.close()``.

Emits ``fleet_smoke.json`` (gitignored) with the row (fleet_rps,
fleet_p99_ms, fleet_tokens_per_s, recovery_secs).
FAILS (exit 1) on any gate.  Runs serially (single-core box — never
concurrent with tier-1; replica subprocesses are part of THIS smoke's
budget).
"""
from __future__ import annotations

import json
import os
import shutil
import signal
import sys
import threading
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# NOT imported from decode_smoke/disagg_smoke on purpose: those modules
# force MXNET_COMPILE_CACHE=0 at import (their X004 gate needs the CPU
# donation guard disarmed), and the fleet workers load THIS file as
# their --spec — the persistent cache is load-bearing here (the warm
# respawn gate), so the helpers are local copies instead.


def _metric(snap, name, field="value", default=0):
    return snap.get(name, {}).get(field, default)


def thread_check_gate(report):
    """Zero-findings gate for the runtime lock witness (the Makefile
    recipe arms MXNET_THREAD_CHECK=raise)."""
    from mxnet_tpu.analysis import thread_check as tchk

    diags = tchk.diagnostics() if tchk.enabled() else []
    report["thread_check"] = {"armed": tchk.enabled(),
                              "findings": [d.to_dict() for d in diags]}
    return not diags


def thread_survivor_gate(report):
    """No ``mx-*`` thread survives Fleet.close() + shutdown."""
    left = sorted(t.name for t in threading.enumerate()
                  if t.name.startswith("mx-"))
    report["thread_survivors"] = {"alive": left, "ok": not left}
    return not left

MIN_REPLICAS = 2
SEQ_REQUESTS = 16
CLIENTS = 4
REQS_PER_CLIENT = 16
RECOVERY_BOUND_S = 120.0


# --------------------------------------------------------- worker spec
def build_models():
    """The replica spec (runs INSIDE each worker subprocess): one tiny
    batch-predict MLP + one tiny decode LM, both fully warmed so the
    zero-post-warmup-compiles gate is meaningful."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import serve
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo import transformer_lm

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8))
    net.add(nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((1, 8)))
    serve.register("mlp", net, bucketer={0: [2, 8]},
                   sample=onp.zeros((8,), "float32"))
    mx.random.seed(21)
    lm = transformer_lm(vocab_size=32, units=64, hidden_size=128,
                        num_heads=2, num_layers=2, max_length=64)
    lm.initialize(mx.init.Xavier())
    # two prompt x two capacity buckets: enough gridded executables
    # that compile time dominates replica startup — which is what the
    # warm-respawn gate measures (cache replay vs fixed standup cost)
    serve.register_decode("tlm", lm, slots=2, prompt_buckets=(4, 8),
                          capacity_buckets=(16, 32), max_new_tokens=6)
    return {"models": ["mlp", "tlm"]}


def _reference_tokens(prompt, cache_dir):
    """In-process greedy reference: the SAME model/seed the workers
    build, generated through the same DecodeServer code — what the
    streamed tokens must match bit-exactly."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    import mxnet_tpu as mx
    from mxnet_tpu import serve
    from mxnet_tpu.gluon.model_zoo import transformer_lm

    mx.random.seed(21)
    lm = transformer_lm(vocab_size=32, units=64, hidden_size=128,
                        num_heads=2, num_layers=2, max_length=64)
    lm.initialize(mx.init.Xavier())
    entry = serve.DecodeEntry("tlm_ref", lm, slots=1, prompt_buckets=(4,),
                              capacity_buckets=(16,), max_new_tokens=6)
    srv = serve.DecodeServer(entry)
    try:
        return srv.generate(list(prompt), timeout=120.0)
    finally:
        srv.close(60.0)


# -------------------------------------------------------------- phases
def boot_fleet(report, cache_dir):
    from mxnet_tpu import serve

    t0 = time.perf_counter()
    fleet = serve.Fleet(
        spec=os.path.abspath(__file__) + ":build_models",
        min_replicas=MIN_REPLICAS, max_replicas=MIN_REPLICAS + 1,
        env={"JAX_COMPILATION_CACHE_DIR": cache_dir,
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
             "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
             "MXNET_COMPILE_CACHE": "1", "MXNET_OBS": "1"},
        heartbeat_every=0.5)
    boot = time.perf_counter() - t0
    st = fleet.stats
    report["boot"] = {
        "replicas": len(fleet.ready_replicas()),
        "boot_secs": round(boot, 2),
        "cold_start_secs": st["cold_start_secs"],
        "initial_warm_start_secs": list(st["warm_start_secs"]),
    }
    ok = len(fleet.ready_replicas()) == MIN_REPLICAS
    return fleet, ok


def _predict_once(router, results, latencies):
    from mxnet_tpu.serve import RejectedError

    t0 = time.perf_counter()
    try:
        doc = router.predict("mlp", [[0.1] * 8], timeout=60.0)
        ok = len(doc["outputs"]) == 1 and len(doc["outputs"][0]) == 4
        results.append("ok" if ok else "bad")
        latencies.append(time.perf_counter() - t0)
    except RejectedError:
        results.append("shed")
    except Exception as e:  # noqa: BLE001 — counted, gated below
        results.append(f"error:{type(e).__name__}")


def throughput_phase(fleet, report):
    """Sequential then multi-client concurrent load; every admitted
    request must be answered."""
    seq_res, seq_lat = [], []
    t0 = time.perf_counter()
    for _ in range(SEQ_REQUESTS):
        _predict_once(fleet.router, seq_res, seq_lat)
    seq_secs = time.perf_counter() - t0
    seq_rps = SEQ_REQUESTS / seq_secs

    con_res, con_lat = [], []

    def client():
        for _ in range(REQS_PER_CLIENT):
            _predict_once(fleet.router, con_res, con_lat)

    threads = [threading.Thread(target=client,
                                name=f"mx-fleetsmoke-client-{i}")
               for i in range(CLIENTS)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    con_secs = time.perf_counter() - t0
    total = CLIENTS * REQS_PER_CLIENT
    con_rps = total / con_secs
    lat = sorted(con_lat)
    p99_ms = lat[min(len(lat) - 1, int(0.99 * len(lat)))] * 1e3 \
        if lat else None
    errors = [r for r in seq_res + con_res
              if r not in ("ok", "shed")]
    sheds = sum(1 for r in seq_res + con_res if r == "shed")
    speedup = con_rps / seq_rps
    ok = not errors and sum(1 for r in con_res if r == "ok") > 0
    report["throughput"] = {
        "sequential_rps": round(seq_rps, 2),
        "concurrent_rps": round(con_rps, 2),
        "speedup": round(speedup, 2),
        "p99_ms": round(p99_ms, 2) if p99_ms else None,
        "sheds": sheds, "errors": errors, "ok": ok,
    }
    return ok


def kill_phase(fleet, report):
    """SIGKILL one replica under live load: zero admitted-request
    loss, bounded recovery, warm respawn."""
    results, latencies = [], []
    stop = threading.Event()

    def loader():
        while not stop.is_set():
            _predict_once(fleet.router, results, latencies)

    threads = [threading.Thread(target=loader,
                                name=f"mx-fleetsmoke-load-{i}")
               for i in range(2)]
    for t in threads:
        t.start()
    time.sleep(1.0)
    victim = fleet.ready_replicas()[0]
    os.kill(victim.pid, signal.SIGKILL)
    t_kill = time.perf_counter()
    # the supervisor must detect (victim leaves the set — it stays
    # listed "ready" until the next heartbeat tick polls the corpse),
    # then respawn back to MIN: wait for the RESPAWN, not the listing
    recovered = False
    while time.perf_counter() - t_kill < RECOVERY_BOUND_S:
        if (fleet.stats["respawns"] >= 1
                and len(fleet.ready_replicas()) >= MIN_REPLICAS):
            recovered = True
            break
        time.sleep(0.25)
    time.sleep(1.0)  # load continues against the recovered fleet
    stop.set()
    for t in threads:
        t.join()
    st = fleet.stats
    errors = [r for r in results if r not in ("ok", "shed")]
    recovery = st["recoveries_secs"][0] if st["recoveries_secs"] else None

    # a second, IDLE respawn: its build seconds are reported beside the
    # cold start's (which was idle too)
    idle_recovered = False
    if recovered:
        victim2 = fleet.ready_replicas()[0]
        os.kill(victim2.pid, signal.SIGKILL)
        t2 = time.perf_counter()
        while time.perf_counter() - t2 < RECOVERY_BOUND_S:
            if (fleet.stats["respawns"] >= 2
                    and len(fleet.ready_replicas()) >= MIN_REPLICAS):
                idle_recovered = True
                break
            time.sleep(0.25)
    # build+warmup seconds are the phase the persistent cache replays;
    # the gate is the replay's count in the respawn's READY announcement
    cold = st["cold_build_secs"]
    warm = st["warm_build_secs"][-1] if st["warm_build_secs"] else None
    warm_ratio = (warm / cold) if (warm and cold) else None
    respawn_hits = fleet.replicas()[-1].doc.get("persistent_cache_hits", 0)
    ok = (recovered and idle_recovered and not errors
          and st["respawns"] >= 2
          and recovery is not None and recovery <= RECOVERY_BOUND_S
          and respawn_hits > 0
          and sum(1 for r in results if r == "ok") > 0)
    report["kill"] = {
        "recovered": recovered, "idle_recovered": idle_recovered,
        "respawns": st["respawns"], "drains": st["drains"],
        "recovery_secs": recovery,
        "requests_ok": sum(1 for r in results if r == "ok"),
        "sheds": sum(1 for r in results if r == "shed"),
        "errors": errors,
        "cold_build_secs": cold, "respawn_warm_build_secs": warm,
        "cold_start_secs": st["cold_start_secs"],
        "respawn_warm_start_secs":
            st["warm_start_secs"][-1] if st["warm_start_secs"] else None,
        "warm_ratio": round(warm_ratio, 3) if warm_ratio else None,
        "respawn_persistent_hits": respawn_hits, "ok": ok,
    }
    return ok


def streaming_phase(fleet, report, cache_dir):
    """Streamed generate through the router: incremental delivery +
    bit-exact greedy parity vs the in-process reference."""
    prompt = [1, 2, 3]
    ref = _reference_tokens(prompt, cache_dir)
    t0 = time.perf_counter()
    out = fleet.router.generate("tlm", prompt, stream=True, timeout=120.0)
    secs = time.perf_counter() - t0
    ts = out.get("chunk_ts", [])
    incremental = len(ts) >= 2 and ts[0] < ts[-1]
    exact = out["tokens"] == ref
    tokens_per_s = len(out["tokens"]) / secs if secs else 0.0
    ok = incremental and exact and out.get("finish_reason") == "length"
    report["streaming"] = {
        "tokens": out["tokens"], "reference": ref,
        "bit_exact": exact, "incremental": incremental,
        "first_to_last_chunk_ms":
            round((ts[-1] - ts[0]) * 1e3, 2) if incremental else None,
        "finish_reason": out.get("finish_reason"),
        "tokens_per_s": round(tokens_per_s, 2), "ok": ok,
    }
    return ok


def compile_phase(fleet, report):
    """Zero post-warmup compiles on EVERY replica: /statusz misses now
    == misses in the replica's READY announcement."""
    rows = []
    ok = True
    for rep in fleet.replicas():
        with urllib.request.urlopen(rep.obs_url + "/statusz",
                                    timeout=5.0) as r:
            doc = json.loads(r.read())
        now = doc["compile_cache"]["misses"]
        at_ready = rep.doc.get("misses_at_ready", 0)
        rows.append({"replica": rep.idx, "misses_at_ready": at_ready,
                     "misses_now": now,
                     "persistent_hits":
                         doc["compile_cache"]["persistent_hits"]})
        ok = ok and now == at_ready
    report["compiles"] = {"replicas": rows, "ok": ok}
    return ok


def chaos_phase(fleet, report):
    """fleet.dispatch error chaos at p=0.5: the bounded sibling retry
    must absorb every injected failure."""
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.resilience import chaos

    retries0 = _metric(tel.snapshot(), "fleet.dispatch_retries")
    results, latencies = [], []
    chaos.configure("fleet.dispatch:error:0.5", seed=7)
    try:
        for _ in range(10):
            _predict_once(fleet.router, results, latencies)
    finally:
        chaos.reset()
    retries = _metric(tel.snapshot(), "fleet.dispatch_retries") - retries0
    errors = [r for r in results if r != "ok"]
    ok = not errors and retries > 0
    report["chaos"] = {"requests_ok": len(results) - len(errors),
                       "errors": errors,
                       "dispatch_retries": retries, "ok": ok}
    return ok


def make_row(report, platform="cpu"):
    """The fleet_rps row of this smoke's report."""
    return {"metric": "fleet_rps",
            "value": report["throughput"]["concurrent_rps"],
            "unit": "req/s",
            "fleet_rps": report["throughput"]["concurrent_rps"],
            "fleet_p99_ms": report["throughput"]["p99_ms"],
            "fleet_tokens_per_s": report["streaming"]["tokens_per_s"],
            "recovery_secs": report["kill"]["recovery_secs"],
            "replicas": MIN_REPLICAS,
            "platform": platform, "ts": round(time.time(), 1)}


def main():
    report = {"live": False, "platform": "cpu"}
    # a fixed path (the directory is part of the cache's key), emptied
    # first: the respawn's hits must be entries THIS run's cold replica
    # wrote, and the cold build must be cold
    cache_dir = os.path.join(ROOT, ".jax_cache", "fleet-smoke")
    shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir)
    fleet, ok = boot_fleet(report, cache_dir)
    try:
        ok = throughput_phase(fleet, report) and ok
        ok = kill_phase(fleet, report) and ok
        ok = streaming_phase(fleet, report, cache_dir) and ok
        ok = chaos_phase(fleet, report) and ok
        ok = compile_phase(fleet, report) and ok
    finally:
        fleet.close()
        from mxnet_tpu import serve

        serve.shutdown_decode(60.0)
    ok = thread_survivor_gate(report) and ok
    ok = thread_check_gate(report) and ok
    report["row"] = make_row(report)
    report["ok"] = bool(ok)
    out = os.path.join(ROOT, "fleet_smoke.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    print(f"fleet-smoke: {'OK' if ok else 'FAIL'} -> {out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
