"""Serving smoke gate (`make serve-smoke`).

Proves the mx.serve continuous-batching tier end to end on CPU
(docs/serving.md) — the acceptance gates of the serving design, checked
without a chip:

  * **Zero compiles after warmup**: a LeNet + tiny-BERT registry is
    AOT-warmed over both models' FULL bucket grids at registration;
    the whole load phase (ragged shapes included) must add exactly 0
    ``hybridize.cache_misses``.
  * **Batched >= 2x sequential**: N mixed ragged requests submitted
    concurrently (the coalescer batches them) must clear at least twice
    the request rate of the same N requests dispatched one-at-a-time
    through the same server path (no co-batching — each pays its own
    dispatch + sync).
  * **p99 bound**: end-to-end latency p99 of the batched phase under
    ``P99_BOUND_S`` (generous for CPU, but a hang/recompile blows it).
  * **Load shedding**: a flood against a ``queue_max=2`` server must
    shed at least one request (``RejectedError`` + ``serve.rejected``).

Emits ``serve_smoke.json`` (gitignored) with a bench-style row — p50/p99
latency + batch occupancy — so the serving tier enters the perf
trajectory alongside the training rows.  FAILS (exit 1) on any gate.
Runs serially (single-core box — never concurrent with tier-1).
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N_REQS = 48          # mixed load-gen requests (24 lenet + 24 bert)
SPEEDUP_GATE = 2.0   # batched rps >= GATE x sequential rps
P99_BOUND_S = 2.0    # end-to-end p99 bound on CPU


def _metric(snap, name, field="value", default=0):
    return snap.get(name, {}).get(field, default)


def build_registry():
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import get_bert
    from mxnet_tpu.serve.registry import Registry

    reg = Registry()
    mx.random.seed(0)
    lenet = mx.gluon.model_zoo.get_model("lenet")
    lenet.initialize(mx.init.Xavier())
    lenet(mx.np.zeros((1, 1, 28, 28)))
    reg.register("lenet", lenet, bucketer={0: [4, 16]},
                 sample=onp.zeros((1, 28, 28), "float32"))

    bert = get_bert("bert_12_768_12", vocab_size=97, max_length=16,
                    num_layers=2, units=32, hidden_size=64, num_heads=4,
                    dropout=0.0)
    bert.initialize(mx.init.Xavier())
    bert(mx.nd.NDArray(onp.zeros((1, 8), "int32")),
         mx.nd.NDArray(onp.zeros((1, 8), "int32")),
         mx.nd.NDArray(onp.full((1,), 8, "int32")))
    reg.register("bert", bert, bucketer={0: [4, 8], 1: ("pow2", 8, 16)},
                 sample=(onp.zeros((8,), "int32"),
                         onp.zeros((8,), "int32"),
                         onp.asarray(8, "int32")))
    return reg


def make_requests(n):
    """Mixed ragged request stream: alternating lenet / variable-T bert."""
    import numpy as onp

    rs = onp.random.RandomState(7)
    reqs = []
    for i in range(n):
        if i % 2 == 0:
            reqs.append(("lenet",
                         (rs.rand(1, 28, 28).astype("float32"),)))
        else:
            t = int(rs.randint(3, 17))
            reqs.append(("bert",
                         (rs.randint(0, 97, (t,)).astype("int32"),
                          onp.zeros((t,), "int32"),
                          onp.asarray(t, "int32"))))
    return reqs


def load_phases(reg, report):
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.serve.server import Server

    reqs = make_requests(N_REQS)
    misses0 = _metric(tel.snapshot(), "hybridize.cache_misses")

    # -- sequential baseline: same server path, one request at a time --
    with Server(registry=reg, max_wait_ms=1, max_batch=16,
                max_inflight=2) as srv:
        t0 = time.perf_counter()
        for model, args in reqs:
            srv.predict(model, *args, timeout=120)
        seq_wall = time.perf_counter() - t0
    seq_rps = N_REQS / seq_wall
    seq_misses = _metric(tel.snapshot(),
                         "hybridize.cache_misses") - misses0

    # telemetry reset between phases: the row's p50/p99/occupancy must
    # describe the BATCHED phase, not a mix (counters restart at 0)
    tel.reset()

    # -- batched: concurrent clients each fire their whole chunk before
    # collecting results — real load-gen, deep queues for the coalescer
    with Server(registry=reg, max_wait_ms=8, max_batch=16,
                max_inflight=2) as srv:
        errs = []

        def client(chunk):
            try:
                futs = [srv.submit(model, *args) for model, args in chunk]
                for f in futs:
                    f.result(timeout=120)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append(repr(e))

        nt = 6
        chunks = [reqs[i::nt] for i in range(nt)]
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(c,))
                   for c in chunks]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        batch_wall = time.perf_counter() - t0
    if errs:
        raise RuntimeError(f"batched phase errors: {errs[:3]}")
    batch_rps = N_REQS / batch_wall

    snap = tel.snapshot()
    misses = seq_misses + _metric(snap, "hybridize.cache_misses")
    rows = _metric(snap, "serve.rows")
    padded = _metric(snap, "serve.padded_rows")
    occupancy = rows / max(1, padded)
    p50 = _metric(snap, "serve.e2e_seconds", "p50")
    p99 = _metric(snap, "serve.e2e_seconds", "p99")
    speedup = batch_rps / seq_rps

    ok_speed = speedup >= SPEEDUP_GATE
    ok_p99 = 0 < p99 <= P99_BOUND_S
    ok_compiles = misses == 0
    report["load"] = {
        "n_requests": N_REQS,
        "sequential_rps": round(seq_rps, 2),
        "batched_rps": round(batch_rps, 2),
        "batched_vs_sequential": round(speedup, 3),
        "speedup_gate": SPEEDUP_GATE, "speedup_ok": ok_speed,
        "e2e_p50_ms": round(p50 * 1e3, 3),
        "e2e_p99_ms": round(p99 * 1e3, 3),
        "p99_bound_ms": P99_BOUND_S * 1e3, "p99_ok": ok_p99,
        "compiles_after_warmup": misses, "compiles_ok": ok_compiles,
        "batches": _metric(snap, "serve.batches"),
        "batch_occupancy": round(occupancy, 4),
        "inflight_high_water":
            _metric(snap, "serve.inflight_batches", "max"),
    }
    return ok_speed and ok_p99 and ok_compiles


def shed_phase(reg, report):
    """Forced queue overflow: a tiny bound + a flood must shed."""
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.serve import RejectedError
    from mxnet_tpu.serve.server import Server

    import numpy as onp

    shed = 0
    futs = []
    with Server(registry=reg, max_wait_ms=1, max_batch=4, queue_max=2,
                max_inflight=1) as srv:
        x = onp.zeros((1, 28, 28), "float32")
        for _ in range(200):
            try:
                futs.append(srv.submit("lenet", x))
            except RejectedError:
                shed += 1
        for f in futs:
            f.result(timeout=120)  # every ADMITTED request still answers
    counter = _metric(tel.snapshot(), "serve.rejected")
    ok = shed >= 1 and counter >= shed
    report["shed"] = {"submitted": 200, "shed": shed,
                      "served": len(futs),
                      "rejected_counter": counter, "ok": ok}
    return ok


def make_row(load, platform="cpu"):
    """The serve_mixed_p99_ms row of this smoke's report."""
    return {"metric": "serve_mixed_p99_ms", "value": load["e2e_p99_ms"],
            "unit": "ms", "p50_ms": load["e2e_p50_ms"],
            "throughput_rps": load["batched_rps"],
            "batched_vs_sequential": load["batched_vs_sequential"],
            "batch_occupancy": load["batch_occupancy"],
            "n_requests": load["n_requests"],
            "platform": platform, "ts": round(time.time(), 1)}



def thread_check_gate(report):
    """Zero-findings gate for the runtime lock witness: the Makefile
    recipe arms MXNET_THREAD_CHECK=raise, so any inversion/long-hold in
    the serve path fails the smoke (docs/analysis.md T1xx rules)."""
    from mxnet_tpu.analysis import thread_check as tchk

    diags = tchk.diagnostics() if tchk.enabled() else []
    report["thread_check"] = {"armed": tchk.enabled(),
                              "findings": [d.to_dict() for d in diags]}
    return not diags

def main():
    report = {"live": False, "platform": "cpu"}
    reg = build_registry()
    ok = load_phases(reg, report)
    ok = shed_phase(reg, report) and ok
    ok = thread_check_gate(report) and ok
    # the bench-style row: serving enters the perf trajectory
    report["row"] = make_row(report["load"])
    report["ok"] = bool(ok)
    out = os.path.join(ROOT, "serve_smoke.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    print(f"serve-smoke: {'OK' if ok else 'FAIL'} -> {out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
