"""Generative-decode smoke gate (`make decode-smoke`).

Proves the mx.serve token-level decode tier end to end on CPU
(docs/serving.md "Decode lifecycle") — the acceptance gates of the
decode design, checked without a chip:

  * **Zero compiles after warmup**: the :class:`DecodeEntry` AOT-warms
    the full executable grid (prefill per prompt-bucket x capacity,
    decode step / slot write per capacity, growth per bucket pair); the
    whole serving run — TWO capacity buckets, occupancies 1 through
    ``SLOTS`` — must add exactly 0 ``hybridize.cache_misses``.
  * **Batched >= 2x sequential tokens/s**: N prompts decoded through
    saturated slots (token-level continuous batching) must clear at
    least twice the tokens/s of the same N prompts decoded one at a
    time through the same server path (each paying its own steps).
  * **Per-token p99**: ``serve.decode_step_seconds`` p99 of the batched
    phase under ``STEP_P99_BOUND_S`` (generous for CPU — a recompile or
    a hang blows it).
  * **Donated cache aliased (X004)**: the warmup runs under
    ``MXNET_XLA_LINT`` with the lint capture armed — any donated-but-
    unaliased cache fails here; the check is proven non-vacuous by
    requiring donated argnums on the decode-step executable AND
    observing that a donated cache buffer is actually invalidated.
  * **int8 KV cache (the ISSUE 20 precision ladder)**: a second entry
    registered with ``precision="int8"`` must (a) serve >=
    ``INT8_SLOTS_GATE``x the slots at fixed cache bytes (per-slot int8
    pages + f32 scales vs the f32 cache), (b) add ZERO compiles after
    its own warmup through a saturated run with capacity growth, and
    (c) keep greedy decode within ``INT8_AGREEMENT_GATE`` agreement of
    the f32 twin on the same weights (bounded quantization
    divergence).

``MXNET_COMPILE_CACHE=0`` is forced: the CPU donation guard drops
aliasing when the persistent cache is armed (deserialized executables
corrupt donated buffers on XLA:CPU), which would make the X004 gate
vacuous.

Emits ``decode_smoke.json`` (gitignored) with a bench-style row
(``decode_tokens_per_s``) so the decode tier enters the perf trajectory
alongside the serving row.  FAILS (exit 1) on any gate.  Runs serially
(single-core box — never concurrent with tier-1).
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the CPU donation guard keys on the armed persistent cache; disarm it
# so the donated-cache aliasing (X004) gate tests the real thing
os.environ["MXNET_COMPILE_CACHE"] = "0"
os.environ["MXNET_XLA_LINT"] = "1"

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N_REQS = 12            # prompts per phase
MAX_NEW = 24           # tokens generated per prompt (no EOS: exact);
                       # 16-token prompts reach 16 + 23 = 39 > 32, so
                       # the batched phase must cross a capacity bucket
SLOTS = 4
SPEEDUP_GATE = 2.0     # batched tokens/s >= GATE x sequential
STEP_P99_BOUND_S = 0.25
INT8_SLOTS_GATE = 1.8       # servable slots at fixed cache bytes
INT8_AGREEMENT_GATE = 0.75  # greedy token agreement vs the f32 twin


def _metric(snap, name, field="value", default=0):
    return snap.get(name, {}).get(field, default)


def build_entry(report):
    """Tiny transformer LM DecodeEntry; warmup runs under the lint
    capture so every gridded executable passes the X rules (X004
    included) before any measurement."""
    import mxnet_tpu as mx
    from mxnet_tpu import serve
    from mxnet_tpu.analysis import xla_lint as xl

    mx.random.seed(0)
    lm = mx.gluon.model_zoo.get_model(
        "transformer_lm", vocab_size=64, units=64, hidden_size=128,
        num_heads=4, num_layers=2, max_length=128)
    lm.initialize(mx.init.Xavier())
    t0 = time.perf_counter()
    with xl.capture() as cap:
        entry = serve.DecodeEntry(
            "decode_lm", lm, slots=SLOTS, prompt_buckets=(8, 16),
            capacity_buckets=(32, 64), max_new_tokens=MAX_NEW)
    warm_s = time.perf_counter() - t0
    diags = [d for _f, dg in cap for d in dg]
    report["warmup"] = {
        "seconds": round(warm_s, 2),
        "executables_linted": len(cap),
        "lint_findings": [d.format() for d in diags],
        "lint_ok": not diags,
    }
    return entry, (not diags)


def donation_gate(entry, report):
    """The X004 pass above must not be vacuous: the decode-step
    executable really declares donated argnums, and stepping on a cache
    tree really invalidates the donated buffers (XLA reused them)."""
    import numpy as onp

    import mxnet_tpu as mx

    donated = [h.get("donate_argnums", ())
               for h in entry.stepper._cached_op._holders.values()]
    have_donation = any(donated)
    cache = entry.block.begin_cache(entry.slots, 32)
    old_leaf = cache[0][0]
    idle = onp.zeros(entry.slots, onp.int32)
    _ids, _logits, new_cache, _counts = entry.step(
        mx.np.zeros((entry.slots,), dtype="int32"), idle, idle, idle,
        idle + 1, cache)
    try:
        old_leaf.asnumpy()
        invalidated = False
    except RuntimeError:
        invalidated = True
    alive = bool(onp.isfinite(new_cache[0][0].asnumpy()).all())
    ok = have_donation and invalidated and alive
    report["donation"] = {
        "executables_with_donation": sum(1 for d in donated if d),
        "donated_buffer_invalidated": invalidated,
        "returned_cache_alive": alive, "ok": ok,
    }
    return ok


def make_prompts(n):
    import numpy as onp

    rs = onp.random.RandomState(7)
    return [list(rs.randint(1, 64, size=int(rs.randint(4, 17))))
            for _ in range(n)]


def decode_phases(entry, report):
    """Sequential (occupancy 1) vs continuous-batched (slots saturated)
    tokens/s through the same DecodeServer path, plus the zero-compile
    and per-token p99 gates."""
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.serve import DecodeServer

    prompts = make_prompts(N_REQS)
    misses0 = _metric(tel.snapshot(), "hybridize.cache_misses")

    # -- sequential baseline: one request at a time, each paying its own
    # prefill + MAX_NEW steps at occupancy 1
    srv = DecodeServer(entry)
    t0 = time.perf_counter()
    seq_tokens = 0
    for p in prompts:
        seq_tokens += len(srv.generate(p, timeout=300))
    seq_wall = time.perf_counter() - t0
    srv.close(60.0)
    seq_tps = seq_tokens / seq_wall
    seq_misses = _metric(tel.snapshot(), "hybridize.cache_misses") - misses0

    # telemetry reset between phases: the per-token p99 and occupancy
    # high-water must describe the BATCHED phase alone
    tel.reset()

    # -- batched: all prompts in flight, slots saturated, requests
    # joining/leaving at token boundaries (continuous batching)
    srv = DecodeServer(entry)
    t0 = time.perf_counter()
    futs = [srv.submit(p) for p in prompts]
    batch_tokens = sum(len(f.result(300)) for f in futs)
    batch_wall = time.perf_counter() - t0
    srv.close(60.0)
    batch_tps = batch_tokens / batch_wall

    snap = tel.snapshot()
    misses = seq_misses + _metric(snap, "hybridize.cache_misses")
    p99 = _metric(snap, "serve.decode_step_seconds", "p99")
    ttft_p99 = _metric(snap, "serve.ttft_seconds", "p99")
    occ_max = _metric(snap, "serve.decode_slots_active", "max")
    grows = _metric(snap, "serve.cache_grows")
    speedup = batch_tps / seq_tps

    ok_speed = speedup >= SPEEDUP_GATE
    ok_p99 = 0 < p99 <= STEP_P99_BOUND_S
    ok_compiles = misses == 0
    # >=2 capacity buckets (growth fired) and >=2 occupancies (saturated
    # slots in THIS phase; the sequential phase ran the same executables
    # at occupancy 1) — the zero-compile claim covers the whole grid
    ok_coverage = grows >= 1 and occ_max >= 2
    report["decode"] = {
        "n_requests": N_REQS, "max_new_tokens": MAX_NEW, "slots": SLOTS,
        "sequential_tokens_per_s": round(seq_tps, 2),
        "batched_tokens_per_s": round(batch_tps, 2),
        "batched_vs_sequential": round(speedup, 3),
        "speedup_gate": SPEEDUP_GATE, "speedup_ok": ok_speed,
        "step_p50_ms": round(
            _metric(snap, "serve.decode_step_seconds", "p50") * 1e3, 3),
        "step_p99_ms": round(p99 * 1e3, 3),
        "step_p99_bound_ms": STEP_P99_BOUND_S * 1e3, "p99_ok": ok_p99,
        "ttft_p99_ms": round(ttft_p99 * 1e3, 3),
        "prefix_hit_rate": 0.0,     # unified path; tools/disagg_smoke.py
                                    # measures the trie-backed rate
        "compiles_after_warmup": misses, "compiles_ok": ok_compiles,
        "cache_grows": grows, "occupancy_high_water": occ_max,
        "coverage_ok": ok_coverage,
        "tokens_total": seq_tokens + batch_tokens,
    }
    return ok_speed and ok_p99 and ok_compiles and ok_coverage


def _smoke_lm(**extra):
    import mxnet_tpu as mx

    mx.random.seed(0)
    lm = mx.gluon.model_zoo.get_model(
        "transformer_lm", vocab_size=64, units=64, hidden_size=128,
        num_heads=4, num_layers=2, max_length=128, **extra)
    lm.initialize(mx.init.Xavier())
    return lm


def _eager_greedy(f32_lm, prompt, n_new, capacity=64):
    """One-row greedy reference on the f32 twin: full eager re-forward
    per token — no jit signatures, no quantization."""
    import numpy as onp
    import jax.numpy as jnp

    from mxnet_tpu.ndarray.ndarray import NDArray

    def nd(a):
        return NDArray(jnp.asarray(a, jnp.int32))

    toks, out = list(prompt), []
    for _ in range(n_new):
        logits, _ = f32_lm.forward(
            nd([toks]), f32_lm.begin_cache(1, capacity), nd([0]),
            nd([len(toks)]))
        out.append(int(onp.argmax(logits.asnumpy()[0, len(toks) - 1])))
        toks.append(out[-1])
    return out


def int8_phase(report):
    """The ISSUE 20 int8-KV serving gates: >=INT8_SLOTS_GATE x servable
    slots at fixed cache bytes, zero compiles after the int8 entry's
    own warmup through saturated slots + capacity growth, and greedy
    agreement >= INT8_AGREEMENT_GATE vs the f32 twin."""
    from mxnet_tpu import serve
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.analysis import xla_lint as xl

    f32 = _smoke_lm()
    t0 = time.perf_counter()
    with xl.capture() as cap:
        entry = serve.DecodeEntry(
            "decode_lm_int8", _smoke_lm(), slots=SLOTS,
            prompt_buckets=(8, 16), capacity_buckets=(32, 64),
            max_new_tokens=MAX_NEW, precision="int8")
    warm_s = time.perf_counter() - t0
    diags = [d for _f, dg in cap for d in dg]

    # servable slots at fixed cache bytes: what one slot costs (int8
    # pages + f32 per-position scales) vs the f32 cache at the same
    # capacity — the DecodeServer serves that many more slots from the
    # same HBM budget
    f32_bytes = sum(leaf.nbytes for pair in f32.begin_cache(1, 64)
                    for leaf in pair)
    int8_bytes = sum(leaf.nbytes
                     for pair in entry.block.begin_cache(1, 64)
                     for leaf in pair)
    slots_ratio = f32_bytes / int8_bytes

    prompts = make_prompts(N_REQS)
    tel.reset()
    misses0 = _metric(tel.snapshot(), "hybridize.cache_misses")
    srv = serve.DecodeServer(entry)
    t0 = time.perf_counter()
    futs = [srv.submit(p) for p in prompts]
    outs = [f.result(300) for f in futs]
    wall = time.perf_counter() - t0
    srv.close(60.0)
    snap = tel.snapshot()
    misses = _metric(snap, "hybridize.cache_misses") - misses0
    saved = _metric(snap, "serve.cache_quant_bytes_saved")
    grows = _metric(snap, "serve.cache_grows")
    tps = sum(len(o) for o in outs) / wall

    # bounded greedy divergence: first 4 prompts against the eager f32
    # reference (same seed => identical weights)
    agree_n = tok_n = 0
    for p, got in zip(prompts[:4], outs[:4]):
        want = _eager_greedy(f32, p, len(got))
        agree_n += sum(a == b for a, b in zip(got, want))
        tok_n += len(got)
    agreement = agree_n / max(tok_n, 1)

    ok_lint = not diags
    ok_slots = slots_ratio >= INT8_SLOTS_GATE
    ok_compiles = misses == 0
    ok_agree = agreement >= INT8_AGREEMENT_GATE
    ok_savings = saved > 0
    report["int8"] = {
        "warmup_seconds": round(warm_s, 2),
        "lint_findings": [d.format() for d in diags], "lint_ok": ok_lint,
        "f32_cache_bytes_per_slot": int(f32_bytes),
        "int8_cache_bytes_per_slot": int(int8_bytes),
        "slots_at_fixed_cache_bytes": round(slots_ratio, 3),
        "slots_gate": INT8_SLOTS_GATE, "slots_ok": ok_slots,
        "tokens_per_s": round(tps, 2),
        "compiles_after_warmup": misses, "compiles_ok": ok_compiles,
        "cache_grows": grows,
        "cache_quant_bytes_saved": int(saved), "savings_ok": ok_savings,
        "greedy_agreement": round(agreement, 3),
        "agreement_gate": INT8_AGREEMENT_GATE, "agreement_ok": ok_agree,
        "tokens_compared": tok_n,
    }
    return ok_lint and ok_slots and ok_compiles and ok_agree and ok_savings


def make_row(decode, platform="cpu", int8=None):
    """The decode_tokens_per_s row of this smoke's report.  The int8
    fields are zero when the int8 phase did not run."""
    int8 = int8 or {}
    return {"metric": "decode_tokens_per_s",
            "value": decode["batched_tokens_per_s"], "unit": "tokens/s",
            "sequential_tokens_per_s": decode["sequential_tokens_per_s"],
            "batched_vs_sequential": decode["batched_vs_sequential"],
            "step_p50_ms": decode["step_p50_ms"],
            "step_p99_ms": decode["step_p99_ms"],
            "decode_ttft_p99_ms": decode.get("ttft_p99_ms", 0.0),
            "prefix_hit_rate": decode.get("prefix_hit_rate", 0.0),
            "occupancy_high_water": decode["occupancy_high_water"],
            "n_requests": decode["n_requests"],
            "max_new_tokens": decode["max_new_tokens"],
            "int8_tokens_per_s": int8.get("tokens_per_s", 0.0),
            "int8_slots_at_fixed_cache_bytes":
                int8.get("slots_at_fixed_cache_bytes", 0.0),
            "int8_greedy_agreement": int8.get("greedy_agreement", 0.0),
            "platform": platform, "ts": round(time.time(), 1)}



def thread_check_gate(report):
    """Zero-findings gate for the runtime lock witness: the Makefile
    recipe arms MXNET_THREAD_CHECK=raise, so any inversion/long-hold in
    the decode path fails the smoke (docs/analysis.md T1xx rules)."""
    from mxnet_tpu.analysis import thread_check as tchk

    diags = tchk.diagnostics() if tchk.enabled() else []
    report["thread_check"] = {"armed": tchk.enabled(),
                              "findings": [d.to_dict() for d in diags]}
    return not diags

def main():
    report = {"live": False, "platform": "cpu"}
    entry, ok = build_entry(report)
    ok = donation_gate(entry, report) and ok
    ok = decode_phases(entry, report) and ok
    ok = int8_phase(report) and ok
    ok = thread_check_gate(report) and ok
    report["row"] = make_row(report["decode"], int8=report.get("int8"))
    report["ok"] = bool(ok)
    out = os.path.join(ROOT, "decode_smoke.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    print(f"decode-smoke: {'OK' if ok else 'FAIL'} -> {out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
