"""Disaggregated prefill/decode smoke gate (`make disagg-smoke`).

Proves the split serving design end to end on CPU (docs/serving.md
"Disaggregated prefill/decode" + "Prefix cache") — the acceptance gates
of ISSUE 18, checked without a chip:

  * **Disaggregated == unified, token for token**: the same mixed
    open-loop workload (long prefill-heavy prompts + short ones, all
    submitted at once) runs through a unified server (prompt forwards
    inline in the decode loop, first token waits for a free slot) and a
    disaggregated one (``prefill_workers`` pool, first token sampled at
    prefill completion, independent of slot availability).  The greedy
    outputs must match; both ``serve.ttft_seconds`` p99s are reported,
    not gated (a CPU timing is no speed: the cells of BENCHMARK.json
    judge those).
  * **Prefix hits skip prefill**: resubmitting a batch of long prompts
    must (a) add exactly 0 to the ``serve.prefill_seconds`` count (the
    remainder forwards run under ``serve.prefix_fill_seconds``) and
    (b) reproduce the cold run's greedy outputs bit-exactly; cold and
    hit tokens/s are reported, not gated.
  * **Zero compiles after warmup, BOTH pools**: the whole serving run —
    unified, disaggregated-cold, disaggregated-hit — adds exactly 0
    ``hybridize.cache_misses``; prefill-worker forwards, prefix-hit
    remainder forwards, and cache moves all land on warmed executables.
  * **xlalint-clean**: warmup runs under the lint capture (X004
    donated-must-alias included, for the mover's donated batch cache).
  * **Thread hygiene**: MXNET_THREAD_CHECK=raise stays clean (Makefile
    recipe arms it) and no ``mx-*`` thread survives ``close()``.

``MXNET_COMPILE_CACHE=0`` is forced for the same reason as
tools/decode_smoke.py: the CPU donation guard would otherwise drop
aliasing and make the X004 gate vacuous.

Emits ``disagg_smoke.json`` (gitignored).  FAILS (exit 1) on any gate.
Runs serially (single-core box — never concurrent with tier-1).
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["MXNET_COMPILE_CACHE"] = "0"
os.environ["MXNET_XLA_LINT"] = "1"
# 3 prompt buckets x 2 capacities + the step/mover/grower signatures sit
# right at the default J001 warn limit (8); the grid is intentional here
os.environ.setdefault("MXNET_RETRACE_WARN_LIMIT", "16")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from decode_smoke import _metric, thread_check_gate  # noqa: E402

SLOTS = 4
PREFILL_WORKERS = 2
N_TTFT = 12            # mixed open-loop requests per TTFT phase
MAX_NEW_TTFT = 16      # long enough that unified admissions wait on slots
N_PFX = 6              # long prompts per prefix cold/hit round
PFX_ROUNDS = 3         # disjoint-prompt cold/hit rounds
PFX_PROMPT_LEN = 225   # trie matches 224 (28 blocks), remainder
                       # forwards in the 8-token bucket: a hit skips
                       # ~99% of the prompt compute (cold ~7ms vs hit
                       # ~3.4ms per prompt on CPU), so the tokens/s
                       # gate has a structural margin, not a
                       # statistical one
MAX_NEW_PFX = 2        # short decode: prefill dominates, so the hit
                       # speedup is attributable to skipped prefill


def build_entry(report):
    """Tiny transformer LM DecodeEntry with a long-prompt bucket grid;
    warmup (prefill grid, decode step, mover incl. cross-capacity
    pairs, growth) runs under the lint capture."""
    import mxnet_tpu as mx
    from mxnet_tpu import serve
    from mxnet_tpu.analysis import xla_lint as xl

    mx.random.seed(0)
    lm = mx.gluon.model_zoo.get_model(
        "transformer_lm", vocab_size=64, units=128, hidden_size=512,
        num_heads=4, num_layers=2, max_length=256)
    lm.initialize(mx.init.Xavier())
    t0 = time.perf_counter()
    with xl.capture() as cap:
        entry = serve.DecodeEntry(
            "disagg_lm", lm, slots=SLOTS, prompt_buckets=(8, 16, 32, 232),
            capacity_buckets=(48, 240), max_new_tokens=MAX_NEW_TTFT)
    warm_s = time.perf_counter() - t0
    diags = [d for _f, dg in cap for d in dg]
    report["warmup"] = {
        "seconds": round(warm_s, 2),
        "executables_linted": len(cap),
        "lint_findings": [d.format() for d in diags],
        "lint_ok": not diags,
    }
    return entry, (not diags)


def mixed_prompts(n):
    """Half long (prefill-heavy), half short — every prompt >= 9 tokens
    so a resubmission always crosses the trie's 8-token block floor.
    First token is the request index: no cross-request prefix sharing,
    so the COLD phase is all misses by construction."""
    import numpy as onp

    rs = onp.random.RandomState(11)
    out = []
    for i in range(n):
        length = int(rs.randint(25, 33)) if i % 2 == 0 \
            else int(rs.randint(9, 13))
        p = [i + 1] + [int(x) for x in rs.randint(1, 64, size=length - 1)]
        out.append(p)
    return out


def long_prompts(n, offset, seed):
    """n distinct ``PFX_PROMPT_LEN``-token prompts; first token
    ``offset + i`` keys each prompt so rounds with disjoint offsets
    never share a trie prefix.  Every token must stay < vocab_size
    (64): an out-of-range id makes the jitted embedding gather FILL
    (NaN), poisoning the logits."""
    import numpy as onp

    assert offset + n <= 64
    rs = onp.random.RandomState(seed)
    return [[offset + i]
            + [int(x) for x in rs.randint(1, 64, size=PFX_PROMPT_LEN - 1)]
            for i in range(n)]


def run_phase(srv, prompts, max_new):
    """Open-loop: everything submitted at once; returns (outputs,
    wall_seconds, tokens)."""
    t0 = time.perf_counter()
    futs = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    outs = [f.result(600) for f in futs]
    wall = time.perf_counter() - t0
    return outs, wall, sum(len(o) for o in outs)


def ttft_phases(entry, report):
    """Unified vs disaggregated serving of the same mixed workload:
    same greedy tokens; the TTFT p99s are reported."""
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.serve import DecodeServer

    prompts = mixed_prompts(N_TTFT)

    tel.reset()       # zero the warmup's compile count: post-reset
                      # snapshots measure ONLY post-warmup compiles
    uni = DecodeServer(entry)                     # prefill inline
    uni_outs, uni_wall, _ = run_phase(uni, prompts, MAX_NEW_TTFT)
    uni.close(120.0)
    snap = tel.snapshot()
    uni_ttft = _metric(snap, "serve.ttft_seconds", "p99")
    uni_misses = _metric(snap, "hybridize.cache_misses")

    tel.reset()
    dis = DecodeServer(entry, prefill_workers=PREFILL_WORKERS)
    dis_outs, dis_wall, _ = run_phase(dis, prompts, MAX_NEW_TTFT)
    dis.close(120.0)
    snap = tel.snapshot()
    dis_ttft = _metric(snap, "serve.ttft_seconds", "p99")
    misses = uni_misses + _metric(snap, "hybridize.cache_misses")

    ok_parity = uni_outs == dis_outs            # same greedy tokens
    report["ttft"] = {
        "n_requests": N_TTFT, "max_new_tokens": MAX_NEW_TTFT,
        "slots": SLOTS, "prefill_workers": PREFILL_WORKERS,
        "unified_ttft_p99_ms": round(uni_ttft * 1e3, 3),
        "disagg_ttft_p99_ms": round(dis_ttft * 1e3, 3),
        "unified_wall_s": round(uni_wall, 3),
        "disagg_wall_s": round(dis_wall, 3),
        "output_parity_ok": ok_parity,
    }
    return ok_parity, misses


def prefix_phases(entry, report):
    """Cold vs prefix-hit serving on one disaggregated server: the hit
    rounds must skip ``serve.prefill_seconds`` entirely and match the
    cold outputs bit-exactly (greedy).  The best tokens/s of
    ``PFX_ROUNDS`` disjoint-prompt rounds on each side is reported."""
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.serve import DecodeServer

    # disjoint first-token offsets: no trie sharing ACROSS rounds, so
    # every cold round is all-miss and every hit round all-hit
    sets = [long_prompts(N_PFX, offset=30 + 10 * r, seed=13 + r)
            for r in range(PFX_ROUNDS)]
    tel.reset()
    srv = DecodeServer(entry, prefill_workers=PREFILL_WORKERS)

    cold = [run_phase(srv, s, MAX_NEW_PFX) for s in sets]
    snap = tel.snapshot()
    prefill_cold = _metric(snap, "serve.prefill_seconds", "count")

    hits = [run_phase(srv, s, MAX_NEW_PFX) for s in sets]
    snap = tel.snapshot()
    prefill_delta = _metric(snap, "serve.prefill_seconds",
                            "count") - prefill_cold
    prefix_fills = _metric(snap, "serve.prefix_fill_seconds", "count")
    stats = srv.prefix.stats()
    srv.close(120.0)
    misses = _metric(tel.snapshot(), "hybridize.cache_misses")

    cold_tps = max(tokens / wall for _o, wall, tokens in cold)
    hit_tps = max(tokens / wall for _o, wall, tokens in hits)
    ok_skip = prefill_delta == 0 and prefix_fills == PFX_ROUNDS * N_PFX
    ok_exact = all(h[0] == c[0] for h, c in zip(hits, cold))
    report["prefix"] = {
        "n_requests": N_PFX, "rounds": PFX_ROUNDS,
        "max_new_tokens": MAX_NEW_PFX,
        "cold_tokens_per_s": round(cold_tps, 2),
        "hit_tokens_per_s": round(hit_tps, 2),
        "hit_vs_cold": round(hit_tps / cold_tps, 3),
        "cold_walls_ms": [round(w * 1e3, 1) for _o, w, _t in cold],
        "hit_walls_ms": [round(w * 1e3, 1) for _o, w, _t in hits],
        "prefill_count_delta_on_hits": prefill_delta,
        "prefix_fill_count": prefix_fills,
        "prefill_skipped_ok": ok_skip,
        "bit_exact_ok": ok_exact,
        "cache": stats,
        "prefix_hit_rate": stats["hit_rate"],
    }
    return (ok_skip and ok_exact), misses


def thread_survivor_gate(report):
    """No ``mx-*`` thread (prefill pool included) survives close()."""
    import threading

    left = sorted(t.name for t in threading.enumerate()
                  if t.name.startswith("mx-"))
    report["thread_survivors"] = {"alive": left, "ok": not left}
    return not left


def main():
    report = {"live": False, "platform": "cpu"}
    entry, ok = build_entry(report)
    ok_ttft, misses_a = ttft_phases(entry, report)
    ok_pfx, misses_b = prefix_phases(entry, report)
    misses = misses_a + misses_b
    report["compiles_after_warmup"] = misses
    report["compiles_ok"] = misses == 0
    ok = ok and ok_ttft and ok_pfx and misses == 0
    ok = thread_survivor_gate(report) and ok
    ok = thread_check_gate(report) and ok
    report["ok"] = bool(ok)
    out = os.path.join(ROOT, "disagg_smoke.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    print(f"disagg-smoke: {'OK' if ok else 'FAIL'} -> {out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
