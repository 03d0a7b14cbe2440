"""chip_smoke.py — the quickest proof that mxnet_tpu still starts on the chip.

One process, no child that needs the chip.  It first requires a TPU
(``jax.devices()[0].platform == "tpu"``) and exits non-zero otherwise;
then it drives the two main paths once through the entry points a user
calls, at the full published width of the model each serves:

  * **trainer** — ResNet-50 v1 NHWC, batch 128 at 224x224, bf16 compute,
    ``ShardedTrainer`` (sgd + momentum) on a one-chip mesh, 5 steps on one
    fixed seeded batch.
  * **server** — a ``TransformerLM`` at GPT-2-small widths (vocab 50257,
    768 units, 12 layers, 12 heads, 1024 positions, bf16, random weights
    from a seed) behind ``serve.register_decode`` (8 slots), six
    ``serve.generate`` requests of mixed prompt length, 32 greedy tokens
    each, some concurrent; then the same model with the int8 KV cache.
  * **attention** (between the two, seconds) — the flash forward-with-lse
    and backward kernels every transformer training step takes, at
    BERT-base and 1k-causal shapes, against the reference path.

Every phase checks what came out by the repo's own means (finite, falling
loss; Pallas kernels dispatched and no fallback counted; no compile after
warm-up; the kernel path agrees with the reference path on the chip).  Any
failure propagates as an exception: the exit code is 0 only when every
phase passed, and only then is the last line of stdout

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--chips 4`` runs, and only runs, the data-parallel path: BERT-base
pretraining (batch 32, seq 128, AdamW, bf16) with ``partition="zero1"`` on
a ``dp=4`` mesh, against the same global batch on one chip of the same
process; the last line then says ``"count": 4``.

Run it on the chip through the builder's tool (``chiprun -- python
chip_smoke.py``); here in the sandbox it must, and does, fail.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

LOGIT_RTOL = 0.05      # kernel-vs-reference logits: max|d| <= 5% of max|ref|
LOSS_RTOL = 0.002      # dp=4 zero1 vs one chip, per step (measured: 0.0003)

# The published widths each phase runs at.  Module constants, not options:
# the only reader that changes them is the builder's CPU rehearsal, which
# imports this module and shrinks them (on-chip-measurement guide, s.2).
RESNET = dict(model="resnet50_v1", batch=128, image=224, classes=1000)
GPT2_SMALL = dict(vocab_size=50257, units=768, hidden_size=3072,
                  num_layers=12, num_heads=12, max_length=1024)
SERVE = dict(slots=8, prompt_buckets=(64, 256), capacity_buckets=(256, 512),
             max_new_tokens=32, prompt_lens=(12, 40, 100, 200, 250, 60))
BERT = dict(batch=32, seq=128, npred=20,
            model=dict(vocab_size=30522, max_length=512))
# (name, (B, H, T, d), causal, ragged kv_len): BERT-base's attention at the
# --chips 4 phase's batch, and a 1k-token causal block
ATTENTION = (("bert", (32, 12, 128, 64), False, True),
             ("causal", (8, 12, 1024, 64), True, False))
ATTN_RTOL = 0.02       # kernel vs reference out/dq/dk/dv: 2% of max|ref|


class SmokeFailure(AssertionError):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def _count(snap, name):
    """A counter's value, or a timer's number of observations."""
    m = snap.get(name, {})
    return m.get("count", m.get("value", 0))


class CompileCounter:
    """Every XLA backend compile of the process, straight from jax's own
    monitoring events — independent of the framework's counters."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ trainer
def trainer_phase(dev, compiles, seed):
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    print("[trainer] ResNet-50 v1 NHWC b128 224x224 bf16, sgd+momentum, "
          "one-chip mesh", flush=True)
    tel.reset()
    t_setup = time.perf_counter()
    mx.random.seed(seed)
    batch, image = RESNET["batch"], RESNET["image"]
    net = mx.gluon.model_zoo.get_model(RESNET["model"], layout="NHWC")
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, image, image, 3)))

    def ce(pred, y):
        logp = jax.nn.log_softmax(pred.astype(jnp.float32))
        return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]

    rs = onp.random.RandomState(seed)
    x = onp.asarray(rs.rand(batch, image, image, 3), onp.float32)
    y = onp.asarray(rs.randint(0, RESNET["classes"], size=(batch,)),
                    onp.int32)
    mesh = make_mesh({"dp": -1}, devices=jax.devices()[:1])
    trainer = ShardedTrainer(net, ce, mesh=mesh, optimizer="sgd",
                             learning_rate=0.05, momentum=0.9,
                             compute_dtype=jnp.bfloat16)
    losses = []
    for _ in range(2):                      # set-up: compile + first steps
        losses.append(trainer.step(x, y, block=True))
    setup_s = time.perf_counter() - t_setup
    jit_compiles = _count(tel.snapshot(), "hybridize.compile_seconds")
    xla_compiles = compiles.n
    t_run = time.perf_counter()
    for _ in range(3):
        losses.append(trainer.step(x, y, block=True))
    run_s = time.perf_counter() - t_run
    snap = tel.snapshot()

    print(f"  losses: {[round(l, 4) for l in losses]}")
    print(f"  set-up (build + compile + 2 steps) {setup_s:.1f}s; 3 more "
          f"steps {run_s:.2f}s (not a speed: includes host sync per step)")
    print(f"  kernels.dispatches.opt_arena="
          f"{_count(snap, 'kernels.dispatches.opt_arena')} "
          f"kernels.fallbacks={_count(snap, 'kernels.fallbacks')} "
          f"persistent_cache_hits="
          f"{_count(snap, 'hybridize.persistent_cache_hits')} "
          f"adapter={type(trainer._adapter).__name__} "
          f"peak_bytes_in_use={_peak_bytes(dev)}", flush=True)
    check(all(p.devices() == {dev} for p in trainer.pvals),
          f"all {len(trainer.pvals)} parameters live on {dev}")
    check(all(onp.isfinite(l) for l in losses), "every loss is finite")
    check(losses[4] < losses[0],
          f"loss fell: step 5 {losses[4]:.4f} < step 1 {losses[0]:.4f}")
    check(_count(snap, "kernels.dispatches.opt_arena") >= 1,
          "the flat-arena optimizer kernel was dispatched")
    check(_count(snap, "kernels.fallbacks") == 0,
          "no kernel fell back to a reference path")
    check(_count(snap, "hybridize.compile_seconds") == jit_compiles,
          f"no step compile after step 2 ({jit_compiles} in set-up; XLA "
          f"programs of any size: {xla_compiles} in set-up, "
          f"{compiles.n - xla_compiles} after)")
    return {"setup_s": round(setup_s, 1), "run_s": round(run_s, 2)}


# ------------------------------------------------- training attention kernels
def attention_phase(seed):
    """The kernels every transformer TRAINING step takes — flash forward
    with its saved row lse, and the dq / dk-dv backward — through the
    public op, against the reference path, both on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    from mxnet_tpu import telemetry as tel
    from mxnet_tpu.kernels import registry as kreg
    from mxnet_tpu.ops.attention import flash_attention

    print("[attention] flash forward(+lse) and backward vs the reference "
          "path, bf16", flush=True)
    tel.reset()
    rs = onp.random.RandomState(seed)
    for name, shape, causal, ragged in ATTENTION:
        q, k, v, g = (jnp.asarray(rs.standard_normal(shape), jnp.bfloat16)
                      for _ in range(4))
        kv_len = jnp.asarray(rs.randint(shape[2] // 2, shape[2] + 1,
                                        size=shape[0]), jnp.int32) \
            if ragged else None

        def make_fwd_bwd():
            # a NEW function per kernel mode: selection happens at trace
            # time, and jit caches traces by function identity
            def fwd_bwd(q, k, v, g):
                out, vjp = jax.vjp(
                    lambda q, k, v: flash_attention(
                        q, k, v, causal=causal, kv_valid_length=kv_len),
                    q, k, v)
                return (out,) + vjp(g)

            return jax.jit(fwd_bwd)

        got = {}
        for mode in (None, "off"):
            n0 = _count(tel.snapshot(), "kernels.dispatches")
            with kreg.override(mode):
                got[mode] = [onp.asarray(a.astype(jnp.float32))
                             for a in make_fwd_bwd()(q, k, v, g)]
            ran = _count(tel.snapshot(), "kernels.dispatches") - n0
            check(ran == (0 if mode == "off" else 2),
                  f"{name}: mode {mode!r} traced {ran} kernel dispatches")
        for what, ker, ref in zip(("out", "dq", "dk", "dv"),
                                  got[None], got["off"]):
            tol = ATTN_RTOL * float(onp.abs(ref).max())
            err = float(onp.abs(ker - ref).max())
            check(onp.isfinite(ker).all() and err <= tol,
                  f"{name} {shape}: {what} matches the reference path "
                  f"(max|d|={err:.4g} <= {tol:.4g})")
    snap = tel.snapshot()
    check(_count(snap, "kernels.dispatches.flash_attention") >= 2
          and _count(snap, "kernels.dispatches.flash_attention_bwd") >= 2
          and _count(snap, "kernels.fallbacks") == 0,
          "flash forward and backward kernels dispatched, no fallback")
    return {}


# ------------------------------------------------------------------- server
def _gpt2_small(seed, **extra):
    import jax.numpy as jnp

    import mxnet_tpu as mx

    mx.random.seed(seed)
    lm = mx.gluon.model_zoo.get_model(
        "transformer_lm", dtype=jnp.bfloat16, **GPT2_SMALL, **extra)
    lm.initialize(mx.init.Xavier())
    lm.cast(jnp.bfloat16)
    return lm


def _teacher_forced_logits(lm, tokens, capacity, kernels):
    """(T, V) f32 logits of one eager causal forward over ``tokens`` on
    the chip, traced in THIS thread under the given kernel mode
    (selection is made at trace time and ``override`` is thread-local)."""
    import jax.numpy as jnp
    import numpy as onp

    from mxnet_tpu.kernels import registry as kreg
    from mxnet_tpu.ndarray.ndarray import NDArray

    def nd(a):
        return NDArray(jnp.asarray(a, jnp.int32))

    with kreg.override(kernels):
        logits, _ = lm.forward(nd([tokens]), lm.begin_cache(1, capacity),
                               nd([0]), nd([len(tokens)]))
    return onp.asarray(logits._data[0].astype(jnp.float32))


def server_phase(dev, compiles, seed):
    import numpy as onp

    from mxnet_tpu import serve
    from mxnet_tpu import telemetry as tel

    print("[server] TransformerLM GPT-2-small widths (50257/768/12L/12H, "
          "bf16), 8 slots, prompt buckets (64, 256), capacity buckets "
          "(256, 512)", flush=True)
    tel.reset()
    t_setup = time.perf_counter()
    lm = _gpt2_small(seed)
    n_new = SERVE["max_new_tokens"]
    caps = SERVE["capacity_buckets"]
    serve.register_decode("smoke_lm", lm, slots=SERVE["slots"],
                          prompt_buckets=SERVE["prompt_buckets"],
                          capacity_buckets=caps, max_new_tokens=n_new)
    setup_s = time.perf_counter() - t_setup
    snap0 = tel.snapshot()
    misses0 = _count(snap0, "hybridize.cache_misses")
    xla0 = compiles.n

    rs = onp.random.RandomState(seed)
    # the longest prompt ends past the first capacity bucket (250 + 32 >
    # 256): it grows the cache for every slot
    prompts = [list(map(int, rs.randint(1, GPT2_SMALL["vocab_size"], size=n)))
               for n in SERVE["prompt_lens"]]
    t_run = time.perf_counter()
    # one alone, then five in flight together (generate = submit + result)
    outs = [serve.generate("smoke_lm", prompts[0], timeout=600)]
    futures = [serve.decode_submit("smoke_lm", p) for p in prompts[1:]]
    outs += [f.result(600) for f in futures]
    run_s = time.perf_counter() - t_run
    snap = tel.snapshot()
    print(f"  set-up (build + warm-up grid) {setup_s:.1f}s, "
          f"{misses0} executables; six requests {run_s:.2f}s (not a speed)")
    print(f"  tokens[0][:16]={outs[0][:16]}")
    print(f"  kernels.dispatches.flash_attention_decode="
          f"{_count(snap, 'kernels.dispatches.flash_attention_decode')} "
          f"kernels.fallbacks={_count(snap, 'kernels.fallbacks')} "
          f"cache_grows={_count(snap, 'serve.cache_grows')} "
          f"persistent_cache_hits="
          f"{_count(snap, 'hybridize.persistent_cache_hits')} "
          f"peak_bytes_in_use={_peak_bytes(dev)}", flush=True)
    check(all(len(o) == n_new for o in outs),
          f"all six requests returned {n_new} tokens")
    check(_count(snap, "kernels.dispatches.flash_attention_decode") >= 1,
          "the decode attention kernel was dispatched")
    check(_count(snap, "kernels.fallbacks") == 0,
          "no kernel fell back to a reference path")
    check(_count(snap, "serve.cache_grows") >= 1, "one request grew the cache")
    check(_count(snap, "hybridize.cache_misses") == misses0,
          f"zero executables compiled after warmup() through all six "
          f"requests (eager helper programs XLA built meanwhile: "
          f"{compiles.n - xla0})")

    # kernel path vs reference path, same weights, on the chip: one
    # teacher-forced forward over prompt + the server's first 16 tokens
    exact = 0
    for i in (1, 3):
        seq = prompts[i] + outs[i][:16]
        ref = _teacher_forced_logits(lm, seq, caps[-1], "off")
        ker = _teacher_forced_logits(lm, seq, caps[-1], None)
        tol = LOGIT_RTOL * float(onp.abs(ref).max())
        err = float(onp.abs(ker - ref).max())
        check(onp.isfinite(ker).all() and err <= tol,
              f"request {i}: kernel logits match the reference path "
              f"(max|d|={err:.4g} <= {tol:.4g}, {LOGIT_RTOL:.0%} of "
              f"max|ref|)")
        n0 = len(prompts[i])
        rows = ref[n0 - 1:n0 + 15]          # the rows that chose tokens 1-16
        picked = rows[onp.arange(16), outs[i][:16]]
        exact += int((rows.argmax(-1) == onp.asarray(outs[i][:16])).sum())
        check(bool((picked >= rows.max(-1) - tol).all()),
              f"request {i}: each of the server's first 16 greedy tokens "
              f"is the reference path's argmax (or ties it within the "
              f"tolerance)")
    print(f"  exact greedy-token agreement with the reference path: "
          f"{exact}/32", flush=True)
    serve.shutdown_decode(60.0)

    # ---- the int8 KV-cache entry answers one request with its kernel
    print("[server/int8] same widths, precision='int8' KV cache", flush=True)
    tel.reset()
    t_setup8 = time.perf_counter()
    serve.register_decode("smoke_lm_int8", _gpt2_small(seed),
                          slots=SERVE["slots"],
                          prompt_buckets=SERVE["prompt_buckets"][:1],
                          capacity_buckets=caps[:1],
                          max_new_tokens=n_new, precision="int8")
    setup8_s = time.perf_counter() - t_setup8
    misses8 = _count(tel.snapshot(), "hybridize.cache_misses")
    xla8 = compiles.n
    out8 = serve.generate("smoke_lm_int8", prompts[1], timeout=600)
    snap = tel.snapshot()
    serve.shutdown_decode(60.0)
    agree = sum(a == b for a, b in zip(out8, outs[1]))
    print(f"  set-up {setup8_s:.1f}s; tokens[:16]={out8[:16]}; agreement "
          f"with the bf16-cache answer {agree}/{n_new} (reported, not gated: "
          f"quantization may move a near-tie)")
    check(len(out8) == n_new, f"the int8 request returned {n_new} tokens")
    check(_count(snap, "kernels.dispatches.flash_attention_decode") >= 1
          and _count(snap, "kernels.fallbacks") == 0,
          "the int8 decode kernel was dispatched, no fallback")
    check(_count(snap, "hybridize.cache_misses") == misses8,
          f"zero executables compiled after the int8 warm-up (eager helper "
          f"programs: {compiles.n - xla8})")
    return {"setup_s": round(setup_s + setup8_s, 1), "run_s": round(run_s, 2)}


# ---------------------------------------------------------------- four chips
def _bert_trainer(devices, partition, seed):
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import BERTForPretrain, get_bert
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    batch, seq, npred = BERT["batch"], BERT["seq"], BERT["npred"]
    mx.random.seed(seed)
    net = BERTForPretrain(get_bert("bert_12_768_12", **BERT["model"]))
    net.initialize(mx.init.Xavier())
    vocab = net._vocab_size
    rs = onp.random.RandomState(seed)
    net(mx.np.array(rs.randint(0, vocab, size=(2, seq)).astype("int32")),
        mx.np.array(onp.zeros((2, seq), "int32")),
        mx.np.array(onp.full((2,), seq, "int32")),
        mx.np.array(rs.randint(0, seq, size=(2, npred)).astype("int32")))

    def loss_fn(pred, y):
        mlm_scores, nsp_scores = pred
        mlm_y, nsp_y = y
        lp = jax.nn.log_softmax(mlm_scores.astype(jnp.float32), -1)
        mlm = -jnp.take_along_axis(lp, mlm_y[..., None], -1)[..., 0]
        lp2 = jax.nn.log_softmax(nsp_scores.astype(jnp.float32), -1)
        nsp = -jnp.take_along_axis(lp2, nsp_y[:, None], -1)[:, 0]
        return jnp.mean(mlm, axis=-1) + nsp

    x = (rs.randint(0, vocab, size=(batch, seq)).astype("int32"),
         onp.zeros((batch, seq), "int32"),
         # ragged valid lengths: kv_len rides the flash kernels
         rs.randint(seq // 2, seq + 1, size=(batch,)).astype("int32"),
         rs.randint(0, seq // 2, size=(batch, npred)).astype("int32"))
    y = (rs.randint(0, vocab, size=(batch, npred)).astype("int32"),
         rs.randint(0, 2, size=(batch,)).astype("int32"))
    mesh = make_mesh({"dp": len(devices)}, devices=devices)
    trainer = ShardedTrainer(net, loss_fn, mesh=mesh, optimizer="adamw",
                             learning_rate=1e-4, weight_decay=0.01,
                             compute_dtype=jnp.bfloat16, partition=partition)
    return trainer, x, y


def four_chip_phase(devs, compiles, seed):
    import jax
    import numpy as onp

    from mxnet_tpu import telemetry as tel

    print("[dp4] BERT-base pretrain b32 s128 AdamW bf16: zero1 on dp=4 vs "
          "the same global batch on one chip", flush=True)
    check(len(devs) >= 4, f"four chips attached (found {len(devs)})")
    devs = devs[:4]
    results = {}
    for label, devices, partition in (("dp4", devs, "zero1"),
                                      ("one", devs[:1], "replicated")):
        tel.reset()
        t0 = time.perf_counter()
        trainer, x, y = _bert_trainer(devices, partition, seed)
        losses = [trainer.step(x, y, block=True) for _ in range(3)]
        snap = tel.snapshot()
        print(f"  {label}: losses {[round(l, 5) for l in losses]} in "
              f"{time.perf_counter() - t0:.1f}s incl. compile; "
              f"flash fwd/bwd dispatches "
              f"{_count(snap, 'kernels.dispatches.flash_attention')}/"
              f"{_count(snap, 'kernels.dispatches.flash_attention_bwd')} "
              f"fallbacks {_count(snap, 'kernels.fallbacks')} "
              f"(opt_arena {_count(snap, 'kernels.fallbacks.opt_arena')})",
              flush=True)
        check(all(onp.isfinite(l) for l in losses),
              f"{label}: every loss is finite")
        check(_count(snap, "kernels.dispatches.flash_attention") >= 1
              and _count(snap, "kernels.dispatches.flash_attention_bwd") >= 1,
              f"{label}: flash forward and backward kernels dispatched")
        # AdamW is not arena-fusible: exactly that, counted, nothing else
        check(_count(snap, "kernels.fallbacks")
              == _count(snap, "kernels.fallbacks.opt_arena") == 1,
              f"{label}: the only counted fallback is opt_arena (AdamW is "
              "not arena-fusible: per-parameter adapter)")
        results[label] = (trainer, losses)

    trainer, l4 = results["dp4"]
    _, l1 = results["one"]
    for i, (a, b) in enumerate(zip(l4, l1)):
        check(abs(a - b) <= LOSS_RTOL * abs(b),
              f"step {i + 1}: dp=4 zero1 loss {a:.5f} agrees with one chip "
              f"{b:.5f} within {LOSS_RTOL:.1%}")
    state = [s for s in jax.tree_util.tree_leaves(trainer.opt_state)
             if hasattr(s, "addressable_shards") and s.ndim >= 1
             and s.size >= 1024]
    on = {frozenset(sh.device for sh in s.addressable_shards) for s in state}
    check(state and all(len(d) == 4 for d in on),
          f"{len(state)} optimizer-state leaves each have shards on four "
          "distinct devices")
    sharded = [s for s in state
               if s.addressable_shards[0].data.size * 4 == s.size]
    check(len(sharded) >= len(state) // 2,
          f"{len(sharded)}/{len(state)} optimizer-state leaves hold a "
          "quarter per device (zero1)")
    check(all(len({sh.device for sh in p.addressable_shards}) == 4
              for p in trainer.pvals),
          "every parameter has shards on four distinct devices")
    full_state = sum(s.size * s.dtype.itemsize for s in state)
    for d in devs:
        stats = d.memory_stats() or {}
        print(f"  {d}: bytes_in_use={stats.get('bytes_in_use')} "
              f"peak={stats.get('peak_bytes_in_use')}")
    per_dev = sum(s.addressable_shards[0].data.size * s.dtype.itemsize
                  for s in state)
    check(per_dev * 2 < full_state,
          f"no chip holds the whole optimizer state ({per_dev} of "
          f"{full_state} bytes per device)")
    return {}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp=4 zero1 BERT-base phase and "
                         "its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: jax found no TPU (platform {dev.platform!r}, "
              f"{dev.device_kind}); this script runs on the chip only",
              file=sys.stderr)
        return 1

    import mxnet_tpu as mx
    from mxnet_tpu.jit import cache as jit_cache
    from mxnet_tpu.kernels import registry as kreg
    import jaxlib

    t0 = time.perf_counter()
    compiles = CompileCounter()
    print(f"device: {dev.device_kind} x{len(devs)}; jax {jax.__version__} "
          f"jaxlib {jaxlib.__version__}; kernels mode {kreg.mode()!r}; "
          f"engine {type(mx.engine.get()).__name__}; compile cache "
          f"{jit_cache.ensure_cache()}", flush=True)
    if args.chips == 4:
        phases = {"dp4": four_chip_phase(devs, compiles, args.seed)}
    else:
        phases = {"trainer": trainer_phase(dev, compiles, args.seed),
                  "attention": attention_phase(args.seed),
                  "server": server_phase(dev, compiles, args.seed)}
    print(f"phases: {json.dumps(phases)}; XLA compiles {compiles.n}; total "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
