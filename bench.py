"""Headline benchmarks over the five BASELINE configs.

Prints ONE JSON line. Top-level fields are the headline metric (ResNet-50
training img/s/chip vs the reference's published V100 fp32 b128 number,
BASELINE.md perf.md:243-254); ``extra_metrics`` carries the other BASELINE
configs (BERT-base pretrain samples/sec, LeNet-5, LSTM LM, SSD-ResNet50) —
the reference publishes no numbers for those, so their vs_baseline is null.

Each config times the raw jitted SPMD step (fwd+bwd+optimizer as one XLA
computation) end to end with a device sync; host-side write-backs are
excluded by driving the step function directly, with the param chain
carrying the step-to-step dependency.

Chip only: every row is measured on a TPU.  The parent process never
imports jax (a process that has touched jax holds the chip, and a child
that needs it then fails or hangs); it runs every config in its own
subprocess, one at a time, with a hard timeout.  A child that finds no
TPU fails, the row carries the error, and the script exits non-zero —
there is no CPU row and no stored row in place of a chip row.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def _timed_raw_steps(trainer, xd, yd, n_steps):
    """Drive trainer._step_fn directly; returns seconds for n_steps.

    Dispatch rides the async step pipeline: each step's loss handle goes
    through an engine.InflightQueue (MXNET_MAX_INFLIGHT_STEPS, default 2)
    so the dispatch queue stays bounded exactly like a real training loop
    — the row's telemetry snapshot then carries engine.inflight_steps /
    pipeline.stall_seconds alongside the throughput it explains."""
    import jax.numpy as jnp

    from mxnet_tpu.engine import InflightQueue

    step = trainer._step_fn
    pvals, avals, key = trainer.pvals, trainer.avals, trainer._key
    opt_state, t = trainer.opt_state, trainer._t
    scale = trainer._scale_state
    lr = jnp.float32(trainer.learning_rate)

    xd = trainer._put(xd)
    yd = trainer._put(yd)
    t += 1
    pvals, mutated, opt_state, scale, loss = step(
        pvals, avals, key, opt_state, t, lr, scale, xd, yd)
    float(loss)  # absorb residual compile before the timed region
    inflight = InflightQueue()
    t0 = time.perf_counter()
    for _ in range(n_steps):
        t += 1
        pvals, mutated, opt_state, scale, loss = step(
            pvals, avals, key, opt_state, t, lr, scale, xd, yd)
        inflight.push(loss)
    float(loss)  # scalar D2H read drains the pipeline
    return time.perf_counter() - t0


def _ce(pred, y):
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(pred.astype(jnp.float32))
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]


def _quick():
    """MXNET_BENCH_QUICK=1: run the smoke-scale shapes even on TPU.

    One tiny jitted step per BASELINE config.  Quick rows carry
    ``quick: true`` and a null vs_baseline (tiny shapes are existence
    proof + compile-cache warming, not a comparable throughput).
    """
    return bool(os.environ.get("MXNET_BENCH_QUICK"))


def _row_extras(on_tpu, full, cold, warm=None):
    """Shared row fields for the quick/full split (see _quick).

    ``warmup_secs`` keeps its historical meaning (cold warmup — what a
    fresh process pays) so rows stay comparable across rounds;
    ``warmup_secs_cold``/``warmup_secs_warm`` split it into the
    first-build compile cost vs a rebuild with the persistent
    compilation cache primed (mx.jit, docs/jit.md) — the delta is the
    compile-cost win every later process of this model keeps."""
    return {"quick": True if (on_tpu and not full) else None,
            "warmup_secs": round(cold, 1),
            "warmup_secs_cold": round(cold, 2),
            "warmup_secs_warm": round(warm, 2) if warm is not None else None}


def _xla_cols(trainer, x, y, secs, n_steps):
    """XLA cost-attribution columns (docs/tracing.md): every BENCH row
    reports BOTH the paper-FLOP MFU (external comparison) and the
    XLA-counted utilization of the compiled step — PERF.md: the nominal
    MFU understates what the chip executes (~15% vs ~28% on ResNet-50).
    The numbers come from mx.trace.cost via the trainer (one
    cost_analysis() registry, no ad-hoc lowering here), and publishing
    them also sets the ``trainer.xla_utilization`` gauge the row's
    telemetry snapshot carries."""
    try:
        cols = trainer.publish_xla_utilization((x, y), secs / n_steps)
    except Exception as e:  # a backend without cost_analysis stays a row
        return {"xla_utilization": None, "xla_error": str(e)[-160:]}
    if not cols:
        return {"xla_utilization": None}
    return cols


def _trainer_cols(trainer):
    """Sharding + kernel columns every BENCH/MULTICHIP row carries: the
    mesh shape, the weight-update partition (select zero1 for a whole run
    via MXNET_PARTITION=zero1 — ShardedTrainer's env default), the
    measured per-device optimizer-state bytes, and the kernels config
    (MXNET_KERNELS mode + whether THIS trainer runs the flat-arena
    optimizer), so kernel-on vs kernel-off runs stay distinguishable in
    the perf trajectory (docs/sharding.md, docs/kernels.md).  ``pp``
    (pipeline-axis degree, MXNET_PP) and ``overlap`` (bucketed
    collective/compute overlap, MXNET_OVERLAP=1 + zero1) mark the
    latency-hiding rows the same way."""
    from mxnet_tpu import kernels as _kern
    from mxnet_tpu.parallel.trainer import (_ArenaOptAdapter,
                                            _OverlapOptAdapter)

    return {"mesh_shape": dict(trainer.mesh.shape),
            "partition": trainer.partition,
            "pp": trainer.mesh.shape.get("pp", 1),
            "overlap": isinstance(trainer._adapter, _OverlapOptAdapter),
            "opt_state_bytes_per_device":
                trainer.opt_state_bytes_per_device,
            "kernels": _kern.mode(),
            "fused_opt_arena": isinstance(trainer._adapter,
                                          _ArenaOptAdapter)}


def _timed_warmup(make_trainer, x, y, n_steps=2):
    """Cold-vs-warm warmup measurement.

    Builds the trainer twice (fresh jit functions each time) and times
    ``n_steps`` warmup steps for each.  The second build's XLA compiles
    hit the persistent compilation cache the first build filled — the
    cache mx.jit arms lazily (``JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache``; docs/jit.md) — so ``warm`` measures
    trace + executable deserialization
    only.  Returns ``(trainer, cold_secs, warm_secs)`` with the WARM
    trainer ready for the timed region (its dispatch cache is seeded by
    its own warmup steps)."""
    trainer = make_trainer()
    t0 = time.perf_counter()
    for _ in range(n_steps):
        trainer.step(x, y)
    cold = time.perf_counter() - t0
    trainer = make_trainer()
    t0 = time.perf_counter()
    for _ in range(n_steps):
        trainer.step(x, y)
    warm = time.perf_counter() - t0
    return trainer, cold, warm


def bench_resnet50(on_tpu):
    """BASELINE config #2: ResNet-50 training img/s (vs V100 fp32 b128)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    # MXNET_BENCH_BATCH overrides the per-chip batch (PERF.md lever: b256
    # amortizes the fixed-cost stem/tail stages, MLPerf-style).  It is a
    # TPU lever only — the CPU smoke must keep its tiny shapes even when
    # the override is exported in the environment.
    full = on_tpu and not _quick()
    try:
        override = int(os.environ.get("MXNET_BENCH_BATCH") or 0)
    except ValueError:
        override = 0
    batch = override if (override > 0 and full) else (128 if full else 8)
    image = 224 if full else 64
    # channel-last everywhere: channels ride the 128-lane minor tile, so
    # convs feed the MXU without layout-transpose pairs (see ops/nn.py).
    # The CPU smoke certifies the SAME graph the TPU row benches (round-4
    # verdict weak #4: an NCHW smoke re-certifies the wrong layout).
    layout = "NHWC"

    mx.random.seed(0)
    # MXNET_BENCH_STEM=s2d selects the space-to-depth stem variant
    # (MXU-friendly 3->12 channel packing; PERF.md) — a model variant, so
    # opt-in; the default row stays the reference-architecture number
    stem = os.environ.get("MXNET_BENCH_STEM", "default")
    # MXNET_BENCH_FUSED_BN=1 builds the fused BatchNormReLU zoo variant
    # (single-pass Pallas BN-stat+relu kernels when MXNET_KERNELS is
    # active, docs/kernels.md) — like the stem, a model variant, opt-in
    fused_bn = os.environ.get("MXNET_BENCH_FUSED_BN", "0") == "1"
    net = mx.gluon.model_zoo.get_model("resnet50_v1", layout=layout,
                                       stem_type=stem,
                                       fused_bn_relu=fused_bn)
    net.initialize(mx.init.Xavier())
    shape = ((2, image, image, 3) if layout == "NHWC"
             else (2, 3, image, image))
    net(mx.np.zeros(shape))

    mesh = make_mesh({"dp": -1}, devices=jax.devices()[:1])
    # low-precision compute on the MXU (master params fp32) — bf16 by
    # default (the TPU-native analog of the reference's fp16 rows), fp16
    # with in-step dynamic loss scaling via MXNET_BENCH_DTYPE=fp16; the
    # fp32 baseline row stays the comparison denominator, conservatively.
    dt = os.environ.get("MXNET_BENCH_DTYPE", "bf16").lower()
    dtypes = {"bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
              "fp16": jnp.float16, "float16": jnp.float16,
              "fp32": None, "float32": None}
    if dt not in dtypes:
        raise SystemExit(f"MXNET_BENCH_DTYPE={dt!r} invalid; "
                         f"choose from {sorted(dtypes)}")
    compute = dtypes[dt]
    rs = onp.random.RandomState(0)
    xshape = ((batch, image, image, 3) if layout == "NHWC"
              else (batch, 3, image, image))
    x = onp.asarray(rs.rand(*xshape), onp.float32)
    y = onp.asarray(rs.randint(0, 1000, size=(batch,)), onp.int32)
    # bf16 compute in the smoke too — same graph as the TPU row
    trainer, cold, warm = _timed_warmup(
        lambda: ShardedTrainer(net, _ce, mesh=mesh, optimizer="sgd",
                               learning_rate=0.05, momentum=0.9,
                               compute_dtype=compute), x, y)
    n_steps = 20 if full else 3
    secs = _timed_raw_steps(trainer, x, y, n_steps)
    ips = batch * n_steps / secs
    # MFU: ResNet-50 fwd ≈ 4.1 GFLOP/img @224², train ≈ 3× fwd, against
    # the chip's bf16 peak from the one peaks table (an unknown device
    # kind raises there rather than reporting a wrong MFU)
    from mxnet_tpu.trace.cost import peak_flops

    mfu = (ips * 3 * 4.089e9 / peak_flops()) if full else None
    return {"metric": "resnet50_train_imgs_per_sec_per_chip",
            "value": round(ips, 2), "unit": "images/sec",
            "vs_baseline": round(ips / 363.69, 4) if full else None,
            "layout": layout, "dtype": dt if compute is not None else "fp32",
            "batch": batch,
            "mfu": round(mfu, 4) if mfu is not None else None,
            **_xla_cols(trainer, x, y, secs, n_steps),
            **_trainer_cols(trainer),
            **_row_extras(on_tpu, full, cold, warm)}


def bench_bert_base(on_tpu):
    """BASELINE config #3: BERT-base pretraining samples/sec (MLM+NSP,
    seq 128, masked positions 20; ref example/ ... no published number)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.bert import BERTForPretrain, get_bert
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    full = on_tpu and not _quick()
    if full:
        batch, seq, npred = 32, 128, 20
        bert = get_bert("bert_12_768_12", vocab_size=30522, max_length=512)
    else:
        batch, seq, npred = 4, 32, 4
        bert = get_bert("bert_12_768_12", vocab_size=1000, max_length=64,
                        num_layers=2, units=64, hidden_size=128, num_heads=2)
    mx.random.seed(0)
    net = BERTForPretrain(bert)
    net.initialize(mx.init.Xavier())
    vocab = net._vocab_size

    rs = onp.random.RandomState(0)
    tokens = rs.randint(0, vocab, size=(2, seq)).astype("int32")
    segs = onp.zeros((2, seq), "int32")
    vlen = onp.full((2,), seq, "int32")
    pos = rs.randint(0, seq, size=(2, npred)).astype("int32")
    net(mx.np.array(tokens), mx.np.array(segs), mx.np.array(vlen),
        mx.np.array(pos))

    def loss_fn(pred, y):
        mlm_scores, nsp_scores = pred
        mlm_y, nsp_y = y
        lp = jax.nn.log_softmax(mlm_scores.astype(jnp.float32), -1)
        mlm = -jnp.take_along_axis(lp, mlm_y[..., None], -1)[..., 0]
        lp2 = jax.nn.log_softmax(nsp_scores.astype(jnp.float32), -1)
        nsp = -jnp.take_along_axis(lp2, nsp_y[:, None], -1)[:, 0]
        return jnp.mean(mlm, axis=-1) + nsp

    mesh = make_mesh({"dp": -1}, devices=jax.devices()[:1])
    x = (rs.randint(0, vocab, size=(batch, seq)).astype("int32"),
         onp.zeros((batch, seq), "int32"),
         onp.full((batch,), seq, "int32"),
         rs.randint(0, seq, size=(batch, npred)).astype("int32"))
    y = (rs.randint(0, vocab, size=(batch, npred)).astype("int32"),
         rs.randint(0, 2, size=(batch,)).astype("int32"))
    # bf16 on CPU too: the smoke certifies the SAME graph the TPU row runs
    trainer, cold, warm = _timed_warmup(
        lambda: ShardedTrainer(net, loss_fn, mesh=mesh, optimizer="adamw",
                               learning_rate=1e-4, weight_decay=0.01,
                               compute_dtype=jnp.bfloat16), x, y)
    n_steps = 20 if full else 3
    secs = _timed_raw_steps(trainer, x, y, n_steps)
    return {"metric": "bert_base_pretrain_samples_per_sec_per_chip",
            "value": round(batch * n_steps / secs, 2), "unit": "samples/sec",
            "vs_baseline": None, "seq_len": seq,
            **_xla_cols(trainer, x, y, secs, n_steps),
            **_trainer_cols(trainer),
            **_row_extras(on_tpu, full, cold, warm)}


def bench_lenet(on_tpu):
    """BASELINE config #1: LeNet-5 training img/s."""
    import jax
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    full = on_tpu and not _quick()
    batch = 1024 if full else 64
    mx.random.seed(0)
    net = mx.gluon.model_zoo.get_model("lenet")
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 1, 28, 28)))
    mesh = make_mesh({"dp": -1}, devices=jax.devices()[:1])
    rs = onp.random.RandomState(0)
    x = onp.asarray(rs.rand(batch, 1, 28, 28), onp.float32)
    y = onp.asarray(rs.randint(0, 10, size=(batch,)), onp.int32)
    trainer, cold, warm = _timed_warmup(
        lambda: ShardedTrainer(net, _ce, mesh=mesh, optimizer="sgd",
                               learning_rate=0.05, momentum=0.9), x, y)
    n_steps = 30 if full else 5
    secs = _timed_raw_steps(trainer, x, y, n_steps)
    return {"metric": "lenet_train_imgs_per_sec_per_chip",
            "value": round(batch * n_steps / secs, 2), "unit": "images/sec",
            "vs_baseline": None,
            **_xla_cols(trainer, x, y, secs, n_steps),
            **_trainer_cols(trainer),
            **_row_extras(on_tpu, full, cold, warm)}


def bench_lstm_lm(on_tpu):
    """BASELINE config #4: word-level LSTM LM (PTB-style: 2x650, seq 35)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn, rnn
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    full = on_tpu and not _quick()
    if full:
        vocab, embed, hidden, layers, batch, seq = 10000, 650, 650, 2, 64, 35
    else:
        vocab, embed, hidden, layers, batch, seq = 200, 32, 32, 1, 8, 12

    class LSTMLM(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.embedding = nn.Embedding(vocab, embed)
            self.lstm = rnn.LSTM(hidden, num_layers=layers)
            self.decoder = nn.Dense(vocab, flatten=False)

        def forward(self, x):          # (B, T) tokens
            e = self.embedding(x).transpose(1, 0, 2)   # TNC for the RNN
            out = self.lstm(e)                          # (T, B, H)
            return self.decoder(out).transpose(1, 0, 2)  # (B, T, V)

    mx.random.seed(0)
    net = LSTMLM()
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, seq), dtype="int32"))

    def loss_fn(pred, y):
        lp = jax.nn.log_softmax(pred.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(lp, y[..., None], -1)[..., 0]
        return jnp.mean(nll, axis=-1)

    mesh = make_mesh({"dp": -1}, devices=jax.devices()[:1])
    rs = onp.random.RandomState(0)
    x = rs.randint(0, vocab, size=(batch, seq)).astype("int32")
    y = rs.randint(0, vocab, size=(batch, seq)).astype("int32")
    trainer, cold, warm = _timed_warmup(
        lambda: ShardedTrainer(net, loss_fn, mesh=mesh, optimizer="sgd",
                               learning_rate=1.0), x, y)
    n_steps = 20 if full else 3
    secs = _timed_raw_steps(trainer, x, y, n_steps)
    toks = batch * seq * n_steps / secs
    return {"metric": "lstm_lm_tokens_per_sec_per_chip",
            "value": round(toks, 2), "unit": "tokens/sec",
            "vs_baseline": None, "samples_per_sec": round(toks / seq, 2),
            **_xla_cols(trainer, x, y, secs, n_steps),
            **_trainer_cols(trainer),
            **_row_extras(on_tpu, full, cold, warm)}


def bench_ssd(on_tpu):
    """BASELINE config #5: SSD-ResNet50 training img/s. Targets
    (multibox_target) are precomputed for the synthetic labels — anchors
    are static per input shape — so the timed step is the same one-jit
    fwd+bwd+update as the other configs."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.ssd import training_targets
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    mx.random.seed(0)
    full = on_tpu and not _quick()
    if full:
        batch, image = 32, 512
        net = mx.gluon.model_zoo.get_model("ssd_512_resnet50_v1", classes=20)
    else:
        batch, image = 2, 64
        from mxnet_tpu.gluon.model_zoo.ssd import SSD
        from mxnet_tpu.gluon import nn

        backbone = nn.HybridSequential()
        backbone.add(nn.Conv2D(8, 3, strides=2, padding=1,
                               activation="relu"),
                     nn.Conv2D(16, 3, strides=2, padding=1,
                               activation="relu"))
        net = SSD([backbone], num_classes=3,
                  sizes=[[0.2, 0.272]] * 4, ratios=[[1, 2, 0.5]] * 4)
    net.initialize(mx.init.Xavier())
    cls_p, box_p, anchors = net(mx.np.zeros((2, 3, image, image)))

    rs = onp.random.RandomState(0)
    x = onp.asarray(rs.rand(batch, 3, image, image), onp.float32)
    # synthetic ground truth: one box per image, padded label rows = -1
    ncls = net.num_classes
    labels = onp.full((batch, 3, 5), -1.0, "float32")
    labels[:, 0, 0] = rs.randint(0, ncls, size=batch)
    xy = rs.rand(batch, 2) * 0.5
    labels[:, 0, 1:3] = xy
    labels[:, 0, 3:5] = xy + 0.3
    bt, bm, ct = training_targets(anchors, mx.np.array(labels))
    targets = (ct._data, bt._data, bm._data)

    def loss_fn(pred, y):
        cls_preds, box_preds, _anchors = pred
        cls_t, box_t, box_m = y
        lp = jax.nn.log_softmax(cls_preds.astype(jnp.float32), -1)
        cls_l = -jnp.take_along_axis(
            lp, cls_t[..., None].astype(jnp.int32), -1)[..., 0]
        box_l = jnp.abs(box_preds.astype(jnp.float32) - box_t) * box_m
        return jnp.mean(cls_l, axis=-1) + jnp.mean(box_l, axis=-1)

    mesh = make_mesh({"dp": -1}, devices=jax.devices()[:1])
    # bf16 on CPU too: the smoke certifies the SAME graph the TPU row runs
    trainer, cold, warm = _timed_warmup(
        lambda: ShardedTrainer(net, loss_fn, mesh=mesh, optimizer="sgd",
                               learning_rate=0.01, momentum=0.9,
                               compute_dtype=jnp.bfloat16), x, targets)
    n_steps = 10 if full else 2
    secs = _timed_raw_steps(trainer, x, targets, n_steps)
    return {"metric": "ssd_resnet50_train_imgs_per_sec_per_chip",
            "value": round(batch * n_steps / secs, 2), "unit": "images/sec",
            "vs_baseline": None, "image_size": image,
            **_xla_cols(trainer, x, targets, secs, n_steps),
            **_trainer_cols(trainer),
            **_row_extras(on_tpu, full, cold, warm)}


_CONFIGS = {
    "resnet50": bench_resnet50,
    "bert_base": bench_bert_base,
    "lenet": bench_lenet,
    "lstm_lm": bench_lstm_lm,
    "ssd": bench_ssd,
}

# canonical metric names, so failure rows keep the same identity the
# success path emits (artifact consumers key on these)
_METRIC_NAMES = {
    "resnet50": "resnet50_train_imgs_per_sec_per_chip",
    "bert_base": "bert_base_pretrain_samples_per_sec_per_chip",
    "lenet": "lenet_train_imgs_per_sec_per_chip",
    "lstm_lm": "lstm_lm_tokens_per_sec_per_chip",
    "ssd": "ssd_resnet50_train_imgs_per_sec_per_chip",
}

def _last_json_or_error(stdout, stderr, returncode, metric):
    """Parse the last JSON line of a child's stdout, else an error row."""
    for line in reversed(stdout.splitlines()):
        try:
            return json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
    tail = (stderr.strip().splitlines() or [f"rc={returncode}"])[-1]
    return {"metric": metric, "value": None, "error": tail}


def _run_child(argv, env, timeout, metric):
    """Run self with ``argv`` in a subprocess; never raises."""
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + argv,
            timeout=timeout, capture_output=True, text=True, env=env)
    except subprocess.TimeoutExpired:
        return {"metric": metric, "value": None,
                "error": f"timed out after {timeout}s"}
    return _last_json_or_error(out.stdout, out.stderr, out.returncode,
                               metric)


def _run_config(name, env, timeout):
    return _run_child(["--config", name], env, timeout,
                      _METRIC_NAMES[name])


def _require_tpu():
    """Child-side gate: a bench row is a chip measurement or nothing."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        raise SystemExit(f"bench.py measures on a TPU only; jax found "
                         f"{d.platform!r} ({d.device_kind})")
    return d.platform


def _telemetry_snapshot():
    """The run's telemetry aggregates (None when disabled/empty/broken) —
    each BENCH row carries the evidence needed to EXPLAIN its number:
    compile seconds, input wait, sync stalls, collective bytes."""
    try:
        from mxnet_tpu import telemetry

        return telemetry.snapshot() or None
    except Exception:
        return None


def _child(name):
    """Child mode: run one config in-process, print its JSON line."""
    platform = _require_tpu()
    row = _CONFIGS[name](True)
    row["platform"] = platform
    row["ts"] = round(time.time(), 1)
    row["telemetry"] = _telemetry_snapshot()
    print(json.dumps(row))


# ---------------------------------------------------------------------------
# inference ("scoring") mode — the reference's headline tables are mostly
# inference (BASELINE.md perf.md:72-211, measured by
# example/image-classification/benchmark_score.py).  `bench.py --infer`
# sweeps the published configs; each row reports img/s and vs_baseline
# against the best published V100 number for that model+batch (fp16 rows
# compared against our bf16, fp32 rows against fp32-dominant models where
# the reference never published fp16).
# ---------------------------------------------------------------------------

# name -> (zoo model, batch, image, V100 baseline img/s, baseline precision)
_INFER_CONFIGS = {
    "resnet50_b32": ("resnet50_v1", 32, 224, 2085.51, "fp16"),
    "resnet50_b128": ("resnet50_v1", 128, 224, 2355.04, "fp16"),
    "resnet152_b32": ("resnet152_v1", 32, 224, 887.34, "fp16"),
    "inceptionv3_b32": ("inceptionv3", 32, 299, 1512.08, "fp16"),
    "vgg16_b32": ("vgg16", 32, 224, 708.43, "fp32"),
    "alexnet_b32": ("alexnet", 32, 224, 7906.09, "fp32"),
}


def _infer_child(name):
    """One scoring config: forward-only jit over the param pytree."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.parallel.trainer import _functional_apply

    model, batch, image, baseline, base_prec = _INFER_CONFIGS[name]
    on_tpu = _require_tpu() == "tpu"
    full = on_tpu and not _quick()
    if not full:
        # inception's tail pooling is sized for exactly 299^2 inputs
        batch, image = (1, 299) if model == "inceptionv3" else (2, 64)

    mx.random.seed(0)
    # all swept models thread layout; channel-last keeps convs on the
    # MXU minor tile without transpose pairs (PERF.md)
    layout = "NHWC" if on_tpu else "NCHW"
    net = mx.gluon.model_zoo.get_model(model, layout=layout)
    net.initialize(mx.init.Xavier())
    shape = ((2, image, image, 3) if layout == "NHWC"
             else (2, 3, image, image))
    net(mx.np.zeros(shape))

    names = sorted(n for n, p in net.collect_params().items()
                   if p._data is not None)
    fn, _arrs, _holder = _functional_apply(net, names, training=False)
    params = net.collect_params()
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    pvals = [params[n].data()._data.astype(dt)
             if jnp.issubdtype(params[n].data()._data.dtype,
                               jnp.floating)
             else params[n].data()._data for n in names]

    def make_score():
        @jax.jit
        def score(pvals, x):
            outs, _mut = fn(pvals, x)
            # scoring reads one scalar per batch to force materialization
            return jnp.sum(outs[0].astype(jnp.float32))

        return score

    from mxnet_tpu.jit import cache as jit_cache

    jit_cache.ensure_cache()  # direct --infer-child runs arm the cache too
    rs = onp.random.RandomState(0)
    xshape = ((batch, image, image, 3) if layout == "NHWC"
              else (batch, 3, image, image))
    x = jnp.asarray(rs.rand(*xshape).astype(onp.float32)).astype(dt)
    tw = time.perf_counter()
    score = make_score()
    float(score(pvals, x))                      # compile (cold)
    cold = time.perf_counter() - tw
    tw = time.perf_counter()
    score = make_score()                        # fresh jit, same HLO:
    float(score(pvals, x))                      # persistent-cache hit
    warm = time.perf_counter() - tw
    n_steps = 50 if full else 3
    t0 = time.perf_counter()
    acc = None
    for _ in range(n_steps):
        acc = score(pvals, x)
    float(acc)                                  # D2H read drains pipeline
    dtime = time.perf_counter() - t0
    ips = batch * n_steps / dtime
    row = {
        "metric": f"infer_{name}_imgs_per_sec", "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(ips / baseline, 4) if full else None,
        "baseline_precision": base_prec, "batch": batch,
        "platform": "tpu" if on_tpu else "cpu",
        "ts": round(time.time(), 1),
        **_row_extras(on_tpu, full, cold, warm)}
    row["telemetry"] = _telemetry_snapshot()
    print(json.dumps(row))


def _infer_sweep():
    """Parent: run each scoring config in a subprocess, one at a time.

    Every row is printed the moment it lands; a config that fails (no
    TPU, a crash, a timeout) is an error row and a non-zero exit.
    """
    rows = []
    for name in _INFER_CONFIGS:
        row = _run_child(["--infer-child", name], dict(os.environ), 1100,
                         f"infer_{name}_imgs_per_sec")
        rows.append(row)
        print(json.dumps(row), flush=True)
    head = rows[0] if rows else {}
    out = {"metric": "inference_sweep",
           "value": head.get("value"), "unit": "images/sec",
           "vs_baseline": head.get("vs_baseline"), "rows": rows}
    print(json.dumps(out))
    return _rc(rows)


# ---------------------------------------------------------------------------
# serving mode — the inference tier's perf trajectory (docs/serving.md).
# `bench.py --serve` reuses the serve-smoke measurement core (LeNet +
# tiny-BERT registry, mixed ragged load) and reports a bench-shaped row:
# e2e p50/p99 latency, batched throughput, batched-vs-sequential speedup,
# and batch occupancy.
# ---------------------------------------------------------------------------

def _serve_child():
    """One serving measurement in-process; prints its row."""
    import jax

    # initialize the backend BEFORE importing serve_smoke: its module
    # level setdefaults JAX_PLATFORMS=cpu (standalone-smoke safety),
    # which would silently force a TPU child onto CPU if it ran first
    platform = _require_tpu()
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import serve_smoke as _sm
    report = {}
    reg = _sm.build_registry()
    ok = _sm.load_phases(reg, report)
    # ONE row schema, owned by serve_smoke (drift here would desync the
    # bench row from the smoke's report["row"])
    row = _sm.make_row(report["load"], platform=platform)
    row.update(vs_baseline=None, gates_ok=bool(ok))
    row["telemetry"] = _telemetry_snapshot()
    print(json.dumps(row))


def _serve_sweep():
    """Parent: run the serving row in a killable subprocess."""
    row = _run_child(["--serve-child"], dict(os.environ), 1800,
                     "serve_mixed_p99_ms")
    print(json.dumps(row))
    return _rc([row])


# ---------------------------------------------------------------------------
# decode mode — the generative tier's perf trajectory (docs/serving.md
# "Decode lifecycle").  `bench.py --decode` reuses the decode-smoke
# measurement core (tiny transformer LM, token-level continuous batching
# over cache slots) and reports a bench-shaped row: batched tokens/s,
# batched-vs-sequential speedup, per-token decode-step p50/p99.
# ---------------------------------------------------------------------------

def _decode_child():
    """One decode measurement in-process; prints its row."""
    import jax

    # initialize the backend BEFORE importing decode_smoke: its module
    # level setdefaults JAX_PLATFORMS=cpu (standalone-smoke safety),
    # which would silently force a TPU child onto CPU if it ran first
    platform = _require_tpu()
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import decode_smoke as _dsm
    report = {}
    entry, ok = _dsm.build_entry(report)
    ok = _dsm.donation_gate(entry, report) and ok
    ok = _dsm.decode_phases(entry, report) and ok
    ok = _dsm.int8_phase(report) and ok
    # ONE row schema, owned by decode_smoke (drift here would desync the
    # bench row from the smoke's report["row"])
    row = _dsm.make_row(report["decode"], platform=platform,
                        int8=report.get("int8"))
    row.update(vs_baseline=None, gates_ok=bool(ok))
    row["telemetry"] = _telemetry_snapshot()
    print(json.dumps(row))


def _decode_sweep():
    """Parent: run the decode row in a killable subprocess."""
    row = _run_child(["--decode-child"], dict(os.environ), 1800,
                     "decode_tokens_per_s")
    print(json.dumps(row))
    return _rc([row])


# ---------------------------------------------------------------------------
# fleet mode — the network-edge + replica-fleet trajectory (docs/serving.md
# "Network edge + fleet").  `bench.py --fleet` reuses the fleet-smoke
# measurement core (N worker replicas behind the router, persistent
# compile cache, SIGKILL-under-load recovery) and reports a bench-shaped
# row: routed RPS, routed p99, streamed tokens/s, and kill->ready
# recovery seconds.
# ---------------------------------------------------------------------------

def _fleet_child():
    """One fleet measurement in-process; prints its row."""
    import jax

    # initialize the backend BEFORE importing fleet_smoke: its module
    # level setdefaults JAX_PLATFORMS=cpu (standalone-smoke safety),
    # which would silently force a TPU child onto CPU if it ran first
    platform = _require_tpu()
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import fleet_smoke as _fsm
    report = {}
    from mxnet_tpu.jit import cache as jit_cache

    cache_dir = jit_cache.cache_dir()
    fleet, ok = _fsm.boot_fleet(report, cache_dir)
    try:
        ok = _fsm.throughput_phase(fleet, report) and ok
        ok = _fsm.kill_phase(fleet, report) and ok
        ok = _fsm.streaming_phase(fleet, report, cache_dir) and ok
    finally:
        fleet.close()
        from mxnet_tpu import serve

        serve.shutdown_decode(60.0)
    # ONE row schema, owned by fleet_smoke (drift here would desync the
    # bench row from the smoke's report["row"])
    row = _fsm.make_row(report, platform=platform)
    row.update(vs_baseline=None, gates_ok=bool(ok))
    row["telemetry"] = _telemetry_snapshot()
    print(json.dumps(row))


def _fleet_sweep():
    """Parent: run the fleet row in a killable subprocess."""
    row = _run_child(["--fleet-child"], dict(os.environ), 2400,
                     "fleet_rps")
    print(json.dumps(row))
    return _rc([row])


# ---------------------------------------------------------------------------
# multichip scaling mode (BASELINE target: 8->64-chip scaling efficiency).
# `bench.py --multichip n` measures the ResNet + BERT SPMD step on a 1-device
# and an n-device dp mesh and reports per-device throughput + scaling
# efficiency, on n real chips of one host.
# Reference tooling analogue: tools/bandwidth/measure.py.
# ---------------------------------------------------------------------------

def _mc_measure(config, ndev, on_tpu):
    """Per-device img|samples/sec for ``config`` on an ndev dp mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    mx.random.seed(0)
    # MXNET_PP=k carves a k-deep pipeline ('pp') axis out of the bench
    # mesh for the resnet config (GPipe path, docs/sharding.md
    # "Pipeline axis"); bert keeps pure dp — tuple-input nets cannot
    # pipeline.  MXNET_OVERLAP=1 (+ MXNET_PARTITION=zero1) selects the
    # bucketed overlap update inside ShardedTrainer itself; both land
    # in the row via _trainer_cols.
    pp = 0
    if config == "resnet":
        try:
            pp = int(os.environ.get("MXNET_PP") or 0)
        except ValueError:
            pp = 0
    if pp > 1 and ndev % pp == 0:
        mesh = make_mesh({"dp": -1, "pp": pp},
                         devices=jax.devices()[:ndev])
    else:
        mesh = make_mesh({"dp": -1}, devices=jax.devices()[:ndev])
    rs = onp.random.RandomState(0)
    if config == "resnet":
        per = 64 if on_tpu else 4
        image = 224 if on_tpu else 32
        layout = "NHWC" if on_tpu else "NCHW"
        name = "resnet50_v1" if on_tpu else "resnet18_v1"
        net = mx.gluon.model_zoo.get_model(name, layout=layout)
        net.initialize(mx.init.Xavier())
        shape = ((2, image, image, 3) if layout == "NHWC"
                 else (2, 3, image, image))
        net(mx.np.zeros(shape))
        trainer = ShardedTrainer(
            net, _ce, mesh=mesh, optimizer="sgd", learning_rate=0.05,
            momentum=0.9,
            compute_dtype=jnp.bfloat16 if on_tpu else None)
        batch = per * ndev
        xshape = ((batch, image, image, 3) if layout == "NHWC"
                  else (batch, 3, image, image))
        x = onp.asarray(rs.rand(*xshape), onp.float32)
        y = onp.asarray(rs.randint(0, 1000, size=(batch,)), onp.int32)
    elif config == "bert":
        from mxnet_tpu.gluon.model_zoo.bert import BERTForPretrain, get_bert

        if on_tpu:
            per, seq, npred = 8, 128, 20
            bert = get_bert("bert_12_768_12", vocab_size=30522,
                            max_length=512)
        else:
            per, seq, npred = 2, 32, 4
            bert = get_bert("bert_12_768_12", vocab_size=1000, max_length=64,
                            num_layers=2, units=64, hidden_size=128,
                            num_heads=2)
        net = BERTForPretrain(bert)
        net.initialize(mx.init.Xavier())
        vocab = net._vocab_size
        tk = rs.randint(0, vocab, size=(2, seq)).astype("int32")
        net(mx.np.array(tk), mx.np.array(onp.zeros((2, seq), "int32")),
            mx.np.array(onp.full((2,), seq, "int32")),
            mx.np.array(rs.randint(0, seq, size=(2, npred)).astype("int32")))

        def loss_fn(pred, yy):
            mlm_scores, nsp_scores = pred
            mlm_y, nsp_y = yy
            lp = jax.nn.log_softmax(mlm_scores.astype(jnp.float32), -1)
            mlm = -jnp.take_along_axis(lp, mlm_y[..., None], -1)[..., 0]
            lp2 = jax.nn.log_softmax(nsp_scores.astype(jnp.float32), -1)
            nsp = -jnp.take_along_axis(lp2, nsp_y[:, None], -1)[:, 0]
            return jnp.mean(mlm, axis=-1) + nsp

        trainer = ShardedTrainer(
            net, loss_fn, mesh=mesh, optimizer="adamw", learning_rate=1e-4,
            weight_decay=0.01,
            compute_dtype=jnp.bfloat16 if on_tpu else None)
        batch = per * ndev
        x = (rs.randint(0, vocab, size=(batch, seq)).astype("int32"),
             onp.zeros((batch, seq), "int32"),
             onp.full((batch,), seq, "int32"),
             rs.randint(0, seq, size=(batch, npred)).astype("int32"))
        y = (rs.randint(0, vocab, size=(batch, npred)).astype("int32"),
             rs.randint(0, 2, size=(batch,)).astype("int32"))
    else:
        raise ValueError(config)
    for _ in range(2):
        trainer.step(x, y)
    n_steps = 20 if on_tpu else 3
    dt = _timed_raw_steps(trainer, x, y, n_steps)
    return batch * n_steps / dt / ndev, per, _trainer_cols(trainer)


def _multichip_child(n):
    import jax

    plat = _require_tpu()
    on_tpu = True
    if len(jax.devices()) < n:
        print(json.dumps({"metric": "multichip_scaling", "value": None,
                          "error": f"need {n} devices, have "
                                   f"{len(jax.devices())}"}))
        return 1
    configs = {}
    for config in ("resnet", "bert"):
        one, per, _cols1 = _mc_measure(config, 1, on_tpu)
        many, _, cols = _mc_measure(config, n, on_tpu)
        configs[config] = {
            "per_device_batch": per,
            "ips_per_device_1dev": round(one, 2),
            "ips_per_device_ndev": round(many, 2),
            "scaling_efficiency": round(many / one, 4),
            # sharding columns from the n-device run (docs/sharding.md):
            # MXNET_PARTITION=zero1 turns the dp-replicated optimizer
            # state into the sharded layout, measured here
            **cols}
    # headline value: the weaker of the two efficiencies (a pod is only as
    # scalable as its worst headline model)
    eff = min(c["scaling_efficiency"] for c in configs.values())
    print(json.dumps({"metric": "multichip_scaling", "value": eff,
                      "unit": "efficiency", "n_devices": n,
                      "platform": plat,
                      "vs_baseline": round(eff / 0.90, 4),
                      "configs": configs}))
    return 0


def _multichip(n):
    """Parent: rerun self as --multichip-child (it needs n real chips)."""
    row = _run_child(["--multichip-child", str(n)], dict(os.environ),
                     timeout=3600, metric="multichip_scaling")
    print(json.dumps(row))
    return _rc([row])


def _rc(rows):
    """Exit code of a parent run: 0 only when every row was measured."""
    return 0 if all(r.get("value") is not None for r in rows) else 1


def main():
    if len(sys.argv) == 3 and sys.argv[1] == "--config":
        return _child(sys.argv[2])
    if len(sys.argv) == 2 and sys.argv[1] == "--infer":
        return _infer_sweep()
    if len(sys.argv) == 3 and sys.argv[1] == "--infer-child":
        return _infer_child(sys.argv[2])
    if len(sys.argv) == 2 and sys.argv[1] == "--serve":
        return _serve_sweep()
    if len(sys.argv) == 2 and sys.argv[1] == "--serve-child":
        return _serve_child()
    if len(sys.argv) == 2 and sys.argv[1] == "--decode":
        return _decode_sweep()
    if len(sys.argv) == 2 and sys.argv[1] == "--decode-child":
        return _decode_child()
    if len(sys.argv) == 2 and sys.argv[1] == "--fleet":
        return _fleet_sweep()
    if len(sys.argv) == 2 and sys.argv[1] == "--fleet-child":
        return _fleet_child()
    if len(sys.argv) == 3 and sys.argv[1] == "--multichip":
        return _multichip(int(sys.argv[2]))
    if len(sys.argv) == 3 and sys.argv[1] == "--multichip-child":
        return _multichip_child(int(sys.argv[2]))

    env = dict(os.environ)
    # the caps bound a WEDGED child, not a slow compile, so they are
    # generous; the headline config runs first
    timeouts = {"resnet50": 3600, "bert_base": 3600, "lenet": 2400,
                "lstm_lm": 3000, "ssd": 3600}
    result = _run_config("resnet50", env, timeouts["resnet50"])
    if "unit" not in result:
        result.setdefault("unit", "images/sec")
        result.setdefault("vs_baseline", None)
    result["extra_metrics"] = []
    for name in ("bert_base", "lenet", "lstm_lm", "ssd"):
        result["extra_metrics"].append(
            _run_config(name, env, timeouts[name]))
    print(json.dumps(result))
    return _rc([result] + result["extra_metrics"])


if __name__ == "__main__":
    sys.exit(main())
