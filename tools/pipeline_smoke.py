"""Pipeline smoke gate (`make pipeline-smoke`).

Two 20-step LeNet runs through the SAME compiled SPMD step, CPU:

  phase A (synchronous baseline)  plain DataLoader, ``step(block=True)``
                                  — fetch+batchify inline, loss synced
                                  every step (the pre-pipeline loop)
  phase B (async pipeline)        DataLoader(prefetch_to_device=trainer)
                                  → DevicePrefetcher → non-blocking
                                  ``step()`` with bounded in-flight
                                  dispatch

FAILS (exit 1) unless the pipeline demonstrably engaged:

  * ``pipeline.h2d_overlap_seconds`` ticked — the transfers moved off
    the training loop's thread;
  * the ``engine.inflight_steps`` high-water mark is > 1 — dispatch ran
    ahead of retirement, i.e. the loss really came back lazy and the
    queue really held more than one step.

Both phases' ``dataloader.wait_seconds`` p50 are reported, not gated: a
CPU timing is no speed.

If an async seam regresses (a step starts syncing, the prefetch thread
dies, backpressure collapses to depth 1), this gate goes red before a
perf round burns chip time on it.  Companion gate to
tools/telemetry_smoke.py (docs/pipeline.md).
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# runnable as `python tools/pipeline_smoke.py` from a source checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STEPS = 20
BATCH = 64


def _build():
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    def ce(pred, y):
        logp = jax.nn.log_softmax(pred.astype(jnp.float32))
        return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]

    mx.random.seed(0)
    net = mx.gluon.model_zoo.get_model("lenet")
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 1, 28, 28)))
    mesh = make_mesh({"dp": -1}, devices=jax.devices()[:1])
    trainer = ShardedTrainer(net, ce, mesh=mesh, optimizer="sgd",
                             learning_rate=0.05, momentum=0.9)
    rs = onp.random.RandomState(0)
    n = STEPS * BATCH
    x = rs.rand(n, 1, 28, 28).astype("float32")
    y = rs.randint(0, 10, size=(n,)).astype("int32")

    def loader(**kw):
        return DataLoader(ArrayDataset(x, y), batch_size=BATCH, **kw)

    return trainer, loader


def _run(trainer, loader, block: bool) -> int:
    steps = 0
    for xb, yb in loader:
        trainer.step(xb, yb, block=block)
        steps += 1
        if steps >= STEPS:
            break
    trainer.drain()
    return steps


def main() -> int:
    from mxnet_tpu import telemetry

    if not telemetry.enabled():
        print("pipeline-smoke: MXNET_TELEMETRY=0 — nothing to verify; "
              "run with telemetry enabled", file=sys.stderr)
        return 1

    trainer, loader = _build()
    # one untimed step absorbs the jit compile so BOTH phases time the
    # same compiled executable
    import numpy as onp

    rs = onp.random.RandomState(1)
    trainer.step(rs.rand(BATCH, 1, 28, 28).astype("float32"),
                 rs.randint(0, 10, size=(BATCH,)).astype("int32"),
                 block=True)

    telemetry.reset()
    sync_loader = loader()
    steps_a = _run(trainer, sync_loader, block=True)
    sync_loader.close()
    snap_a = telemetry.snapshot()

    telemetry.reset()
    with loader(prefetch_to_device=trainer) as pipe_loader:
        steps_b = _run(trainer, pipe_loader, block=False)
    snap_b = telemetry.snapshot()

    assert steps_a == steps_b == STEPS, (steps_a, steps_b)
    wait_a = snap_a.get("dataloader.wait_seconds", {})
    wait_b = snap_b.get("dataloader.wait_seconds", {})
    p50_a, p50_b = wait_a.get("p50", 0.0), wait_b.get("p50", 0.0)
    inflight = snap_b.get("engine.inflight_steps", {})
    hwm = inflight.get("max", 0)
    overlap = snap_b.get("pipeline.h2d_overlap_seconds", {})
    stall = snap_b.get("pipeline.stall_seconds", {})

    out_path = os.environ.get("MXNET_PIPELINE_JSON") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "pipeline_smoke.json")
    doc = {"steps": STEPS, "batch": BATCH,
           "sync_wait_p50": p50_a, "pipeline_wait_p50": p50_b,
           "inflight_high_water": hwm,
           "h2d_overlap_seconds": overlap.get("total", 0.0),
           "stall_seconds": stall.get("total", 0.0),
           "sync": snap_a, "pipeline": snap_b}
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True, default=str)
        f.write("\n")

    print(f"pipeline-smoke: {STEPS} steps x batch {BATCH} -> {out_path}")
    print(f"  dataloader.wait_seconds p50   sync={p50_a * 1e3:.3f}ms  "
          f"pipeline={p50_b * 1e3:.3f}ms")
    print(f"  engine.inflight_steps max     {hwm}")
    print(f"  pipeline.h2d_overlap_seconds  {overlap.get('total', 0.0):.4f}s"
          f"  ({overlap.get('count', 0)} transfers)")
    print(f"  pipeline.stall_seconds        {stall.get('total', 0.0):.4f}s")

    failures = []
    if not hwm > 1:
        failures.append(
            f"engine.inflight_steps high-water mark {hwm} <= 1 — dispatch "
            "never ran ahead (loss is syncing per step?)")
    if not overlap.get("count"):
        failures.append("pipeline.h2d_overlap_seconds never ticked — "
                        "transfers did not move off the main thread")
    if failures:
        for msg in failures:
            print(f"pipeline-smoke: FAIL — {msg}", file=sys.stderr)
        return 1
    print("pipeline-smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
