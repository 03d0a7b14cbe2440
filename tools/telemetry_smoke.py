"""Telemetry smoke gate (`make telemetry-smoke`).

Trains 20 LeNet steps on CPU through the full instrumented stack — gluon
DataLoader → hybridized forward → autograd → gluon Trainer — plus a short
engine-backed PrefetchingIter eval pass, then dumps ``telemetry.json`` and
FAILS (exit 1) unless every core metric ticked:

    hybridize.compile_seconds   the jit-compile cost of the net
    dataloader.wait_seconds     input-pipeline wait
    trainer.step_seconds        optimizer step wall time
    engine.ops_pushed           native/naive engine activity

This is the observability ISSUE's acceptance run: if an instrumentation
seam regresses (a refactor drops a counter), this gate goes red before a
perf round burns chip time discovering the snapshot is empty.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# runnable as `python tools/telemetry_smoke.py` from a source checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CORE = ["hybridize.compile_seconds", "dataloader.wait_seconds",
        "trainer.step_seconds", "engine.ops_pushed"]


def main() -> int:
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    if not telemetry.enabled():
        print("telemetry-smoke: MXNET_TELEMETRY=0 — nothing to verify; "
              "run with telemetry enabled", file=sys.stderr)
        return 1

    out_path = os.environ.get("MXNET_TELEMETRY_JSON") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "telemetry.json")

    mx.random.seed(0)
    net = mx.gluon.model_zoo.get_model("lenet")
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 1, 28, 28)))
    net.hybridize()

    rs = onp.random.RandomState(0)
    x = rs.rand(352, 1, 28, 28).astype("float32")
    y = rs.randint(0, 10, size=(352,)).astype("int32")
    loader = DataLoader(ArrayDataset(x, y), batch_size=16, shuffle=True)
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.05, "momentum": 0.9})
    loss_fn = gloss.SoftmaxCrossEntropyLoss()

    steps = 0
    for xb, yb in loader:
        with mx.autograd.record():
            out = net(xb)
            loss = loss_fn(out, yb)
        loss.backward()
        trainer.step(xb.shape[0])
        steps += 1
        if steps >= 20:
            break
    assert steps == 20, f"expected 20 train steps, ran {steps}"

    # engine-backed input path: PrefetchingIter pushes each fetch onto the
    # dependency engine (the seam engine.ops_pushed instruments)
    it = mx.io.PrefetchingIter(mx.io.NDArrayIter(x[:64], y[:64],
                                                 batch_size=16))
    for batch in it:
        net(batch.data[0]).wait_to_read()

    doc = telemetry.dump_json(out_path)
    snap = doc["metrics"]

    missing = []
    for name in CORE:
        m = snap.get(name)
        if m is None or not m.get("value"):
            missing.append(name)
    print(f"telemetry-smoke: {len(snap)} metrics -> {out_path}")
    for name in CORE:
        m = snap.get(name, {})
        print(f"  {name:32s} value={m.get('value')} "
              f"count={m.get('count', '-')}")
    if missing:
        print(f"telemetry-smoke: FAIL — core metrics missing/zero: "
              f"{missing}", file=sys.stderr)
        return 1

    # the aggregate table must render the same metrics (profiler merge)
    table = mx.profiler.dumps()
    absent = [n for n in CORE if n not in table]
    if absent:
        print(f"telemetry-smoke: FAIL — profiler.dumps() missing {absent}",
              file=sys.stderr)
        return 1
    print("telemetry-smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
