"""Warmup smoke gate (`make warmup-smoke`).

Proves the persistent compilation cache's cross-process win end to end
(docs/jit.md): run the SAME LeNet compile workload in two fresh
processes sharing one ``JAX_COMPILATION_CACHE_DIR`` —

  * **cold**: empty cache directory; every jit pays a real XLA compile
    and fills the cache;
  * **warm**: second process; every compile should be served from disk.

FAILS (exit 1) unless the cold process compiled and filled the cache,
the warm process recorded ``hybridize.persistent_cache_hits > 0`` and
both computed the same loss.  Emits ``warmup_smoke.json`` with both
runs' numbers, the compile wall times (``hybridize.compile_seconds``
total: hybridized forward + the AOT ``ShardedTrainer.compile`` step)
among them: reported, not gated — a CPU timing is no speed.

This is the compile-cost ISSUE's acceptance gate: if a jax upgrade
stops serializing executables, a config regression re-disables the
cache, or the lazy ``ensure_cache`` seam is dropped by a refactor,
this goes red before a TPU round burns its first hour recompiling.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _child() -> int:
    """One process's workload: hybridized LeNet forward (warmup API) +
    ShardedTrainer AOT step compile.  Prints one JSON line."""
    import numpy as onp

    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    t_start = time.perf_counter()
    mx.random.seed(0)
    net = mx.gluon.model_zoo.get_model("lenet")
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 1, 28, 28)))
    net.hybridize()
    net.warmup([(32, 1, 28, 28), (64, 1, 28, 28)])

    def ce(pred, y):
        logp = jax.nn.log_softmax(pred.astype(jnp.float32))
        return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]

    mesh = make_mesh({"dp": -1}, devices=jax.devices()[:1])
    trainer = ShardedTrainer(net, ce, mesh=mesh, optimizer="sgd",
                             learning_rate=0.05, momentum=0.9)
    rs = onp.random.RandomState(0)
    x = rs.rand(32, 1, 28, 28).astype("float32")
    y = rs.randint(0, 10, size=(32,)).astype("int32")
    trainer.compile((x, y))
    loss = float(trainer.step(x, y))

    snap = telemetry.snapshot()

    def val(name, field="value"):
        return snap.get(name, {}).get(field, 0)

    from mxnet_tpu.jit import cache as jit_cache

    print(json.dumps({
        "compile_secs": val("hybridize.compile_seconds", "total"),
        "compiles": val("hybridize.compile_seconds", "count"),
        "warmup_compiles": val("hybridize.warmup_compiles"),
        "persistent_hits": val("hybridize.persistent_cache_hits"),
        "warmup_secs": val("jit.warmup_seconds", "total"),
        "wall_secs": round(time.perf_counter() - t_start, 3),
        "cache_dir": jit_cache.ensure_cache(),
        "loss": loss,
    }))
    return 0


def _run_child(env) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"],
        capture_output=True, text=True, timeout=900, env=env)
    for line in reversed(out.stdout.splitlines()):
        try:
            return json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
    raise SystemExit(
        f"warmup-smoke: child produced no JSON (rc={out.returncode}):\n"
        f"{out.stderr[-2000:]}")


def main() -> int:
    if "--child" in sys.argv:
        return _child()

    cache_dir = tempfile.mkdtemp(prefix="mxjit-smoke-")
    # a FRESH directory: the cold child must find the cache empty.  The
    # cache is placed the one external way (docs/jit.md); with it jax's
    # own thresholds apply, so persist even this smoke's tiny programs
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", MXNET_TELEMETRY="1",
               MXNET_COMPILE_CACHE="1", JAX_COMPILATION_CACHE_DIR=cache_dir,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    try:
        cold = _run_child(env)
        n_entries = len([f for f in os.listdir(cache_dir)
                         if f.endswith("-cache")])
        warm = _run_child(env)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)

    ratio = (warm["compile_secs"] / cold["compile_secs"]
             if cold["compile_secs"] else float("inf"))
    doc = {"version": 1, "ts": round(time.time(), 3),
           "cold": cold, "warm": warm,
           "cache_entries_after_cold": n_entries,
           "warm_over_cold_compile": round(ratio, 4)}
    out_path = os.path.join(ROOT, "warmup_smoke.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")

    print(f"warmup-smoke: cold compile {cold['compile_secs']:.3f}s "
          f"({cold['compiles']} compiles), warm {warm['compile_secs']:.3f}s "
          f"-> ratio {ratio:.3f}; "
          f"persistent hits: {warm['persistent_hits']}; "
          f"cache entries: {n_entries} -> {out_path}")

    failures = []
    if not cold["compiles"]:
        failures.append("cold process recorded zero compiles")
    if n_entries == 0:
        failures.append("cold process wrote no cache entries "
                        "(persistent cache never armed?)")
    if warm["persistent_hits"] <= 0:
        failures.append("warm process had zero persistent-cache hits")
    if cold["loss"] != warm["loss"]:
        failures.append(f"cold/warm losses diverge "
                        f"({cold['loss']} vs {warm['loss']}): the cached "
                        f"executable computed something different")
    if failures:
        for msg in failures:
            print(f"warmup-smoke: FAIL — {msg}", file=sys.stderr)
        return 1
    print("warmup-smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
