"""Collective-bandwidth microbench over the device mesh.

The analogue of the reference's KVStore bandwidth tool
(/root/reference/tools/bandwidth/measure.py): measures the primitive
collectives the SPMD trainer actually issues — psum (allreduce),
all_gather, reduce_scatter via psum_scatter, ppermute ring step — over a
`jax.sharding.Mesh`, reporting per-collective algorithmic bandwidth.
This is the tool that localizes a scaling miss: if `bench.py --multichip`
efficiency drops, run this to see WHICH collective regressed.

On n virtual CPU devices the numbers measure host memcpy contention, not
ICI — meaningful only for relative regressions; on a real pod they are
the ICI utilization table (ring allreduce moves 2(n-1)/n bytes/element).

Usage: python tools/commbench.py [--ndev 8] [--sizes 1,4,16] [--json out]
       (sizes in MiB per device)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _measure(fn, x, steps):
    out = fn(x)
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(out)
    out.block_until_ready()
    return (time.perf_counter() - t0) / steps


def run(ndev, sizes_mib, steps=10):
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()[:ndev]
    mesh = Mesh(onp.array(devs), ("x",))
    n = len(devs)
    rows = []
    for mib in sizes_mib:
        elems = int(mib * (1 << 20) // 4)  # f32 per device
        x = jnp.ones((n * elems,), jnp.float32)
        spec = P("x")

        def mk(body):
            return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                                     out_specs=spec))

        psum = mk(lambda v: jax.lax.psum(v, "x") / n)
        # all_gather then take own shard back (keeps in/out specs equal so
        # the timed region is the collective, not a reshard)
        gather = mk(lambda v: jax.lax.all_gather(
            v, "x", tiled=True)[:v.shape[0]])
        scatter = mk(lambda v: jnp.tile(jax.lax.psum_scatter(
            v, "x", tiled=True) / n, n))
        ring = mk(lambda v: jax.lax.ppermute(
            v, "x", [(i, (i + 1) % n) for i in range(n)]))

        bytes_per_dev = elems * 4
        # algorithmic bytes moved per device (ring algorithms)
        traffic = {
            "psum": 2 * (n - 1) / n * bytes_per_dev,
            "all_gather": (n - 1) / n * bytes_per_dev * n,
            "psum_scatter": (n - 1) / n * bytes_per_dev,
            "ppermute": bytes_per_dev,
        }
        for name, fn in (("psum", psum), ("all_gather", gather),
                         ("psum_scatter", scatter), ("ppermute", ring)):
            sec = _measure(fn, x, steps)
            rows.append({
                "collective": name, "mib_per_device": mib,
                "ms": round(sec * 1e3, 3),
                "algo_gbps": round(traffic[name] / sec / 1e9, 4)})
            print(f"{name:>13} {mib:>5} MiB/dev  {sec * 1e3:8.3f} ms  "
                  f"{traffic[name] / sec / 1e9:7.2f} GB/s", flush=True)
    return {"n_devices": n, "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "virtual": devs[0].platform == "cpu", "rows": rows}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ndev", type=int, default=8)
    ap.add_argument("--sizes", default="1,4,16")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    import jax

    if len(jax.devices()) < args.ndev:
        print(f"need {args.ndev} devices, jax has {len(jax.devices())} "
              f"({jax.devices()[0].platform}); for a structural run on "
              "virtual CPU devices set JAX_PLATFORMS=cpu XLA_FLAGS="
              f"--xla_force_host_platform_device_count={args.ndev}",
              file=sys.stderr)
        return 1

    sizes = [float(s) for s in args.sizes.split(",")]
    res = run(args.ndev, sizes, args.steps)
    print(json.dumps({k: v for k, v in res.items() if k != "rows"}))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
