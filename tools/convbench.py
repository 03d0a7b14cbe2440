"""Per-convolution utilization microbench.

Traces a model-zoo network's forward+backward, collects every
``conv_general_dilated`` equation from the jaxpr (so backward
input/filter-gradient convs are included, not just the forward graph),
then times each distinct conv shape as its own jitted XLA computation and
reports achieved TFLOP/s vs the chip's bf16 peak.

This is the tool that localizes the ResNet-50 utilization gap (PERF.md:
"the remaining gap ... would have to come from the conv kernels
themselves"): it turns "it's XLA's stem/tail lowering" from a hypothesis
into a per-shape table.

Usage:  python tools/convbench.py [--model resnet50_v1] [--batch 128]
        [--image 224] [--dtype bf16] [--steps 30] [--json out.json]

Reference analogue: the per-op timing harness in
/root/reference/benchmark/opperf/ (run_benchmark_operator) — here
specialized to the conv corpus with MXU utilization math.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def collect_convs(model, batch, image, layout, compute_dtype):
    """Jaxpr-walk the train-step closure; return conv eqn descriptors."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.parallel.trainer import _functional_apply

    mx.random.seed(0)
    net = mx.gluon.model_zoo.get_model(model, layout=layout)
    net.initialize(mx.init.Xavier())
    shape = ((2, image, image, 3) if layout == "NHWC"
             else (2, 3, image, image))
    net(mx.np.zeros(shape))
    names = sorted(n for n, p in net.collect_params().items()
                   if p._data is not None)
    fn, arrs, _holder = _functional_apply(net, names, training=True)
    pvals = [a._data for a in arrs]
    if compute_dtype is not None:
        pvals = [v.astype(compute_dtype)
                 if v.dtype == jnp.float32 and v.ndim > 1 else v
                 for v in pvals]

    xshape = ((batch, image, image, 3) if layout == "NHWC"
              else (batch, 3, image, image))
    x = jnp.zeros(xshape, compute_dtype or jnp.float32)
    y = jnp.zeros((batch,), jnp.int32)

    def loss(pvals, x, y):
        outs, _ = fn(list(pvals), x)
        logp = jax.nn.log_softmax(outs[0].astype(jnp.float32))
        return -jnp.take_along_axis(logp, y[:, None], 1).mean()

    jaxpr = jax.make_jaxpr(jax.grad(loss))(pvals, x, y)

    convs = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "conv_general_dilated":
                lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
                out = eqn.outvars[0].aval
                convs.append({
                    "lhs": tuple(lhs.shape), "rhs": tuple(rhs.shape),
                    "out": tuple(out.shape),
                    "dtype": str(lhs.dtype),
                    "params": {k: v for k, v in eqn.params.items()
                               if k in ("window_strides", "padding",
                                        "lhs_dilation", "rhs_dilation",
                                        "feature_group_count",
                                        "dimension_numbers")}})
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)
                elif isinstance(sub, (list, tuple)):
                    for s in sub:
                        if hasattr(s, "jaxpr"):
                            walk(s.jaxpr)
    walk(jaxpr.jaxpr)
    return convs


def conv_flops(desc) -> float:
    """2 * out_elements * reduction_size (per conv application)."""
    import numpy as onp

    dn = desc["params"]["dimension_numbers"]
    rhs = desc["rhs"]
    out = desc["out"]
    groups = desc["params"].get("feature_group_count", 1)
    # rhs spec: kernel spatial dims are everything except the two feature dims
    rhs_spec = dn.rhs_spec  # (out_feature, in_feature, *spatial)
    k_spatial = [rhs[d] for i, d in enumerate(rhs_spec) if i >= 2]
    cin_per_group = rhs[rhs_spec[1]]
    red = float(onp.prod(k_spatial)) * cin_per_group
    return 2.0 * float(onp.prod(out)) * red * (1 if groups else 1)


def bench_one(desc, steps: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    dt = jnp.bfloat16 if "bfloat16" in desc["dtype"] else jnp.float32
    lhs = jnp.ones(desc["lhs"], dt)
    rhs = jnp.ones(desc["rhs"], dt)
    p = desc["params"]

    @jax.jit
    def f(lhs, rhs):
        return lax.conv_general_dilated(
            lhs, rhs, window_strides=p["window_strides"],
            padding=p["padding"], lhs_dilation=p["lhs_dilation"],
            rhs_dilation=p["rhs_dilation"],
            dimension_numbers=p["dimension_numbers"],
            feature_group_count=p.get("feature_group_count", 1))

    out = f(lhs, rhs)
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        out = f(lhs, rhs)
    out.block_until_ready()
    return (time.perf_counter() - t0) / steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50_v1")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "fp32"])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    layout = "NHWC" if on_tpu else "NCHW"
    compute = jnp.bfloat16 if (args.dtype == "bf16" and on_tpu) else None
    from mxnet_tpu.trace.cost import peak_flops

    peak = peak_flops(dev) if on_tpu else None

    convs = collect_convs(args.model, args.batch, args.image, layout,
                          compute)
    # dedupe identical shapes; keep multiplicity for the weighted total
    seen: dict = {}
    for c in convs:
        key = (c["lhs"], c["rhs"], c["out"], c["dtype"],
               str(c["params"]["window_strides"]),
               str(c["params"]["padding"]))
        if key in seen:
            seen[key]["count"] += 1
        else:
            seen[key] = dict(c, count=1)

    rows = []
    total_t, total_f = 0.0, 0.0
    for c in seen.values():
        sec = bench_one(c, args.steps)
        fl = conv_flops(c)
        tfs = fl / sec / 1e12
        util = (fl / sec / peak) if peak else None
        total_t += sec * c["count"]
        total_f += fl * c["count"]
        rows.append({"lhs": c["lhs"], "rhs": c["rhs"], "out": c["out"],
                     "count": c["count"], "ms": round(sec * 1e3, 3),
                     "gflop": round(fl / 1e9, 2),
                     "tflops": round(tfs, 1),
                     "util": round(util, 3) if util is not None else None})
        print(f"{str(c['lhs']):>28} * {str(c['rhs']):>22} x{c['count']} "
              f"{sec*1e3:8.3f} ms  {tfs:7.1f} TF/s"
              + (f"  {util*100:5.1f}%" if util is not None else ""))

    rows.sort(key=lambda r: -r["ms"] * r["count"])
    agg = {"device": dev.device_kind, "model": args.model,
           "batch": args.batch, "conv_count": len(convs),
           "distinct_shapes": len(rows),
           "sum_ms_isolated": round(total_t * 1e3, 2),
           "sum_gflop": round(total_f / 1e9, 1),
           "aggregate_tflops": round(total_f / total_t / 1e12, 1),
           "aggregate_util": (round(total_f / total_t / peak, 3)
                              if peak else None),
           "rows": rows}
    print(json.dumps({k: v for k, v in agg.items() if k != "rows"}))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(agg, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
