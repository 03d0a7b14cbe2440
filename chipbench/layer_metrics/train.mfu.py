"""Model FLOP/s utilization of one chip: the model builder's
``flops_per_sample`` (from the configuration's shapes, recomputation not
counted) x samples/s/chip of THIS run over the peak in ``peaks.json``.  This run
is the traced one, so the profiler's cost over its ~3 s slice is inside the
figure; the untraced rate is the end-to-end metric beside it."""
from lib.stats import mfu_percent


def read(ctx):
    rate = ctx["result"].get("samples_per_s_per_chip")
    if rate is None:
        return None
    flops = ctx["model"].flops_per_sample(ctx["config"], ctx["traffic"])
    return mfu_percent(flops, rate, ctx["peaks"]["bf16_flops_per_s"])
