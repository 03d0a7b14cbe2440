"""Token-expert pairs computed here per token and MoE layer: the counter
``serve.moe_held_picks`` over the tokens forwarded in the window (decode
tokens = ``serve.tokens`` less one per prefill, plus the prompts' true
lengths ``serve.prefill_tokens``) and the configuration's MoE layers.
Uniform routing over the published experts gives top-k x held / routed
(8 x 16 / 256 = 0.5)."""
from lib import roofline
from lib.stats import counter_delta, timer_delta


def read(ctx):
    picks = roofline.counted(ctx, "serve.moe_held_picks")
    layers = roofline.builder_fn(ctx, "moe_layers")
    if picks is None or layers is None:
        return None
    prefills, _ = timer_delta(ctx["telemetry"], "serve.prefill_seconds")
    tokens = counter_delta(ctx["telemetry"], "serve.tokens") - prefills \
        + counter_delta(ctx["telemetry"], "serve.prefill_tokens")
    return picks / (tokens * layers(ctx["config"])) if tokens > 0 else None
