"""Roofline shares of named device scopes in serving cells.

The numerator is the least device time the WINDOW's work under a scope
could take -- bytes over ``hbm_bytes_per_s`` or operations over
``bf16_flops_per_s`` of ``peaks.json``, from the program's counters over the
window and the byte/operation functions the model builder keeps -- as a
share of the window; the denominator is the share of the profiler's slice
that device 0 spent in ops traced under the scope (``lib/host_spans.py``'s
union of named intervals).  Both come from the same traced run, and the
closed loop is steady, so the slice stands for the window -- **as far as it
does**: the ~3 s slice holds more or fewer admissions than the window's
share, so two traced runs of one tree read a quarter apart
(``kernels.kda_step_roofline.serve`` 56.9 and 45.4, ``kda_chunk`` 0.33 to
1.33; PERF.md section 7, PR 29).  Read a roofline share of this module to
one digit, and compare two trees on several seeds.  A scope that ran
nothing, a program without the counters (the parent of the PR that brought
them) and a builder without the functions all read as None.
"""
from lib import host_spans
from lib.stats import counter_delta, timer_delta


def scope_seconds(ctx, scopes):
    """Seconds of the slice device 0 spent under one of ``scopes``; None
    when none ran or there is no trace."""
    percent = host_spans.scope_share(ctx, scopes)
    return None if percent is None else percent / 100.0 * ctx["trace"]["busy_s"]


def share(ctx, scopes, least_seconds):
    """Percent of its roofline: ``least_seconds`` (for the whole window)
    over the window, against the scope's seconds over the slice."""
    seconds = scope_seconds(ctx, scopes)
    if seconds is None or not least_seconds:
        return None
    return 100.0 * (least_seconds / ctx["window_s"]) \
        / (seconds / ctx["trace"]["slice_s"])


# the routed experts on a v5e trace (looked at by hand, PR 29): the sort,
# gathers and combine carry ``jax.named_scope("moe_experts")``;
# ``lax.ragged_dot`` runs as custom calls that XLA names ``ragged-dot-none``
# and ``ragged-dot-metadata`` and that do NOT carry the scope they were
# traced under, so they are read by those names
MOE_EXPERTS_SCOPES = ("moe_experts", "ragged-dot-none", "ragged-dot-metadata")


def counted(ctx, name):
    """The window's gain of a program counter; None when the program has
    no such counter (it then never shows in a snapshot)."""
    if name not in ctx["telemetry"][1]:
        return None
    return counter_delta(ctx["telemetry"], name)


def builder_fn(ctx, name):
    return getattr(ctx["model"], name, None)


def decode_tokens(ctx):
    """Tokens the window's decode steps delivered = occupied slots summed
    over its steps (a prefill delivers its request's first token itself)."""
    return counter_delta(ctx["telemetry"], "serve.tokens") \
        - timer_delta(ctx["telemetry"], "serve.prefill_seconds")[0]
