"""Readers that several per-layer metrics share.  A metric names exactly one
end-to-end metric it moves, so a quantity that serves both training and
serving cells is two metrics (``.train`` / ``.serve``) over one reader."""
from reduce_trace import PALLAS_TAG, share_of_busy


def compiles_in_window(ctx):
    """XLA backend compiles between window start and end, counted from jax's
    own monitoring events.  Should be 0: every shape is warmed in set-up."""
    return ctx["compiles_in_window"]


def idle_share(ctx):
    """Percent of the profiled slice in which no operation ran on device 0:
    one minus the union of the op line's intervals over the time the
    profiler was on, both on the trace's own clock (``reduce_trace.py``),
    never host time."""
    return 100.0 * ctx["trace"]["idle_share"] if ctx["trace"] else None


def pallas_share(ctx):
    """Percent of device-0 busy time in Pallas kernels, ALL of them: the
    trace prints each as a custom call with
    ``custom_call_target="tpu_custom_call"`` under the name of the jax
    function it was traced in, so the class is what can be told apart until
    each ``pallas_call`` has a name of its own (the ``tracing`` issue's).
    0 when none ran."""
    if not ctx["trace"]:
        return None
    return share_of_busy(ctx["trace"], lambda n: PALLAS_TAG in n)
