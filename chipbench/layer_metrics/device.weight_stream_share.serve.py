"""How near the device's busy time is to the least its weights' stream
allows: the bytes any implementation must read a pass over the stack
(``serve.stack_passes``, the passes the window's forwards were dispatched
for, x ``models/<builder>.loop_dense_bytes``) over ``hbm_bytes_per_s``, as a
share of the window, against device 0's BUSY seconds as a share of the
slice.

A share of the whole step and not a kernel's roofline: the compiler cuts
every matrix in four and brings each piece into fast memory with an
asynchronous copy (``slice-start`` / ``slice-done``) that carries no name and
runs under whatever else the device does -- attention, norms, the append --
so what moves the bytes cannot be told from the rest of a step (the products
under ``jax.named_scope("loop_dense")`` read operands that are already there:
``kernels.loop_dense_share.serve``, 38% of busy time for bytes that need 77%
of it; PERF.md section 6, PR 37).  It reads low by everything else a step
does (attention's rows, the head, prefill pieces, whose products are
compute-bound), never high.  Nothing on a program without the counter or a
builder without the function."""
from lib import roofline


def read(ctx):
    passes = roofline.counted(ctx, "serve.stack_passes")
    fn = roofline.builder_fn(ctx, "loop_dense_bytes")
    trace = ctx["trace"]
    if passes is None or fn is None or not trace:
        return None
    least_s = fn(ctx["config"], passes) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * (least_s / ctx["window_s"]) \
        / (trace["busy_s"] / trace["slice_s"])
