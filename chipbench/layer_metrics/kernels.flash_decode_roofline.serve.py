"""``flash_decode``'s share of its roofline (memory-bound): the live cache
rows the window's decode steps had to read (``serve.step_live_positions``,
counted in ``serve/decode.py:_step``) x the bytes of one cache position
over all layers (K and V of every head: ``n_layer`` x ``n_embd`` x 2 halves
x the configuration's dtype) over ``hbm_bytes_per_s``, against the device
seconds of the kernel named ``flash_decode`` -- how far the block skip and
the program's shape have come.  The prefill chunks run under the same name:
their time lies in the denominator and their few bytes are left out of the
numerator, so the share reads a little low, never high.  Swings between
traced runs of one tree as every share of ``lib/roofline.py`` does."""
from lib import roofline

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def position_bytes(config):
    """Bytes one cache position holds over the whole model."""
    return config["n_layer"] * config["n_embd"] * 2 \
        * DTYPE_BYTES[config["dtype"]]


def read(ctx):
    rows = roofline.counted(ctx, "serve.step_live_positions")
    if rows is None or "n_embd" not in ctx["config"]:
        return None
    return roofline.share(ctx, ("flash_decode",),
                          rows * position_bytes(ctx["config"])
                          / ctx["peaks"]["hbm_bytes_per_s"])
