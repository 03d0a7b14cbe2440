"""Share of busy time in Pallas kernels, whichever they are.  On today's decode
path the only one is ``flash_attention_decode``, in decode steps and in the
prefill chunks alike."""
from lib.readers import pallas_share as read  # noqa: F401
