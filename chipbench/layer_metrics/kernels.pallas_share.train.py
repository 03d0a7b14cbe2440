"""Share of busy time in Pallas kernels, whichever they are.  In today's BERT
step these are the flash-attention forward (``%jvp__``) and backward
(``%transpose_jvp___``); ``opt_arena`` or any later kernel would count too."""
from lib.readers import pallas_share as read  # noqa: F401
