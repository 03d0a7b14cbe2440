"""Plain float32 reference forwards the program's own tests compare with
(``jax.numpy`` at ``highest`` matmul precision: no cache, no kernel, no
batching).  Each module is a byte-for-byte copy of the benchmark's
``chipbench/references/<name>.py``, which must run from a checkout without
importing the program; a tier-1 test holds the two together."""
