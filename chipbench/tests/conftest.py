"""chipbench's own tests: ``pytest chipbench/tests -q`` (by hand; the repo's
tier-1 command collects ``tests/`` only).  They run on the CPU at tiny sizes
and give counts and correctness, never a speed."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("MXNET_COMPILE_CACHE", "0")     # keep .jax_cache empty
os.environ.setdefault("MXNET_KERNELS", "interpret")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
