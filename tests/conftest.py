"""Test fixtures (ref: tests/python/unittest/common.py:98,197 + conftest.py).

Forces an 8-device virtual CPU mesh BEFORE jax import so sharding tests run
without TPU hardware, and reproduces the reference's seed-reporting fixture:
every test runs under a known seed, printed on failure as
``MXNET_TEST_SEED=...`` for reproduction.
"""
import os

# Force the 8-device virtual CPU mesh unless the user explicitly asks to run
# the suite on TPU (MXNET_TEST_TPU=1).  jax.config.update pins the platform
# even when something imported jax before this conftest loaded.
if not os.environ.get("MXNET_TEST_TPU"):
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")

# The suite never fills the in-checkout compile cache (<checkout>/.jax_cache,
# docs/jit.md): the chip tool copies the tree as it stands on disk, and CPU
# entries cannot hit there.  Tests that exercise the persistent cache turn
# it back on and point JAX_COMPILATION_CACHE_DIR at their tmp_path.
os.environ["MXNET_COMPILE_CACHE"] = "0"

import random as _pyrandom

import numpy as _onp
import pytest


@pytest.fixture(autouse=True)
def seed_everything(request):
    """Ref common.py with_seed(): seed python/numpy/mxnet per test; log the
    seed so failures reproduce with MXNET_TEST_SEED=N."""
    env_seed = os.environ.get("MXNET_TEST_SEED")
    seed = int(env_seed) if env_seed else _onp.random.randint(0, 2 ** 31)
    _pyrandom.seed(seed)
    _onp.random.seed(seed)
    import mxnet_tpu as mx

    mx.random.seed(seed)
    yield seed
    if request.node.rep_call.failed if hasattr(request.node, "rep_call") else False:
        print(f"To reproduce: MXNET_TEST_SEED={seed}")


@pytest.hookimpl(tryfirst=True, hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)


def run_in_x64_subprocess(code: str, timeout: int = 900):
    """Run python code in a FRESH process with MXNET_INT64_TENSOR_SIZE=1
    (jax x64 must be configured before backend init). Returns the
    CompletedProcess; asserts rc 0."""
    import subprocess
    import sys

    env = {**os.environ, "MXNET_INT64_TENSOR_SIZE": "1",
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-1500:]
    return out
