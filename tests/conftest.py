"""Test harness config: force a virtual 8-device CPU mesh.

The backend choice is steered to CPU *before any backend init* so tests
are hermetic, fast, and can exercise 8-way sharding without chips.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: wall-clock-sensitive tests (timing assertions)")


@pytest.fixture(autouse=True)
def _seed():
    import numpy as np
    import paddle_tpu as pt
    pt.seed(42)
    np.random.seed(42)
    yield


@pytest.fixture(autouse=True, scope="module")
def _drop_compile_caches():
    """Release each module's compiled executables when it finishes.

    This jaxlib's CPU backend_compile segfaults deterministically once
    enough LoadedExecutables have accumulated in one process (the full
    suite used to die mid-run in whatever module crossed the threshold
    — the faulthandler stack bottoms out in XLA's LLVM JIT). Modules
    rarely share jit cache entries, so dropping the caches between
    modules costs almost nothing and keeps the resident-executable
    count bounded."""
    yield
    import gc
    jax.clear_caches()
    gc.collect()
