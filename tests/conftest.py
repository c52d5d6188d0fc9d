"""Test harness: run all tests on a virtual 8-device CPU mesh.

TPU analogue of the reference's distributed-in-one-box harness
(``tests/unit/common.py:129`` ``DistributedExec``): instead of spawning N
processes over NCCL/gloo, we give XLA 8 virtual CPU devices and express
"world_size=N" tests as meshes/submeshes over them.
"""

import os

# Must run before any XLA backend is initialized: the CPU backend's device
# count is fixed when it starts. jax.config.update repeats the platform
# choice for a process in which jax was imported before this file ran.
_TPU_LANE = os.environ.get("DSTPU_TPU_TESTS") == "1"  # `pytest -m tpu` runs
if not _TPU_LANE:
    if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

if not _TPU_LANE:
    jax.config.update("jax_platforms", "cpu")
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


def require_devices(n):
    """Skip a test when fewer than n XLA devices are available."""
    return pytest.mark.skipif(len(jax.devices()) < n, reason=f"needs {n} devices")
