"""Test fixtures.

Multi-device tests run on a virtual 8-device CPU mesh (the analogue of the
reference's multi-raylet-in-one-machine Cluster fixture,
python/ray/tests/conftest.py:375) — real TPU hardware is not required.
"""
import os

# Force the CPU platform and 8 virtual devices.  Both must be in the
# environment before the CPU backend initializes (first jax.devices()
# call), which this import-time hook is.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import pytest


@pytest.fixture
def rt_init():
    import ray_tpu
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax
    return jax.devices("cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: longer learning/convergence tests")
    config.addinivalue_line(
        "markers", "chaos: scripted fault-injection tests "
                   "(core/fault_injection.py); quick deterministic ones "
                   "run in tier-1, long kill-a-host flows are also "
                   "marked slow")
    config.addinivalue_line(
        "markers", "serve_fleet: fleet serving-layer tests "
                   "(serve/fleet/); quick deterministic ones run in "
                   "tier-1, trace-replay load runs are also marked "
                   "slow so tier-1 skips them")
