"""Test fixtures. Mirrors the reference's conftest strategy
(reference conftest.py:61 waitall-between-modules; pytest.ini markers):
tests run with JAX_PLATFORMS=cpu on a virtual 8-device CPU mesh so
multi-chip sharding logic is exercised without TPU hardware, and per-test
seeding keeps runs reproducible. Both settings must be in the environment
before jax is imported.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_and_sync():
    import mxnet_tpu as mx
    mx.random.seed(0)
    yield
    # localize async failures to the test that caused them (reference conftest.py:61)
    mx.waitall()
