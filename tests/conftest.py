"""Test configuration: force JAX onto a virtual 8-device CPU platform so
sharding tests run anywhere (the driver's multi-chip dry-run uses the same
mechanism). Must run before jax is imported anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Children spawned by the actor runtime inherit these so any jax import in a
# storage-volume process also lands on CPU.
os.environ.setdefault("TORCHSTORE_TPU_TEST_MODE", "1")

import pytest


@pytest.fixture
def anyio_backend():
    # pytest-asyncio isn't in this image; async tests run via anyio's plugin
    # in auto mode (see pyproject.toml) on the stdlib asyncio backend.
    return "asyncio"
