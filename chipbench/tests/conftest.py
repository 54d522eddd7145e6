"""The benchmark's own tests run by hand, on virtual CPU devices:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

(not under the tier-1 command, which stays `tests/`). The environment must
be set before jax is imported anywhere in the test process."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()

import pytest


@pytest.fixture
def anyio_backend():
    return "asyncio"
