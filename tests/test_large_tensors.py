"""Large-tensor stress sweep (reference tests/test_large_tensors.py:28-125):
put/get across sizes per transport, with the slow upper sizes gated by
TORCHSTORE_TPU_ENABLE_SLOW_TESTS (reference's slow-test gate pattern)."""

import os

import numpy as np
import pytest

import torchstore_tpu as ts

SIZES_MB = [4, 64]
if os.environ.get("TORCHSTORE_TPU_ENABLE_SLOW_TESTS"):
    SIZES_MB += [512, 2048]


@pytest.fixture(params=["shm", "bulk", "rpc"])
async def store(request):
    await ts.initialize(
        store_name="big",
        strategy=ts.SingletonStrategy(default_transport_type=request.param),
    )
    yield "big"
    await ts.shutdown("big")


@pytest.mark.parametrize("size_mb", SIZES_MB)
async def test_large_roundtrip(store, size_mb):
    n = size_mb * 1024 * 1024 // 4
    x = np.random.rand(1024, n // 1024).astype(np.float32)
    await ts.put("big", x, store_name=store)
    out = await ts.get("big", store_name=store)
    np.testing.assert_array_equal(out, x)
    # In-place get into a preallocated destination too.
    dest = np.zeros_like(x)
    got = await ts.get("big", like=dest, store_name=store)
    assert got is dest
    np.testing.assert_array_equal(dest, x)
    await ts.delete("big", store_name=store)


async def test_large_sharded_reshard(store):
    jax = pytest.importorskip("jax")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    g = np.random.rand(2048, 2048).astype(np.float32)  # 16 MB
    devs = np.array(jax.devices())
    src = jax.device_put(g, NamedSharding(Mesh(devs.reshape(8), ("x",)), P("x")))
    await ts.put("s", src, store_name=store)
    like = jax.device_put(
        np.zeros_like(g),
        NamedSharding(Mesh(devs.reshape(4, 2), ("a", "b")), P("b", "a")),
    )
    out = await ts.get("s", like=like, store_name=store)
    np.testing.assert_array_equal(np.asarray(out), g)
