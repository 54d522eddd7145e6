"""Resharding demo: put a jax.Array on one mesh layout, get it on another,
with PUT/GET wall-time printed (equivalent of the reference's
example/dtensor.py). Needs 8 devices; without 8 chips, run on virtual CPU
devices:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/reshard.py
"""

import asyncio
import time

import numpy as np

import torchstore_tpu as ts


async def main():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchstore_tpu.utils import enable_compile_cache

    enable_compile_cache()

    await ts.initialize(store_name="reshard")
    try:
        devs = np.array(jax.devices())
        mesh_src = Mesh(devs.reshape(2, 4), ("x", "y"))
        mesh_dst = Mesh(devs.reshape(4, 2), ("a", "b"))
        global_arr = np.arange(1024 * 768, dtype=np.float32).reshape(1024, 768)

        src = jax.device_put(global_arr, NamedSharding(mesh_src, P("x", "y")))
        t0 = time.perf_counter()
        await ts.put("weights", src, store_name="reshard")
        t1 = time.perf_counter()
        print(f"PUT 2x4 mesh ({global_arr.nbytes/1e6:.1f} MB): {t1-t0:.4f}s")

        like = jax.device_put(
            np.zeros_like(global_arr), NamedSharding(mesh_dst, P("b", "a"))
        )
        t0 = time.perf_counter()
        out = await ts.get("weights", like=like, store_name="reshard")
        t1 = time.perf_counter()
        print(f"GET as 4x2 mesh (transposed spec): {t1-t0:.4f}s")

        np.testing.assert_array_equal(np.asarray(out), global_arr)
        print("reshard example OK:", out.sharding)
    finally:
        await ts.shutdown("reshard")


if __name__ == "__main__":
    asyncio.run(main())
