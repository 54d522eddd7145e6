"""Direct one-hop weight sync: the store carries only metadata handles; the
consumer pulls straight from the trainer's staging buffers (SHM on the same
host). This is the steady-state RL weight-sync fast path. Needs 8 devices;
without 8 chips, run on virtual CPU devices:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/direct_sync.py
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

    await ts.initialize(store_name="direct_demo")
    try:
        devs = np.array(jax.devices())
        w = np.random.rand(1024, 512).astype(np.float32)
        trainer_sd = {
            "w": jax.device_put(
                w, NamedSharding(Mesh(devs.reshape(8), ("fsdp",)), P("fsdp", None))
            )
        }
        consumer_sd = {"w": np.zeros_like(w)}

        # First publish registers staging buffers; first pull builds the plan.
        await ts.put_state_dict("policy", trainer_sd, direct=True,
                                store_name="direct_demo")
        await ts.get_state_dict("policy", user_state_dict=consumer_sd,
                                direct=True, store_name="direct_demo")

        # Steady state: refresh + pull, writing straight into consumer memory.
        for step in range(3):
            t0 = time.perf_counter()
            await ts.put_state_dict("policy", trainer_sd, direct=True,
                                    store_name="direct_demo")
            out = await ts.get_state_dict("policy", user_state_dict=consumer_sd,
                                          direct=True, store_name="direct_demo")
            dt = time.perf_counter() - t0
            np.testing.assert_array_equal(out["w"], w)
            print(f"step {step}: sync {2 * w.nbytes / 1e6:.1f} MB in {dt*1e3:.1f} ms")
    finally:
        await ts.shutdown("direct_demo")
    print("direct sync example OK")


if __name__ == "__main__":
    asyncio.run(main())
