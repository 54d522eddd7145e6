"""RL weight sync: a learner actor trains a flax Llama and publishes weights;
generator actors pull them (resharded) and run inference.

Equivalent of the reference's example/torchstore_rl.py, TPU-first: the
learner trains fsdp-sharded on its mesh, generators pull tensor-parallel on
theirs — the store reshards automatically. Publishing rides the versioned
weight channel (WeightPublisher/WeightSubscriber): the learner publishes,
generators BLOCK until a newer version commits (no version bookkeeping, no
polling), and old versions are garbage-collected automatically.

The learner and each generator are separate actor PROCESSES that all use
jax, and a chip belongs to one process at a time: on real hardware this
layout needs a chip (or slice) per actor process. On a one-chip machine run
it on virtual CPU devices:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/torchstore_rl.py

(``chip_smoke.py`` drives the same trainer -> store -> generator loop on one
chip from one process.)
"""

import asyncio

import numpy as np

import torchstore_tpu as ts
from torchstore_tpu.runtime import Actor, endpoint, spawn_actors

STORE = "rl_example"
STEPS = 3


def _jax():
    import jax

    from torchstore_tpu.utils import enable_compile_cache

    enable_compile_cache()
    return jax


class Learner(Actor):
    def __init__(self):
        jax = _jax()
        import jax.numpy as jnp
        import optax

        from torchstore_tpu import parallel
        from torchstore_tpu.models.llama import Llama, LlamaConfig

        self.jax = jax
        cfg = LlamaConfig.tiny()
        self.model = Llama(cfg)
        self.mesh = parallel.make_mesh({"fsdp": 4})
        boxed = self.model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
        self.params = parallel.unbox(parallel.shard_params(boxed, self.mesh))
        self.optimizer = optax.adamw(1e-3)
        self.opt_state = self.optimizer.init(self.params)
        self.step_fn = parallel.make_train_step(self.model, self.optimizer)
        self.vocab = cfg.vocab_size
        self.publisher = ts.WeightPublisher("policy", store_name=STORE)

    @endpoint
    async def train_and_publish(self, step: int) -> float:
        jax = self.jax
        tokens = jax.random.randint(
            jax.random.key(step), (4, 16), 0, self.vocab
        )
        self.params, self.opt_state, loss = self.step_fn(
            self.params, self.opt_state, tokens
        )
        await self.publisher.publish({"params": self.params})
        return float(loss)


class Generator(Actor):
    def __init__(self):
        jax = _jax()
        import jax.numpy as jnp

        from torchstore_tpu import parallel
        from torchstore_tpu.models.llama import Llama, LlamaConfig

        self.jax = jax
        cfg = LlamaConfig.tiny()
        self.model = Llama(cfg)
        self.mesh = parallel.make_mesh({"tp": 8})
        boxed = self.model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
        self.template = parallel.unbox(parallel.shard_params(boxed, self.mesh))
        self.subscriber = ts.WeightSubscriber("policy", store_name=STORE)

    @endpoint
    async def sync_and_generate(self) -> list[int]:
        import jax.numpy as jnp

        # Blocks until a version NEWER than the last acquired one commits;
        # the fsdp-sharded push reshards into this mesh's tp layout on pull.
        synced, _version = await self.subscriber.acquire(
            user_state_dict={"params": self.template}, timeout=60.0
        )
        self.template = synced["params"]
        prompt = jnp.zeros((1, 4), jnp.int32)
        logits = self.model.apply(self.template, prompt)
        return [int(t) for t in jnp.argmax(logits[0, -2:], axis=-1)]


async def main():
    await ts.initialize(store_name=STORE)
    learner = await spawn_actors(1, Learner, "learner")
    generators = await spawn_actors(2, Generator, "generator")
    try:
        for step in range(STEPS):
            loss = await learner.train_and_publish.call_one(step)
            outs = await generators.sync_and_generate.call()
            print(f"step {step}: loss={loss:.4f} generator_tokens={outs}")
            assert outs[0] == outs[1], "generators must agree after sync"
    finally:
        await generators.stop()
        await learner.stop()
        await ts.shutdown(STORE)
    print("RL weight-sync example OK")


if __name__ == "__main__":
    asyncio.run(main())
