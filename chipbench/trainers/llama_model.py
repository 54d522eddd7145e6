"""Trainer plug-in ``llama_model``: the repo's own decoder
(`torchstore_tpu/models/llama.py`) at a preset's widths, trained by the real
step (`parallel.make_train_step`, `optax.sgd`). After an acquire the
generator's forward on a short prompt must equal the trainer's bitwise
(the trainer's params laid out as the generator's).

The configuration's ``trainer`` block:

    {"plugin": "llama_model", "preset": "<LlamaConfig classmethod>",
     "param_dtype": "bfloat16", "train_tokens": [1, 512],
     "learning_rate": 0.05, "prompt_len": 16}

Depth comes from the configuration's ``num_hidden_layers``; the test checks
the preset's widths against the file's keys."""

import dataclasses

STEP_PROGRAM = "train_step"  # jit's name for the step, as the device trace has it


def model_config(config: dict):
    import jax.numpy as jnp

    from torchstore_tpu.models.llama import LlamaConfig

    spec = config["trainer"]
    return dataclasses.replace(
        getattr(LlamaConfig, spec["preset"])(),
        num_layers=config["num_hidden_layers"],
        param_dtype=jnp.dtype(spec["param_dtype"]),
    )


class Trainer:
    def __init__(self, config: dict, devices, rule_set: dict, seed: int):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec

        from chipbench import trees
        from torchstore_tpu import parallel
        from torchstore_tpu.models.llama import Llama

        spec = config["trainer"]
        cfg = model_config(config)
        model = Llama(cfg)

        def init(rng):
            return parallel.unbox(model.init(rng, jnp.zeros((1, 8), jnp.int32)))

        shapes = jax.eval_shape(init, jax.random.key(seed))
        self.shardings = trees.shardings_for(shapes, rule_set, devices)
        # One jitted call from the seed, in the type the weights are served
        # in, created already placed.
        self.params = jax.jit(init, out_shardings=self.shardings)(
            jax.random.key(seed)
        )
        optimizer = optax.sgd(spec["learning_rate"])
        self._opt_state = optimizer.init(self.params)
        self._train_step = parallel.make_train_step(model, optimizer)
        self._forward = jax.jit(model.apply)
        batch, seq = spec["train_tokens"]
        # One batch for every step, from seed+1: the inputs are the seed's.
        first = jax.tree.leaves(self.shardings)[0]
        self._tokens = jax.device_put(
            jax.random.randint(
                jax.random.key(seed + 1), (batch, seq + 1), 0, cfg.vocab_size
            ),
            NamedSharding(first.mesh, PartitionSpec())
            if rule_set.get("mesh")
            else first,
        )
        self._prompt = self._tokens[:1, : spec["prompt_len"]]
        self.loss = None

    def step(self) -> None:
        """One optimizer step; the old params are donated."""
        self.params, self._opt_state, self.loss = self._train_step(
            self.params, self._opt_state, self._tokens
        )

    def check(self, generator, reference) -> list[str]:
        """The generator's forward on the prompt is finite and equals, bit
        for bit, the forward of ``reference``: the trainer's params laid out
        as the generator's are (another layout sums in another order)."""
        import jax.numpy as jnp

        from chipbench.check import mismatched_leaves

        got = self._forward(generator, self._prompt)
        want = self._forward(reference, self._prompt)
        problems = []
        if not bool(jnp.isfinite(got).all()):
            problems.append("generator logits are not finite")
        if mismatched_leaves(got, want):
            problems.append("generator logits differ from the trainer's forward")
        return problems


def make(config: dict, devices, rule_set: dict, seed: int) -> Trainer:
    return Trainer(config, devices, rule_set, seed)
