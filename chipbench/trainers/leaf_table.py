"""Trainer plug-in ``leaf_table``: a state dict given leaf for leaf by the
configuration's file, as a checkpoint has it, with no forward pass (the
store needs none). The tree is a flat dict ``{name: array}``.

The configuration's ``trainer`` block:

    {"plugin": "leaf_table", "dtype": "bfloat16", "init_std": 0.02,
     "leaves": [{"name": "model.layers.{layer}.mlp.gate.weight",
                 "shape": ["num_experts", "hidden_size"],
                 "for": {"layer": "num_hidden_layers"}}, ...]}

A shape entry is a number or the name of a key of the configuration (or of
its ``assumed`` group), so the widths are the file's own. ``for`` repeats a
leaf over ``range(<key>)`` for each placeholder in its name.

The trainer's step adds one to every element's bit pattern (a jitted,
donated, elementwise pass over the whole tree): version *v* then differs
bitwise, in every element of every leaf, from each of the 65 535 versions
before it, so a stale or mixed read cannot pass the comparison."""

import itertools

STEP_PROGRAM = "next_version"  # jit's name for the step, as the device trace has it


def _size(config: dict, entry) -> int:
    if isinstance(entry, int):
        return entry
    if entry in config:
        return int(config[entry])
    return int(config["assumed"][entry])


def leaf_shapes(config: dict) -> dict[str, tuple[int, ...]]:
    """{leaf name: shape} in the order of the file."""
    out: dict[str, tuple[int, ...]] = {}
    for leaf in config["trainer"]["leaves"]:
        shape = tuple(_size(config, s) for s in leaf["shape"])
        loops = leaf.get("for", {})
        ranges = [range(_size(config, key)) for key in loops.values()]
        for index in itertools.product(*ranges):
            name = leaf["name"].format(**dict(zip(loops, index)))
            if name in out:
                raise ValueError(f"leaf {name} is listed twice")
            out[name] = shape
    return out


class Trainer:
    def __init__(self, config: dict, devices, rule_set: dict, seed: int):
        import jax
        import jax.numpy as jnp

        from chipbench import trees

        spec = config["trainer"]
        dtype = jnp.dtype(spec["dtype"])
        std = spec["init_std"]
        shapes = {
            name: jax.ShapeDtypeStruct(shape, dtype)
            for name, shape in leaf_shapes(config).items()
        }
        self.shardings = trees.shardings_for(shapes, rule_set, devices)
        bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[dtype.itemsize]

        makers: dict = {}

        def maker(shape, sharding):
            # One small program per distinct (shape, sharding), run once per
            # leaf with the leaf's index: ONE program for the whole tree took
            # 610 s to compile for the chip (807 generator ops; PERF.md, PR 22).
            if (shape, sharding) not in makers:
                makers[shape, sharding] = jax.jit(
                    lambda rng, i: (
                        std * jax.random.normal(jax.random.fold_in(rng, i), shape)
                    ).astype(dtype),
                    out_shardings=sharding,
                )
            return makers[shape, sharding]

        def next_version(tree):
            return jax.tree.map(
                lambda x: jax.lax.bitcast_convert_type(
                    jax.lax.bitcast_convert_type(x, bits) + bits(1), dtype
                ),
                tree,
            )

        # Made on the device from the seed, in the type the weights are
        # served in, already placed. The fast generator: threefry over two
        # billion elements is seconds of set-up that serve no request.
        rng = jax.random.key(seed, impl="rbg")
        self.params = {
            name: maker(s.shape, self.shardings[name])(rng, i)
            for i, (name, s) in enumerate(shapes.items())
        }
        self._next_version = jax.jit(next_version, donate_argnums=0)

    def step(self) -> None:
        self.params = self._next_version(self.params)

    def check(self, generator, reference) -> list[str]:
        return []  # no forward to compare: the bitwise comparison is the check


def make(config: dict, devices, rule_set: dict, seed: int) -> Trainer:
    return Trainer(config, devices, rule_set, seed)
