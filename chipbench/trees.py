"""Trees of weights: how a configuration's named sharding rule sets become
shardings, what an acquire lands in, and how many bytes a tree holds. Knows
no configuration by name: the rule sets are data in the configuration's
file. jax is imported inside functions only."""

import re


def leaf_name(path) -> str:
    """A leaf's name as the rules see it: the keys of its path joined by
    dots (``model.layers.0.mlp.gate.weight``, ``params.layer_0.attn...``)."""
    parts = []
    for entry in path:
        for attr in ("key", "name", "idx"):
            if hasattr(entry, attr):
                parts.append(str(getattr(entry, attr)))
                break
        else:
            parts.append(str(entry))
    return ".".join(parts)


def shardings_for(tree, rule_set: dict, devices):
    """The sharding of every leaf of ``tree`` (arrays or shapes) under one
    rule set of a configuration:

        {"mesh": null}                                  all on devices[0]
        {"mesh": {"x": 4}, "rules": [[regex, spec], ...]}

    The first rule whose regex is found in the leaf's name gives its
    ``PartitionSpec`` (a list of axis names and nulls); a leaf no rule names
    is an error, so that a new leaf cannot be replicated by oversight."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

    axes = rule_set.get("mesh")
    if not axes:
        single = SingleDeviceSharding(devices[0])
        return jax.tree.map(lambda _: single, tree)
    n = int(np.prod(list(axes.values())))
    if len(devices) < n:
        raise ValueError(f"mesh {axes} needs {n} devices, {len(devices)} given")
    mesh = Mesh(np.asarray(devices[:n]).reshape(tuple(axes.values())), tuple(axes))
    rules = [(re.compile(rx), spec) for rx, spec in rule_set["rules"]]

    def pick(path, leaf):
        name = leaf_name(path)
        for rx, spec in rules:
            if rx.search(name):
                if len(spec) > len(leaf.shape):
                    raise ValueError(f"{name}: spec {spec} for shape {leaf.shape}")
                return NamedSharding(mesh, PartitionSpec(*spec))
        raise ValueError(f"no sharding rule names the leaf {name}")

    return jax.tree_util.tree_map_with_path(pick, tree)


def as_targets(tree, shardings):
    """Acquire targets that hold nothing on the device: one sharded
    ``ShapeDtypeStruct`` per leaf."""
    import jax

    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree,
        shardings,
    )


def tree_nbytes(tree) -> int:
    """Bytes of a tree of arrays (or of their shapes)."""
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
