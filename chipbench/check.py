"""The comparison that decides ``correct``: bitwise equality per leaf on the
device, placement, version. Copied from `chip_smoke.py` (PR 21) and widened
to compare a whole tree in one program (a tree of 800 leaves is otherwise
800 round trips to the device). jax is imported inside functions only."""

import functools


@functools.cache
def _same_bits_per_leaf():
    import jax
    import jax.numpy as jnp

    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}

    def same(a, b):
        as_bits = uint[a.dtype.itemsize]
        return jnp.array_equal(
            jax.lax.bitcast_convert_type(a, as_bits),
            jax.lax.bitcast_convert_type(b, as_bits),
        )

    @jax.jit
    def same_tree(got, want):
        return jnp.stack([same(a, b) for a, b in zip(got, want)])

    return same_tree


def mismatched_leaves(got, want) -> list[str]:
    """Paths of the leaves of ``got`` that are not BITWISE equal to
    ``want``'s (compared on the device, as unsigned integers: a NaN equals
    itself, -0.0 differs from 0.0). A leaf of another shape or dtype is a
    mismatch, a tree of another length an error."""
    import jax
    import numpy as np

    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    if len(flat_got) != len(flat_want):
        raise AssertionError(
            f"{len(flat_got)} leaves acquired, {len(flat_want)} expected"
        )
    names = [jax.tree_util.keystr(path) for path, _ in flat_got]
    bad = [
        name
        for name, (_, a), b in zip(names, flat_got, flat_want)
        if a.shape != b.shape or a.dtype != b.dtype
    ]
    if bad:
        return bad
    verdicts = np.asarray(
        _same_bits_per_leaf()([a for _, a in flat_got], flat_want)
    )
    return [name for name, ok in zip(names, verdicts) if not ok]


def misplaced_leaves(tree, shardings) -> list[str]:
    """One line per leaf that is not a ``jax.Array`` laid out as its entry of
    ``shardings`` says: on exactly those devices, each shard of the shape the
    sharding prescribes."""
    import jax

    out = []
    flat = jax.tree_util.tree_leaves_with_path(tree)
    for (path, x), want in zip(flat, jax.tree.leaves(shardings)):
        name = jax.tree_util.keystr(path)
        if not isinstance(x, jax.Array):
            out.append(f"{name} is {type(x).__name__}")
            continue
        on = sorted(s.device.id for s in x.addressable_shards)
        expected = sorted(d.id for d in want.device_set)
        if on != expected:
            out.append(f"{name} sits on {on}, expected {expected}")
        elif not x.sharding.is_equivalent_to(want, x.ndim):
            out.append(f"{name} is sharded {x.sharding}, expected {want}")
        elif any(
            s.data.shape != want.shard_shape(x.shape) for s in x.addressable_shards
        ):
            out.append(f"{name} has a shard of the wrong shape")
    return out
