"""Mesh + sharding helpers: logical-axis rules, param sharding, train step.

This is where the framework's multi-chip story lives (SURVEY §2.4: any
sharding expressible as per-device slices over an N-D mesh can be stored and
re-fetched under any other). Models annotate params with logical axes
(``vocab``/``embed``/``heads``/``mlp``/``expert``); these rules map them onto
mesh axes (dp/fsdp/tp/ep) and XLA inserts the collectives — the jax-native
replacement for the reference's NCCL/process-group machinery.
"""

from __future__ import annotations

import numpy as np


def make_mesh(shape: dict[str, int], devices=None):
    """Mesh from {axis: size}, e.g. {"dp": 2, "tp": 4}."""
    import jax
    from jax.sharding import Mesh

    sizes = tuple(shape.values())
    if devices is None:
        devices = jax.devices()[: int(np.prod(sizes))]
    return Mesh(np.array(devices).reshape(sizes), tuple(shape.keys()))


# Logical-axis -> mesh-axis rules (MaxText-style). First matching mesh axis
# present in the mesh wins; unmatched axes replicate.
DEFAULT_RULES = (
    ("vocab", ("tp",)),
    ("embed", ("fsdp",)),
    ("heads", ("tp",)),
    ("kv_heads", ("tp",)),
    ("mlp", ("tp",)),
    ("expert", ("ep", "tp")),
    ("batch", ("dp", "fsdp")),
    ("seq", ("sp",)),
)


def logical_to_mesh_axes(logical_axes, mesh, rules=DEFAULT_RULES):
    from jax.sharding import PartitionSpec

    if logical_axes is None:
        return PartitionSpec()
    out = []
    used = set()
    for axis in logical_axes:
        resolved = None
        for name, candidates in rules:
            if axis == name:
                for cand in candidates:
                    if cand in mesh.axis_names and cand not in used:
                        resolved = cand
                        break
                break
        if resolved is not None:
            used.add(resolved)
        out.append(resolved)
    return PartitionSpec(*out)


def _is_boxed(leaf) -> bool:
    from flax.core import meta

    return isinstance(leaf, meta.Partitioned)


def param_shardings(params, mesh, rules=DEFAULT_RULES):
    """The ``NamedSharding`` of every param on ``mesh``, as a tree shaped
    like ``unbox(params)``: logical-axis metadata (flax
    ``nn.with_logical_partitioning``) resolved through ``rules``; params
    without metadata replicate. Needs only the boxes, so the abstract tree
    of ``jax.eval_shape(model.init, ...)`` does — its result is what a
    jitted init takes as ``out_shardings`` to create params already
    sharded."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    def sharding(leaf):
        spec = (
            logical_to_mesh_axes(leaf.names, mesh, rules)
            if _is_boxed(leaf)
            else PartitionSpec()
        )
        return NamedSharding(mesh, spec)

    return jax.tree.map(sharding, params, is_leaf=_is_boxed)


def shard_params(params, mesh, rules=DEFAULT_RULES):
    """Apply logical-axis metadata (flax ``nn.with_logical_partitioning``) to
    place a param pytree on the mesh; params without metadata replicate."""
    import jax

    return jax.tree.map(
        lambda leaf, sharding: jax.device_put(
            leaf.value if _is_boxed(leaf) else leaf, sharding
        ),
        params,
        param_shardings(params, mesh, rules),
        is_leaf=_is_boxed,
    )


def unbox(params):
    """Strip flax Partitioned metadata boxes, keeping raw arrays."""
    from flax.core import meta

    return meta.unbox(params)


def activation_rules(mesh, rules=DEFAULT_RULES):
    """flax ``logical_axis_rules`` context manager resolving our logical
    axes against ``mesh`` — activates the model's activation sharding
    constraints (batch->dp/fsdp, seq->sp for sequence parallelism)."""
    import flax.linen as nn

    # Different logical axes may share one mesh axis (they live on different
    # tensors); per-tensor axis-uniqueness is handled in logical_to_mesh_axes.
    resolved = [
        (name, next((c for c in candidates if c in mesh.axis_names), None))
        for name, candidates in rules
    ]
    return nn.logical_axis_rules(resolved)


def reshard(x, sharding):
    """In-process resharding over ICI: when source and destination live in
    the same jax runtime (one process, or SPMD multi-controller where every
    participant calls this), ``device_put`` compiles to direct device-to-
    device transfers / XLA collectives over ICI — no host round trip.

    This is the TPU answer to the reference's device-side RDMA rung
    (SURVEY §2.3 monarch.rdma): between *separate* actor groups with
    separate runtimes the store's SHM/bulk transports carry the bytes, but
    whenever the caller's own mesh holds both layouts this path wins by an
    order of magnitude."""
    import jax

    return jax.device_put(x, sharding)


def make_train_step(model, optimizer):
    """A jittable causal-LM train step (loss = next-token cross-entropy).
    Sharding propagates from the input shardings (params/opt_state/tokens
    placed via ``shard_params`` / device_put); params and optimizer state are
    donated so updates happen in place on device."""
    import jax
    import optax

    def loss_fn(params, tokens):
        logits = model.apply(params, tokens[:, :-1])
        targets = tokens[:, 1:]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        ).mean()

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(train_step, donate_argnums=(0, 1))
