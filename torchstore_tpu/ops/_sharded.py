"""Shared shard_map wrapper for the sequence-parallel attention ops."""

from __future__ import annotations

import functools


@functools.cache
def make_sharded_attention(
    body,
    mesh,
    axis_name: str,
    causal: bool,
    head_axis: str | None = None,
    impl: str | None = None,
    relax_vma: bool = False,
):
    """jit(shard_map(body)) over (q, k, v) sequence-sharded on ``axis_name``
    (and optionally head-sharded on ``head_axis`` — tensor-parallel heads
    compose with both bodies since they only collective over the sequence
    axis). ``impl`` forwards a block-body selector to bodies that take one
    (ring attention). ``relax_vma``: set by callers whose body may run a
    pallas kernel — pallas calls inside shard_map trip the vma type checker
    in interpret mode (jax's own error suggests the flag); every other body
    keeps shard_map's varying-type checking (it catches mis-specified
    collectives loudly). Cached so repeat calls reuse the compiled
    executable."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    kwargs = {"axis_name": axis_name, "causal": causal}
    if impl is not None:
        kwargs["impl"] = impl
    spec = P(None, axis_name, head_axis, None)
    fn = shard_map(
        functools.partial(body, **kwargs),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=not relax_vma,
    )
    return jax.jit(fn)
