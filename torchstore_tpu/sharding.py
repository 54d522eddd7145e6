"""jax.Array / NamedSharding <-> TensorSlice bridge.

This replaces the reference's DTensor integration
(/root/reference/torchstore/transport/types.py:58-196, which leans on
``_compute_local_shape_and_global_offset``): here shard placement comes from
``jax.sharding.NamedSharding`` — each addressable shard's ``.index`` gives its
(offsets, local_shape) and the mesh position of its device gives the commit
coordinates. jax is imported lazily so storage volumes / host-only processes
never pay for it.
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

from torchstore_tpu.observability.tracing import span, trace_enabled
from torchstore_tpu.transport.types import Request, TensorSlice
from torchstore_tpu.utils import Box


def is_jax_array(value: Any) -> bool:
    try:
        import jax
    except ImportError:
        return False
    return isinstance(value, jax.Array)


def is_sharded_spec(value: Any) -> bool:
    """A jax.ShapeDtypeStruct carrying a sharding: a fetch target that needs
    no prefilled array (orbax-style restore targets)."""
    try:
        import jax
    except ImportError:
        return False
    return (
        isinstance(value, jax.ShapeDtypeStruct)
        and getattr(value, "sharding", None) is not None
    )


def is_plain_spec(value: Any) -> bool:
    """A jax.ShapeDtypeStruct WITHOUT a sharding: fetch target producing a
    default-placed device array of the spec's shape/dtype."""
    try:
        import jax
    except ImportError:
        return False
    return (
        isinstance(value, jax.ShapeDtypeStruct)
        and getattr(value, "sharding", None) is None
    )


def _mesh_coords_map(mesh) -> dict:
    """device -> coordinates in the mesh array."""
    coords = {}
    for idx, dev in np.ndenumerate(mesh.devices):
        coords[dev] = tuple(int(i) for i in idx)
    return coords


def _is_demotable(sharding) -> bool:
    """Fully-replicated / single-device arrays are stored as plain tensors —
    the reference's fully-local DTensor demotion (MoE/EP use case, invariant
    7; /root/reference/torchstore/transport/types.py:58-85)."""
    import jax

    if not isinstance(sharding, jax.sharding.NamedSharding):
        return True
    if sharding.mesh.devices.size == 1:
        return True
    return sharding.is_fully_replicated


class D2HSeconds:
    """Seconds one put batch spent issuing device->host copies and waiting
    for their bytes: the put's ``d2h`` stage (observability/timeline.py)."""

    __slots__ = ("seconds",)

    def __init__(self) -> None:
        self.seconds = 0.0


def issue_d2h(arrays, d2h: Optional[D2HSeconds] = None) -> None:
    """Start the device->host copy of every array in ``arrays`` (whole
    arrays or shards' ``.data``) before any is awaited: one ``d2h.issue``
    span for the lot."""
    with span("d2h.issue") as sp:
        for arr in arrays:
            arr.copy_to_host_async()
    if d2h is not None:
        d2h.seconds += sp.elapsed


def _to_host(arr, d2h: Optional[D2HSeconds]) -> np.ndarray:
    """The wait for one device array's bytes (``d2h.wait``)."""
    with span("d2h.wait", nbytes=arr.nbytes) as sp:
        out = np.asarray(arr)
    if d2h is not None:
        d2h.seconds += sp.elapsed
    return out


def put_requests(
    key: str, x, d2h: Optional[D2HSeconds] = None
) -> list[Request]:
    """Expand a jax.Array into per-addressable-shard put requests.

    One process may own several devices (a TPU host owns 4-8 chips), so a
    single put covers all addressable shards — the multi-controller analog of
    the reference's one-shard-per-rank DTensor put. Device->host staging is
    OVERLAPPED: every shard's async D2H copy is issued before the first is
    awaited, so transfers from different chips ride their DMA engines
    concurrently (the reference overlaps CUDA side-stream copies the same
    way, /root/reference/torchstore/transport/shared_memory.py:362-420).
    ``d2h`` accumulates the seconds spent issuing and waiting."""
    import jax  # noqa: F401

    sharding = x.sharding
    if _is_demotable(sharding):
        issue_d2h((x,), d2h)
        return [Request.from_tensor(key, _to_host(x, d2h))]
    mesh = sharding.mesh
    mesh_shape = tuple(int(s) for s in mesh.devices.shape)
    coords_map = _mesh_coords_map(mesh)
    global_shape = tuple(int(s) for s in x.shape)
    shards = list(x.addressable_shards)
    issue_d2h((shard.data for shard in shards), d2h)
    requests = []
    for shard in shards:
        data = _to_host(shard.data, d2h)
        offsets = tuple(int(sl.start or 0) for sl in shard.index)
        ts = TensorSlice(
            offsets=offsets,
            local_shape=tuple(int(s) for s in data.shape),
            global_shape=global_shape,
            coordinates=coords_map[shard.device],
            mesh_shape=mesh_shape,
        )
        requests.append(Request.from_tensor_slice(key, ts, data))
    return requests


def target_slices(like) -> list[tuple[Any, TensorSlice]]:
    """(device, TensorSlice) for every addressable shard a resharding get
    must produce to rebuild ``like``'s sharding locally."""
    import jax

    sharding = like.sharding
    global_shape = tuple(int(s) for s in like.shape)
    if _is_demotable(sharding):
        dev = next(iter(sharding.device_set))
        full = TensorSlice(
            offsets=(0,) * len(global_shape),
            local_shape=global_shape,
            global_shape=global_shape,
            coordinates=(),
            mesh_shape=(),
        )
        return [(dev, full)]
    mesh = sharding.mesh
    mesh_shape = tuple(int(s) for s in mesh.devices.shape)
    coords_map = _mesh_coords_map(mesh)
    out = []
    index_map = sharding.addressable_devices_indices_map(global_shape)
    for dev, index in index_map.items():
        offsets = tuple(int(sl.start or 0) for sl in index)
        local_shape = tuple(
            int((sl.stop if sl.stop is not None else dim) - (sl.start or 0))
            for sl, dim in zip(index, global_shape)
        )
        ts = TensorSlice(
            offsets=offsets,
            local_shape=local_shape,
            global_shape=global_shape,
            coordinates=coords_map[dev],
            mesh_shape=mesh_shape,
        )
        out.append((dev, ts))
    return out


def build_array(like, parts: list[tuple[Any, np.ndarray]]):
    """Assemble a jax.Array with ``like``'s sharding from fetched host parts
    [(device, local_array)] — the functional analog of the reference's
    in-place DTensor update (jax arrays are immutable, so a reshard-get
    returns a new array; TPU-first semantics)."""
    import jax

    sharding = like.sharding
    with span("h2d.dispatch", parts=len(parts)) as sp:
        if trace_enabled():
            sp.set(nbytes=sum(arr.nbytes for _, arr in parts))
        if _is_demotable(sharding):
            # target_slices produced a single full-array part; replicate it
            # onto every addressable device of the target sharding.
            ((_, arr),) = parts
            arrays = [
                jax.device_put(arr, d) for d in sharding.addressable_devices
            ]
        else:
            arrays = [jax.device_put(arr, dev) for dev, arr in parts]
        return jax.make_array_from_single_device_arrays(
            tuple(int(s) for s in like.shape), sharding, arrays
        )


def full_box(global_shape: tuple[int, ...]) -> Box:
    return Box((0,) * len(global_shape), tuple(global_shape))


def plan_signature(value: Any):
    """Hashable transfer-plan signature component for a jax leaf, or None
    for non-jax values. Includes the SHARDING, not just shape/dtype: two
    pushes of the same global shape under different meshes decompose into
    different request sets, so a cached plan keyed without the sharding
    would replay the wrong fan-out (the iteration-stable plan cache keys on
    this, client.SyncPlanCache)."""
    if is_jax_array(value) or is_sharded_spec(value):
        return (
            "jax",
            tuple(int(s) for s in value.shape),
            str(value.dtype),
            value.sharding,  # NamedSharding et al. are hashable
        )
    if is_plain_spec(value):
        return ("spec", tuple(int(s) for s in value.shape), str(value.dtype))
    return None
