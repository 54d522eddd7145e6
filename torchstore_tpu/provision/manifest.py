"""StateDictManifest: the shape of a working set, without its bytes.

A manifest describes what a state-dict publish WILL put through the store —
per-flat-key shapes, dtypes, shardings (as per-request payload sizes) and the
total — derived purely from metadata: no device->host copies, no array
materialization. It is the planner's input (provision/planner.py) and the
picklable currency of ``ts.prewarm``: a trainer can derive it from a live
state dict, a ShapeDtypeStruct tree, or construct it by hand from a model
config before any weights exist at all.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from torchstore_tpu.transport.types import _np_dtype


@dataclass(frozen=True)
class ManifestEntry:
    """One flat state-dict leaf as the data plane will see it: the key,
    global shape/dtype, and the payload size of every put request the leaf
    decomposes into (one per addressable shard for mesh-sharded jax arrays,
    exactly one otherwise)."""

    key: str
    shape: tuple[int, ...]
    dtype: str
    # Bytes of each put-request payload this leaf expands to. Sums to the
    # leaf's (transfer-dtype-adjusted) nbytes.
    request_nbytes: tuple[int, ...]

    @property
    def nbytes(self) -> int:
        return sum(self.request_nbytes)


@dataclass
class StateDictManifest:
    """Keys, shapes, dtypes, shardings (as request sizes), and total bytes of
    a working set — everything the provisioning planner needs to size pools,
    dials, and transfer plans before the first byte moves."""

    entries: list[ManifestEntry] = field(default_factory=list)
    # True when any tensor leaf is a jax array the device rung can serve
    # (device_transfer.serves): the transfer server is worth prewarming too.
    device_resident: bool = False
    # Flat keys in the SOURCE dict's insertion order — for a model state
    # dict this is model-forward order (flatten preserves dict iteration
    # order), the key order layer-streamed acquires consume layers in.
    # ``entries`` stays name-sorted for stable pool planning.
    order: tuple = ()

    @property
    def total_bytes(self) -> int:
        return sum(e.nbytes for e in self.entries)

    @property
    def key_order(self) -> list[str]:
        """Tensor-leaf flat keys in model-forward (insertion) order — the
        ``key_order`` argument of streamed acquires
        (``get_state_dict(stream=True, key_order=...)``,
        ``WeightSubscriber.acquire_streamed``)."""
        if self.order:
            named = {e.key for e in self.entries}
            return [k for k in self.order if k in named]
        return [e.key for e in self.entries]

    def segment_sizes(self, arena_max_bytes: int = 0) -> dict[int, int]:
        """{segment size: count} over every put request — exactly the pool
        the SHM transport's put handshake will ask the volume for (request
        payloads land in size-exact segments; empty tensors take the 1-byte
        minimum mapping).

        With ``arena_max_bytes`` > 0, requests at or below the threshold are
        packed the way the transport's small-key arena packs them (same
        layout function — ``transport.landing.compute_arena_layout``), so
        the provisioned pool holds ONE arena-sized segment instead of a
        thousand tiny ones the first put would never ask for."""
        sizes: dict[int, int] = {}
        small: list[int] = []
        for entry in self.entries:
            for nbytes in entry.request_nbytes:
                if 0 < arena_max_bytes and int(nbytes) <= arena_max_bytes:
                    small.append(int(nbytes))
                    continue
                size = max(int(nbytes), 1)
                sizes[size] = sizes.get(size, 0) + 1
        if len(small) >= 2:
            from torchstore_tpu.transport.landing import compute_arena_layout

            _, total = compute_arena_layout(small)
            sizes[total] = sizes.get(total, 0) + 1
        elif small:
            size = max(small[0], 1)
            sizes[size] = sizes.get(size, 0) + 1
        return sizes

    def arena_hint(self, arena_max_bytes: int) -> Optional[dict]:
        """The transport-shape arena layout for this manifest (plan-cache
        seed: ``ts.prewarm`` hands it to the client so even the FIRST
        put_state_dict adopts the provisioned layout verbatim)."""
        if arena_max_bytes <= 0:
            return None
        small = [
            int(n)
            for entry in self.entries
            for n in entry.request_nbytes
            if int(n) <= arena_max_bytes
        ]
        if len(small) < 2:
            return None
        from torchstore_tpu.transport.landing import compute_arena_layout

        offsets, total = compute_arena_layout(small)
        return {"sizes": tuple(small), "offsets": offsets, "total": total}

    def max_request_nbytes(self) -> int:
        return max(
            (n for e in self.entries for n in e.request_nbytes), default=0
        )

    @classmethod
    def from_state_dict(
        cls,
        state_dict: Any,
        transfer_dtype=None,
        transfer_quant: Optional[str] = None,
        quant_block: int = 256,
    ) -> "StateDictManifest":
        """Derive a manifest from a (possibly nested) state dict without
        moving any bytes. Tensor-ish leaves (numpy, torch, jax arrays and
        ShapeDtypeStructs, ``Shard`` wrappers) become entries; everything
        else (scalars, configs, opaque objects) is skipped — object puts ride
        the RPC codec and need no provisioning.

        ``transfer_quant`` sizes floating leaves as fused quant blobs
        (header + bitmap + packed codes + SCALE SLOT, via the shared
        ``landing.quant_wire_nbytes`` layout), so prewarmed pools hold
        exactly the scale-bearing arena segment a quantized first publish
        asks for."""
        from torchstore_tpu.state_dict_utils import flatten_state_dict

        if transfer_quant in (None, "none", ""):
            transfer_quant = None
        flat, _ = flatten_state_dict(state_dict)
        entries: list[ManifestEntry] = []
        device = False
        for key, value in sorted(flat.items()):
            entry, on_device = _entry_of(
                key, value, transfer_dtype, transfer_quant, quant_block
            )
            if entry is not None:
                entries.append(entry)
                device = device or on_device
        return cls(
            entries=entries,
            device_resident=device,
            order=tuple(flat),
        )


def _itemsize(dtype_name: str) -> int:
    try:
        return _np_dtype(dtype_name).itemsize
    except Exception:  # noqa: BLE001 - exotic dtype: assume 4 bytes
        return 4


def _is_floating_name(dtype_name: str) -> bool:
    if "bfloat16" in dtype_name:
        return True
    try:
        return np.issubdtype(np.dtype(dtype_name), np.floating)
    except TypeError:
        return "float" in dtype_name


def _transfer_itemsize(dtype_name: str, transfer_dtype) -> int:
    """Per-element wire size after the optional transfer-dtype cast (floating
    leaves only — ints/bools cross uncast, mirroring cast_floating_tensors)."""
    if transfer_dtype is not None and _is_floating_name(dtype_name):
        return _itemsize(str(np.dtype(transfer_dtype)))
    return _itemsize(dtype_name)


def _quant_entry(
    key: str,
    shape: tuple,
    dtype: str,
    transfer_quant: str,
    quant_block: int,
) -> ManifestEntry:
    """One floating leaf under wire quantization: a SINGLE fused-blob
    request (the blob is host-assembled whatever the source sharding),
    sized by the arena-layout module's quant_wire_nbytes so the scale slot
    is accounted for."""
    from torchstore_tpu.transport.landing import quant_wire_nbytes

    nelems = int(np.prod(shape)) if shape else 1
    block = quant_block if transfer_quant != "int8" else max(1, nelems)
    nbytes = quant_wire_nbytes(transfer_quant, block, nelems, len(shape))
    return ManifestEntry(key, shape, dtype, (nbytes,))


def _entry_of(
    key: str,
    value: Any,
    transfer_dtype,
    transfer_quant: Optional[str] = None,
    quant_block: int = 256,
) -> tuple[Optional[ManifestEntry], bool]:
    """(entry, is_device_resident) for one flat leaf; (None, False) for
    non-tensor leaves."""
    from torchstore_tpu import sharding as shd
    from torchstore_tpu import torch_interop
    from torchstore_tpu.client import Shard
    from torchstore_tpu.transport import device_transfer

    if transfer_quant is not None:
        entry, on_device = _entry_of(key, value, None)
        if entry is not None and _is_floating_name(entry.dtype):
            return (
                _quant_entry(
                    key, entry.shape, entry.dtype, transfer_quant, quant_block
                ),
                on_device,
            )
        return entry, on_device
    if isinstance(value, Shard):
        ts = value.tensor_slice
        shape = tuple(ts.local_shape)
        data = value.data
        dtype = str(data.dtype) if data is not None else "float32"
        itemsize = _transfer_itemsize(dtype, transfer_dtype)
        nbytes = int(np.prod(shape)) * itemsize if shape else itemsize
        return ManifestEntry(key, shape, dtype, (nbytes,)), False
    if isinstance(value, np.ndarray) or torch_interop.is_torch_tensor(value):
        shape = tuple(int(s) for s in value.shape)
        dtype = str(value.dtype).replace("torch.", "")
        itemsize = _transfer_itemsize(dtype, transfer_dtype)
        count = int(np.prod(shape)) if shape else 1
        return ManifestEntry(key, shape, dtype, (count * itemsize,)), False
    if (
        shd.is_jax_array(value)
        or shd.is_sharded_spec(value)
        or shd.is_plain_spec(value)
    ):
        shape = tuple(int(s) for s in value.shape)
        dtype = str(value.dtype)
        itemsize = _transfer_itemsize(dtype, transfer_dtype)
        on_device = shd.is_jax_array(value) and device_transfer.serves(value)
        sharding = getattr(value, "sharding", None)
        if sharding is None or shd._is_demotable(sharding):
            count = int(np.prod(shape)) if shape else 1
            return ManifestEntry(key, shape, dtype, (count * itemsize,)), on_device
        # Per-shard request sizes from the sharding's index map — the exact
        # decomposition sharding.put_requests will produce (one request per
        # addressable shard, replicated coordinates included), metadata-only.
        sizes: list[int] = []
        index_map = sharding.addressable_devices_indices_map(shape)
        for index in index_map.values():
            local = tuple(
                int((sl.stop if sl.stop is not None else dim) - (sl.start or 0))
                for sl, dim in zip(index, shape)
            )
            count = int(np.prod(local)) if local else 1
            sizes.append(count * itemsize)
        return ManifestEntry(key, shape, dtype, tuple(sizes)), on_device
    if hasattr(value, "__array_interface__"):
        arr = np.asarray(value)
        itemsize = _transfer_itemsize(str(arr.dtype), transfer_dtype)
        count = int(np.prod(arr.shape)) if arr.shape else 1
        return (
            ManifestEntry(key, tuple(arr.shape), str(arr.dtype), (count * itemsize,)),
            False,
        )
    return None, False
