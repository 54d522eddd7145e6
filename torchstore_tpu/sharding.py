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

import collections
import functools
import math
import threading
import weakref
from typing import Any, Optional

import numpy as np

from torchstore_tpu.native import copy_into
from torchstore_tpu.observability import metrics as obs_metrics
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


# An array of this many bytes or more leaves the device in row blocks of at
# most D2H_CHUNK_BYTES, D2H_WINDOW of them in flight per device, into a
# recycled host buffer; a smaller one leaves whole. Measured on a TPU v5e
# (PERF.md section 6, PR 25): an array of 32 MiB or more, awaited whole,
# lands in memory the allocator has just mapped and is held to the rate at
# which fresh pages fault in (0.7 GB/s); 8 MiB blocks into touched memory
# leave at 5.8 GB/s. Constants, not settings: they follow the allocator's
# 32 MiB mmap ceiling, not a deployment.
D2H_CHUNK_BYTES = 8 << 20
D2H_CHUNK_THRESHOLD = 3 * D2H_CHUNK_BYTES
D2H_WINDOW = 4

_D2H_BYTES = obs_metrics.counter(
    "ts_d2h_bytes_total",
    "Bytes copied device to host for puts, by path (chunked/whole)",
)
_D2H_CHUNKS = obs_metrics.counter(
    "ts_d2h_chunks_total", "Row-block chunks of chunked device to host copies"
)
_POOL_HITS = obs_metrics.counter(
    "ts_d2h_pool_hits_total",
    "Chunked device to host copies that landed in a recycled host buffer",
)
_POOL_MISSES = obs_metrics.counter(
    "ts_d2h_pool_misses_total",
    "Chunked device to host copies that landed in a fresh (unfaulted) buffer",
)
_POOL_BYTES = obs_metrics.gauge(
    "ts_d2h_pool_bytes", "Host bytes the device to host buffer pool holds free"
)


class HostBufferPool:
    """Recycled host buffers for chunked device->host copies: their pages
    are already faulted in, which a fresh allocation of this size never is.

    ``take`` hands out a uint8 array over the smallest free buffer that
    fits (a miss allocates). The buffer comes back only when the last
    reference to that array, or to any view of it, has died: whoever still
    holds a put request's tensor keeps its bytes, and no later put can
    write into memory an earlier put's reader can see. The pool holds free
    at most what was out at once, the oldest buffers going first."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._free: list[np.ndarray] = []  # oldest release first
        # Buffers given back and not yet counted: a finalizer may run while
        # this very thread holds the lock (the collector starts anywhere),
        # so giving back only appends and settles if the lock is free.
        self._returned: collections.deque = collections.deque()
        self._out = 0
        self._most_out = 0

    def take(self, nbytes: int) -> np.ndarray:
        with self._lock:
            self._settle()
            fits = [b for b in self._free if b.nbytes >= nbytes]
            raw = min(fits, key=lambda b: b.nbytes) if fits else None
            if raw is None:
                _POOL_MISSES.inc()
                raw = np.empty(nbytes, np.uint8)
            else:
                _POOL_HITS.inc()
                self._free = [b for b in self._free if b is not raw]
            self._out += raw.nbytes
            self._most_out = max(self._most_out, self._out)
            self._settle()
        # A memoryview between the two keeps every view's ``.base`` on
        # ``owner`` (numpy collapses chains of ndarray bases), so ``owner``
        # dies exactly when the last view of these bytes does.
        owner = np.frombuffer(memoryview(raw), np.uint8, count=nbytes)
        weakref.finalize(owner, self._give_back, raw).atexit = False
        return owner

    def _give_back(self, raw: np.ndarray) -> None:
        self._returned.append(raw)
        if self._lock.acquire(blocking=False):
            try:
                self._settle()
            finally:
                self._lock.release()

    def _settle(self) -> None:
        while self._returned:
            raw = self._returned.popleft()
            self._out -= raw.nbytes
            self._free.append(raw)
        held = sum(b.nbytes for b in self._free)
        while self._free and held + self._out > self._most_out:
            held -= self._free.pop(0).nbytes
        _POOL_BYTES.set(held)

    def clear(self) -> None:
        """Drop every free buffer; what is still out is dropped when it
        comes back."""
        with self._lock:
            self._most_out = 0
            self._settle()


_host_pool = HostBufferPool()


def host_pool() -> HostBufferPool:
    """The process's pool (``ts.shutdown`` empties it)."""
    return _host_pool


def chunk_plan(arr) -> Optional[tuple[int, int]]:
    """How a single-device array (a whole array or a shard's ``.data``)
    leaves the device: None for whole, or ``(axis, rows)`` for blocks of
    ``rows`` indices along ``axis``, one index at a time on the axes before
    it and everything after it. Decided from the array's bytes and shape
    alone: whole under D2H_CHUNK_THRESHOLD, and whole for sub-byte dtypes
    (packed on the device, a byte an element on the host)."""
    nbytes = arr.nbytes
    if nbytes < D2H_CHUNK_THRESHOLD:
        return None
    from jax import dtypes

    if dtypes.itemsize_bits(arr.dtype) < 8:
        return None
    return _block_plan(tuple(arr.shape), arr.dtype.itemsize, D2H_CHUNK_BYTES)


@functools.lru_cache(maxsize=4096)
def _block_plan(
    shape: tuple[int, ...], itemsize: int, chunk_bytes: int
) -> Optional[tuple[int, int]]:
    below = itemsize  # bytes of one index along ``axis``
    for axis in reversed(range(len(shape))):
        if below * shape[axis] > chunk_bytes:
            most = chunk_bytes // below
            # Equal blocks where the axis divides (one program, no tail).
            rows = next(
                (r for r in range(most, most // 2, -1) if shape[axis] % r == 0),
                most,
            )
            return axis, rows
        below *= shape[axis]
    return None


@functools.cache
def _slicer():
    """The one device program a chunked copy runs: a block of ``rows``
    along ``axis`` starting at the TRACED index ``at``, so a leaf compiles
    once (and once more for a ragged tail), never per chunk."""
    import jax
    from jax import lax

    def block(x, at, *, axis: int, rows: int):
        starts = [at[i] for i in range(axis + 1)] + [0] * (x.ndim - axis - 1)
        sizes = (1,) * axis + (rows,) + x.shape[axis + 1 :]
        return lax.dynamic_slice(x, starts, sizes).reshape(sizes[axis:])

    return jax.jit(block, static_argnames=("axis", "rows"))


class _ChunkedCopy:
    """One big single-device array on its way into a pooled host buffer,
    block by block; ``_host_copies`` keeps its window."""

    def __init__(self, arr, plan: tuple[int, int]) -> None:
        self.arr = arr
        self.axis, self.rows = plan
        shape = tuple(int(s) for s in arr.shape)
        self.chunks = math.prod(shape[: self.axis]) * -(
            -shape[self.axis] // self.rows
        )
        self._dest = host_pool().take(arr.nbytes).view(arr.dtype).reshape(shape)
        self._blocks = (
            (lead, start, min(self.rows, shape[self.axis] - start))
            for lead in np.ndindex(*shape[: self.axis])
            for start in range(0, shape[self.axis], self.rows)
        )
        self.pending: collections.deque = collections.deque()

    def issue(self, d2h: Optional[D2HSeconds]) -> None:
        """Start the next block's copy, if one is left."""
        block = next(self._blocks, None)
        if block is None:
            return
        lead, start, rows = block
        with span("d2h.issue") as sp:
            chunk = _slicer()(
                self.arr,
                np.asarray((*lead, start), np.int32),
                axis=self.axis,
                rows=rows,
            )
            chunk.copy_to_host_async()
        self.pending.append((lead, start, rows, chunk))
        if d2h is not None:
            d2h.seconds += sp.elapsed

    def land(self, d2h: Optional[D2HSeconds]) -> None:
        """Wait for the oldest block, copy it to its rows and drop it (the
        runtime's few MB of host memory go back to the heap, pages mapped,
        for the next block)."""
        lead, start, rows, chunk = self.pending.popleft()
        with span(
            "d2h.wait", nbytes=chunk.nbytes, chunks=self.chunks, window=D2H_WINDOW
        ) as sp:
            copy_into(self._dest[lead][start : start + rows], np.asarray(chunk))
            del chunk
        if d2h is not None:
            d2h.seconds += sp.elapsed

    def result(self) -> np.ndarray:
        """The host array, read-only as ``np.asarray`` of a device array is."""
        _D2H_BYTES.inc(self._dest.nbytes, path="chunked")
        _D2H_CHUNKS.inc(self.chunks)
        out = self._dest.view()
        out.flags.writeable = False
        return out


def issue_d2h(arrays, d2h: Optional[D2HSeconds] = None) -> None:
    """Start the device->host copy of every array in ``arrays`` (whole
    arrays or shards' ``.data``) that leaves the device whole, before any
    is awaited: one ``d2h.issue`` span for the lot. An array that leaves in
    chunks is skipped: issued whole as well, every byte would move twice."""
    with span("d2h.issue") as sp:
        for arr in arrays:
            if chunk_plan(arr) is None:
                arr.copy_to_host_async()
    if d2h is not None:
        d2h.seconds += sp.elapsed


def _to_host(arr, d2h: Optional[D2HSeconds]) -> np.ndarray:
    """The wait for one device array's bytes (``d2h.wait``)."""
    with span("d2h.wait", nbytes=arr.nbytes) as sp:
        out = np.asarray(arr)
    _D2H_BYTES.inc(out.nbytes, path="whole")
    if d2h is not None:
        d2h.seconds += sp.elapsed
    return out


def _host_copies(arrays: list, d2h: Optional[D2HSeconds]) -> list[np.ndarray]:
    """Host copies of single-device arrays, in order. The small ones as
    ever: every copy issued before the first is awaited. The big ones in
    chunks, each device's window filled in turn, so copies from different
    chips still ride their DMA engines at the same time."""
    issue_d2h(arrays, d2h)
    copies = {
        i: _ChunkedCopy(arr, plan)
        for i, arr in enumerate(arrays)
        if (plan := chunk_plan(arr)) is not None
    }
    for copy in copies.values():
        for _ in range(D2H_WINDOW):  # the window: one more only as one lands
            copy.issue(d2h)
    live = collections.deque(copies.values())
    while live:
        copy = live.popleft()
        copy.land(d2h)
        copy.issue(d2h)
        if copy.pending:
            live.append(copy)
    return [
        copies[i].result() if i in copies else _to_host(arr, d2h)
        for i, arr in enumerate(arrays)
    ]


def put_requests(
    key: str, x, d2h: Optional[D2HSeconds] = None
) -> list[Request]:
    """Expand a jax.Array into per-addressable-shard put requests.

    One process may own several devices (a TPU host owns 4-8 chips), so a
    single put covers all addressable shards — the multi-controller analog of
    the reference's one-shard-per-rank DTensor put. Device->host staging is
    OVERLAPPED across chips (the reference overlaps CUDA side-stream copies
    the same way, /root/reference/torchstore/transport/shared_memory.py:362-420):
    shards under D2H_CHUNK_THRESHOLD have their async copies issued before
    the first is awaited; bigger ones leave in row blocks, a window of them
    in flight per chip (``_host_copies``). Either way a request holds ONE
    contiguous host array per shard. ``d2h`` accumulates the seconds spent
    issuing and waiting."""
    import jax  # noqa: F401

    sharding = x.sharding
    if _is_demotable(sharding):
        src = x
        if chunk_plan(x) is not None and len(sharding.device_set) > 1:
            src = x.addressable_data(0)  # replicated: one chip's copy
        (data,) = _host_copies([src], d2h)
        return [Request.from_tensor(key, data)]
    mesh = sharding.mesh
    mesh_shape = tuple(int(s) for s in mesh.devices.shape)
    coords_map = _mesh_coords_map(mesh)
    global_shape = tuple(int(s) for s in x.shape)
    shards = list(x.addressable_shards)
    requests = []
    for shard, data in zip(
        shards, _host_copies([shard.data for shard in shards], d2h)
    ):
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
