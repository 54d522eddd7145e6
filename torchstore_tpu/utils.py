"""Reshard math and small helpers (numpy-only, no jax imports at module scope).

This is the TPU-native equivalent of the reference's ``torchstore/utils.py``
(see /root/reference/torchstore/utils.py:25-307): byte views for bulk
transports, global->local destination-view mapping for in-place writes,
interval intersection of tensor slices, and bounding-box assembly of fetched
parts. All math operates on host ``numpy`` arrays; ``jax.Array`` values are
converted to host views at the client boundary (see ``sharding.py``).
"""

from __future__ import annotations

import os
import socket
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np


@dataclass(frozen=True)
class Box:
    """An axis-aligned region of a global index space: ``offsets`` + ``shape``."""

    offsets: tuple[int, ...]
    shape: tuple[int, ...]

    def __post_init__(self) -> None:
        if len(self.offsets) != len(self.shape):
            raise ValueError(
                f"rank mismatch: offsets={self.offsets} shape={self.shape}"
            )

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    def stops(self) -> tuple[int, ...]:
        return tuple(o + s for o, s in zip(self.offsets, self.shape))

    def contains(self, other: "Box") -> bool:
        return all(
            oo >= so and oo + osz <= so + ssz
            for so, ssz, oo, osz in zip(
                self.offsets, self.shape, other.offsets, other.shape
            )
        )

    def to_index(self) -> tuple[slice, ...]:
        return tuple(slice(o, o + s) for o, s in zip(self.offsets, self.shape))


def intersect_boxes(a: Box, b: Box) -> Optional[Box]:
    """Per-dimension interval intersection; None when disjoint.

    Equivalent role to the reference's ``get_slice_intersection``
    (/root/reference/torchstore/utils.py:248-307), expressed over ``Box``
    regions in global coordinates.
    """
    if a.ndim != b.ndim:
        raise ValueError(f"rank mismatch: {a} vs {b}")
    offsets = []
    shape = []
    for ao, asz, bo, bsz in zip(a.offsets, a.shape, b.offsets, b.shape):
        start = max(ao, bo)
        stop = min(ao + asz, bo + bsz)
        if stop <= start:
            return None
        offsets.append(start)
        shape.append(stop - start)
    return Box(tuple(offsets), tuple(shape))


def subtract_box(base: Box, cut: Box) -> list[Box]:
    """``base`` minus ``cut``: up to 2*ndim disjoint boxes covering every
    element of ``base`` outside ``cut``. Returns ``[base]`` when disjoint,
    ``[]`` when fully covered — the exact-coverage primitive (overlap-safe,
    unlike element-count sums)."""
    inter = intersect_boxes(base, cut)
    if inter is None:
        return [base]
    out: list[Box] = []
    cur_off = list(base.offsets)
    cur_shape = list(base.shape)
    for d in range(base.ndim):
        lo, hi = cur_off[d], cur_off[d] + cur_shape[d]
        ilo = inter.offsets[d]
        ihi = ilo + inter.shape[d]
        if ilo > lo:
            off = list(cur_off)
            shp = list(cur_shape)
            shp[d] = ilo - lo
            out.append(Box(tuple(off), tuple(shp)))
        if ihi < hi:
            off = list(cur_off)
            shp = list(cur_shape)
            off[d] = ihi
            shp[d] = hi - ihi
            out.append(Box(tuple(off), tuple(shp)))
        cur_off[d], cur_shape[d] = ilo, ihi - ilo
    return out


def boxes_cover(region: Box, covers: list[Box]) -> bool:
    """True iff the union of ``covers`` contains every element of
    ``region`` (overlaps and duplicates are fine)."""
    remaining = [region]
    for cut in covers:
        if not remaining:
            return True
        remaining = [r for base in remaining for r in subtract_box(base, cut)]
    return not remaining


def to_byte_view(arr: np.ndarray) -> np.ndarray:
    """Flat uint8 view over a contiguous array (for bulk/byte transports).

    Mirrors the role of the reference's ``to_byte_view``
    (/root/reference/torchstore/utils.py:25-33).
    """
    if not arr.flags["C_CONTIGUOUS"]:
        raise ValueError("to_byte_view requires a C-contiguous array")
    return arr.view(np.uint8).reshape(-1)


def get_destination_view(
    dest: np.ndarray,
    dest_box: Box,
    region: Box,
    require_contiguous: bool = True,
) -> Optional[np.ndarray]:
    """View into ``dest`` (which occupies ``dest_box`` of the global space)
    covering global ``region``; None when the region is not representable as
    a single C-contiguous view and ``require_contiguous`` is set.

    The contiguity requirement exists because byte-oriented transports (SHM,
    bulk TCP, ICI staging) land data into a flat destination buffer — same
    constraint as the reference's RDMA path
    (/root/reference/torchstore/utils.py:36-98).
    """
    if not dest_box.contains(region):
        return None
    rel = tuple(ro - do for ro, do in zip(region.offsets, dest_box.offsets))
    index = tuple(slice(r, r + s) for r, s in zip(rel, region.shape))
    view = dest[index]
    if require_contiguous and view.size > 1 and not view.flags["C_CONTIGUOUS"]:
        return None
    return view


def tensors_overlap_in_memory(dest: np.ndarray, parts: Sequence[np.ndarray]) -> bool:
    """True when every part aliases memory inside ``dest`` (i.e. all parts
    already landed in-place and no assembly copy is needed). Equivalent of
    /root/reference/torchstore/utils.py:101-120."""
    if dest.size == 0:
        return False
    d0, d1 = byte_range(dest)
    for p in parts:
        if p.size == 0:
            continue
        p0, p1 = byte_range(p)
        if p0 < d0 or p1 > d1 or p.base is None:
            return False
    return True


def byte_range(arr: np.ndarray) -> tuple[int, int]:
    """[lo, hi) byte address range touched by ``arr`` under arbitrary
    (including negative) strides."""
    start = arr.__array_interface__["data"][0]
    if arr.size == 0:
        return (start, start)
    lo = start
    hi = start
    for sz, st in zip(arr.shape, arr.strides):
        if sz > 1:
            extent = (sz - 1) * st
            if extent > 0:
                hi += extent
            else:
                lo += extent
    return (lo, hi + arr.itemsize)


def bounding_box(boxes: Sequence[Box]) -> Box:
    if not boxes:
        raise ValueError("bounding_box of no boxes")
    ndim = boxes[0].ndim
    mins = [min(b.offsets[d] for b in boxes) for d in range(ndim)]
    maxs = [max(b.offsets[d] + b.shape[d] for b in boxes) for d in range(ndim)]
    return Box(tuple(mins), tuple(m - n for m, n in zip(maxs, mins)))


def assemble_tensor(
    parts: Sequence[tuple[np.ndarray, tuple[int, ...]]],
) -> tuple[np.ndarray, tuple[int, ...]]:
    """Assemble fetched parts (each with its global offsets) into one array.

    Returns ``(array, offsets)`` where ``offsets`` is the global offset of the
    assembled bounding box (so a full fetch yields offsets == zeros).
    Equivalent of /root/reference/torchstore/utils.py:158-245.
    """
    if not parts:
        raise ValueError("assemble_tensor of no parts")
    dtype = parts[0][0].dtype
    for p, _ in parts:
        if p.dtype != dtype:
            raise ValueError(f"dtype mismatch during assembly: {p.dtype} vs {dtype}")
        if p.ndim != parts[0][0].ndim:
            raise ValueError("rank mismatch during assembly")
    boxes = [Box(tuple(off), tuple(p.shape)) for p, off in parts]
    bbox = bounding_box(boxes)
    if len(parts) == 1 and boxes[0] == bbox:
        return parts[0][0], bbox.offsets
    out = np.empty(bbox.shape, dtype=dtype)
    covered = 0
    for (p, off), box in zip(parts, boxes):
        rel = tuple(o - bo for o, bo in zip(off, bbox.offsets))
        out[tuple(slice(r, r + s) for r, s in zip(rel, p.shape))] = p
        covered += box.size
    if covered < bbox.size:
        raise ValueError(
            f"assembled parts cover {covered} elements but bounding box has "
            f"{bbox.size}; parts do not tile the requested region"
        )
    # A plain size sum double-counts OVERLAPPING parts and can mask an
    # uncovered hole (np.empty garbage served as tensor data). Overlaps only
    # occur in anomalous states (e.g. mixed-layout crash recovery), so the
    # exact check — painting a coverage byte per cell — runs only then.
    if any(
        intersect_boxes(a, b) is not None
        for i, a in enumerate(boxes)
        for b in boxes[i + 1 :]
    ):
        painted = np.zeros(bbox.shape, dtype=np.uint8)
        for (p, off), box in zip(parts, boxes):
            rel = tuple(o - bo for o, bo in zip(off, bbox.offsets))
            painted[tuple(slice(r, r + s) for r, s in zip(rel, p.shape))] = 1
        holes = int(painted.size - int(painted.sum()))
        if holes:
            raise ValueError(
                f"assembled parts overlap yet leave {holes} of {bbox.size} "
                "elements uncovered; parts do not tile the requested region"
            )
    return out, bbox.offsets


async def maybe_await(value):
    """Await ``value`` when it is a coroutine, else return it — lets
    transport hooks be either sync or async."""
    import inspect

    if inspect.iscoroutine(value):
        return await value
    return value


def get_free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def get_hostname() -> str:
    """THE host identity every layer keys on — same-host transport
    selection, volume hostnames, ledger host labels, relay membership.
    ``TORCHSTORE_TPU_HOSTNAME`` overrides it (tests/benches emulating a
    multi-host fleet on one box); keeping every consumer on one source
    means an emulated host is consistently 'remote' everywhere instead of
    same-host for transports but cross-host for traffic attribution."""
    return os.environ.get("TORCHSTORE_TPU_HOSTNAME") or socket.gethostname()


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache at a STABLE directory and
    return it. Entry points (chip_smoke.py, the jitting benches, the
    examples) call this before their first jit. ``JAX_COMPILATION_CACHE_DIR``
    wins — jax reads it itself, so no directory is set in code; otherwise
    the cache lives in ``<checkout>/.jax_cache``. The path is part of the
    cache key, so it never carries a pid, a time or a temporary name. Every
    program is cached, however quick its compile: a second run of the same
    entry point then compiles nothing anew."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache",
        )
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def spawn_logged(coro, *, name: str, tasks: Optional[set] = None, log=None):
    """``asyncio.ensure_future`` with the retention + error contract every
    fire-and-forget task in this codebase must honor (tslint rule
    ``orphan-task``): the task is retained in ``tasks`` until done (asyncio
    holds spawned tasks weakly — an unretained task can be garbage-collected
    mid-flight), and a done-callback RETRIEVES the exception, logs it, and
    increments ``ts_background_task_errors_total{task=name}`` instead of
    letting the failure vanish. Cancellation is not an error."""
    import asyncio

    task = asyncio.ensure_future(coro)
    if tasks is not None:
        tasks.add(task)

    def _done(t: "asyncio.Task") -> None:
        if tasks is not None:
            tasks.discard(t)
        if t.cancelled():
            return
        exc = t.exception()
        if exc is not None:
            from torchstore_tpu.logging import get_logger
            from torchstore_tpu.observability import metrics as obs_metrics

            obs_metrics.counter(
                "ts_background_task_errors_total",
                "Unhandled exceptions from background (fire-and-forget) tasks",
            ).inc(task=name)
            (log or get_logger("torchstore_tpu.tasks")).error(
                "background task %r failed: %r", name, exc, exc_info=exc
            )

    task.add_done_callback(_done)
    return task
