"""Shared landing-copy pool: overlapped segment copies + arena layout math.

The steady-state put/get hot path used to run one ``fast_copy`` per request,
serially, on the event loop thread — every copy blocked the loop, so a batch
of landings could overlap neither each other nor the RPC/D2H work the loop
still had in flight. This module provides the shared, bounded executor all
landing sites fan out to:

- **put side**: ``SharedMemoryTransportBuffer._post_handshake`` lands every
  request's client->segment copy through ``land_async``;
- **get side**: in-place destination copies in the SHM response handler;
- **volume side**: arena member indexing / inline landings.

The pool is budgeted against cores (``TORCHSTORE_TPU_LANDING_THREADS``,
0 = one per core capped at 4): ``fast_copy`` is already internally threaded
for large contiguous arrays, so stacking a wide pool on top of it would
oversubscribe the host. Very large tensors are additionally CHUNKED into
row blocks, so a single tensor's landing pipelines across pool threads and
yields the event loop between chunks instead of occupying one thread (and,
pre-pool, the loop) for the whole copy.

Arena layout (``compute_arena_layout``) lives here too so the SHM
transport, the bulk packed frame, and the provisioning manifest all pack
small keys identically — a prewarm-provisioned arena segment is exactly the
size the first put's handshake asks for.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

import numpy as np

from torchstore_tpu.config import StoreConfig, default_config
from torchstore_tpu.native import copy_into
from torchstore_tpu.observability import metrics as obs_metrics

# Chunk size for pipelining one very large tensor's landing: big enough that
# per-chunk submission overhead is invisible, small enough that a 1 GB
# tensor becomes ~32 overlappable units.
CHUNK_BYTES = 32 << 20

# Arena members are aligned so every packed tensor starts on a cache-line
# boundary (also satisfies any dtype's alignment).
ARENA_ALIGN = 64

_LANDING_SECONDS = obs_metrics.histogram(
    "ts_landing_copy_seconds",
    "Wall time of one overlapped landing-copy batch, by pipeline stage",
)
_PIPELINE_COPIES = obs_metrics.counter(
    "ts_sync_pipeline_copies_total",
    "Landing copies routed through the overlap pool, by stage",
)
_PIPELINE_BYTES = obs_metrics.counter(
    "ts_sync_pipeline_bytes_total",
    "Bytes landed through the overlap pool, by stage",
)
_PIPELINE_CHUNKS = obs_metrics.counter(
    "ts_sync_pipeline_chunks_total",
    "Row-block chunks large tensors were split into for pipelined landing",
)
ARENA_KEYS = obs_metrics.counter(
    "ts_arena_packed_keys_total",
    "Small tensors packed into a shared arena, by transport",
)
ARENA_BYTES = obs_metrics.counter(
    "ts_arena_bytes_total",
    "Payload bytes carried inside packed arenas, by transport",
)

_exec: Optional[ThreadPoolExecutor] = None
_exec_threads = 0
_exec_lock = threading.Lock()


def configured_threads(config: Optional[StoreConfig] = None) -> int:
    n = (config or default_config()).landing_threads
    if n > 0:
        return n
    return max(1, min(4, os.cpu_count() or 1))


def get_executor(config: Optional[StoreConfig] = None) -> ThreadPoolExecutor:
    """The process-wide landing pool (created lazily; resized only if a
    config asks for MORE threads than the pool was built with)."""
    global _exec, _exec_threads
    want = configured_threads(config)
    with _exec_lock:
        if _exec is None or want > _exec_threads:
            old = _exec
            _exec = ThreadPoolExecutor(
                max_workers=want, thread_name_prefix="ts-landing"
            )
            _exec_threads = want
            if old is not None:
                old.shutdown(wait=False)
        return _exec


def reinit_after_fork() -> None:
    """Forked children inherit a dead pool object (executor threads do not
    survive fork); drop it so the first landing re-creates a live one."""
    global _exec, _exec_threads
    _exec = None
    _exec_threads = 0


def _chunk_pairs(dst: np.ndarray, src: np.ndarray) -> list[tuple]:
    """Split one large contiguous same-dtype copy into row-block chunks so
    it pipelines across pool threads. Non-chunkable shapes return the pair
    unsplit."""
    if (
        dst.nbytes <= CHUNK_BYTES
        or dst.dtype != src.dtype
        or not dst.flags["C_CONTIGUOUS"]
        or not src.flags["C_CONTIGUOUS"]
    ):
        return [(dst, src)]
    flat_d = dst.reshape(-1)
    flat_s = src.reshape(-1)
    step = max(1, CHUNK_BYTES // max(1, dst.itemsize))
    chunks = [
        (flat_d[off : off + step], flat_s[off : off + step])
        for off in range(0, flat_d.shape[0], step)
    ]
    _PIPELINE_CHUNKS.inc(len(chunks))
    return chunks


def _copy_group(group: list[tuple], copy: Callable) -> None:
    for dst, src in group:
        copy(dst, src)


def _plan_tasks(
    pairs: list[tuple[np.ndarray, np.ndarray]],
    threads: int,
    copy: Callable,
) -> list[tuple[Callable, list[tuple]]]:
    """Partition a landing batch into at most ~2x``threads`` executor tasks:
    very large pairs are chunked into row blocks (one task each — a single
    huge tensor pipelines across threads), everything else is grouped into
    byte-balanced runs so a 2048-small-key batch costs a handful of
    submissions, not 2048 (per-future overhead on a 2-core host exceeds a
    64 KB memcpy by an order of magnitude)."""
    tasks: list[tuple[Callable, list[tuple]]] = []
    small: list[tuple] = []
    small_bytes = 0
    for dst, src in pairs:
        if dst.nbytes > CHUNK_BYTES:
            for cd, cs in _chunk_pairs(dst, src):
                tasks.append((copy, [(cd, cs)]))
        else:
            small.append((dst, src))
            small_bytes += dst.nbytes
    if small:
        target = max(1, -(-small_bytes // max(1, threads)))
        group: list[tuple] = []
        acc = 0
        for pair in small:
            group.append(pair)
            acc += pair[0].nbytes
            if acc >= target:
                tasks.append((copy, group))
                group, acc = [], 0
        if group:
            tasks.append((copy, group))
    return tasks


async def land_async(
    pairs: list[tuple[np.ndarray, np.ndarray]],
    stage: str,
    copy: Callable[[np.ndarray, np.ndarray], None] = copy_into,
    config: Optional[StoreConfig] = None,
) -> None:
    """Land every (dst, src) pair through the shared pool, concurrently,
    without blocking the event loop. Pairs above CHUNK_BYTES are split so a
    single huge tensor pipelines too; small pairs are grouped so per-future
    overhead stays amortized. Exceptions (shape mismatches — the fast_copy
    no-broadcast rule) propagate to the caller."""
    import asyncio

    pairs = [(d, s) for d, s in pairs if d.nbytes]
    if not pairs:
        return
    t0 = time.perf_counter()
    nbytes = sum(d.nbytes for d, _ in pairs)
    _PIPELINE_COPIES.inc(len(pairs), stage=stage)
    _PIPELINE_BYTES.inc(nbytes, stage=stage)
    threads = configured_threads(config)
    tasks = _plan_tasks(pairs, threads, copy)
    if len(tasks) == 1 and nbytes <= (256 << 10):
        # One small batch: the submission round trip costs more than it
        # could overlap; run it inline.
        _copy_group(tasks[0][1], copy)
        _LANDING_SECONDS.observe(time.perf_counter() - t0, stage=stage)
        return
    loop = asyncio.get_running_loop()
    pool = get_executor(config)
    await asyncio.gather(
        *(
            loop.run_in_executor(pool, _copy_group, group, fn)
            for fn, group in tasks
        )
    )
    _LANDING_SECONDS.observe(time.perf_counter() - t0, stage=stage)


async def land_batch_async(
    dst_addrs: list[int],
    src_addrs: list[int],
    lens: list[int],
    stage: str,
    config: Optional[StoreConfig] = None,
) -> bool:
    """Single-submission scatter landing: ONE executor hop runs the native
    v3 ``ts_copy_batch`` (GIL-free, internally threaded) over every
    (dst, src, len) triple. This is the one-sided warm get's copy stage —
    the grouped ``land_async`` path pays a pool submission per group plus
    per-pair interpreter/GIL hand-off, which measured ~2x the raw copy
    time for many-small-key batches on a 2-vCPU host. The CALLER owns
    eligibility (same-size, both sides C-contiguous, non-overlapping
    pairs). Returns False (nothing copied) when the native entry is
    unavailable — the caller falls back to :func:`land_async`."""
    import asyncio

    from torchstore_tpu import native

    if not native.available():
        return False
    if not lens:
        return True
    t0 = time.perf_counter()
    da = np.array(dst_addrs, dtype=np.uint64)
    sa = np.array(src_addrs, dtype=np.uint64)
    ln = np.array(lens, dtype=np.uint64)
    total = int(ln.sum())
    _PIPELINE_COPIES.inc(len(lens), stage=stage)
    _PIPELINE_BYTES.inc(total, stage=stage)
    threads = configured_threads(config)
    if total <= (256 << 10):
        # Small batch: the executor round trip costs more than the copy.
        ok = native.copy_batch(da, sa, ln, threads)
    else:
        loop = asyncio.get_running_loop()
        ok = await loop.run_in_executor(
            get_executor(config), native.copy_batch, da, sa, ln, threads
        )
    if ok:
        _LANDING_SECONDS.observe(time.perf_counter() - t0, stage=stage)
    return ok


def land_sync(
    pairs: list[tuple[np.ndarray, np.ndarray]],
    stage: str,
    copy: Callable[[np.ndarray, np.ndarray], None] = copy_into,
    config: Optional[StoreConfig] = None,
) -> None:
    """Blocking variant for sync contexts (no running loop): still spreads
    the pairs across the pool so copies overlap each other."""
    pairs = [(d, s) for d, s in pairs if d.nbytes]
    if not pairs:
        return
    t0 = time.perf_counter()
    _PIPELINE_COPIES.inc(len(pairs), stage=stage)
    _PIPELINE_BYTES.inc(sum(d.nbytes for d, _ in pairs), stage=stage)
    threads = configured_threads(config)
    tasks = _plan_tasks(pairs, threads, copy)
    if len(tasks) == 1:
        _copy_group(tasks[0][1], copy)
    else:
        pool = get_executor(config)
        list(pool.map(lambda t: _copy_group(t[1], t[0]), tasks))
    _LANDING_SECONDS.observe(time.perf_counter() - t0, stage=stage)


async def run_in_pool(fn: Callable, *args, config: Optional[StoreConfig] = None):
    """Run one CPU-bound callable on the landing pool with the caller's
    contextvars (so spans/trace ids opened inside still stitch to the
    active trace)."""
    import asyncio

    ctx = contextvars.copy_context()
    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(
        get_executor(config), lambda: ctx.run(fn, *args)
    )


def align_up(n: int, align: int = ARENA_ALIGN) -> int:
    return (n + align - 1) // align * align


# Scale tables are f32: the slot fused after each payload only needs 4-byte
# alignment (64B between MEMBERS stays — the payload start dominates cache
# behavior; padding a 4-byte-aligned scale run to 64B would waste more than
# the whole table for small tensors).
SCALE_ALIGN = 4


def compute_arena_layout(
    sizes: list[int], scale_sizes: Optional[list[int]] = None
):
    """Offsets + total for packing ``sizes`` byte payloads back-to-back at
    ARENA_ALIGN boundaries. THE arena layout function: the SHM transport,
    the bulk packed frame, and the provisioning manifest all call this, so
    a prewarmed pool segment is exactly the size the first put asks for.

    ``scale_sizes`` (quantized wire tier) fuses a per-member SCALE SLOT
    into the SAME layout: member ``i``'s slot holds its payload at
    ``offsets[i]`` and its f32 scale table at ``scale_offsets[i]``
    (4-byte-aligned immediately after the payload) — one segment, one
    handshake, and the scales can never ride a separate RPC from the
    bytes they decode. Returns ``(offsets, scale_offsets, total)`` in
    that mode, ``(offsets, total)`` classically."""
    offsets: list[int] = []
    scale_offsets: list[int] = []
    off = 0
    for i, nbytes in enumerate(sizes):
        offsets.append(off)
        end = off + int(nbytes)
        if scale_sizes is not None:
            s_off = align_up(end, SCALE_ALIGN)
            scale_offsets.append(s_off)
            end = s_off + int(scale_sizes[i])
        off = align_up(end)
    total = max(off, 1)
    if scale_sizes is not None:
        return offsets, scale_offsets, total
    return offsets, total


# ---------------------------------------------------------------------------
# fused quant-blob layout (blockwise int8/int4 wire tier)
# ---------------------------------------------------------------------------
#
# A blockwise-quantized tensor crosses the wire as ONE self-describing
# uint8 blob: [header+shape | changed-block bitmap | packed codes | f32
# scale table]. The scale slot rides compute_arena_layout's scale_sizes
# mode, so payload and scales share a segment by construction — the
# transport, the bulk packed frame, and the provisioning manifest all see
# a single ordinary byte payload. Layout math lives HERE (the arena
# layout module); encode/decode live in state_dict_utils (the only other
# module allowed to touch scale tables, per the tslint quant-discipline
# rule).

QUANT_HEADER_BYTES = 64


def quant_payload_nbytes(fmt: str, block: int, changed: int) -> int:
    """Packed-code bytes for ``changed`` blocks of ``block`` elements:
    int8_block stores one byte per element; int4_block packs two 4-bit
    codes per byte (blocks are whole slots — the tail block zero-pads)."""
    if fmt == "int4_block":
        return changed * ((block + 1) // 2)
    return changed * block


def quant_blob_layout(
    rank: int, nblocks: int, changed: int, fmt: str, block: int
) -> dict:
    """Section offsets + total size of one fused quant blob. The payload/
    scale pair goes through compute_arena_layout's scale-slot mode, so the
    scale table provably occupies the same segment as the codes it
    decodes."""
    head = QUANT_HEADER_BYTES + 8 * rank
    bitmap = (nblocks + 7) // 8
    offsets, scale_offsets, total = compute_arena_layout(
        [head, bitmap, quant_payload_nbytes(fmt, block, changed)],
        scale_sizes=[0, 0, 4 * changed],
    )
    return {
        "header": offsets[0],
        "bitmap": offsets[1],
        "payload": offsets[2],
        "scales": scale_offsets[2],
        "total": total,
    }


def quant_wire_nbytes(fmt: str, block: int, nelems: int, rank: int) -> int:
    """Full-keyframe wire size of an ``nelems``-element tensor under
    blockwise quantization — what the provisioning manifest sizes pools
    with, so a prewarmed pool holds the scale-bearing arena segment the
    first quantized publish asks for."""
    nblocks = max(1, -(-int(nelems) // max(1, block)))
    return quant_blob_layout(rank, nblocks, nblocks, fmt, block)["total"]
