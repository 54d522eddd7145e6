"""Shared-memory transport: the same-host fast path.

TPU-native equivalent of /root/reference/torchstore/transport/shared_memory.py
(:41-523). The storage volume owns tensor storage living in POSIX shared
memory (``/dev/shm`` files + mmap — same substrate as ``shm_open``, and the
ABI the native C++ backend accelerates); clients copy directly into/out of
those segments, so a put is exactly one memcpy client-side and zero copies
server-side (the volume's stored array IS a view of the segment).

PUT:  handshake returns existing/pooled descriptors for reuse -> client
      allocates or attaches + copies -> volume attaches and stores the view.
GET:  the volume serves an (offset, strides) descriptor into its own segment
      whenever the requested data is segment-backed — including arbitrary
      sub-slices of stored shards (the reference's descriptor-view serve,
      shared_memory.py:133-198) — so the server side is always zero-copy.
      A client with an in-place destination copies once; a client without one
      KEEPS the view: gets are zero-copy by default.

Safety of zero-copy reads (replaces an earlier opt-in ``mutable_shm`` flag):
the volume lease-counts every descriptor it serves, and a put NEVER writes
into a live entry segment — each put lands in a pooled (or fresh) segment
and the previous one is *retired* until every lease is released, then
recycled. Data a reader views — or is mid-copy out of — is therefore
immutable for the life of the read. Clients track served views with
weakrefs and piggyback release notices on their next RPC; released segments
return to a volume-side free pool, so the steady state of a put/get loop
recycles warm segments instead of allocating (double-buffer rotation).

Caches: ``ShmServerCache`` (volume side: entries, leases, retired/free
pools, staged-get TTLs), ``ShmClientCache`` (client side: attachments +
view weakrefs), both invalidated per-key on delete (reference cache
semantics, shared_memory.py:56-131).

One-sided warm gets (the "RPC Considered Harmful" data plane): every entry
carries a slot in a per-volume **stamp table** — a shared-memory array of
uint64 seqlock words (even = stable, odd = write-in-flight), bumped by the
volume around every landing that can change what the entry's bytes mean
(replace, in-place overwrite, delete, repair pull). Get descriptors are
annotated with (stamp segment, slot, generation); the client caches them as
one-sided plans and serves warm repeat gets by ``stamped_read_batch``:
check the stamp, memcpy straight out of the pre-attached segment through
the landing pool, re-check the stamp — ZERO RPCs. Any mismatch (replaced
entry, writer in flight, torn copy, unlinked segment) invalidates the plan
and falls back loudly to the RPC path (``ts_one_sided_fallbacks_total``);
a post-copy stamp change additionally counts ``ts_one_sided_torn_total``
and discards the copy, so mixed-generation bytes are never served. The
protocol leans on two existing invariants: puts never write a live entry
segment (so an even, matching stamp means the mapped bytes are the exact
generation the plan was built against), and a retired segment can only be
re-offered to a writer AFTER the replacing put bumped the entry stamp (so
a reader that raced the recycling always sees the bump on its re-check).
Staleness is bounded exactly like the location cache: a detached replica
serves its last committed generation until the reclaim deletes it (stamp
tombstone), never torn bytes.
"""

from __future__ import annotations

import math
import mmap
import os
import time
import uuid
import weakref
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from torchstore_tpu import faults
from torchstore_tpu.config import StoreConfig, default_config
from torchstore_tpu.logging import get_logger
from torchstore_tpu.native import copy_into, fast_copy
from torchstore_tpu.observability import ledger as obs_ledger
from torchstore_tpu.observability import metrics as obs_metrics
from torchstore_tpu.observability import profile as obs_profile
from torchstore_tpu.observability import timeline as obs_timeline
from torchstore_tpu.observability import tracing
from torchstore_tpu.utils import spawn_logged
from torchstore_tpu.transport import landing
from torchstore_tpu.transport.buffers import (
    TransportBuffer,
    TransportCache,
    TransportContext,
)
from torchstore_tpu.transport.types import Request, TensorMeta

logger = get_logger("torchstore_tpu.transport.shm")

# Segment-pool economics. Offer hits/misses are counted where the decision
# is made: the server's handshake (volume process) and the client's
# post-handshake landing (client process) each see their own side.
_POOL_OFFERS = obs_metrics.counter(
    "ts_shm_pool_offers_total",
    "Put-handshake segment offers by outcome (spare/pooled/miss)",
)
_SEGMENTS_CREATED = obs_metrics.counter(
    "ts_shm_segments_created_total", "Fresh /dev/shm segments created"
)
_SEGMENTS_RECYCLED = obs_metrics.counter(
    "ts_shm_segments_recycled_total", "Segments drawn from the warm free pool"
)
_SEGMENTS_REAPED = obs_metrics.counter(
    "ts_shm_segments_reaped_total", "Segments unlinked by TTL sweep, by kind"
)
_CLIENT_ATTACH = obs_metrics.counter(
    "ts_shm_client_attach_total",
    "Client-side segment handling on put (offer_hit / cold_create)",
)
_POOL_BYTES = obs_metrics.gauge(
    "ts_shm_pool_bytes", "Bytes held in the volume's warm free pool"
)
_RETIRED_SEGMENTS = obs_metrics.gauge(
    "ts_shm_retired_segments", "Viewed-then-replaced segments awaiting release"
)
_RESERVED_SEGMENTS = obs_metrics.gauge(
    "ts_shm_reserved_segments", "Handshake-offered segments awaiting their put"
)

# One-sided data-plane instruments (client side). Shared by the SHM stamped
# read and the bulk doorbell (transport label distinguishes them).
ONE_SIDED_READS = obs_metrics.counter(
    "ts_one_sided_reads_total",
    "Warm gets served one-sided (zero RPCs), by transport",
)
ONE_SIDED_FALLBACKS = obs_metrics.counter(
    "ts_one_sided_fallbacks_total",
    "One-sided attempts that fell back to the RPC path, by reason",
)
ONE_SIDED_TORN = obs_metrics.counter(
    "ts_one_sided_torn_total",
    "One-sided reads discarded because the stamp moved mid-copy, by transport",
)

SHM_DIR = "/dev/shm"

STAGED_TTL_S = 120.0  # staged-get segments a crashed client never unlinked
RETIRED_TTL_S = 600.0  # viewed-then-replaced segments never released
RESERVED_TTL_S = 60.0  # handshake offers whose put never arrived

# Puts at or under this ride INLINE in the put RPC (pickle-5 out-of-band
# frames) instead of negotiating a segment handshake first: one RPC instead
# of two — the small-op fast path. The volume still lands them in (pooled)
# segments, so zero-copy gets work identically.
SMALL_INLINE_BYTES = 64 * 1024

# Handshake-reply key for the batch's shared arena segment offer; request
# indices are always >= 0 so -1 can never collide.
ARENA_OFFER_KEY = -1

# Stamp-table capacity: one uint64 seqlock word per live (key, coords)
# entry. 64K slots = a 512 KB segment; entries beyond capacity simply are
# not one-sided-servable (their gets stay on the RPC path).
STAMP_SLOTS = 1 << 16

# A one-sided get WITHOUT a destination must copy (a zero-copy view of a
# recyclable segment cannot be stamp-re-checked after it is handed out), so
# above this size the RPC path's zero-copy snapshot view wins and the
# one-sided path stands down. In-place gets copy on both paths, so they go
# one-sided at any size.
ONE_SIDED_COPY_MAX = 4 << 20

# The OneSidedMiss reasons that invalidate the cached plan (the bytes or
# stamps the plan points at moved/vanished): the fallback RPC serve
# re-records a fresh plan. Other reasons (e.g. shape policy) keep it.
PLAN_DROPPING_MISSES = frozenset(
    {"stale_stamp", "torn", "segment_gone", "stamp_table_gone"}
)


def covered_plan(
    one_sided: dict, key: str, slice_key, has_dest: bool
) -> Optional[dict]:
    """The cached one-sided plan for ``(key, slice_key)`` IF the one-sided
    path may serve it — the single coverage predicate every client-side
    coverage check shares. A destination-less get above
    ``ONE_SIDED_COPY_MAX`` stands down to the RPC zero-copy path (standing
    policy, not a fallback), so it reports uncovered."""
    plan = one_sided.get((key, slice_key))
    if plan is None or (
        not has_dest and plan["nbytes"] > ONE_SIDED_COPY_MAX
    ):
        return None
    return plan


def is_available() -> bool:
    return os.path.isdir(SHM_DIR) and os.access(SHM_DIR, os.W_OK)


def shm_available_bytes() -> int:
    """Free bytes in /dev/shm right now (0 when unreadable). Provisioners
    clamp against this: tmpfs pages are allocated by WRITES, and a write
    past tmpfs-full raises SIGBUS — not an exception any try/except can
    catch — so pre-faulting must never be allowed to run past it."""
    try:
        st = os.statvfs(SHM_DIR)
        return int(st.f_frsize * st.f_bavail)
    except OSError:
        return 0


def reap_orphaned_segments() -> int:
    """Unlink ts_shm_* segments whose creating process is gone (crashed
    volumes/clients leave them behind; nothing else ever cleans /dev/shm).
    Safe: segment names embed the creator pid, and a dead pid's segments
    can have no owner left. Called at volume startup."""
    reaped = 0
    try:
        names = os.listdir(SHM_DIR)
    except OSError:
        return 0
    for name in names:
        if not name.startswith("ts_shm_"):
            continue
        parts = name.split("_")
        try:
            pid = int(parts[2])
        except (IndexError, ValueError):
            continue
        if not _pid_alive(pid):
            try:
                os.unlink(os.path.join(SHM_DIR, name))
                reaped += 1
            except OSError:
                pass
    if reaped:
        logger.info("reaped %d orphaned shm segments", reaped)
    return reaped


def _copy_obj(obj: Any) -> Any:
    """Value-semantics copy for object payloads on in-process dispatch."""
    import copy

    return copy.deepcopy(obj)


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # exists, owned by someone else — leave it alone


# --------------------------------------------------------------------------
# segments
# --------------------------------------------------------------------------


class ShmSegment:
    """A named shared-memory segment (file in /dev/shm + mmap)."""

    def __init__(self, name: str, size: int, mm: mmap.mmap, owner: bool):
        self.name = name
        self.size = size
        self.mmap = mm
        self.owner = owner
        self._closed = False
        self._base_addr: Optional[int] = None

    @staticmethod
    def _path(name: str) -> str:
        return os.path.join(SHM_DIR, name)

    # MAP_POPULATE batches page allocation + zeroing into the mmap call
    # instead of a trap per 4K page on first touch: measured 4.5x on the
    # COLD create+copy path (1.30s -> 0.29s per 256 MB on this host) — the
    # exact cost behind the bench's warm-path collapse (an RL loop's first
    # two syncs allocate fresh segment sets while the consumer still holds
    # snapshot leases on the old ones).
    _POPULATE = getattr(mmap, "MAP_POPULATE", 0)

    @classmethod
    def create(
        cls,
        size: int,
        name: Optional[str] = None,
        populate: bool = True,
        count: bool = True,
    ) -> "ShmSegment":
        """``populate=False`` skips MAP_POPULATE's eager page zeroing — for
        the volume's inline-put residual path, where actor dispatch must not
        stall on population (tiny segments fault their few pages during the
        landing copy instead). ``count=False`` keeps protocol-metadata
        segments (the stamp table) out of the pool-economics counter."""
        name = name or f"ts_shm_{os.getpid()}_{uuid.uuid4().hex[:12]}"
        fd = os.open(cls._path(name), os.O_CREAT | os.O_EXCL | os.O_RDWR, 0o600)
        try:
            os.ftruncate(fd, size)
            flags = mmap.MAP_SHARED | (cls._POPULATE if populate else 0)
            mm = mmap.mmap(fd, size, flags=flags)
        finally:
            os.close(fd)
        if count:
            _SEGMENTS_CREATED.inc()
        return cls(name, size, mm, owner=True)

    @classmethod
    def create_provisioned(
        cls, size: int, hugepages: bool = True, nthreads: int = 0
    ) -> "ShmSegment":
        """Cold-start provisioning variant of ``create``: map WITHOUT
        MAP_POPULATE, advise transparent huge pages while the range is still
        untouched (the advice must precede the faults to influence them),
        then prefault every page with the native multi-threaded entry
        (``tsnative.cc ts_prefault``; single-thread touch fallback). Used by
        the prewarm path to build the volume's warm pool off the first
        sync's critical path."""
        name = f"ts_shm_{os.getpid()}_{uuid.uuid4().hex[:12]}"
        fd = os.open(cls._path(name), os.O_CREAT | os.O_EXCL | os.O_RDWR, 0o600)
        try:
            os.ftruncate(fd, size)
            mm = mmap.mmap(fd, size, flags=mmap.MAP_SHARED)
        finally:
            os.close(fd)
        _SEGMENTS_CREATED.inc()
        seg = cls(name, size, mm, owner=True)
        if hugepages:
            seg.madvise_hugepage()
        seg.prefault(nthreads)
        return seg

    def madvise_hugepage(self) -> None:
        """Advise the kernel to back this mapping with transparent huge
        pages (fewer TLB misses on the hot memcpy). Fail-open: kernels
        without THP-on-shmem, or mmap modules without MADV_HUGEPAGE, leave
        the mapping on plain pages."""
        advice = getattr(mmap, "MADV_HUGEPAGE", None)
        if advice is None or self.size == 0:
            return
        try:
            self.mmap.madvise(advice)
        except (OSError, ValueError):
            pass

    def prefault(self, nthreads: int = 0) -> None:
        """Touch every page so later copies into this segment never
        soft-fault. Native multi-threaded path when the library is
        present; single-thread stride touch otherwise."""
        if self.size == 0:
            return
        from torchstore_tpu import native as native_mod

        addr = self.base_addr()
        if addr is not None and native_mod.prefault(addr, self.size, nthreads):
            return
        view = np.frombuffer(self.mmap, dtype=np.uint8)
        view[::4096] = 0  # page starts are 4096-multiples: every page hit

    @classmethod
    def attach(cls, name: str, size: int, populate: bool = False) -> "ShmSegment":
        """``populate=True`` pre-wires the mapping's page tables (pages
        already exist — the creator populated them) so a big copy into the
        attachment skips per-page soft faults. Leave False for attachments
        that never touch the bytes (the volume's zero-copy descriptor
        serving) — wiring there is pure put-RPC overhead."""
        fd = os.open(cls._path(name), os.O_RDWR)
        try:
            flags = mmap.MAP_SHARED | (cls._POPULATE if populate else 0)
            mm = mmap.mmap(fd, size, flags=flags)
        finally:
            os.close(fd)
        return cls(name, size, mm, owner=False)

    def base_addr(self) -> Optional[int]:
        """Address of the mapping's first byte in THIS process (used to test
        whether a stored array aliases this segment)."""
        if self._base_addr is None:
            if self.size == 0:
                return None
            self._base_addr = np.frombuffer(
                self.mmap, dtype=np.uint8, count=1
            ).__array_interface__["data"][0]
        return self._base_addr

    def view(self, meta: TensorMeta, offset: int = 0) -> np.ndarray:
        # math.prod, not np.prod: this runs once per member on the warm
        # one-sided batch path, and the ufunc reduction is ~30x the cost
        # of the builtin on the small shape tuples that dominate there.
        count = math.prod(meta.shape)
        if count == 0:
            # Zero-size tensors carry no bytes; an empty array of the right
            # shape/dtype IS the value (np.frombuffer(count=0) would also
            # work but the reshape from the `or 1` minimum-map hack can't).
            return np.empty(meta.shape, meta.np_dtype)
        return np.frombuffer(
            self.mmap, dtype=meta.np_dtype, count=count, offset=offset
        ).reshape(meta.shape)

    def strided_view(
        self, meta: TensorMeta, offset: int, strides: Optional[tuple[int, ...]]
    ) -> np.ndarray:
        """View with explicit byte strides — serves sub-slices of stored
        shards without staging (descriptor-view serve)."""
        if strides is None:
            return self.view(meta, offset)
        return np.ndarray(
            meta.shape,
            dtype=meta.np_dtype,
            buffer=self.mmap,
            offset=offset,
            strides=strides,
        )

    def rename_to_owner(self) -> None:
        """Rename the segment so its name embeds THIS process's pid. Volumes
        call this when adopting a client-created segment: the pid in a
        segment name must always be its current owner, or the orphan reaper
        could unlink live volume storage after the creating client exits."""
        new_name = f"ts_shm_{os.getpid()}_{uuid.uuid4().hex[:12]}"
        os.rename(self._path(self.name), self._path(new_name))
        self.name = new_name

    def unlink(self) -> None:
        try:
            os.unlink(self._path(self.name))
        except FileNotFoundError:
            pass

    def close(self) -> None:
        # The mmap stays open while numpy views reference it; python frees the
        # mapping at GC. Unlink only removes the name.
        self._closed = True


class StampTable:
    """Per-volume shared array of per-entry seqlock words.

    Word semantics: even = entry stable at that generation; odd = a write
    that can change the entry's bytes/placement is in flight. Values only
    ever increase (slots are reused across entries without reset), so a
    reader comparing against the generation its plan recorded can never be
    fooled by wrap-behind. Aligned 8-byte loads/stores of the numpy view
    are single instructions on the platforms this runs on; the protocol
    additionally re-checks after the copy, so even a torn stamp read only
    costs a spurious fallback, never wrong data."""

    def __init__(self, seg: ShmSegment) -> None:
        self.seg = seg
        self.words = np.frombuffer(seg.mmap, dtype=np.uint64)

    @classmethod
    def create(cls) -> "StampTable":
        # populate=True zeroes every word: slot generation starts at 0.
        # count=False: the table is protocol metadata, not pool economics —
        # its lazy creation must not move ts_shm_segments_created_total
        # across a prewarmed first put.
        return cls(ShmSegment.create(STAMP_SLOTS * 8, count=False))

    @classmethod
    def attach(cls, name: str, size: int) -> "StampTable":
        return cls(ShmSegment.attach(name, size, populate=True))

    def read(self, slot: int) -> int:
        return int(self.words[slot])

    def write(self, slot: int, value: int) -> None:
        self.words[slot] = value


@dataclass
class ShmDescriptor:
    """Picklable handle to a tensor inside a segment."""

    segment_name: str
    segment_size: int
    meta: TensorMeta
    offset: int = 0
    # Byte strides for non-contiguous views (sub-slices of stored shards);
    # None means C-contiguous at ``offset``.
    strides: Optional[tuple[int, ...]] = None
    # 'volume' -> long-lived, volume owns; 'client' -> staged for one get,
    # the client unlinks after landing the data.
    owner: str = "volume"
    # One-sided annotation: (stamp segment name, stamp segment size, slot,
    # generation at serve time). Present only for volume-owned serves whose
    # entry stamp was stable (even) — the client caches it as a one-sided
    # plan and serves warm repeats without the RPC.
    stamp: Optional[tuple] = None


@dataclass
class _Entry:
    """One stored (key, coords) tensor backed by a volume-owned segment."""

    seg: ShmSegment
    meta: TensorMeta
    # Stamp-table slot carried across replacements (the entry identity owns
    # the slot; the segment rotates underneath it). None = table full or
    # stamping unavailable — the entry is just not one-sided-servable.
    slot: Optional[int] = None


def slice_sig(ts) -> Optional[tuple]:
    """Hashable identity of a sub-request's wanted slice — the one-sided
    plan index key component (None for whole-tensor requests)."""
    if ts is None:
        return None
    return (ts.offsets, ts.local_shape, ts.coordinates)


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------


class ShmServerCache(TransportCache):
    """Volume-side segment bookkeeping: live entries, view leases, retired
    (viewed-then-replaced) segments awaiting release, a free pool of
    recyclable segments, handshake reservations, and staged-get TTLs."""

    def __init__(self) -> None:
        self.by_key: dict[str, dict[Optional[tuple], _Entry]] = {}
        # name -> number of live (key, coords) entries backed by the
        # segment. 1 for ordinary segments; >1 for arena segments shared by
        # a whole batch of small keys — the segment retires/frees/unlinks
        # only when the LAST referencing entry is replaced or deleted.
        self.seg_refs: dict[str, int] = {}
        self.staged: dict[str, tuple[ShmSegment, float]] = {}
        # name -> outstanding read leases across all clients (zero-copy
        # views AND in-flight destination copies)
        self.grants: dict[str, int] = {}
        # client_id -> highest applied release-batch seq (exactly-once
        # application of retransmitted release batches)
        self.last_applied: dict[str, int] = {}
        # name -> (seg, ts): replaced while leased; released -> free pool
        self.retired: dict[str, tuple[ShmSegment, float]] = {}
        # exact-size free pool of volume-owned, still-linked segments
        self.free_by_size: dict[int, list[ShmSegment]] = {}
        self.free_order: list[tuple[str, float]] = []  # (name, ts) oldest-first
        self.free_bytes = 0
        # Env-seeded default; overridden per-request from the StoreConfig the
        # client buffer carries (see adopt_config) so programmatic
        # initialize(config=...) settings reach the volume side.
        self.pool_cap = default_config().shm_pool_max_bytes
        # pooled segments offered in a put handshake, awaiting the put RPC
        self.reserved: dict[str, tuple[ShmSegment, float]] = {}
        # size -> [reserved names] pre-announced to a client in a put reply
        # (the client pre-attaches them in the background); the next
        # handshake offers these first so the second working-set rotation
        # pays neither allocation nor attach on its critical path.
        self.spare_by_size: dict[int, list[str]] = {}
        # size -> number of background warm-up tasks in flight
        self._warming: dict[int, int] = {}
        # segments being prefaulted (not yet pooled): clear() must unlink
        # these too, or an interrupted warm-up leaks the file for the
        # process lifetime (colocated volumes never exit to be reaped)
        self._warm_inflight: set[ShmSegment] = set()
        # strong refs to in-flight warm-up tasks (asyncio holds tasks
        # weakly; an unretained warmer can be GC'd mid-prefault)
        self._warm_tasks: set = set()
        self._closed = False
        # last time a client RPC touched this cache (warm-up tasks only
        # burn CPU in idle windows, never against live traffic)
        self.last_activity = 0.0
        # Per-entry seqlock stamps (one-sided reads). Lazily created on the
        # first entry; creation failure disables stamping (entries are then
        # simply not one-sided-servable — fail open, never fail the put).
        self.stamps: Optional[StampTable] = None
        self._stamps_failed = False
        self._stamp_next = 0
        self._stamp_free: list[int] = []
        # Open write brackets per (key, coords): endpoints dispatch as
        # independent tasks, so two puts of the same key can overlap at
        # awaits — the stamp may only settle EVEN when the LAST of them
        # closes, else a reader validates against bytes the other put is
        # still writing.
        self._write_nesting: dict[tuple, int] = {}

    def adopt_config(self, config: Optional[StoreConfig]) -> None:
        if config is not None:
            self.pool_cap = config.shm_pool_max_bytes

    # ---- sweeping --------------------------------------------------------

    def sweep(self) -> None:
        now = time.monotonic()
        for name, (seg, ts) in list(self.staged.items()):
            if now - ts > STAGED_TTL_S:
                seg.unlink()  # no-op if the client already unlinked it
                del self.staged[name]
                _SEGMENTS_REAPED.inc(kind="staged")
        for name, (seg, ts) in list(self.retired.items()):
            if now - ts > RETIRED_TTL_S:
                # Client never released (likely crashed). Live readers keep
                # their mapping after the unlink; the name is done either way.
                seg.unlink()
                del self.retired[name]
                self.grants.pop(name, None)
                _SEGMENTS_REAPED.inc(kind="retired")
        for name, (seg, ts) in list(self.reserved.items()):
            if now - ts > RESERVED_TTL_S:
                # The reserving put never arrived (client crashed or is
                # extremely slow). Unlink rather than re-pool: re-pooling
                # could hand the segment to a second writer while the
                # original put is still copying into it — a very late put
                # then fails cleanly on attach instead of corrupting data.
                del self.reserved[name]
                seg.unlink()
                _SEGMENTS_REAPED.inc(kind="reserved")
                # A reaped spare's name must leave spare_by_size too: the
                # stale name was only discarded lazily when a handshake for
                # that exact size popped it, so under many distinct sizes
                # the lists grew without bound (ADVICE r4).
                names = self.spare_by_size.get(seg.size)
                if names is not None:
                    try:
                        names.remove(name)
                    except ValueError:
                        pass
                    if not names:
                        del self.spare_by_size[seg.size]
        _POOL_BYTES.set(self.free_bytes)
        _RETIRED_SEGMENTS.set(len(self.retired))
        _RESERVED_SEGMENTS.set(len(self.reserved))

    # ---- leases ----------------------------------------------------------

    def grant(self, name: str) -> None:
        self.grants[name] = self.grants.get(name, 0) + 1

    def apply_releases(self, payload: Optional[dict]) -> None:
        """Apply a client's release batches. Batches are (seq, counts) pairs
        retransmitted until acked; ``last_applied`` makes application
        exactly-once, so neither a lost response nor a retransmission can
        over- or under-decrement a lease (an over-decrement would recycle a
        segment under a still-live reader)."""
        if not payload:
            return
        client_id = payload["client"]
        last = self.last_applied.get(client_id, 0)
        for seq, counts in sorted(payload["batches"]):
            if seq <= last:
                continue
            last = seq
            for name, n in counts.items():
                have = self.grants.get(name)
                if have is None:
                    continue
                have -= n
                if have > 0:
                    self.grants[name] = have
                    continue
                del self.grants[name]
                entry = self.retired.pop(name, None)
                if entry is not None:
                    self._add_free(entry[0])
        self.last_applied[client_id] = last

    # ---- free pool -------------------------------------------------------

    def _add_free(self, seg: ShmSegment) -> None:
        self.free_by_size.setdefault(seg.size, []).append(seg)
        self.free_order.append((seg.name, time.monotonic()))
        self.free_bytes += seg.size
        while self.free_bytes > self.pool_cap and self.free_order:
            old_name, _ = self.free_order.pop(0)
            for size, segs in self.free_by_size.items():
                victim = next((s for s in segs if s.name == old_name), None)
                if victim is not None:
                    segs.remove(victim)
                    self.free_bytes -= victim.size
                    victim.unlink()
                    break

    def schedule_warm(self, sizes: list[int]) -> None:
        """A put just allocated COLD segments (pool miss): pre-create and
        prefault same-sized spares in the background, so the NEXT push of
        this working set draws warm segments from the pool instead of
        paying first-touch page faults (the cold-start cost an RL loop's
        first weight sync pays; VERDICT r1 item 10)."""
        import asyncio

        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return
        wanted: dict[int, int] = {}
        for size in sizes:
            wanted[size] = wanted.get(size, 0) + 1
        # Segments already earmarked for rotation count against the want:
        # reserved ones (handshake offers + announced spares) re-enter the
        # cycle when their put lands. Without this, every handshake miss of
        # a rotating working set warms ANOTHER full spare set — unbounded
        # page-zeroing that starves the very copies it was meant to speed
        # up (worst on few-core hosts).
        reserved_by_size: dict[int, int] = {}
        for seg, _ in self.reserved.values():
            reserved_by_size[seg.size] = reserved_by_size.get(seg.size, 0) + 1
        budget = self.pool_cap - self.free_bytes
        for size, count in wanted.items():
            have = (
                len(self.free_by_size.get(size, ()))
                + self._warming.get(size, 0)
                + reserved_by_size.get(size, 0)
            )
            for _ in range(max(0, count - have)):
                if budget < size:
                    break
                budget -= size
                self._warming[size] = self._warming.get(size, 0) + 1
                spawn_logged(
                    self._warm_one(size),
                    name="shm.pool_warm",
                    tasks=self._warm_tasks,
                    log=logger,
                )

    async def _warm_one(self, size: int) -> None:
        import asyncio

        seg = None
        try:
            if ShmSegment._POPULATE:
                # MAP_POPULATE prefaults the whole segment inside the mmap
                # call — run it on an executor thread so the (0.1-0.2s/GB)
                # kernel work never stalls the volume's event loop. No
                # idle-gating needed: one batched kernel pass is far
                # cheaper than trap-per-page faulting, and the segment is
                # fully warm the moment create returns.
                loop = asyncio.get_running_loop()
                seg = await loop.run_in_executor(None, ShmSegment.create, size)
                self._warm_inflight.add(seg)
                if self._closed:
                    seg.unlink()
                else:
                    self._add_free(seg)
                return
            seg = ShmSegment.create(size)
            self._warm_inflight.add(seg)
            view = np.frombuffer(seg.mmap, dtype=np.uint8) if size else None
            step = 1 << 20
            off = 0
            while off < size:
                if self._closed:
                    seg.unlink()
                    return
                # No MAP_POPULATE on this platform: prefault by touching,
                # only in LONG idle windows (>=1s since the last RPC) —
                # page-zeroing steals CPU from in-flight transfers (brutal
                # on few-core hosts), and a volume-side gate cannot see the
                # client's own copy work between RPCs. An RL loop's
                # multi-second training step provides exactly these gaps.
                if time.monotonic() - self.last_activity < 1.0:
                    await asyncio.sleep(0.25)
                    continue
                view[off : min(off + step, size) : 4096] = 0
                off += step
                # ~10% duty cycle: a trickle keeps warm-up invisible to
                # concurrent transfers; RL gaps are seconds long, so
                # spares still arrive in time.
                await asyncio.sleep(0.005)
            if self._closed:
                seg.unlink()
            else:
                self._add_free(seg)
        except OSError:
            pass
        finally:
            if seg is not None:
                self._warm_inflight.discard(seg)
            left = self._warming.get(size, 1) - 1
            if left > 0:
                self._warming[size] = left
            else:
                self._warming.pop(size, None)

    async def provision(
        self,
        sizes: dict[int, int],
        hugepages: bool = True,
        nthreads: int = 0,
    ) -> dict:
        """Manifest-driven pool pre-sizing (the prewarm executor's SHM leg):
        for each requested ``{size: count}``, create-and-prefault enough
        segments that the pool can serve that many put-handshake offers —
        counting segments already pooled, warming, or reserved against the
        want. Creation + prefault run on executor threads (the native
        prefault releases the GIL, so multi-segment provisioning
        parallelizes); pool bookkeeping happens back on the event loop.
        Largest sizes first and clamped to the pool cap's remaining budget:
        when everything can't fit, prewarm covers the allocations that hurt
        the cold path most."""
        import asyncio

        loop = asyncio.get_running_loop()
        reserved_by_size: dict[int, int] = {}
        for seg, _ in self.reserved.values():
            reserved_by_size[seg.size] = reserved_by_size.get(seg.size, 0) + 1
        # Clamped at zero: adopt_config may have SHRUNK pool_cap below what
        # the pool already holds — a negative budget would let the floor
        # division below go negative and corrupt the accounting. ALSO
        # clamped to actual tmpfs availability (minus a safety margin for
        # concurrent tenants): the prefault WRITES every page, and a write
        # past tmpfs-full is SIGBUS — fatal to the volume process — not a
        # catchable exception. The controller's reservation normally
        # prevents this, but the volume must protect itself when the
        # reserve step failed and the plan arrived unclamped.
        budget = max(0, self.pool_cap - self.free_bytes)
        budget = min(budget, max(0, shm_available_bytes() - (256 << 20)))
        created = 0
        created_bytes = 0
        already = 0
        clamped_bytes = 0
        plan: list[int] = []
        for size in sorted(sizes, reverse=True):
            count = int(sizes[size])
            if size <= 0 or count <= 0:
                continue
            have = (
                len(self.free_by_size.get(size, ()))
                + self._warming.get(size, 0)
                + reserved_by_size.get(size, 0)
            )
            want = max(0, count - have)
            already += count - want
            fits = min(want, budget // size) if want else 0
            budget -= fits * size
            clamped_bytes += (want - fits) * size
            plan.extend([size] * fits)
        segs = await asyncio.gather(
            *(
                loop.run_in_executor(
                    None, ShmSegment.create_provisioned, size, hugepages, nthreads
                )
                for size in plan
            ),
            return_exceptions=True,
        )
        errors = 0
        names: list[tuple[str, int]] = []
        for seg in segs:
            if isinstance(seg, BaseException):
                errors += 1
                continue
            if self._closed:
                seg.unlink()  # clear() ran mid-provision: don't leak the file
                continue
            self._add_free(seg)
            created += 1
            created_bytes += seg.size
            names.append((seg.name, seg.size))
        _POOL_BYTES.set(self.free_bytes)
        return {
            "created": created,
            "bytes": created_bytes,
            "already_pooled": already,
            "clamped_bytes": clamped_bytes,
            "errors": errors,
            # Created segment names: the prewarming CLIENT pre-attaches these
            # (populate=True page-table wiring off the critical path) so the
            # first put's handshake offers hit its attachment cache and only
            # the copy remains on the hot path.
            "names": names,
        }

    def take_free(self, size: int) -> Optional[ShmSegment]:
        segs = self.free_by_size.get(size)
        if not segs:
            return None
        seg = segs.pop()
        self.free_bytes -= seg.size
        self.free_order = [(n, t) for n, t in self.free_order if n != seg.name]
        _SEGMENTS_RECYCLED.inc()
        return seg

    # ---- entry stamps (one-sided read seqlocks) --------------------------

    def _stamp_table(self) -> Optional[StampTable]:
        if self.stamps is None and not self._stamps_failed:
            try:
                self.stamps = StampTable.create()
            except OSError:
                self._stamps_failed = True
        return self.stamps

    def _alloc_slot(self) -> Optional[int]:
        if self._stamp_table() is None:
            return None
        if self._stamp_free:
            return self._stamp_free.pop()
        if self._stamp_next < STAMP_SLOTS:
            slot = self._stamp_next
            self._stamp_next += 1
            return slot
        return None

    def _tombstone(self, entry: "_Entry") -> None:
        """Entry is going away: leave its stamp ODD forever (until the slot
        is reused, when the word keeps counting up) so one-sided readers of
        any plan built against it fall back from the first check."""
        if entry.slot is None or self.stamps is None:
            return
        w = self.stamps.read(entry.slot)
        if w % 2 == 0:
            self.stamps.write(entry.slot, w + 1)
        self._stamp_free.append(entry.slot)
        entry.slot = None

    def begin_writes(self, pairs: list[tuple[str, Optional[tuple]]]) -> None:
        """Mark every existing entry about to be (re)written as
        write-in-flight (stamp odd). Called by the volume at put/pull entry
        — BEFORE any transport lands bytes that could alias entry memory
        (the bulk/rpc in-place overwrite paths) and before the entry is
        repointed. The volume fires the ``shm.landing_stamp`` faultpoint
        (async, so a delay/wedge holds entries visibly write-in-flight
        without freezing the event loop's RPC fallback path) right after
        this returns."""
        for key, coords in pairs:
            pair = (key, coords)
            nesting = self._write_nesting.get(pair, 0)
            self._write_nesting[pair] = nesting + 1
            if nesting or self.stamps is None:
                continue  # already held odd by an overlapping writer
            entry = self.by_key.get(key, {}).get(coords)
            if entry is not None and entry.slot is not None:
                w = self.stamps.read(entry.slot)
                if w % 2 == 0:
                    self.stamps.write(entry.slot, w + 1)

    def end_writes(self, pairs: list[tuple[str, Optional[tuple]]]) -> None:
        """Settle every written entry at its next EVEN generation (allocate
        slots for fresh entries). Runs after the store adopted the new
        values and strictly before the old segments could be re-offered to
        another writer (both happen inside the same RPC dispatch), which is
        what makes the reader's post-copy re-check sound. An entry another
        put still holds open (overlapping writes of one key) stays ODD —
        only the last closing bracket settles it."""
        for key, coords in pairs:
            pair = (key, coords)
            nesting = self._write_nesting.get(pair, 1) - 1
            if nesting > 0:
                self._write_nesting[pair] = nesting
                continue
            self._write_nesting.pop(pair, None)
            entry = self.by_key.get(key, {}).get(coords)
            if entry is None:
                continue
            if entry.slot is None:
                entry.slot = self._alloc_slot()
                if entry.slot is None:
                    continue
            w = self.stamps.read(entry.slot)
            self.stamps.write(entry.slot, w + 1 if w % 2 else w + 2)

    # ---- entries ---------------------------------------------------------

    def track_staged(self, seg: ShmSegment) -> None:
        self.staged[seg.name] = (seg, time.monotonic())

    def lookup(self, key: str, coords: Optional[tuple]) -> Optional[_Entry]:
        return self.by_key.get(key, {}).get(coords)

    def put(
        self, key: str, coords: Optional[tuple], seg: ShmSegment, meta: TensorMeta
    ) -> None:
        entries = self.by_key.setdefault(key, {})
        prev = entries.get(coords)
        # The stamp slot rides the ENTRY identity across segment rotations
        # (end_writes settles it even once the new bytes are adopted).
        entries[coords] = _Entry(
            seg, meta, slot=prev.slot if prev is not None else None
        )
        if prev is not None and prev.seg.name == seg.name:
            return  # in-place overwrite: refcount unchanged
        self.seg_refs[seg.name] = self.seg_refs.get(seg.name, 0) + 1
        if prev is not None and self._release_entry_ref(prev.seg):
            self._retire_or_free(prev.seg)

    def _release_entry_ref(self, seg: ShmSegment) -> bool:
        """One entry stopped referencing ``seg``. Returns True when it was
        the last reference (the segment left the entry set)."""
        left = self.seg_refs.get(seg.name, 1) - 1
        if left > 0:
            self.seg_refs[seg.name] = left
            return False
        self.seg_refs.pop(seg.name, None)
        return True

    def _retire_or_free(self, seg: ShmSegment) -> None:
        if self.grants.get(seg.name):
            self.retired[seg.name] = (seg, time.monotonic())
        else:
            self._add_free(seg)

    def segments_for(self, key: str) -> list[ShmSegment]:
        return [e.seg for e in self.by_key.get(key, {}).values()]

    def locate(self, key: str, arr: np.ndarray) -> Optional[tuple[_Entry, int]]:
        """Find the entry whose segment ``arr``'s memory lives in (anywhere
        within it — sub-slice views included), or None. Returns the entry
        (its segment AND its stamp slot) plus the byte offset."""
        if arr.nbytes == 0:
            return None
        ptr = arr.__array_interface__["data"][0]
        for entry in self.by_key.get(key, {}).values():
            base = entry.seg.base_addr()
            if base is not None and base <= ptr < base + entry.seg.size:
                return entry, ptr - base
        return None

    def delete_key(self, key: str) -> None:
        for entry in self.by_key.pop(key, {}).values():
            self._tombstone(entry)
            if not self._release_entry_ref(entry.seg):
                # Arena segment still backing other live keys: its bytes
                # stay until the last referencing entry goes.
                continue
            entry.seg.unlink()
            self.grants.pop(entry.seg.name, None)

    def clear(self) -> None:
        for entries in self.by_key.values():
            for entry in entries.values():
                # Readers keep their stamp-table mapping after the unlink
                # below; the tombstone makes every cached plan fall back.
                self._tombstone(entry)
                entry.seg.unlink()
        self.by_key.clear()
        if self.stamps is not None:
            self.stamps.seg.unlink()
            self.stamps = None
        self._stamp_next = 0
        self._stamp_free.clear()
        for seg, _ in self.staged.values():
            seg.unlink()
        self.staged.clear()
        for seg, _ in self.retired.values():
            seg.unlink()
        self.retired.clear()
        for segs in self.free_by_size.values():
            for seg in segs:
                seg.unlink()
        self.free_by_size.clear()
        self.free_order.clear()
        self.free_bytes = 0
        for seg, _ in self.reserved.values():
            seg.unlink()
        self.reserved.clear()
        self.spare_by_size.clear()
        self._closed = True  # interrupt in-flight warm-ups
        for seg in list(self._warm_inflight):
            seg.unlink()
        self._warm_inflight.clear()
        self.grants.clear()
        self.seg_refs.clear()


class ShmClientCache(TransportCache):
    """Client-side: segment name -> attachment, so repeat transfers skip the
    open+mmap syscalls; plus weakref tracking of zero-copy views handed to
    the caller. Releases are routed per VOLUME (one client talks to many
    volumes) as sequence-numbered batches retransmitted until acked, so a
    failed RPC can neither lose a release (leaking the server lease) nor
    double-apply one (recycling a segment under a live reader)."""

    def __init__(self) -> None:
        self.client_id = uuid.uuid4().hex
        self.segments: dict[str, ShmSegment] = {}
        self.key_to_segments: dict[str, set[str]] = {}
        self.seg_volume: dict[str, str] = {}  # name -> volume_id
        self.view_refs: dict[str, list] = {}  # name -> [weakref.ref, ...]
        # volume_id -> {name: count} not yet assigned to a batch
        self.pending: dict[str, dict[str, int]] = {}
        # volume_id -> {seq: counts} sent but not yet acked
        self.unacked: dict[str, dict[int, dict[str, int]]] = {}
        self.seq: dict[str, int] = {}
        # Strong refs to in-flight background pre-attaches (see pre_attach).
        self._pre_attach_tasks: set = set()
        # name -> attach time for pre-attached spares not yet offered —
        # evicted after the server's reserved TTL (the server has unlinked
        # an unused spare by then; keeping the populated mapping would pin
        # its tmpfs pages for the client's lifetime).
        self._pre_attached: dict[str, float] = {}
        # One-sided plans: (key, slice_sig) -> plan dict recorded from
        # stamp-annotated get descriptors (the serving volume rides INSIDE
        # the plan). Bounded; cleared wholesale on overflow and on
        # placement-epoch bumps (the client owns that).
        self.one_sided: dict[tuple, dict] = {}
        # Attached volume stamp tables: name -> (segment, uint64 word view).
        self.stamp_tables: dict[str, tuple[ShmSegment, np.ndarray]] = {}

    ONE_SIDED_MAX = 65536

    def record_one_sided(self, volume_id: str, req, desc: ShmDescriptor) -> None:
        """Cache a stamp-annotated descriptor as a one-sided plan for the
        exact (key, wanted-slice) request it answered. Keyed WITHOUT the
        volume id (a warm get must find the plan before it knows which
        replica it would route to); the serving volume rides inside the
        plan so replica re-routing replaces rather than duplicates."""
        if desc.stamp is None or desc.owner != "volume":
            return
        if len(self.one_sided) >= self.ONE_SIDED_MAX:
            self.one_sided.clear()
        meta = desc.meta
        self.one_sided[(req.key, slice_sig(req.tensor_slice))] = {
            # The store key rides the plan so zero-RPC serves can feed the
            # hot-key profiler and the traffic ledger — without it the
            # warmest keys would be invisible to placement telemetry
            # (the PR-7 blind spot: stamped reads never reach any volume's
            # stats()["hot_keys"]).
            "key": req.key,
            "volume_id": volume_id,
            "segment": desc.segment_name,
            "segment_size": desc.segment_size,
            "offset": desc.offset,
            "strides": desc.strides,
            "meta": meta,
            # Pre-resolved meta scalars: the warm loops read these per
            # member per iteration, and the TensorMeta property walks
            # (math.prod, dtype parse) cost more than the stamp checks.
            "nbytes": meta.nbytes,
            "shape": tuple(meta.shape),
            "npdtype": meta.np_dtype,
            "stamp_name": desc.stamp[0],
            "stamp_size": desc.stamp[1],
            "slot": desc.stamp[2],
            "gen": desc.stamp[3],
        }

    def drop_one_sided(self) -> int:
        """Drop every cached one-sided plan (placement-epoch bump /
        quarantine transition: the placement the plans describe changed).
        LIVE attached stamp tables are kept — they re-validate instantly
        and a reinstated volume's table is still the one in use — but a
        table whose backing file is gone (volume reset unlinked it and
        made a fresh one) is closed here, or each reset would pin another
        512KB of unlinked tmpfs pages for this client's lifetime."""
        n = len(self.one_sided)
        self.one_sided.clear()
        for name in list(self.stamp_tables):
            if not os.path.exists(os.path.join(SHM_DIR, name)):
                seg, _ = self.stamp_tables.pop(name)
                seg.close()
        return n

    def stamp_words(self, plan: dict) -> Optional[np.ndarray]:
        """The uint64 word view of the plan's stamp table (attached and
        cached on first use); None when the table is gone (volume reset)."""
        name = plan["stamp_name"]
        cached = self.stamp_tables.get(name)
        if cached is None:
            try:
                seg = ShmSegment.attach(name, plan["stamp_size"], populate=True)
            except (OSError, ValueError):
                return None
            cached = (seg, np.frombuffer(seg.mmap, dtype=np.uint64))
            self.stamp_tables[name] = cached
        return cached[1]

    def attach(self, desc: ShmDescriptor, key: str, volume_id: str) -> ShmSegment:
        seg = self.segments.get(desc.segment_name)
        if seg is None:
            # Client copies/reads touch every byte — pre-wire the mapping.
            seg = ShmSegment.attach(
                desc.segment_name, desc.segment_size, populate=True
            )
            self.segments[desc.segment_name] = seg
        self._pre_attached.pop(desc.segment_name, None)  # offered: in use now
        self.key_to_segments.setdefault(key, set()).add(desc.segment_name)
        self.seg_volume[desc.segment_name] = volume_id
        return seg

    def evict_stale_pre_attached(self) -> None:
        """Evict pre-attached spares that were never offered within the
        server's reserved TTL: the server has unlinked them by now, and only
        this mapping keeps their tmpfs pages alive. Called from EVERY cache
        entry point that observes traffic (pre_attach AND the per-RPC
        collect_released), not just pre_attach — a client whose puts stop
        missing the pool stops receiving spare announcements, and its stale
        mappings would otherwise pin tmpfs pages for the process lifetime
        (ADVICE carried fix)."""
        cutoff = time.monotonic() - RESERVED_TTL_S
        for name, ts in list(self._pre_attached.items()):
            if ts < cutoff:
                del self._pre_attached[name]
                seg = self.segments.pop(name, None)
                if seg is not None:
                    seg.close()

    def pre_attach(self, spares: list[tuple[str, int]]) -> None:
        """Background-attach server-announced warm spares so the NEXT
        handshake's offers of these names hit the attachment cache — the
        second working-set rotation then pays only its copy. Best-effort:
        off the event loop, races with a synchronous attach resolved in
        its favor, reaped names ignored."""
        import asyncio

        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return

        self.evict_stale_pre_attached()

        async def one(name: str, size: int) -> None:
            if name in self.segments:
                return
            try:
                seg = await loop.run_in_executor(
                    None, ShmSegment.attach, name, size, True
                )
            except OSError:
                return  # reserved-TTL reaped (or volume reset) meanwhile
            if name in self.segments:
                seg.close()  # a synchronous attach won the race
            else:
                self.segments[name] = seg
                self._pre_attached[name] = time.monotonic()

        for name, size in spares:
            # spawn_logged keeps a strong ref until done (a pending
            # pre-attach can otherwise be garbage-collected mid-flight) and
            # surfaces unexpected failures instead of dropping them.
            spawn_logged(
                one(name, size),
                name="shm.pre_attach",
                tasks=self._pre_attach_tasks,
                log=logger,
            )

    def rekey(self, old_name: str, new_name: str) -> None:
        """The volume adopted + renamed a segment this client created: track
        the attachment under the new name (the mapping itself is unchanged —
        rename does not invalidate mmaps), so later handshake offers of the
        renamed segment hit the cache instead of leaking a stale entry."""
        seg = self.segments.pop(old_name, None)
        if seg is not None:
            seg.name = new_name
            self.segments[new_name] = seg
        for names in self.key_to_segments.values():
            if old_name in names:
                names.discard(old_name)
                names.add(new_name)
        vid = self.seg_volume.pop(old_name, None)
        if vid is not None:
            self.seg_volume[new_name] = vid

    def track_view(self, name: str, arr: np.ndarray) -> None:
        self.view_refs.setdefault(name, []).append(weakref.ref(arr))

    def count_release(self, name: str, n: int = 1) -> None:
        vid = self.seg_volume.get(name)
        if vid is None:
            return
        counts = self.pending.setdefault(vid, {})
        counts[name] = counts.get(name, 0) + n

    def collect_released(self, volume_id: str) -> Optional[dict]:
        """Release payload for ``volume_id``: all unacked batches (including
        a fresh one from views dropped since the last RPC), or None."""
        self.evict_stale_pre_attached()
        for name, refs in list(self.view_refs.items()):
            live = [r for r in refs if r() is not None]
            dead = len(refs) - len(live)
            if dead:
                self.count_release(name, dead)
            if live:
                self.view_refs[name] = live
            else:
                del self.view_refs[name]
        fresh = self.pending.pop(volume_id, None)
        if fresh:
            s = self.seq[volume_id] = self.seq.get(volume_id, 0) + 1
            self.unacked.setdefault(volume_id, {})[s] = fresh
        batches = self.unacked.get(volume_id)
        if not batches:
            return None
        return {"client": self.client_id, "batches": sorted(batches.items())}

    def ack_released(self, volume_id: str, payload: Optional[dict]) -> None:
        if not payload:
            return
        batches = self.unacked.get(volume_id)
        if batches:
            for seq, _ in payload["batches"]:
                batches.pop(seq, None)

    def delete_key(self, key: str) -> None:
        for name in self.key_to_segments.pop(key, ()):  # drop attachments
            seg = self.segments.pop(name, None)
            if seg is not None:
                seg.close()
            # seg_volume is kept: views handed out for this key may still
            # be alive, and their eventual release must still route to the
            # owning volume (or its retired segment waits out the full TTL).
        for pk in [pk for pk in self.one_sided if pk[0] == key]:
            del self.one_sided[pk]

    def clear(self) -> None:
        for seg in self.segments.values():
            seg.close()
        self.segments.clear()
        self.key_to_segments.clear()
        self.seg_volume.clear()
        self.view_refs.clear()
        self.pending.clear()
        self.unacked.clear()
        self.seq.clear()
        self.one_sided.clear()
        for seg, _ in self.stamp_tables.values():
            seg.close()
        self.stamp_tables.clear()


async def pre_attach_segments(volume, names: list[tuple[str, int]]) -> int:
    """Prewarm helper: synchronously attach volume-provisioned segments into
    this client's attachment cache (populate=True — the page-table wiring a
    put would otherwise pay on its critical path). Unlike the background
    ``ShmClientCache.pre_attach`` (best-effort, races the next handshake),
    this AWAITS completion: prewarm returns only when the first put's offers
    will hit the cache. Attachments are tracked as pre-attached spares, so
    the standard staleness eviction applies — a prewarm more than the
    reserved TTL ahead of the first put keeps the volume-side pool benefit
    but re-attaches lazily. Returns the number of fresh attachments."""
    import asyncio

    cache: ShmClientCache = volume.transport_context.get_cache(ShmClientCache)
    loop = asyncio.get_running_loop()

    async def one(name: str, size: int) -> int:
        if name in cache.segments:
            return 0
        try:
            seg = await loop.run_in_executor(
                None, ShmSegment.attach, name, size, True
            )
        except OSError:
            return 0  # pool-cap evicted (or volume reset) meanwhile
        if name in cache.segments:
            seg.close()  # a concurrent attach won the race
            return 0
        cache.segments[name] = seg
        cache._pre_attached[name] = time.monotonic()
        return 1

    results = await asyncio.gather(*(one(n, s) for n, s in names))
    return sum(results)


# --------------------------------------------------------------------------
# one-sided stamped reads (client side)
# --------------------------------------------------------------------------


class OneSidedMiss(Exception):
    """A one-sided attempt cannot (or must not) serve this request — the
    caller falls back to the RPC path and counts the reason. Carrying the
    reason in the exception keeps every fallback LOUD in metrics while the
    data path stays correct by construction (the fallback re-fetches)."""

    def __init__(self, reason: str):
        self.reason = reason
        super().__init__(reason)


def segment_read_view(
    seg: ShmSegment,
    meta: TensorMeta,
    offset: int = 0,
    strides: Optional[tuple[int, ...]] = None,
) -> np.ndarray:
    """THE blessed raw-segment read accessor for client/direct modules (the
    ``one-sided-discipline`` tslint rule routes every attached-segment read
    here): callers MUST pair it with a seqlock/generation validation around
    the consuming copy — ``stamped_read`` does that internally; the direct
    sync path validates its source generations over the control socket
    before and after consuming the view."""
    return seg.strided_view(meta, offset, strides)


# One-sided accounting sample policy: batches above _ACCOUNT_EXACT_MAX
# plans record 1-in-_ACCOUNT_SAMPLE at weight _ACCOUNT_SAMPLE (the warm
# many-keys leg is the store's hottest per-key path — full per-key
# accounting there costs ~10x the <=2% telemetry budget, and a steady
# consumer repeats the same batch so the weighted sample converges to the
# exact totals). Small batches (p50 1KB gets, layer serves) stay exact.
_ACCOUNT_SAMPLE = 8
_ACCOUNT_EXACT_MAX = 64
_account_tick = 0


def _account_one_sided(plans: list[dict]) -> None:
    """Decision telemetry for zero-RPC serves (the PR-7 blind spot fix):
    stamped reads never touch a volume, so without this the warmest keys of
    a warm working set are invisible to every ``hot_keys`` view and the
    traffic ledger under-counts exactly the path placement decisions care
    about most. One batched tally (single lock) per accounted batch; keys
    ride the plan dicts (plans recorded before the field existed are
    skipped). Large batches are weight-scaled samples — see
    ``_ACCOUNT_SAMPLE`` above."""
    global _account_tick
    weight = 1
    if len(plans) > _ACCOUNT_EXACT_MAX:
        _account_tick += 1
        if _account_tick % _ACCOUNT_SAMPLE:
            return
        weight = _ACCOUNT_SAMPLE
    ledger = obs_ledger.ledger()
    if not ledger.enabled:
        return
    items: list[tuple] = []
    by_volume: dict[str, list] = {}
    for plan in plans:
        key = plan.get("key")
        if key is None:
            continue
        item = (key, plan["nbytes"])
        items.append(item)
        by_volume.setdefault(str(plan.get("volume_id", "")), []).append(item)
    if not items:
        return
    obs_profile.hot_key_tracker("one_sided").record_many(
        items, weight=weight
    )
    host = obs_ledger.local_host()
    for vid, vitems in by_volume.items():
        ledger.record(
            "one_sided",
            obs_ledger.INGRESS,
            sum(n for _, n in vitems) * weight,
            peer_host=host,  # same-host by construction
            volume=vid,
            items=vitems,
            ops=weight,
            weight=weight,
        )


def stamped_read(
    cache: "ShmClientCache",
    plan: dict,
    dest: Optional[np.ndarray] = None,
    borrow: bool = False,
) -> tuple[np.ndarray, Optional[Any]]:
    """Serve one warm get straight out of a pre-attached volume segment
    under the plan's per-entry seqlock stamp — ZERO RPCs.

    Protocol: check the stamp word equals the plan's recorded (even)
    generation, copy the bytes out (into ``dest`` when given), re-check the
    stamp. Any pre-copy mismatch means the entry was replaced/deleted/is
    mid-write (stale plan); a post-copy mismatch means the copy may be torn
    — both raise :class:`OneSidedMiss` so the caller falls back to the RPC
    path, which fully overwrites any partial landing. Soundness leans on
    the volume-side ordering: a recycled segment is only re-offered to a
    writer after the replacing put went through begin_writes (stamp odd)
    — so a reader racing the recycle always sees the stamp move.

    ``borrow=True`` (destination-less device uploads) returns a READ-ONLY
    view of the segment plus a ``recheck`` callable instead of copying;
    the consumer must finish reading (e.g. jax.block_until_ready after
    device_put) and then call ``recheck()`` — False means the upload may
    hold mixed-generation bytes and must be discarded
    (``device_transfer.finalize_stamped`` wraps that)."""
    src, words, slot, gen = _stamped_source(cache, plan)

    def recheck() -> bool:
        return int(words[slot]) == gen

    if borrow and dest is None:
        view = src.view()
        view.flags.writeable = False
        ONE_SIDED_READS.inc(transport="shm")
        _account_one_sided([plan])
        return view, recheck
    if dest is None:
        if plan["nbytes"] > ONE_SIDED_COPY_MAX:
            # Destination-less big get: the RPC path's zero-copy snapshot
            # view wins (a one-sided serve would have to copy).
            raise OneSidedMiss("too_large")
        dest = np.empty(plan["shape"], plan["npdtype"])
    elif dest.shape != plan["shape"] or dest.dtype != plan["npdtype"]:
        # Stale-metadata target (dtype-converting get / re-published shape):
        # the RPC path owns the conversion story.
        raise OneSidedMiss("shape")
    copy_into(dest, src)
    if not recheck():
        # Copy raced a replacement landing: the bytes in ``dest`` may mix
        # generations — discard (the RPC fallback fully overwrites).
        ONE_SIDED_TORN.inc(transport="shm")
        raise OneSidedMiss("torn")
    ONE_SIDED_READS.inc(transport="shm")
    _account_one_sided([plan])
    return dest, None


def _stamped_source(
    cache: "ShmClientCache", plan: dict
) -> tuple[np.ndarray, np.ndarray, int, int]:
    """Resolve a plan's source view after the pre-copy stamp check; returns
    (src_view, stamp_words, slot, gen) or raises :class:`OneSidedMiss`.

    The stamp-word array, the constructed source view, and its base address
    are memoized ON the plan dict: a warm iteration repeats the same plans,
    and per-member view construction was a measurable slice of the
    many-keys get leg. Safe because plans are dropped wholesale whenever
    the underlying placement can change (stale/torn miss, epoch bump,
    delete), and a segment's mapping outlives ``close()`` as long as any
    view references it (close never unmaps; GC does)."""
    words = plan.get("words")
    if words is None:
        words = cache.stamp_words(plan)
        if words is None:
            raise OneSidedMiss("stamp_table_gone")
        plan["words"] = words
    slot, gen = plan["slot"], plan["gen"]
    if int(words[slot]) != gen:
        raise OneSidedMiss("stale_stamp")
    src = plan.get("view")
    if src is None:
        name = plan["segment"]
        seg = cache.segments.get(name)
        if seg is None:
            try:
                seg = ShmSegment.attach(
                    name, plan["segment_size"], populate=True
                )
            except (OSError, ValueError):
                raise OneSidedMiss("segment_gone") from None
            cache.segments[name] = seg
        src = segment_read_view(
            seg, plan["meta"], plan["offset"], plan["strides"]
        )
        plan["view"] = src
        # Base address for the native scatter-copy batch; None marks the
        # member ineligible (strided source — memcpy would read stray
        # bytes), which stands the whole batch down to the grouped path.
        plan["src_addr"] = (
            src.__array_interface__["data"][0]
            if plan["strides"] is None and src.size
            else None
        )
    return src, words, slot, gen


async def stamped_read_batch(
    cache: "ShmClientCache",
    plans: list[dict],
    dests: list[Optional[np.ndarray]],
    config: Optional[StoreConfig] = None,
) -> list[np.ndarray]:
    """The many-keys warm get leg: serve a whole batch of one-sided plans as
    ONE stamped memcpy loop on the shared landing pool — check every stamp,
    fan all copies out to :func:`landing.land_async` together (they overlap
    each other and the event loop), then re-check every stamp.

    All-or-nothing: any pre-copy mismatch, shape drift, or post-copy tear
    raises :class:`OneSidedMiss` for the WHOLE batch — the caller falls back
    to the RPC path, which fully overwrites any partial in-place landings,
    so mixed-generation bytes are never observable. Destination-less members
    above ONE_SIDED_COPY_MAX stand down (the RPC path's zero-copy snapshot
    view wins there)."""
    results: list[np.ndarray] = []
    # Native scatter-copy batch (landing.land_batch_async): one GIL-free
    # call replaces the per-pair grouped pool path. Any ineligible member
    # (strided source, non-contiguous destination) stands the whole batch
    # down to land_async — correctness is identical, only dispatch differs.
    dst_addrs: list[int] = []
    src_addrs: list[int] = []
    lens: list[int] = []
    batch_ok = True
    t_verify = time.perf_counter()
    for plan, dest in zip(plans, dests):
        src, words, slot, gen = _stamped_source(cache, plan)
        nbytes = plan["nbytes"]
        if dest is None:
            if nbytes > ONE_SIDED_COPY_MAX:
                raise OneSidedMiss("too_large")
            dest = np.empty(plan["shape"], plan["npdtype"])
        elif dest.shape != plan["shape"] or dest.dtype != plan["npdtype"]:
            # Stale-metadata target (dtype-converting get / re-published
            # shape): the RPC path owns the conversion story.
            raise OneSidedMiss("shape")
        results.append(dest)
        if batch_ok and nbytes:
            src_addr = plan.get("src_addr")
            if src_addr is None or not dest.flags["C_CONTIGUOUS"]:
                batch_ok = False
            else:
                dst_addrs.append(dest.__array_interface__["data"][0])
                src_addrs.append(src_addr)
                lens.append(nbytes)
    t_copy = time.perf_counter()
    verify_s = t_copy - t_verify
    # ``shm.landing_stamp`` fires inside the landing-copy window of the
    # one-sided read too (client scope) — a delay/wedge here lands squarely
    # in the get's "landing" stage, exactly how a slow landing pool under
    # overload presents, which is what the stage-attribution tests (and
    # fleet-scale chaos legs) lean on.
    await faults.afire("shm.landing_stamp")
    copied = batch_ok and await landing.land_batch_async(
        dst_addrs, src_addrs, lens, stage="one_sided", config=config
    )
    if not copied:
        # Grouped-pool fallback (pre-v3 library / ineligible member): the
        # (dest, src) pairs are rebuilt off the hot path from the plans'
        # memoized views.
        await landing.land_async(
            [(dest, plan["view"]) for plan, dest in zip(plans, results)],
            stage="one_sided",
            config=config,
        )
    t_recheck = time.perf_counter()
    obs_timeline.observe_stage("get", "landing", t_recheck - t_copy)
    # Post-copy recheck, vectorized per stamp table: one fancy-indexed
    # gather + compare replaces a per-member int() round trip.
    by_table: dict[int, tuple[np.ndarray, list, list]] = {}
    for plan in plans:
        words = plan["words"]
        entry = by_table.get(id(words))
        if entry is None:
            entry = by_table[id(words)] = (words, [], [])
        entry[1].append(plan["slot"])
        entry[2].append(plan["gen"])
    try:
        for words, slots, gens in by_table.values():
            if not np.array_equal(
                words[np.asarray(slots)], np.asarray(gens, dtype=np.uint64)
            ):
                ONE_SIDED_TORN.inc(transport="shm")
                raise OneSidedMiss("torn")
    finally:
        # Stage attribution: pre-copy stamp matching + post-copy re-gather
        # are the seqlock-verify cost of the zero-RPC path (torn included —
        # a discarded read still paid its verify).
        obs_timeline.observe_stage(
            "get",
            "stamp_verify",
            verify_s + (time.perf_counter() - t_recheck),
        )
    ONE_SIDED_READS.inc(len(results), transport="shm")
    _account_one_sided(plans)
    return results


# --------------------------------------------------------------------------
# the transport buffer
# --------------------------------------------------------------------------


class SharedMemoryTransportBuffer(TransportBuffer):
    transport_name = "shm"
    requires_handshake = True
    # Gets are self-describing (descriptors ride the get response) — no
    # handshake round trip on the read path.
    handshake_ops = ("put",)
    supports_inplace = True
    requires_contiguous_inplace = False
    supports_batch_puts = True
    supports_batch_gets = True

    def __init__(
        self, config: Optional[StoreConfig] = None, inproc_copy: bool = False
    ):
        # config TRAVELS with the buffer (like the bulk transport's) so the
        # volume side honors programmatic initialize(config=...) overrides.
        self.config = config
        # Colocated volumes dispatch endpoints without serialization:
        # OBJECT payloads would be stored/served by reference (tensors are
        # safe — they always live in segments). Deep-copy restores the
        # value semantics pickling provides.
        self.inproc_copy = inproc_copy
        self.descriptors: dict[int, ShmDescriptor] = {}
        self.objects: dict[int, Any] = {}
        # Small-put fast path: payload arrays riding the put RPC itself
        # (zero-copy pickle-5 frames), landed server-side into segments.
        self.inline: dict[int, np.ndarray] = {}
        # Small-key arena: {"offsets": {req_idx: byte offset}, "total": n,
        # "segment": name, "segment_size": n} — computed client-side before
        # the handshake, ridden to the server on BOTH RPCs (handshake offers
        # one pooled segment for the whole batch; the put indexes every
        # member out of it in one pass).
        self.arena_plan: Optional[dict] = None
        # client -> server piggyback: sequenced view-release batches
        self.released: Optional[dict] = None
        # server -> client (via put_reply): adopted-segment renames
        self.renames: dict[str, str] = {}
        # server -> client (via put_reply): pre-announced warm spares
        # [(name, size)] the client should background-attach.
        self.spares: list[tuple[str, int]] = []
        # Client-only staging state (never pickled).
        self._client_segments: dict[int, ShmSegment] = {}

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_client_segments"] = {}
        return state

    # ---- client: put -----------------------------------------------------

    async def put_to_storage_volume(self, volume, requests) -> None:
        total = sum(r.nbytes for r in requests)
        if 0 < total <= SMALL_INLINE_BYTES:
            # One-RPC small put: skip the segment handshake entirely.
            self.handshake_ops = ()
        else:
            self.arena_plan = self._compute_arena_plan(requests)
        return await super().put_to_storage_volume(volume, requests)

    def _compute_arena_plan(self, requests) -> Optional[dict]:
        """Pack every tensor at or below the arena threshold into one shared
        segment: one handshake entry, one segment rotation, and one
        volume-side index pass for the whole small-key tail of a batch —
        instead of a pooled segment per key. A valid ``plan_hint`` from the
        iteration-stable plan cache (or a prewarm seed) is adopted verbatim
        so repeat iterations skip even the layout arithmetic."""
        config = self.config or default_config()
        limit = config.arena_max_bytes
        if limit <= 0:
            return None
        members = [
            idx
            for idx, req in enumerate(requests)
            if not req.is_object
            and req.tensor_val is not None
            and req.nbytes <= limit
        ]
        if len(members) < 2:
            return None  # nothing to amortize
        sizes = tuple(requests[idx].nbytes for idx in members)
        hint = (self.plan_hint or {}).get("arena")
        if (
            hint is not None
            and hint.get("sizes") == sizes
            and len(hint.get("offsets", ())) == len(members)
        ):
            offsets = hint["offsets"]
            total = hint["total"]
        else:
            offsets, total = landing.compute_arena_layout(list(sizes))
        return {
            "offsets": dict(zip(members, offsets)),
            "sizes": sizes,
            "total": total,
        }

    async def _pre_put_hook(self, volume, requests) -> None:
        if self.handshake_ops:
            return  # handshake path already staged into segments
        cache: ShmClientCache = volume.transport_context.get_cache(ShmClientCache)
        self.released = cache.collect_released(volume.volume_id)
        for idx, req in enumerate(requests):
            if req.is_object:
                self.objects[idx] = req.objects
            else:
                self.inline[idx] = np.ascontiguousarray(req.tensor_val)

    def _pre_handshake(self, volume, requests, op) -> None:
        if op != "put":
            return
        cache: ShmClientCache = volume.transport_context.get_cache(ShmClientCache)
        self.released = cache.collect_released(volume.volume_id)

    async def _post_handshake(self, volume, requests, reply, op) -> None:
        if op != "put":
            return
        cache: ShmClientCache = volume.transport_context.get_cache(ShmClientCache)
        # The handshake RPC delivered the release batches; ack them (a failed
        # RPC leaves them unacked for retransmission instead).
        cache.ack_released(volume.volume_id, self.released)
        self.released = None
        offered: dict[int, ShmDescriptor] = reply or {}
        arena = self.arena_plan
        arena_seg: Optional[ShmSegment] = None
        # Landing copies for the whole batch are collected first, then fanned
        # out to the shared overlap pool: copies run concurrently with each
        # other (and, chunked, within one huge tensor) while the event loop
        # stays free for sibling volumes' RPCs.
        pairs: list[tuple[np.ndarray, np.ndarray]] = []
        # Segment attach / create for the batch: a publish that gets no
        # pooled offer goes cold here (ShmSegment.create per request).
        offer_hit = cold_create = created_bytes = 0
        with tracing.span("shm.attach", keys=len(requests)) as sp:
            if arena:
                arena_seg, created = self._attach_arena(
                    volume, cache, offered, requests
                )
                if created:
                    cold_create += 1
                    created_bytes += arena_seg.size
                else:
                    offer_hit += 1
            for idx, req in enumerate(requests):
                if req.is_object:
                    self.objects[idx] = req.objects
                    continue
                arr = np.ascontiguousarray(req.tensor_val)
                meta = req.meta_only().tensor_meta
                if arena_seg is not None and idx in arena["offsets"]:
                    # Arena member: no per-key descriptor rides the RPC —
                    # the server rebuilds every member view from the
                    # (already carried) arena plan plus the request metas.
                    off = arena["offsets"][idx]
                    cache.key_to_segments.setdefault(req.key, set()).add(
                        arena_seg.name
                    )
                    if arr.nbytes:
                        pairs.append((arena_seg.view(meta, off), arr))
                    self._client_segments[idx] = arena_seg
                    continue
                desc = offered.get(idx)
                if desc is not None and desc.meta == meta:
                    seg = cache.attach(desc, req.key, volume.volume_id)
                    _CLIENT_ATTACH.inc(outcome="offer_hit")
                    offer_hit += 1
                else:
                    _CLIENT_ATTACH.inc(outcome="cold_create")
                    seg = ShmSegment.create(max(arr.nbytes, 1))
                    cold_create += 1
                    created_bytes += seg.size
                    desc = ShmDescriptor(seg.name, seg.size, meta)
                    cache.segments[seg.name] = seg
                    cache.key_to_segments.setdefault(req.key, set()).add(
                        seg.name
                    )
                    cache.seg_volume[seg.name] = volume.volume_id
                pairs.append((seg.view(meta, desc.offset), arr))
                self.descriptors[idx] = desc
                self._client_segments[idx] = seg
            sp.set(
                offer_hit=offer_hit,
                cold_create=cold_create,
                created_bytes=created_bytes,
            )
        # THE hot memcpy: client arrays -> shared segments (native
        # multi-threaded path, overlapped). First-touch page faults of a
        # freshly created segment land here.
        with tracing.span("shm.land", pairs=len(pairs)) as sp:
            if tracing.trace_enabled():
                sp.set(nbytes=sum(src.nbytes for _, src in pairs))
            await landing.land_async(
                pairs, stage="put", copy=fast_copy, config=self.config
            )

    def _attach_arena(
        self, volume, cache: "ShmClientCache", offered: dict, requests
    ) -> tuple[ShmSegment, bool]:
        """Resolve the batch's shared arena segment: the handshake's pooled
        offer when one arrived, a cold create otherwise. Returns the segment
        and whether it was created here."""
        arena = self.arena_plan
        size = max(int(arena["total"]), 1)
        desc = offered.get(ARENA_OFFER_KEY)
        created = desc is None or desc.segment_size < size
        if not created:
            first_key = requests[next(iter(arena["offsets"]))].key
            seg = cache.attach(desc, first_key, volume.volume_id)
            _CLIENT_ATTACH.inc(outcome="offer_hit")
        else:
            _CLIENT_ATTACH.inc(outcome="cold_create")
            seg = ShmSegment.create(size)
            cache.segments[seg.name] = seg
            cache.seg_volume[seg.name] = volume.volume_id
        arena["segment"] = seg.name
        arena["segment_size"] = seg.size
        landing.ARENA_KEYS.inc(len(arena["offsets"]), transport="shm")
        landing.ARENA_BYTES.inc(sum(arena["sizes"]), transport="shm")
        return seg, created

    def _handle_put_reply(self, volume, reply, requests) -> None:
        cache: ShmClientCache = volume.transport_context.get_cache(ShmClientCache)
        if self.released:
            # Inline (no-handshake) puts deliver releases with the put RPC
            # itself; the RPC succeeded, so ack the batches now.
            cache.ack_released(volume.volume_id, self.released)
            self.released = None
        if not reply:
            return
        for old_name, new_name in reply.get("renames", {}).items():
            cache.rekey(old_name, new_name)
        spares = reply.get("spares")
        if spares:
            cache.pre_attach(spares)

    # ---- server: put -----------------------------------------------------

    def recv_handshake(
        self, ctx: TransportContext, metas: list[Request], existing: dict, op: str
    ) -> Any:
        # Sync faultpoint: a "wedge" here blocks the volume's event loop —
        # the WHOLE process (pings included) looks dead to the supervisor,
        # the deterministic stand-in for a volume stuck in a native copy.
        from torchstore_tpu import faults

        faults.fire("shm.handshake")
        if op != "put":
            return None
        cache: ShmServerCache = ctx.get_cache(ShmServerCache)
        cache.adopt_config(self.config)
        cache.last_activity = time.monotonic()
        cache.apply_releases(self.released)
        cache.sweep()
        offered: dict[int, ShmDescriptor] = {}
        misses: list[int] = []
        arena = self.arena_plan
        arena_members = set(arena["offsets"]) if arena else set()
        if arena:
            # ONE offer serves the whole small-key tail of the batch: the
            # arena segment rotates through the pool exactly like a
            # per-key segment, just shared by every member entry.
            size = max(int(arena["total"]), 1)
            seg = self._offer_from_pool(cache, size)
            if seg is not None:
                offered[ARENA_OFFER_KEY] = ShmDescriptor(
                    seg.name,
                    seg.size,
                    TensorMeta(shape=(size,), dtype="uint8"),
                )
            else:
                misses.append(size)
        for idx, meta in enumerate(metas):
            if meta.tensor_meta is None or idx in arena_members:
                continue
            # Puts NEVER overwrite a live entry segment — between this
            # handshake and the put RPC a concurrent get could be serving
            # (or staging a copy of) the current content, and a cross-
            # process writer would tear it. Instead, offer a warm segment
            # from the free pool (retired segments return there once every
            # view lease is released), so steady-state put/get loops rotate
            # buffers instead of allocating cold ones; the old segment is
            # retired or pooled when the put lands (descriptor-reuse
            # handshake role, reference shared_memory.py:340-360, with
            # rotation instead of in-place overwrite).
            size = max(meta.tensor_meta.nbytes, 1)
            seg = self._offer_from_pool(cache, size)
            if seg is not None:
                offered[idx] = ShmDescriptor(
                    seg.name, seg.size, meta.tensor_meta
                )
            else:
                misses.append(size)
        if misses:
            # Warm spares for the sizes this handshake could NOT serve,
            # starting NOW: the client spends the next stretch copying its
            # working set, which is exactly the window the (executor-side,
            # MAP_POPULATE) warming can fill so the NEXT rotation of this
            # set draws warm segments.
            cache.schedule_warm(misses)
        return offered

    @staticmethod
    def _offer_from_pool(
        cache: "ShmServerCache", size: int
    ) -> Optional[ShmSegment]:
        """One handshake offer: pre-announced spares first (the client may
        have background-attached them already), then the warm free pool.
        The returned segment is reserved for the put now in flight."""
        names = cache.spare_by_size.get(size)
        while names:
            name = names.pop()
            entry = cache.reserved.get(name)
            if entry is not None:
                # Membership in `reserved` IS liveness: reserved segments
                # are only unlinked by sweep(), which removes them from
                # `reserved` in the same step. Refresh the reservation
                # timestamp for the put now in flight.
                cache.reserved[name] = (entry[0], time.monotonic())
                _POOL_OFFERS.inc(outcome="spare")
                return entry[0]
        pooled = cache.take_free(size)
        if pooled is not None:
            _POOL_OFFERS.inc(outcome="pooled")
            cache.reserved[pooled.name] = (pooled, time.monotonic())
            return pooled
        _POOL_OFFERS.inc(outcome="miss")
        return None

    def handle_put_request(
        self, ctx: TransportContext, metas: list[Request], existing: dict
    ) -> dict[int, Any]:
        cache: ShmServerCache = ctx.get_cache(ShmServerCache)
        cache.adopt_config(self.config)
        cache.last_activity = time.monotonic()
        cache.apply_releases(self.released)
        out: dict[int, Any] = {}
        for idx, obj in self.objects.items():
            out[idx] = _copy_obj(obj) if self.inproc_copy else obj
        cold_sizes: list[int] = []
        cold_inline: list[int] = []
        for idx, arr in self.inline.items():
            # Small inline put: the VOLUME lands the payload into a pooled
            # segment, so these entries get the same zero-copy get serving
            # as handshake puts. Volume-created segments already carry the
            # volume's pid — no rename round trip needed.
            meta = metas[idx]
            coords = meta.tensor_slice.coordinates if meta.tensor_slice else None
            tmeta = TensorMeta.of(arr)
            seg = cache.take_free(max(arr.nbytes, 1))
            if seg is None:
                # Residual cold path (the arena makes this rare): dispatch
                # must not stall on segment population, so the create skips
                # MAP_POPULATE (an inline payload is <= 64 KB — its few
                # pages fault during the landing copy) and the warm pool is
                # scheduled to absorb the NEXT inline put of this size.
                seg = ShmSegment.create(max(arr.nbytes, 1), populate=False)
                cold_inline.append(max(arr.nbytes, 1))
            view = seg.view(tmeta)
            copy_into(view, arr)
            cache.put(meta.key, coords, seg, tmeta)
            out[idx] = view
        if cold_inline:
            cache.schedule_warm(cold_inline)
        arena = self.arena_plan
        arena_seg: Optional[ShmSegment] = None
        arena_name = arena.get("segment") if arena else None
        if arena_name:
            # Resolve the batch's shared arena segment ONCE; every member
            # below is a pure view+index step against it.
            reserved = cache.reserved.pop(arena_name, None)
            if reserved is not None:
                arena_seg = reserved[0]
            else:
                arena_seg = ShmSegment.attach(
                    arena_name, arena["segment_size"]
                )
                arena_seg.owner = True
                old_name = arena_seg.name
                arena_seg.rename_to_owner()
                self.renames[old_name] = arena_seg.name
                cold_sizes.append(arena_seg.size)
            # One volume-side index pass: each arena member becomes a view
            # at its packed offset (meta from the request list — members
            # carry no per-key descriptors); the segment's entry refcount
            # keeps it alive until the last member is replaced/deleted.
            for idx, off in arena["offsets"].items():
                meta = metas[idx]
                coords = (
                    meta.tensor_slice.coordinates if meta.tensor_slice else None
                )
                tmeta = meta.tensor_meta
                cache.put(meta.key, coords, arena_seg, tmeta)
                out[idx] = arena_seg.view(tmeta, off)
        for idx, desc in self.descriptors.items():
            meta = metas[idx]
            coords = meta.tensor_slice.coordinates if meta.tensor_slice else None
            current = cache.lookup(meta.key, coords)
            reserved = cache.reserved.pop(desc.segment_name, None)
            if current is not None and current.seg.name == desc.segment_name:
                seg = current.seg  # in-place overwrite of the live segment
            elif reserved is not None:
                seg = reserved[0]  # pooled segment, already volume-owned
            else:
                seg = ShmSegment.attach(desc.segment_name, desc.segment_size)
                seg.owner = True  # volume takes ownership of the lifetime
                # The name's pid must track ownership (see rename_to_owner);
                # future handshakes/gets serve the new name from the cache —
                # and the client is told via put_reply so its attachment
                # cache follows the rename instead of leaking.
                old_name = seg.name
                seg.rename_to_owner()
                self.renames[old_name] = seg.name
                cold_sizes.append(seg.size)
            cache.put(meta.key, coords, seg, desc.meta)
            out[idx] = seg.view(desc.meta, desc.offset)
        if cold_sizes:
            # Pool misses: warm same-sized spares in the background so the
            # next push of this working set starts warm.
            cache.schedule_warm(cold_sizes)
            # Spares already warm (handshake-time warming ran during the
            # client's copy): reserve them NOW and announce them in the put
            # reply — the client pre-attaches off the critical path and the
            # next handshake offers exactly these names, so the second
            # rotation of a working set pays neither allocation nor attach.
            for size in cold_sizes:
                seg = cache.take_free(size)
                if seg is None:
                    continue
                cache.reserved[seg.name] = (seg, time.monotonic())
                cache.spare_by_size.setdefault(size, []).append(seg.name)
                self.spares.append((seg.name, size))
        return out

    def put_reply(self):
        reply = {}
        if self.renames:
            reply["renames"] = self.renames
        if self.spares:
            reply["spares"] = self.spares
        return reply or None

    # ---- server: get -----------------------------------------------------

    def handle_get_request(
        self, ctx: TransportContext, metas: list[Request], entries: list[Any]
    ) -> None:
        cache: ShmServerCache = ctx.get_cache(ShmServerCache)
        cache.adopt_config(self.config)
        cache.last_activity = time.monotonic()
        cache.apply_releases(self.released)
        cache.sweep()
        for idx, (meta, entry) in enumerate(zip(metas, entries)):
            if meta.is_object:
                self.objects[idx] = _copy_obj(entry) if self.inproc_copy else entry
                continue
            entry = np.asarray(entry)
            desc = self._serve_descriptor(cache, meta, entry)
            if desc is not None:
                self.descriptors[idx] = desc
                continue
            # Not segment-backed (or write-pending): stage a copy whose
            # ownership transfers to the client (client unlinks after
            # landing; the server reaps it after a TTL otherwise).
            tmeta = TensorMeta.of(entry)
            seg = ShmSegment.create(max(tmeta.nbytes, 1))
            fast_copy(seg.view(tmeta), entry)
            cache.track_staged(seg)
            self.descriptors[idx] = ShmDescriptor(
                seg.name, seg.size, tmeta, owner="client"
            )

    def _serve_descriptor(
        self, cache: ShmServerCache, meta: Request, entry: np.ndarray
    ) -> Optional[ShmDescriptor]:
        """Zero-copy descriptor for ``entry`` if it aliases an entry segment
        (whole tensors AND sub-slice views — any non-negative-stride view of
        segment memory is expressible as offset+strides)."""
        loc = cache.locate(meta.key, entry)
        if loc is None:
            return None
        stored, offset = loc
        seg = stored.seg
        strides = entry.strides
        if any(s < 0 for s in strides):
            return None
        extent = entry.itemsize + sum(
            (d - 1) * s for d, s in zip(entry.shape, strides) if d > 0
        )
        if offset + extent > seg.size:
            return None
        # Lease for EVERY volume-owned serve: zero-copy views hold it until
        # GC'd; in-place destination copies hold it only until the client's
        # copy lands (released on its next RPC). Either way a concurrent
        # put can never be offered this segment mid-read.
        cache.grant(seg.name)
        # One-sided annotation: a stable (even) entry stamp rides the
        # descriptor so the client can serve warm repeats of this exact
        # request with zero RPCs (stamped_read_batch).
        stamp = None
        if stored.slot is not None and cache.stamps is not None:
            gen = cache.stamps.read(stored.slot)
            if gen % 2 == 0:
                stamp = (
                    cache.stamps.seg.name,
                    cache.stamps.seg.size,
                    stored.slot,
                    gen,
                )
        return ShmDescriptor(
            seg.name,
            seg.size,
            TensorMeta.of(entry),
            offset=offset,
            strides=None if entry.flags["C_CONTIGUOUS"] else tuple(strides),
            stamp=stamp,
        )

    # ---- client: get -----------------------------------------------------

    async def _pre_get_hook(self, volume, requests) -> None:
        cache: ShmClientCache = volume.transport_context.get_cache(ShmClientCache)
        self.released = cache.collect_released(volume.volume_id)

    async def _handle_storage_volume_response(
        self, volume, remote: "SharedMemoryTransportBuffer", requests
    ) -> list[Any]:
        cache: ShmClientCache = volume.transport_context.get_cache(ShmClientCache)
        # The get RPC (which carried self.released) succeeded: ack batches.
        cache.ack_released(volume.volume_id, self.released)
        self.released = None
        zero_copy = self.config is None or self.config.zero_copy_get
        results: list[Any] = []
        # Landing copies are collected, fanned out to the overlap pool
        # together, and only then do the per-copy completions (lease
        # releases, staged-segment unlinks) run — a failed landing leaves
        # those to the server's TTL sweeps instead of mis-releasing.
        pairs: list[tuple[np.ndarray, np.ndarray]] = []
        done: list = []
        for idx, req in enumerate(requests):
            if req.is_object or idx in remote.objects:
                results.append(remote.objects[idx])
                continue
            desc = remote.descriptors[idx]
            if desc.owner == "client":
                seg = ShmSegment.attach(
                    desc.segment_name, desc.segment_size, populate=True
                )
                src = seg.view(desc.meta, desc.offset)
                if req.destination_view is not None:
                    landed = req.destination_view
                else:
                    landed = np.empty(src.shape, src.dtype)
                pairs.append((landed, src))
                done.append(seg.unlink)
                results.append(landed)
                continue
            seg = cache.attach(desc, req.key, volume.volume_id)
            if self.config is None or self.config.one_sided:
                # Stamp-annotated serve: cache it as a one-sided plan so the
                # client's next repeat of this exact request skips the RPC.
                cache.record_one_sided(volume.volume_id, req, desc)
            src = seg.strided_view(desc.meta, desc.offset, desc.strides)
            if req.destination_view is not None:
                pairs.append((req.destination_view, src))
                # Once the copy lands: release the read lease the volume
                # granted for the duration of this in-place read.
                done.append(
                    lambda name=desc.segment_name: cache.count_release(name)
                )
                results.append(req.destination_view)
            elif zero_copy:
                # Zero-copy read: hand out a read-only snapshot view of the
                # live segment (the volume retires, never overwrites, leased
                # segments). Released automatically when the array is GC'd.
                src.flags.writeable = False
                cache.track_view(desc.segment_name, src)
                results.append(src)
            else:
                # Copying instead of keeping the view: release once landed.
                buf = np.empty(src.shape, src.dtype)
                pairs.append((buf, src))
                done.append(
                    lambda name=desc.segment_name: cache.count_release(name)
                )
                results.append(buf)
        await landing.land_async(pairs, stage="get", config=self.config)
        for fn in done:
            fn()
        return results

    def drop(self) -> None:
        # self.released is NOT re-credited here: unacked batches persist in
        # the client cache and retransmit on the next RPC to that volume.
        self.descriptors = {}
        self.objects = {}
        self.inline = {}
        self.arena_plan = None
        self.released = None
        self.renames = {}
        self._client_segments = {}
