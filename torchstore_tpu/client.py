"""Client orchestration: the resharding planner.

TPU-native equivalent of /root/reference/torchstore/client.py:52-496. One
logical get becomes: locate (controller RPC) -> expand the wanted region
against every stored shard (slice intersection, replica dedup) -> per-volume
sub-requests fetched in parallel -> bounding-box assembly, with an in-place
fast path that lands transport writes directly in destination memory.
"""

from __future__ import annotations

import asyncio
import os
import time
import zlib
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from torchstore_tpu import sharding as shd
from torchstore_tpu import torch_interop
from torchstore_tpu.config import StoreConfig, default_config
from torchstore_tpu.faults import FaultInjectedError
from torchstore_tpu.controller import ObjectType, StorageInfo
from torchstore_tpu.logging import LatencyTracker, get_logger
from torchstore_tpu.native import copy_into
from torchstore_tpu.observability import context as obs_context
from torchstore_tpu.observability import metrics as obs_metrics
from torchstore_tpu.observability import profile as obs_profile
from torchstore_tpu.observability import recorder as obs_recorder
from torchstore_tpu.observability import timeline as obs_timeline
from torchstore_tpu.observability.tracing import span
from torchstore_tpu.runtime import ActorDiedError, ActorRef
from torchstore_tpu.strategy import StorageVolumeRef
from torchstore_tpu.transport.buffers import TransportContext
from torchstore_tpu.transport.factory import (
    TransportType,
    create_transport_buffer,
    demotion_ladder,
)
from torchstore_tpu.transport.types import (
    OpaqueBlob,
    Request,
    TensorMeta,
    TensorSlice,
)
from torchstore_tpu.utils import (
    Box,
    assemble_tensor,
    get_destination_view,
    intersect_boxes,
    tensors_overlap_in_memory,
)

logger = get_logger("torchstore_tpu.client")

# Client-side op instruments: logical store operations (one put_batch is one
# op however many volumes/replicas it fans out to; transport-level counters
# in transport/buffers.py count the physical transfers underneath).
_OP_COUNT = obs_metrics.counter(
    "ts_client_ops_total", "Logical client operations by op"
)
_OP_BYTES = obs_metrics.counter(
    "ts_client_bytes_total", "Logical payload bytes by op (pre-replication)"
)
_OP_ERRORS = obs_metrics.counter(
    "ts_client_errors_total", "Failed client operations by op"
)
_OP_SECONDS = obs_metrics.histogram(
    "ts_client_op_seconds", "End-to-end wall time of one client op"
)
_FETCH_RETRIES = obs_metrics.counter(
    "ts_client_fetch_retries_total",
    "Batch fetches retried after a stale-location/ref failure",
)
_PLAN_HITS = obs_metrics.counter(
    "ts_plan_cache_hits_total",
    "put/get_state_dict iterations served by a cached transfer plan, by op",
)
_PLAN_MISSES = obs_metrics.counter(
    "ts_plan_cache_misses_total",
    "put/get_state_dict iterations that (re)built their transfer plan, by op",
)
_PLAN_INVALIDATIONS = obs_metrics.counter(
    "ts_plan_cache_invalidations_total",
    "Cached transfer plans dropped, by reason (epoch/capacity)",
)
_PUT_RETRIES = obs_metrics.counter(
    "ts_client_put_retries_total",
    "Non-replicated put landings retried under the unified RetryPolicy, "
    "by the transport rung the retry used",
)
_FAILOVERS = obs_metrics.counter(
    "ts_client_failovers_total",
    "Operations that succeeded only after failing over (get replica "
    "re-route or put transport demotion), by op",
)

# The ONE transient-failure family every retry/failover decision keys on:
# dead/wedged actors (ActorTimeoutError subclasses ActorDiedError), broken
# transport sockets, and injected chaos faults. Anything else (missing key,
# shape mismatch, type error) is a real answer and surfaces immediately.
RETRYABLE_ERRORS = (ActorDiedError, ConnectionError, OSError, FaultInjectedError)


class SyncPlanCache:
    """Iteration-stable transfer plans for ``put_state_dict`` /
    ``get_state_dict`` (the steady-state sync pipeline's control-plane leg).

    An RL weight-sync loop repeats the SAME size signature every iteration,
    yet the naive path re-validates structure, re-fetches the commit
    marker, and rebuilds request metadata each time. Plans are keyed by
    (op, state-dict key, size signature) and validated against the
    controller's placement epoch — which moves only on STRUCTURAL metadata
    changes (new/changed/deleted keys, detaches, repairs), never on
    same-shape overwrites — so iteration N+1 goes straight to the data
    plane; any placement change drops every plan (and the caller clears
    its location cache with them)."""

    MAX_ENTRIES = 64

    def __init__(self) -> None:
        self.entries: dict[tuple, dict] = {}
        # Last adopted controller placement epoch (None until first seen).
        self.epoch: Optional[int] = None
        # signature -> plan hint seeded by ts.prewarm (provision handoff):
        # the first put of a prewarmed working set adopts the arena layout
        # the provisioner already computed instead of re-deriving it.
        self.seeds: dict[tuple, dict] = {}
        # key -> signature of this client's last put_state_dict push: a
        # CHANGED signature under the same key means the structure was
        # republished — the index alone cannot always see that (dropping
        # keys from a push deletes nothing), so the publisher bumps the
        # placement epoch explicitly.
        self.last_put_sig: dict[str, tuple] = {}

    def observe_epoch(self, epoch: Optional[int]) -> bool:
        """Adopt a controller placement epoch; returns True when the bump
        invalidated cached plans (caller should clear location caches)."""
        if epoch is None or epoch == self.epoch:
            return False
        moved = self.epoch is not None
        self.epoch = epoch
        if moved and self.entries:
            _PLAN_INVALIDATIONS.inc(len(self.entries), reason="epoch")
            self.entries.clear()
        return moved

    def lookup(self, op: str, key: str, signature: tuple) -> Optional[dict]:
        entry = self.entries.get((op, key, signature))
        if entry is not None and entry.get("epoch") == self.epoch:
            _PLAN_HITS.inc(op=op)
            return entry
        _PLAN_MISSES.inc(op=op)
        return None

    def peek(self, op: str, key: str, signature: tuple) -> Optional[dict]:
        """Like lookup but without counting a hit/miss — used to decide
        whether an epoch-validation RPC is even worth issuing."""
        return self.entries.get((op, key, signature))

    def store(
        self,
        op: str,
        key: str,
        signature: tuple,
        plan: dict,
        epoch: Optional[int] = None,
    ) -> None:
        """``epoch`` pins the plan to the placement epoch it was BUILT
        under (callers capture it before fetching the data the plan
        describes) — stamping a later-observed epoch onto an earlier-built
        plan would let a mid-build structural change validate forever."""
        if len(self.entries) >= self.MAX_ENTRIES:
            # Wholesale clear, like the location cache: cheap, and a warm
            # working set re-fills in one iteration.
            _PLAN_INVALIDATIONS.inc(len(self.entries), reason="capacity")
            self.entries.clear()
        plan["epoch"] = self.epoch if epoch is None else epoch
        self.entries[(op, key, signature)] = plan

    def seed(self, signature: tuple, hint: dict) -> None:
        if len(self.seeds) >= self.MAX_ENTRIES:
            self.seeds.clear()
        self.seeds[signature] = hint


@dataclass
class Shard:
    """Explicit sharded value for put/get without a jax.Array: the raw shard
    data plus its TensorSlice placement (used by SPMD ranks and tests)."""

    data: Optional[np.ndarray]
    tensor_slice: TensorSlice


class LocalClient:
    # Bound on the per-client location cache; overflow clears wholesale
    # (cheap, and a warm working set re-fills in one locate round).
    LOC_CACHE_MAX = 65536

    def __init__(
        self,
        controller: ActorRef,
        config: Optional[StoreConfig] = None,
    ) -> None:
        from torchstore_tpu.metadata.router import MetadataRouter

        # Every controller RPC routes through the metadata router: it fans
        # index ops out per controller shard (when the store is sharded),
        # counts every metadata RPC into the traffic ledger, and serves
        # the warm-path reads (locate / plan validation / stream polling)
        # from same-host stamped segments with zero RPCs. Coordinator-
        # scoped ops — including the health diagnosis fan-out — pass
        # through to the one coordinator actor unchanged.
        if isinstance(controller, MetadataRouter):
            controller = controller.coordinator
        self._controller = MetadataRouter(controller)
        self._config = config or default_config()
        self._strategy = None
        self._volume_refs: Optional[dict[str, StorageVolumeRef]] = None
        self._ctx = TransportContext()
        # key -> {volume_id: StorageInfo}: saves the locate RPC on repeat
        # gets (the small-op fast path — reference clients locate on every
        # get, /root/reference/torchstore/client.py:204-237). Invalidated
        # on local deletes; cross-client relocations/deletes are discovered
        # by the fetch failing and retried once with a fresh locate.
        self._loc_cache: dict[str, dict[str, StorageInfo]] = {}
        # Negative memo for nearest-copy routing: (key, prefer_volume)
        # pairs a FRESH locate showed lacking the preferred replica.
        # Without it, every fetch of a key that will never land on the
        # relay volume (sharded keys stay point-to-point) would bypass
        # the location cache and pay a locate RPC forever. Cleared with
        # the location cache on every placement-epoch bump — relay
        # landings are structural, so a later local copy is re-seen.
        self._prefer_misses: set[tuple[str, str]] = set()
        # Volumes observed dead/wedged by THIS client: get ordering prefers
        # healthy replicas, so a replicated key survives a volume death
        # transparently (cleared when a later health check reports ok).
        self._dead_volumes: set[str] = set()
        # Last full-fleet diagnosis (monotonic timestamp + statuses): the
        # retry loops can fail many attempts per second during a correlated
        # outage, and each _raise_with_diagnosis would otherwise trigger a
        # controller-side ping fan-out across EVERY volume — one diagnosis
        # per window serves the whole loop.
        self._diag_at: float = 0.0
        self._diag_statuses: dict[str, str] = {}
        # Volumes the CONTROLLER's health supervisor has quarantined: puts
        # route around them and get ordering deprioritizes them. Refreshed
        # lazily after any placement-epoch bump (quarantine/reinstatement
        # transitions always bump the epoch).
        self._avoid_volumes: set[str] = set()
        self._volumes_stale = False
        # Epoch tracking when the plan cache is disabled (the cache tracks
        # it itself otherwise).
        self._seen_epoch: Optional[int] = None
        # Bumped whenever the volume map is dropped as stale (repair
        # replaced actors); _fetch retries once after any bump.
        self._refresh_epoch = 0
        # Iteration-stable transfer-plan cache (state_dict sync hot path);
        # None when disabled by config.
        self.plan_cache: Optional[SyncPlanCache] = (
            SyncPlanCache() if self._config.plan_cache else None
        )
        # Per-tenant admission gate (control plane, client-side half):
        # None unless armed — the unthrottled hot path pays one attribute
        # check per batch. The local overload probe is the router's
        # per-shard inflight view; slo_report overload feeds refresh()
        # when a harness ships it in.
        self._admission = None
        if self._config.control_admission:
            from torchstore_tpu.control.admission import AdmissionController

            self._admission = AdmissionController(
                self._config.admit_rate_hz,
                burst=self._config.admit_burst,
                tenant=self._config.tenant,
                overload_inflight=self._config.overload_inflight,
            )
            self._admission.bind_local_signal(
                self._controller.inflight_snapshot
            )
        # Hot-key read spreading (replica_spread): a stable per-client salt
        # rotates which equally-eligible replica sorts first, per key —
        # otherwise every client drains the same deterministic first choice
        # and the policy engine's hot-key splits never share load.
        self._spread_salt: Optional[str] = (
            f"{os.getpid()}-{id(self):x}"
            if self._config.replica_spread
            else None
        )

    @property
    def controller(self) -> ActorRef:
        return self._controller

    async def _ensure_setup(self) -> None:
        if self._volume_refs is not None:
            return
        await self._load_volumes()

    async def _load_volumes(self) -> None:
        """(Re)fetch strategy + volume map. The swap at the end is a single
        atomic assignment: concurrent operations keep using the previous
        (possibly stale but structurally valid) map mid-await — they fail
        and retry rather than crash on a half-built state."""
        self._controller.rpc_timeout = self._config.rpc_timeout
        # Metadata-plane topology first: shard refs make every index op
        # below routable, and same-host stamped segments arm the zero-RPC
        # warm paths (advisory — a topology-less controller still serves).
        await self._controller.load_topology(
            meta_stamped=self._config.meta_stamped
        )
        # Arm push-on-publish validation: a push-staged arena serves only
        # once the (possibly mirrored) stamped index confirms its pack-time
        # write generations, so a warm push serve stays zero-RPC end to end.
        from torchstore_tpu.transport.bulk import BulkClientCache

        self._ctx.get_cache(BulkClientCache).push_validate = (
            self._controller.stamped_write_gens
        )
        strategy = await self._controller.get_strategy.call_one()
        vmap = await self._controller.get_volume_map.call_one()
        forced = strategy.default_transport_type if strategy else None
        for info in vmap.values():
            # Every endpoint call on these refs inherits the configured RPC
            # deadline (a wedged-but-alive volume must never hang a client
            # forever — the supervision Monarch provides the reference).
            info["ref"].rpc_timeout = self._config.rpc_timeout
        self._strategy = strategy
        self._volume_refs = {
            vid: StorageVolumeRef(
                actor=info["ref"],
                volume_id=vid,
                transport_context=self._ctx,
                hostname=info["hostname"],
                transport_type=forced,
            )
            for vid, info in vmap.items()
        }

    def _observe_epoch(self, epoch: Optional[int]) -> None:
        """Adopt a controller placement epoch from any RPC reply; a bump
        drops cached plans AND cached locations together (both describe the
        placement that just changed) and marks the health view stale —
        quarantine/reinstatement transitions always bump the epoch, so the
        next put re-reads volume health before selecting targets."""
        if epoch is None:
            return
        bumped = False
        if self.plan_cache is not None:
            bumped = self.plan_cache.observe_epoch(epoch)
        elif self._seen_epoch is not None and epoch != self._seen_epoch:
            bumped = True
        self._seen_epoch = epoch
        if bumped:
            self._loc_cache.clear()
            self._prefer_misses.clear()
            self._volumes_stale = True
            self._drop_one_sided()

    def _drop_one_sided(self) -> None:
        """Epoch/stamp coupling: a placement-epoch bump (structural change,
        quarantine, repair) drops every cached one-sided plan — SHM stamped
        reads AND bulk doorbells — together with the location cache they
        were derived from. The seqlock stamps already make stale plans fall
        back on their own; this keeps the fallback storm to one miss per
        plan and re-routes warm gets with the fresh placement."""
        from torchstore_tpu.transport.bulk import BulkClientCache
        from torchstore_tpu.transport.shared_memory import ShmClientCache

        dropped = 0
        for cache_cls in (ShmClientCache, BulkClientCache):
            cache = self._ctx.peek(cache_cls)
            if cache is not None:
                dropped += cache.drop_one_sided()
        if dropped:
            _PLAN_INVALIDATIONS.inc(dropped, reason="one_sided_epoch")

    @staticmethod
    def _one_sided_miss(cache, miss, pairs) -> None:
        """Count a one-sided miss LOUDLY and, for the plan-invalidating
        family (stale/torn/gone), drop the batch's plans so the fallback
        RPC serve re-records fresh ones."""
        from torchstore_tpu.transport import shared_memory as shm_mod

        shm_mod.ONE_SIDED_FALLBACKS.inc(reason=miss.reason)
        if miss.reason in shm_mod.PLAN_DROPPING_MISSES:
            for pair in pairs:
                cache.one_sided.pop(pair, None)

    async def _refresh_health(self) -> None:
        """Re-read the controller's per-volume health (one cheap RPC, only
        after an epoch bump): quarantined AND draining volumes go into the
        avoid set so puts route around them — a draining volume (autoscale
        scale-in) keeps serving reads but must take no new placements or
        the drain never converges. Volumes the autoscaler attached or
        retired since the last refresh are adopted here too (the attach/
        retire epoch bump is what triggered this refresh)."""
        self._volumes_stale = False
        try:
            vmap = await self._controller.get_volume_map.call_one()
        except RETRYABLE_ERRORS:  # controller hiccup: keep the stale view
            return
        self._avoid_volumes = {
            vid
            for vid, info in vmap.items()
            if info.get("health") in ("quarantined", "draining")
        }
        if set(vmap) != set(self._volume_refs or {}):
            # Fleet membership changed (autoscale attach/retire): rebuild
            # the wrapped volume refs so puts can target new volumes and
            # stop holding refs to retired ones.
            await self._load_volumes()

    async def placement_epoch(self) -> int:
        """Fetch + adopt the controller's current placement epoch — the
        warm plan-validation read. Served from the coordinator's stamped
        header with ZERO RPCs whenever it CONFIRMS the epoch this client
        already holds (the steady-state case: nothing changed, plans stay
        valid). Any other stamped value — older (publish lag) or newer —
        falls back to the RPC for the authoritative answer: adopting a
        lagging epoch would spuriously invalidate every cached plan
        (observe_epoch keys on inequality), costing a rebuild storm for
        nothing."""
        from torchstore_tpu.metadata import router as meta_router

        known = (
            self.plan_cache.epoch
            if self.plan_cache is not None
            else self._seen_epoch
        )
        if known is not None:
            stamped = self._controller.stamped_epoch()
            if stamped is not None and stamped == known:
                meta_router.count_stamped("placement_epoch")
                return stamped
        epoch = await self._controller.placement_epoch.call_one()
        self._observe_epoch(epoch)
        return epoch

    async def bump_placement_epoch(self) -> int:
        """Force-invalidate cached transfer plans fleet-wide (publisher-side
        escape hatch for restructures the index cannot see)."""
        epoch = await self._controller.bump_placement_epoch.call_one()
        self._observe_epoch(epoch)
        return epoch

    async def _land_requests(
        self,
        volume: StorageVolumeRef,
        requests: list[Request],
        plan_hint: Optional[dict] = None,
        transport: Optional[TransportType] = None,
    ) -> dict[str, int]:
        """Data-plane landing of ``requests`` on one volume (batched where
        the transport supports it) — shared by put_batch and replicate_to.
        ``transport`` forces a specific rung (the put retry's demotion
        ladder). Returns the volume-assigned per-key write generations,
        forwarded to the controller so stale-replica reclaims can delete
        conditionally."""
        buffer = create_transport_buffer(volume, self._config, force=transport)
        buffer.plan_hint = plan_hint
        if buffer.supports_batch_puts:
            await buffer.put_to_storage_volume(volume, requests)
            return buffer.write_gens or {}
        await buffer.put_to_storage_volume(volume, requests[:1])
        gens = dict(buffer.write_gens or {})
        for req in requests[1:]:
            b = create_transport_buffer(volume, self._config, force=transport)
            await b.put_to_storage_volume(volume, [req])
            gens.update(b.write_gens or {})
        return gens

    def _put_volumes(self) -> list[StorageVolumeRef]:
        """Every volume a put writes to (primary + replicas). The strategy
        selects against the FULL volume list (strategies like
        LocalRankStrategy key on the client's own id being present); any
        selected volume that is quarantined or client-observed-dead is then
        substituted with a healthy unselected volume. With no healthy spare
        the avoided volume stays (degraded put: land on whoever answers,
        detach the rest) rather than starving the write."""
        client_id = self._strategy.get_client_id()
        selected = list(
            self._strategy.select_put_volume_ids(
                client_id, list(self._volume_refs)
            )
        )
        avoid = self._avoid_volumes | self._dead_volumes
        if avoid and any(vid in avoid for vid in selected):
            spares = sorted(
                vid
                for vid in self._volume_refs
                if vid not in avoid and vid not in selected
            )
            selected = [
                spares.pop(0) if vid in avoid and spares else vid
                for vid in selected
            ]
        return [self._volume_refs[vid] for vid in selected]

    # ------------------------------------------------------------------
    # put
    # ------------------------------------------------------------------

    @staticmethod
    def _value_to_requests(
        key: str, value: Any, d2h: Optional[shd.D2HSeconds] = None
    ) -> list[Request]:
        if isinstance(value, Shard):
            data = value.data
            if torch_interop.is_torch_tensor(data):
                data = torch_interop.to_numpy_view(data)
            return [Request.from_tensor_slice(key, value.tensor_slice, data)]
        if shd.is_jax_array(value):
            return shd.put_requests(key, value, d2h)
        if isinstance(value, np.ndarray):
            return [Request.from_tensor(key, value)]
        if torch_interop.is_torch_tensor(value):
            # Zero-copy view: the transport reads straight out of the torch
            # storage (migration parity — reference callers hold torch
            # tensors everywhere).
            return [Request.from_tensor(key, torch_interop.to_numpy_view(value))]
        if isinstance(value, (int, float, complex)) or np.isscalar(value):
            return [Request.from_objects(key, OpaqueBlob.wrap(value))]
        if hasattr(value, "__array_interface__"):
            return [Request.from_tensor(key, np.asarray(value))]
        # Arbitrary objects are pickled HERE, in the client: volumes and
        # transports carry opaque bytes and never materialize user types
        # (materializing a jax-bearing leaf inside a volume process would
        # initialize an accelerator backend there).
        return [Request.from_objects(key, OpaqueBlob.wrap(value))]

    async def put(self, key: str, value: Any) -> None:
        await self.put_batch({key: value})

    async def put_batch(
        self,
        items: dict[str, Any],
        plan_hint: Optional[dict] = None,
        watermark: Optional[tuple] = None,
        unchanged: Optional[dict] = None,
    ) -> None:
        t0 = time.perf_counter()
        try:
            # ensure_root: every logical op roots (or joins) a distributed
            # trace — the id rides the notify/volume RPC frames so remote
            # spans stitch to this one in a merged timeline.
            with obs_context.ensure_root(), span(
                "put_batch",
                keys=len(items),
                key=next(iter(items), None),
            ) as sp:
                nbytes = await self._put_batch(
                    items, sp, plan_hint, watermark, unchanged
                )
                dur = time.perf_counter() - t0
                obs_profile.record_op(
                    "put",
                    next(iter(items), None),
                    nbytes,
                    t0,
                    dur,
                    tally=False,  # per-key tallies happen in _put_batch
                    keys=len(items),
                )
        except BaseException as exc:
            _OP_ERRORS.inc(op="put")
            obs_recorder.record(
                "error", "put", error=f"{type(exc).__name__}: {exc}"[:200]
            )
            raise
        _OP_COUNT.inc(op="put")
        _OP_BYTES.inc(nbytes, op="put")
        _OP_SECONDS.observe(dur, op="put")
        # Decision telemetry: rolling p50/p99 digests (+ their SLO checks)
        # and a flight-recorder breadcrumb — one each per BATCH.
        obs_timeline.observe_op("put", dur)
        obs_recorder.record(
            "op", "put", keys=len(items), nbytes=nbytes,
            ms=round(dur * 1e3, 3),
        )

    async def _put_batch(
        self,
        items: dict[str, Any],
        sp,
        plan_hint: Optional[dict] = None,
        watermark: Optional[tuple] = None,
        unchanged: Optional[dict] = None,
    ) -> int:
        if self._admission is not None:
            # Backpressure BEFORE any volume sees bytes: a bursting tenant
            # queues at its own bucket, not inside the landing pool.
            delay = self._admission.admit(len(items))
            if delay > 0.0:
                await asyncio.sleep(delay)
        await self._ensure_setup()
        if self._volumes_stale:
            await self._refresh_health()
        tracker = LatencyTracker("put_batch")
        # Issue the device->host copy of every SMALL array of the whole
        # batch up front so transfers overlap across arrays too, not just
        # across one array's shards. issue_d2h skips an array that leaves
        # in chunks (shd.chunk_plan): shd.put_requests runs its window when
        # its turn comes, and a whole copy as well would move every byte
        # twice.
        d2h = shd.D2HSeconds()
        on_device = [v for v in items.values() if shd.is_jax_array(v)]
        if on_device:
            shd.issue_d2h(
                (s.data for v in on_device for s in v.addressable_shards), d2h
            )
        requests: list[Request] = []
        with span("put.requests", keys=len(items)):
            for key, value in items.items():
                requests.extend(self._value_to_requests(key, value, d2h))
        volumes = self._put_volumes()
        # Stage attribution, once per batch: the seconds spent getting
        # device bytes to the host are the d2h leg; the rest of what runs
        # before the first byte moves to a volume is the planning leg
        # (setup, request building, placement).
        if d2h.seconds:
            obs_timeline.observe_stage("put", "d2h", d2h.seconds)
        obs_timeline.observe_stage(
            "put", "plan", max(tracker.elapsed - d2h.seconds, 0.0)
        )
        nbytes = sum(r.nbytes for r in requests)
        sp.set(nbytes=nbytes, replicas=len(volumes))
        hot = obs_profile.hot_key_tracker()
        for req in requests:
            hot.record(req.key, req.nbytes)

        async def put_to(volume: StorageVolumeRef) -> dict[str, int]:
            try:
                return await self._land_requests(volume, requests, plan_hint)
            except (ActorDiedError, ConnectionError, OSError) as exc:
                # Bulk/peer transports surface volume death as
                # ConnectionError — normalize so callers and the failover
                # machinery see one exception family.
                await self._raise_with_diagnosis(volume.volume_id, exc)

        async def land_all() -> tuple[list, list]:
            # Replicated puts hit every target volume concurrently.
            # return_exceptions: every write FINISHES before we decide (no
            # detached sibling tasks racing a caller's retry, no
            # unretrieved exceptions).
            results = await asyncio.gather(
                *(put_to(v) for v in volumes), return_exceptions=True
            )
            return (
                [
                    (v, r)
                    for v, r in zip(volumes, results)
                    if not isinstance(r, BaseException)
                ],
                [
                    (v, r)
                    for v, r in zip(volumes, results)
                    if isinstance(r, BaseException)
                ],
            )

        landed, failed = await land_all()
        if (
            not landed
            and len(volumes) > 1
            and all(isinstance(r, RETRYABLE_ERRORS) for _, r in failed)
        ):
            # EVERY replica failed transiently (correlated chaos, a fleet-
            # wide hiccup): a partial failure would detach-and-continue,
            # but with zero landed copies there is nothing to commit —
            # retry the whole replicated landing under the unified policy.
            policy = self._config.retry
            deadline = policy.start()
            attempt = 0
            while not landed and policy.should_retry(attempt, deadline):
                await asyncio.sleep(policy.backoff(attempt))
                attempt += 1
                # Re-resolve placement each attempt: the supervisor may
                # have quarantined the failed replicas meanwhile, or the
                # diagnosis marked them dead — _put_volumes substitutes
                # healthy spares for both, and land_all reads the rebound
                # list (the supersede notify detaches whatever the old
                # replicas still hold under these keys).
                if self._volumes_stale:
                    await self._refresh_health()
                fresh = self._put_volumes()
                if {v.volume_id for v in fresh} != {
                    v.volume_id for v in volumes
                }:
                    logger.warning(
                        "replicated put re-routed: %s -> %s",
                        sorted(v.volume_id for v in volumes),
                        sorted(v.volume_id for v in fresh),
                    )
                    volumes = fresh
                landed, retry_failed = await land_all()
                if landed:
                    failed = retry_failed
                    _FAILOVERS.inc(op="put")
                    logger.warning(
                        "replicated put recovered on retry %d (first "
                        "failure: %s)",
                        attempt,
                        failed[0][1] if failed else "all replicas",
                    )
                elif not all(
                    isinstance(r, RETRYABLE_ERRORS) for _, r in retry_failed
                ):
                    failed = retry_failed
                    break  # a real (non-transient) answer surfaced
        if not landed and len(volumes) == 1:
            # Non-replicated put: no sibling replica absorbs the failure,
            # so retry transient transport failures under the unified
            # RetryPolicy, demoting one transport rung per attempt
            # (shm -> bulk -> rpc). Volumes the controller diagnosed
            # dead/wedged/quarantined are NOT retried here — no transport
            # reaches a dead process (put_to's diagnosis populated
            # _dead_volumes before we got here).
            gens = await self._retry_put_demoted(
                volumes[0], requests, failed[0][1]
            )
            if gens is not None:
                landed, failed = [(volumes[0], gens)], []
            elif isinstance(failed[0][1], RETRYABLE_ERRORS):
                # The target itself is gone (diagnosed dead/wedged): re-
                # resolve placement — _put_volumes now filters it out — and
                # land on the next healthy volume. The supersede notify
                # below detaches whatever the dead volume still holds under
                # these keys, so its stale bytes can never resurface if it
                # is later reinstated.
                if self._volumes_stale:
                    await self._refresh_health()
                retry = self._put_volumes()
                if retry and retry[0].volume_id != volumes[0].volume_id:
                    try:
                        gens = await self._land_requests(retry[0], requests)
                    except RETRYABLE_ERRORS as exc:
                        logger.warning(
                            "put failover to %s failed too: %s",
                            retry[0].volume_id,
                            exc,
                        )
                    else:
                        landed, failed = [(retry[0], gens)], []
                        _FAILOVERS.inc(op="put")
                        logger.warning(
                            "put failed over from %s to %s",
                            volumes[0].volume_id,
                            retry[0].volume_id,
                        )
        if not landed:
            raise failed[0][1]
        # The wire legs themselves record the "transport" stage per volume
        # (transport/buffers.py) — the tracker only logs the wall span here.
        tracker.track_step("data_plane", nbytes)
        for volume, exc in failed:
            # Partial replication failure on an OVERWRITE would leave the
            # failed replica serving the previous value under still-
            # committed metadata — the notify below atomically detaches
            # its copies of exactly these metas, so readers only ever see
            # volumes holding the new bytes. The put succeeds at degraded
            # redundancy; the next successful put re-replicates.
            logger.warning(
                "replicated put degraded: volume %s failed (%s); detaching "
                "its stale copies",
                volume.volume_id,
                exc,
            )
        # Two-plane invariant: metadata notify happens only after the data
        # landed (/root/reference/torchstore/client.py:86-90). ONE RPC
        # indexes every landed replica and detaches every failed one — no
        # window where new metadata coexists with a stale replica location.
        epoch = await self._controller.notify_put_batch.call_one(
            [r.meta_only() for r in requests],
            [v.volume_id for v, _ in landed],
            detach_volume_ids=[v.volume_id for v, _ in failed] or None,
            write_gens={v.volume_id: gens for v, gens in landed},
            # Full overwrite: any volume OUTSIDE this put's replica set
            # still indexed for these metas (an auto-repair extra copy, or
            # a previous placement before failover re-routed) holds
            # superseded bytes — detach + reclaim them in the same step.
            supersede=True,
            # Streamed publishes stamp every key of this batch with the
            # stream version in the same indexing step — the watermark is
            # only ever visible once its bytes are committed.
            watermark=watermark,
            # Unchanged-key aliases (delta tier) ride the same step.
            unchanged=unchanged,
        )
        # The notify reply carries the placement epoch for free: a bump
        # (structural change anywhere in the fleet) drops cached plans.
        self._observe_epoch(epoch)
        obs_timeline.observe_stage("put", "notify", tracker.track_step("notify"))
        tracker.log_summary()
        return nbytes

    async def _retry_put_demoted(
        self,
        volume: StorageVolumeRef,
        requests: list[Request],
        first_exc: BaseException,
    ) -> Optional[dict[str, int]]:
        """Retry a failed single-volume landing under ``config.retry``,
        walking down the transport ladder one rung per attempt. Returns the
        write generations on success, None when the policy is exhausted or
        the volume is diagnosed dead (caller surfaces ``first_exc``)."""
        if not isinstance(first_exc, RETRYABLE_ERRORS):
            return None
        if volume.volume_id in self._dead_volumes:
            return None
        policy = self._config.retry
        deadline = policy.start()
        ladder = demotion_ladder(volume, self._config)
        attempt = 0
        while policy.should_retry(attempt, deadline):
            await asyncio.sleep(policy.backoff(attempt))
            rung = ladder[min(attempt + 1, len(ladder) - 1)]
            try:
                # plan_hint deliberately dropped: it describes the rung
                # that just failed (e.g. an shm arena layout).
                gens = await self._land_requests(
                    volume, requests, transport=rung
                )
            except RETRYABLE_ERRORS as exc:
                attempt += 1
                logger.warning(
                    "put retry %d on %s over %s failed: %s",
                    attempt,
                    volume.volume_id,
                    rung.value,
                    exc,
                )
                if volume.volume_id in self._dead_volumes:
                    return None
                continue
            _PUT_RETRIES.inc(transport=rung.value)
            _FAILOVERS.inc(op="put")
            logger.warning(
                "non-replicated put to %s recovered on transport %s after "
                "%d retr%s (first failure: %s)",
                volume.volume_id,
                rung.value,
                attempt + 1,
                "y" if attempt == 0 else "ies",
                first_exc,
            )
            return gens
        return None

    # ------------------------------------------------------------------
    # get
    # ------------------------------------------------------------------

    async def get(self, key: str, like: Any = None) -> Any:
        results = await self.get_batch({key: like})
        return results[key]

    async def get_batch(
        self,
        items,
        _seed_plan: bool = True,
        prefer_volume: Optional[str] = None,
    ) -> dict[str, Any]:
        """All-or-nothing batched get (invariant 8): any missing key fails the
        whole batch before data moves (locate happens up front). ``items``
        is either a list of keys or {key: fetch_target_or_None} (reference
        signature parity, /root/reference/torchstore/api.py:242-279).

        ``prefer_volume``: replica-selection preference — when a key has a
        copy on this volume (e.g. the caller's RELAY volume, holding the
        broadcast-distributed local copy), fetch from it; other replicas
        stay as fallback. Never a hard pin: a key absent there serves from
        wherever it lives.

        ``_seed_plan=False`` (internal): state-dict ops manage their own
        SyncPlanCache entries and epoch validation — they skip the
        batch-level seeding below to avoid double bookkeeping."""
        t0 = time.perf_counter()
        try:
            with obs_context.ensure_root(), span(
                "get_batch", keys=len(items)
            ) as sp:
                out = await self._get_batch(
                    items, _seed_plan=_seed_plan, prefer_volume=prefer_volume
                )
                # Stored OBJECTS come back as arbitrary user types; only
                # count an nbytes attribute that is actually a number.
                sizes = [
                    (
                        key,
                        n if isinstance((n := getattr(v, "nbytes", 0)), int) else 0,
                    )
                    for key, v in out.items()
                ]
                nbytes = sum(n for _, n in sizes)
                sp.set(nbytes=nbytes)
                dur = time.perf_counter() - t0
                obs_profile.record_keys("get", sizes, t0, dur)
        except BaseException as exc:
            _OP_ERRORS.inc(op="get")
            obs_recorder.record(
                "error", "get", error=f"{type(exc).__name__}: {exc}"[:200]
            )
            raise
        _OP_COUNT.inc(op="get")
        _OP_BYTES.inc(nbytes, op="get")
        _OP_SECONDS.observe(dur, op="get")
        obs_timeline.observe_op("get", dur)
        obs_recorder.record(
            "op", "get", keys=len(items), nbytes=nbytes,
            ms=round(dur * 1e3, 3),
        )
        return out

    async def _get_batch(
        self,
        items,
        _seed_plan: bool = True,
        prefer_volume: Optional[str] = None,
    ) -> dict[str, Any]:
        # Per-leaf Python before any byte moves: target slices, request
        # building, the plan-cache lookup.
        with span("get.plan", keys=len(items)):
            if isinstance(items, str):
                raise TypeError(
                    "get_batch takes a list of keys or a {key: target} dict, "
                    f"not a bare string ({items!r}); use get() for one key"
                )
            if not isinstance(items, dict):
                items = {key: None for key in items}
            if self._admission is not None:
                delay = self._admission.admit(len(items))
                if delay > 0.0:
                    await asyncio.sleep(delay)
            await self._ensure_setup()
            if self._config.one_sided:
                # Covered warm batch: every member served straight from stamped
                # SHM segments BEFORE any Request/signature machinery runs —
                # the many-keys warm get leg is this line plus one native
                # scatter memcpy (zero RPCs; ISSUE 7 acceptance).
                served = await self._get_batch_one_sided(items)
                if served is not None:
                    return served
            plan: list[tuple[str, Request, Any]] = []  # (key, request, like)
            # plan index -> device array served one-sided before any request was
            # built (plain-spec warm path: device_put straight from the stamped
            # segment view — no host copy, no RPC).
            pre_served: dict[int, Any] = {}
            jax_targets: dict[int, list] = {}
            # plan index -> (original torch tensor, its numpy view): the original
            # is handed back only when the fetch actually landed in the view.
            torch_returns: dict[int, tuple[Any, np.ndarray]] = {}
            requests: list[Request] = []
            for key, like in items.items():
                if torch_interop.is_torch_tensor(like):
                    view = torch_interop.to_numpy_view(like, allow_copy=False)
                    torch_returns[len(plan)] = (like, view)
                    like = view
                if like is None:
                    requests.append(Request.meta_request(key))
                    plan.append((key, requests[-1], None))
                elif isinstance(like, Shard):
                    data = like.data
                    if torch_interop.is_torch_tensor(data):
                        view = torch_interop.to_numpy_view(data, allow_copy=False)
                        torch_returns[len(plan)] = (data, view)
                        like = Shard(data=view, tensor_slice=like.tensor_slice)
                    req = Request.from_tensor_slice(key, like.tensor_slice)
                    req.tensor_val = like.data
                    requests.append(req)
                    plan.append((key, req, like))
                elif isinstance(like, TensorSlice):
                    requests.append(Request.from_tensor_slice(key, like))
                    plan.append((key, requests[-1], like))
                elif shd.is_jax_array(like) or shd.is_sharded_spec(like):
                    # target_slices/build_array only need .shape/.sharding, so a
                    # ShapeDtypeStruct works as a no-allocation restore target.
                    targets = shd.target_slices(like)
                    jax_targets[len(plan)] = targets
                    sub_reqs = [Request.from_tensor_slice(key, ts) for _, ts in targets]
                    requests.extend(sub_reqs)
                    plan.append((key, sub_reqs, like))
                elif shd.is_plain_spec(like):
                    # Sharding-less ShapeDtypeStruct: fetch the whole tensor and
                    # return a default-placed device array of the spec's dtype.
                    # Warm path first: upload straight from the stamped segment.
                    served = self._try_one_sided_device(key, like)
                    if served is not None:
                        pre_served[len(plan)] = served
                        plan.append((key, None, like))
                    else:
                        requests.append(Request.meta_request(key))
                        plan.append((key, requests[-1], like))
                elif isinstance(like, np.ndarray):
                    req = Request(key=key, tensor_val=like)
                    requests.append(req)
                    plan.append((key, req, like))
                else:
                    raise TypeError(f"unsupported get target {type(like)} for {key!r}")

            # Batch-level plan seeding (the get_batch leg of the iteration-
            # stable plan cache — previously only state-dict ops populated it):
            # a repeated identical batch validates with ONE epoch check instead
            # of per-key locates, and skips even that when every member has a
            # one-sided plan (the stamped reads self-validate).
            pc = self.plan_cache
            batch_sig = self._batch_signature(items) if _seed_plan and pc else None
            batch_plan = None
            if batch_sig is not None and pc.peek("get_batch", "", batch_sig):
                if not self._one_sided_covers(requests):
                    await self.placement_epoch()
                batch_plan = pc.lookup("get_batch", "", batch_sig)
                if batch_plan is not None:
                    if len(self._loc_cache) + len(batch_plan["located"]) > (
                        self.LOC_CACHE_MAX
                    ):
                        self._loc_cache.clear()
                    for k, infos in batch_plan["located"].items():
                        self._loc_cache.setdefault(k, infos)
        flat_results = await self._fetch(requests, prefer_volume=prefer_volume)
        if batch_sig is not None and batch_plan is None:
            pc.store(
                "get_batch",
                "",
                batch_sig,
                {
                    "located": {
                        r.key: self._loc_cache[r.key]
                        for r in requests
                        if r.key in self._loc_cache
                    }
                },
            )
        by_request = dict(zip((id(r) for r in requests), flat_results))

        out: dict[str, Any] = {}
        h2d_s = 0.0  # the batch's "h2d" stage, booked once below
        for idx, (key, req_or_list, like) in enumerate(plan):
            if idx in pre_served:
                out[key] = pre_served[idx]
                continue
            if isinstance(req_or_list, list):  # jax target
                targets = jax_targets[idx]
                # Honor the target's dtype (the orbax restore idiom: a
                # bf16 spec over fp32-stored weights converts on fetch).
                want_dtype = (
                    TensorMeta(shape=(), dtype=str(like.dtype)).np_dtype
                    if hasattr(like, "dtype")
                    else None
                )
                parts = []
                for (dev, _), r in zip(targets, req_or_list):
                    arr = np.asarray(by_request[id(r)])
                    if want_dtype is not None and arr.dtype != want_dtype:
                        arr = arr.astype(want_dtype)
                    parts.append((dev, arr))
                t_h2d = time.perf_counter()
                out[key] = shd.build_array(like, parts)
                h2d_s += time.perf_counter() - t_h2d
            elif shd.is_plain_spec(like):
                import jax.numpy as jnp

                arr = np.asarray(by_request[id(req_or_list)])
                if tuple(arr.shape) != tuple(like.shape):
                    raise ValueError(
                        f"stored shape {tuple(arr.shape)} != spec shape "
                        f"{tuple(like.shape)} for key {key!r}"
                    )
                with span("h2d.dispatch", nbytes=arr.nbytes, parts=1) as sp:
                    out[key] = jnp.asarray(arr, dtype=like.dtype)
                h2d_s += sp.elapsed
            else:
                out[key] = by_request[id(req_or_list)]
            if idx in torch_returns:
                tensor, view = torch_returns[idx]
                # Hand the caller their tensor object back ONLY if the fetch
                # landed in its storage (assemble returns the dest view). A
                # key stored as a plain object comes back as that object —
                # never a silently unfilled tensor.
                if out[key] is view:
                    out[key] = tensor
        if h2d_s:
            obs_timeline.observe_stage("get", "h2d", h2d_s)
        return out

    async def _get_batch_one_sided(self, items: dict) -> Optional[dict]:
        """Whole-batch one-sided serve for the simple warm shape: every
        target is None or a plain numpy destination and every key has a
        cached stamped plan. Runs before the per-item Request-building
        loop — at many-keys scale that loop (type dispatch, Request
        construction, signature/seeding bookkeeping) costs more than the
        copies. Returns None (untouched batch) when any member doesn't
        qualify; misses drop stale plans and fall back to the full path,
        exactly like ``_fetch_all_one_sided``."""
        from torchstore_tpu.transport import shared_memory as shm_mod

        cache = self._ctx.peek(shm_mod.ShmClientCache)
        if cache is None or not cache.one_sided:
            return None
        one_sided = cache.one_sided
        plans: list[dict] = []
        dests: list[Optional[np.ndarray]] = []
        for key, like in items.items():
            if like is not None and type(like) is not np.ndarray:
                return None
            plan = shm_mod.covered_plan(
                one_sided, key, None, has_dest=like is not None
            )
            if plan is None:
                return None
            plans.append(plan)
            dests.append(like)
        try:
            results = await shm_mod.stamped_read_batch(
                cache, plans, dests, config=self._config
            )
        except shm_mod.OneSidedMiss as miss:
            self._one_sided_miss(cache, miss, [(key, None) for key in items])
            return None
        return dict(zip(items, results))

    def _batch_signature(self, items: dict) -> Optional[tuple]:
        """Hashable identity of a get_batch request set (keys + target
        layouts) — the plan-cache key for batch-level seeding. None when a
        target has no stable signature (that batch is not plan-cached)."""
        from torchstore_tpu.state_dict_utils import _leaf_signature

        try:
            return tuple(
                (key, None if like is None else _leaf_signature(like))
                for key, like in items.items()
            )
        except Exception:  # noqa: BLE001 - unsignable target: skip caching
            return None

    # ------------------------------------------------------------------
    # fetch pipeline
    # ------------------------------------------------------------------

    async def _fetch(
        self,
        requests: list[Request],
        prefer_volume: Optional[str] = None,
    ) -> list[Any]:
        """Fetch with two retry families layered on ``_fetch_once``:

        - *Stale state* (KeyError/ValueError: another client deleted or
          re-published a key, layout mismatch): ONE fresh retry — a missing
          key is an answer, not a transient, so no backoff loop.
        - *Transient* (dead/wedged actors, broken sockets, injected
          faults): retries under the unified RetryPolicy. Each failure's
          diagnosis marks unhealthy volumes, so the re-located retry fails
          over to the next healthy replica; retries continue only while a
          volume this client has NOT seen fail remains (when every volume
          is known-dead, waiting out the deadline helps nobody — surface)."""
        policy = self._config.retry
        deadline = policy.start()
        attempt = 0
        stale_retried = False
        while True:
            epoch = self._refresh_epoch
            try:
                out = await self._fetch_once(
                    requests,
                    use_cache=attempt == 0 and not stale_retried,
                    prefer_volume=prefer_volume,
                )
                if attempt > 0:
                    _FAILOVERS.inc(op="get")
                return out
            except RETRYABLE_ERRORS as exc:
                for req in requests:
                    self._loc_cache.pop(req.key, None)
                alive = [
                    v
                    for v in (self._volume_refs or {})
                    if v not in self._dead_volumes
                ]
                if not alive and attempt > 0:
                    raise  # whole fleet diagnosed down: nothing to fail over to
                if not policy.should_retry(attempt, deadline):
                    raise
                _FETCH_RETRIES.inc()
                logger.warning(
                    "fetch attempt %d failed (%s); failing over "
                    "(%d healthy volume(s) remain)",
                    attempt + 1,
                    exc,
                    len(alive),
                )
                await asyncio.sleep(policy.backoff(attempt))
                attempt += 1
            except (KeyError, ValueError) as exc:
                stale = [r.key for r in requests if r.key in self._loc_cache]
                if stale_retried or (
                    not stale and self._refresh_epoch == epoch
                ):
                    raise
                stale_retried = True
                for key in stale:
                    self._loc_cache.pop(key, None)
                _FETCH_RETRIES.inc()
                logger.info(
                    "stale location/refs for %d key(s) (%s); re-locating",
                    len(stale),
                    exc,
                )

    async def _fetch_once(
        self,
        requests: list[Request],
        use_cache: bool,
        prefer_volume: Optional[str] = None,
    ) -> list[Any]:
        # Refs may have been dropped by a stale-ref diagnosis between the
        # first attempt and this retry; rebuild them from the controller.
        await self._ensure_setup()
        if use_cache and self._config.one_sided:
            served = await self._fetch_all_one_sided(requests)
            if served is not None:
                return served
        t_plan = time.perf_counter()
        keys = list({r.key for r in requests})
        located: dict[str, dict[str, StorageInfo]] = {}
        missing = []
        for key in keys:
            cached = self._loc_cache.get(key) if use_cache else None
            if (
                cached is not None
                and prefer_volume is not None
                and prefer_volume not in cached
                and (key, prefer_volume) not in self._prefer_misses
            ):
                # Nearest-copy routing: the cached locations predate the
                # relay landing this caller's local replica (another
                # subscriber of the same client located the key earlier) —
                # a stale entry here would silently re-route every read
                # back to the origin volumes. Re-locate ONCE per placement
                # epoch; if the fresh view still lacks the preferred
                # replica the miss is memoized and the key serves from
                # wherever it lives.
                cached = None
            if cached is not None:
                located[key] = cached
            else:
                missing.append(key)
        if missing and use_cache and prefer_volume is None:
            # One-sided warm locate: committed locations from the stamped
            # metadata segments (zero RPCs), filling the location cache so
            # the staleness ladder below them is EXACTLY the warm-cache
            # one — a lingering deleted key fails at the volume and the
            # fetch retries with use_cache=False, which skips this path
            # and pays the authoritative RPC locate.
            hits = self._controller.stamped_locate(missing)
            if hits:
                if len(self._loc_cache) + len(hits) > self.LOC_CACHE_MAX:
                    self._loc_cache.clear()
                self._loc_cache.update(hits)
                located.update(hits)
                missing = [k for k in missing if k not in hits]
        if missing:
            fresh = await self._controller.locate_volumes.call_one(missing)
            if len(self._loc_cache) + len(fresh) > self.LOC_CACHE_MAX:
                self._loc_cache.clear()
            self._loc_cache.update(fresh)
            located.update(fresh)
            if prefer_volume is not None:
                if len(self._prefer_misses) > self.LOC_CACHE_MAX:
                    self._prefer_misses.clear()
                self._prefer_misses.update(
                    (key, prefer_volume)
                    for key, infos in fresh.items()
                    if prefer_volume not in infos
                )
        # Stage attribution: location resolve (cache / stamped segments /
        # RPC locate) + request partitioning is the get's planning leg.
        obs_timeline.observe_stage(
            "get", "plan", time.perf_counter() - t_plan
        )
        # volume_id -> list of (request_index, sub_request)
        by_volume: dict[str, list[tuple[int, Request]]] = {}
        if any(
            vid not in self._volume_refs
            for infos in located.values()
            for vid in infos
        ):
            # The controller located a volume attached since this client's
            # last membership refresh (an autoscale attach racing this get):
            # adopt the new fleet before building per-volume requests.
            await self._refresh_health()
        inplace_ok = self._transports_support_inplace(located)
        for idx, req in enumerate(requests):
            subs = self._build_volume_requests(
                req, located[req.key], inplace_ok, prefer_volume=prefer_volume
            )
            for vid, sub in subs:
                by_volume.setdefault(vid, []).append((idx, sub))

        # Results are collected by SIDE EFFECT (tasks return None): a finished
        # asyncio Task retains its result until garbage collection, so
        # returning fetched arrays through gather() would keep zero-copy
        # views alive indefinitely — the volume would never see their
        # releases and every put would retire-and-reallocate segments.
        parts_by_request: dict[int, list[tuple[Request, Any]]] = {}

        # One-sided warm path: volumes whose every sub-request has a cached
        # stamped plan are served straight out of their pre-attached SHM
        # segments — zero RPCs — and leave the fan-out below entirely.
        if use_cache and self._config.one_sided:
            await self._serve_one_sided(by_volume, parts_by_request)

        async def fetch_volume(vid: str, entries: list[tuple[int, Request]]) -> None:
            volume = self._volume_refs[vid]
            buffer = create_transport_buffer(volume, self._config)
            subs = [sub for _, sub in entries]
            # Shard coordinates ride the span so a trace shows exactly which
            # mesh coords each volume served (straggler attribution).
            coords = [
                sub.tensor_slice.coordinates
                for sub in subs
                if sub.tensor_slice is not None
            ]
            with span(
                "fetch_volume",
                volume=vid,
                transport=buffer.transport_name,
                keys=len(subs),
                coords=coords if coords else None,
            ):
                try:
                    if buffer.supports_batch_gets or len(subs) == 1:
                        results = await buffer.get_from_storage_volume(
                            volume, subs
                        )
                    else:
                        results = []
                        for sub in subs:
                            b = create_transport_buffer(volume, self._config)
                            results.extend(
                                await b.get_from_storage_volume(volume, [sub])
                            )
                except (ActorDiedError, ConnectionError, OSError) as exc:
                    # Bulk/peer transports report volume death as
                    # ConnectionError; normalizing through the diagnosis path
                    # marks the volume dead so the retry prefers replicas.
                    await self._raise_with_diagnosis(vid, exc)
            for (idx, sub), res in zip(entries, results):
                parts_by_request.setdefault(idx, []).append((sub, res))

        await asyncio.gather(
            *(fetch_volume(vid, entries) for vid, entries in by_volume.items())
        )
        out = [
            self._assemble_result(req, parts_by_request.pop(idx, []))
            for idx, req in enumerate(requests)
        ]
        return out

    async def _fetch_all_one_sided(
        self, requests: list[Request]
    ) -> Optional[list[Any]]:
        """Whole-batch one-sided fast path: when EVERY request is a plain
        full-tensor fetch with a cached stamped plan, serve the lot as one
        stamped memcpy loop and skip the locate / per-key sub-request
        building / transport-buffer machinery entirely (measured ~40% of
        warm many-keys get wall time on a 2-vCPU host — per-key Python,
        not data movement). Returns None when any member is uncovered or
        the batch misses; stale/torn misses drop the affected plans so the
        normal path's RPC serve re-records fresh ones. Deleted keys miss
        too (tombstoned stamp), so the normal path still owns the loud
        KeyError."""
        from torchstore_tpu.transport import shared_memory as shm_mod

        cache = self._ctx.peek(shm_mod.ShmClientCache)
        if cache is None or not cache.one_sided:
            return None
        plans: list[dict] = []
        dests: list[Optional[np.ndarray]] = []
        for req in requests:
            if req.is_object or req.tensor_slice is not None:
                return None
            plan = shm_mod.covered_plan(
                cache.one_sided,
                req.key,
                None,
                has_dest=req.tensor_val is not None,
            )
            if plan is None:
                # Uncovered, or a destination-less big get where the RPC
                # path's zero-copy snapshot view beats a one-sided copy.
                return None
            plans.append(plan)
            dests.append(req.tensor_val)
        try:
            return await shm_mod.stamped_read_batch(
                cache, plans, dests, config=self._config
            )
        except shm_mod.OneSidedMiss as miss:
            self._one_sided_miss(
                cache, miss, [(req.key, None) for req in requests]
            )
            return None

    async def _serve_one_sided(
        self,
        by_volume: dict[str, list[tuple[int, Request]]],
        parts_by_request: dict[int, list[tuple[Request, Any]]],
    ) -> None:
        """Serve every fully plan-covered volume's sub-requests as one
        stamped memcpy loop (``shared_memory.stamped_read_batch``) and drop
        those volumes from the RPC fan-out. All-or-nothing per volume: a
        partially covered batch stays on the RPC path (it pays the RPC
        anyway, and the RPC serve refreshes every member's plan). Misses
        fall back LOUDLY (``ts_one_sided_fallbacks_total``); stale/torn/
        gone plans are dropped so the fallback RPC re-records fresh ones."""
        from torchstore_tpu.transport import shared_memory as shm_mod

        cache = self._ctx.peek(shm_mod.ShmClientCache)
        if cache is None or not cache.one_sided:
            return
        for vid in list(by_volume):
            entries = by_volume[vid]
            plans: Optional[list[dict]] = []
            for _, sub in entries:
                if sub.is_object:
                    plans = None
                    break
                plan = shm_mod.covered_plan(
                    cache.one_sided,
                    sub.key,
                    shm_mod.slice_sig(sub.tensor_slice),
                    has_dest=sub.destination_view is not None,
                )
                if plan is None:
                    plans = None
                    break
                plans.append(plan)
            if plans is None:
                continue
            dests = [sub.destination_view for _, sub in entries]
            try:
                results = await shm_mod.stamped_read_batch(
                    cache, plans, dests, config=self._config
                )
            except shm_mod.OneSidedMiss as miss:
                self._one_sided_miss(
                    cache,
                    miss,
                    [
                        (sub.key, shm_mod.slice_sig(sub.tensor_slice))
                        for _, sub in entries
                    ],
                )
                continue
            for (idx, sub), res in zip(entries, results):
                parts_by_request.setdefault(idx, []).append((sub, res))
            del by_volume[vid]

    def _one_sided_covers(self, requests: list[Request]) -> bool:
        """True when every request has a cached one-sided plan for its exact
        (key, slice): the warm batch can go ZERO-RPC, so even the epoch-
        validation RPC is skipped — the per-entry stamps self-validate (any
        placement change lands through the volume and moves them, and a
        deleted entry's tombstone forces the fallback that re-locates)."""
        if not self._config.one_sided or not requests:
            return False
        from torchstore_tpu.transport.shared_memory import (
            ShmClientCache,
            covered_plan,
            slice_sig,
        )

        cache = self._ctx.peek(ShmClientCache)
        if cache is None or not cache.one_sided:
            return False
        return all(
            not req.is_object
            and covered_plan(
                cache.one_sided,
                req.key,
                slice_sig(req.tensor_slice),
                has_dest=req.tensor_val is not None,
            )
            is not None
            for req in requests
        )

    def one_sided_covers_items(
        self, items: "list[tuple[str, bool]]"
    ) -> bool:
        """True when every (store key, has_destination) pair would be served
        by the whole-batch one-sided fast path — same coverage test as
        ``_fetch_all_one_sided``, callable before requests are built (the
        warm ``get_state_dict`` plan path uses it to skip even the
        epoch-validation RPC; the per-entry stamps self-validate)."""
        if not self._config.one_sided:
            return False
        from torchstore_tpu.transport.shared_memory import (
            ShmClientCache,
            covered_plan,
        )

        cache = self._ctx.peek(ShmClientCache)
        if cache is None or not cache.one_sided:
            return False
        return all(
            covered_plan(cache.one_sided, key, None, has_dest) is not None
            for key, has_dest in items
        )

    def _try_one_sided_device(self, key: str, spec) -> Optional[Any]:
        """Warm plain-spec (ShapeDtypeStruct) get: upload to device STRAIGHT
        from the borrowed stamped SHM view — jax reads the mapped segment
        bytes itself, so there is no intermediate host copy and no RPC.
        Returns the device array, or None (no plan / shape drift / torn
        upload) and the caller takes the normal fetch path."""
        if not self._config.one_sided:
            return None
        from torchstore_tpu.transport import device_transfer
        from torchstore_tpu.transport import shared_memory as shm_mod

        cache = self._ctx.peek(shm_mod.ShmClientCache)
        if cache is None:
            return None
        plan = cache.one_sided.get((key, None))
        if plan is None:
            return None
        if plan["nbytes"] > shm_mod.ONE_SIDED_COPY_MAX:
            # The upload runs synchronously on the event loop (device_put +
            # block_until_ready); past this size the stall starves every
            # concurrent op — stand down to the normal fetch path.
            return None
        if tuple(plan["meta"].shape) != tuple(spec.shape):
            return None
        try:
            view, recheck = shm_mod.stamped_read(cache, plan, borrow=True)
        except shm_mod.OneSidedMiss as miss:
            shm_mod.ONE_SIDED_FALLBACKS.inc(reason=miss.reason)
            cache.one_sided.pop((key, None), None)
            return None
        arr = device_transfer.upload_stamped(view, recheck, dtype=spec.dtype)
        if arr is None:
            shm_mod.ONE_SIDED_FALLBACKS.inc(reason="torn")
            return None
        return arr

    async def _raise_with_diagnosis(self, vid: str, exc: Exception) -> None:
        """A volume RPC failed or timed out: ask the controller to
        health-check the fleet and re-raise with the diagnosis attached
        (dead vs wedged vs healthy-but-slow is actionable for operators).
        The failed volume is remembered so retried gets prefer healthy
        replicas; volumes the health check clears are forgiven. The fleet
        fan-out runs at most once per 2 s window: retry loops under a
        correlated outage reuse the cached verdict instead of pinging
        every volume on every failed attempt."""
        import time as _time

        self._dead_volumes.add(vid)
        now = _time.monotonic()
        if now - self._diag_at < 2.0:
            cached = self._diag_statuses.get(vid)
            if cached is None or cached == "ok":
                # _dead_volumes means CONTROLLER-confirmed dead (it gates
                # the put demotion retry and replicated re-routing): a
                # failure the last fan-out didn't confirm stays retryable.
                self._dead_volumes.discard(vid)
            raise ActorDiedError(
                f"storage volume {vid!r} RPC failed: {exc} "
                f"[controller diagnosis (cached): "
                f"{cached or 'not in last health check'}]"
            ) from exc
        self._diag_at = now
        diagnosis = "controller unreachable"
        try:
            statuses = await self._controller.check_volumes.with_timeout(
                15.0
            ).call_one(timeout=5.0)
            diagnosis = statuses.get(vid, "unknown volume")
            self._diag_statuses = statuses
            self._dead_volumes = {
                v for v, status in statuses.items() if status != "ok"
            }
            if statuses.get(vid) == "ok":
                # Our RPC to vid failed but the controller reaches it: OUR
                # ref is stale (repair swapped in a replacement actor).
                # Drop cached refs/locations so the retry reconnects to
                # the fresh fleet instead of re-selecting a dead ref.
                diagnosis += " (ref was stale; volume map refreshed)"
                self._loc_cache.clear()
                self._refresh_epoch += 1
                try:
                    await self._load_volumes()
                except Exception:  # noqa: BLE001 - retry will re-attempt
                    pass
        except Exception:  # noqa: BLE001 - diagnosis is best-effort
            pass
        raise ActorDiedError(
            f"storage volume {vid!r} RPC failed: {exc} "
            f"[controller diagnosis: {diagnosis}]"
        ) from exc

    def _transports_support_inplace(self, located) -> tuple[bool, bool]:
        """(supports_inplace, requires_contiguous) across every transport that
        may participate — in-place views are attached only when all do
        (/root/reference/torchstore/client.py:255-314)."""
        supports = True
        contiguous = False
        for infos in located.values():
            for vid in infos:
                volume = self._volume_refs[vid]
                buffer = create_transport_buffer(volume, self._config)
                supports = supports and buffer.supports_inplace
                contiguous = contiguous or buffer.requires_contiguous_inplace
        return supports, contiguous

    def _build_volume_requests(
        self,
        req: Request,
        infos: dict[str, StorageInfo],
        inplace_ok: tuple[bool, bool],
        prefer_volume: Optional[str] = None,
    ) -> list[tuple[str, Request]]:
        supports_inplace, need_contig = inplace_ok
        any_info = next(iter(infos.values()))
        own_id = None
        try:
            own_id = self._strategy.get_client_id()
        except Exception:
            pass
        # Prefer healthy volumes first (replica failover), then the
        # caller's preferred replica (a relay-distributed local copy),
        # then this client's own volume, then stable order (locality) —
        # or, with replica_spread on, a per-(client, key) salted rotation
        # so split replicas of a hot key share the read load across
        # clients instead of all draining the same first choice.
        # Known-dead and supervisor-quarantined volumes stay as a last
        # resort: if they hold the only copy the fetch still tries them
        # and surfaces the real error.
        salt = self._spread_salt
        ordered = sorted(
            infos,
            key=lambda v: (
                v in self._dead_volumes or v in self._avoid_volumes,
                v != prefer_volume,
                v != own_id,
                zlib.crc32(f"{salt}|{req.key}|{v}".encode())
                if salt is not None
                else 0,
                v,
            ),
        )

        if any_info.object_type == ObjectType.OBJECT:
            sub = Request(key=req.key, is_object=True)
            return [(ordered[0], sub)]

        if any_info.object_type == ObjectType.TENSOR:
            wanted: Optional[TensorSlice] = req.tensor_slice
            sub = Request(
                key=req.key,
                tensor_slice=wanted,
                tensor_meta=any_info.tensor_meta,
            )
            if supports_inplace and req.tensor_val is not None:
                dest_box = Box(
                    (0,) * req.tensor_val.ndim, tuple(req.tensor_val.shape)
                )
                region = wanted.box if wanted is not None else dest_box
                sub.destination_view = get_destination_view(
                    req.tensor_val, dest_box, region, require_contiguous=need_contig
                )
            return [(ordered[0], sub)]

        # TENSOR_SLICE: intersect wanted region with every stored shard.
        stored_slices: list[tuple[str, TensorSlice]] = []
        for vid in ordered:
            for ts in infos[vid].tensor_slices.values():
                stored_slices.append((vid, ts))
        if req.tensor_slice is not None:
            wanted_box = req.tensor_slice.box
        else:
            wanted_box = shd.full_box(stored_slices[0][1].global_shape)
        dest = req.tensor_val
        dest_box = (
            req.tensor_slice.box
            if (dest is not None and req.tensor_slice is not None)
            else (
                Box((0,) * dest.ndim, tuple(dest.shape)) if dest is not None else None
            )
        )
        seen_boxes: set[Box] = set()
        subs: list[tuple[str, Request]] = []
        for vid, stored in stored_slices:
            inter = intersect_boxes(stored.box, wanted_box)
            if inter is None or inter in seen_boxes:
                # Replica dedup: identical regions from replicated shards are
                # fetched once (improves on the reference's noted-inefficient
                # redundant replicate fetch, /root/reference/torchstore/client.py:295-297).
                continue
            seen_boxes.add(inter)
            sub = Request(
                key=req.key,
                tensor_slice=stored.with_box(inter),
                tensor_meta=infos[vid].tensor_meta,
            )
            if supports_inplace and dest is not None and dest_box is not None:
                sub.destination_view = get_destination_view(
                    dest, dest_box, inter, require_contiguous=need_contig
                )
            subs.append((vid, sub))
        if not subs:
            raise KeyError(
                f"no stored shard of {req.key!r} overlaps requested region "
                f"{wanted_box}"
            )
        return subs

    def _assemble_result(
        self, req: Request, parts: list[tuple[Request, Any]]
    ) -> Any:
        if not parts:
            raise KeyError(f"fetch produced no data for key {req.key!r}")
        first_sub, first_res = parts[0]
        if first_sub.is_object:
            if isinstance(first_res, OpaqueBlob):
                return first_res.unwrap()
            return first_res  # pre-envelope durable entries read as-is
        dest = req.tensor_val
        arrays = [
            (np.asarray(res), sub.tensor_slice.offsets if sub.tensor_slice else None)
            for sub, res in parts
        ]
        if arrays[0][1] is None:
            # Whole-tensor fetch.
            out = arrays[0][0]
            if dest is not None:
                if out is not dest and not tensors_overlap_in_memory(dest, [out]):
                    # Native landing path; raises on shape mismatch instead
                    # of broadcasting (a stale-plan fetch must fail loudly).
                    copy_into(dest, out)
                return dest
            return out
        if dest is not None and tensors_overlap_in_memory(
            dest, [a for a, _ in arrays]
        ):
            return dest  # in-place fast path: everything already landed
        with span(
            "reshard",
            key=req.key,
            parts=len(arrays),
            nbytes=sum(a.nbytes for a, _ in arrays),
        ):
            out, offsets = assemble_tensor([(a, off) for a, off in arrays])
        if dest is not None:
            dest_box = (
                req.tensor_slice.box
                if req.tensor_slice is not None
                else Box((0,) * dest.ndim, tuple(dest.shape))
            )
            region = Box(offsets, tuple(out.shape))
            view = get_destination_view(
                dest, dest_box, region, require_contiguous=False
            )
            if view is None:
                raise ValueError(
                    f"fetched region {region} does not fit destination "
                    f"{dest_box} for key {req.key!r}"
                )
            copy_into(view, out)
            return dest
        return out

    # ------------------------------------------------------------------
    # delete / keys / exists
    # ------------------------------------------------------------------

    async def delete(self, key: str) -> None:
        await self.delete_batch([key])

    async def delete_batch(self, keys: list[str]) -> None:
        await self._ensure_setup()
        # Notify-before-delete ordering (invariant 1 delete path).
        by_volume = await self._controller.notify_delete_batch.call_one(keys)
        ordered = sorted(by_volume.items())
        results = await asyncio.gather(
            *(
                self._volume_refs[vid].actor.delete_batch.call_one(vkeys)
                for vid, vkeys in ordered
            ),
            return_exceptions=True,
        )
        for (vid, vkeys), result in zip(ordered, results):
            if isinstance(result, RETRYABLE_ERRORS):
                # The keys are already de-indexed (notify above), so a
                # dead/wedged volume only strands unreachable bytes — a
                # GC-during-failure must not kill the caller over them
                # (process exit reclaims memory-backed volumes; durable
                # backends reconcile on rebuild).
                logger.warning(
                    "delete of %d key(s) on unreachable volume %s skipped "
                    "(%s); bytes reclaimed when the volume exits/rebuilds",
                    len(vkeys),
                    vid,
                    result,
                )
            elif isinstance(result, BaseException):
                raise result
        for key in keys:
            self._ctx.delete_key(key)
            self._loc_cache.pop(key, None)

    async def delete_prefix(self, prefix: str) -> int:
        """Delete every key under a prefix (e.g. an old checkpoint version:
        ``delete_prefix("policy/v41")``). Returns the number of keys
        removed. Idempotent like delete_batch."""
        keys = await self._controller.keys.call_one(prefix)
        if keys:
            await self.delete_batch(keys)
        return len(keys)

    async def keys(self, prefix: Optional[str] = None) -> list[str]:
        return await self._controller.keys.call_one(prefix)

    async def exists(self, key: str) -> bool:
        return await self._controller.contains.call_one(key) != "missing"

    # ------------------------------------------------------------------
    # repair support
    # ------------------------------------------------------------------

    async def refresh_volumes(self) -> None:
        """Re-fetch the volume map (repair swapped in replacement actors);
        drops cached locations and dead-volume marks so retries see the
        fresh fleet."""
        self._loc_cache.clear()
        self._prefer_misses.clear()
        self._dead_volumes.clear()
        self._refresh_epoch += 1
        await self._load_volumes()

    async def replicate_to(self, volume_id: str, requests: list[Request]) -> None:
        """Targeted put: land ``requests`` on ONE specific volume and index
        them there (bypasses strategy placement — the re-replication path
        of ``ts.repair``)."""
        await self._ensure_setup()
        gens = await self._land_requests(self._volume_refs[volume_id], requests)
        await self._controller.notify_put_batch.call_one(
            [r.meta_only() for r in requests],
            volume_id,
            write_gens={volume_id: gens},
        )

    # ------------------------------------------------------------------
    # blocking waits
    # ------------------------------------------------------------------

    def _wait_rpc_timeout(self, timeout: Optional[float]) -> float:
        # The RPC deadline must outlive the server-side wait so the server's
        # precise TimeoutError (naming the missing keys) beats the generic
        # client-side one; 0 disables the client deadline for timeout=None.
        return 0 if timeout is None else timeout + 10.0

    async def wait_for(
        self, keys, timeout: Optional[float] = None
    ) -> None:
        """Block until every key exists and is fully committed. Replaces the
        reference pattern of polling get/get_state_dict in a try/except
        loop; raises TimeoutError on expiry."""
        if isinstance(keys, str):
            keys = [keys]
        await self._ensure_setup()
        await self._controller.wait_for_committed.with_timeout(
            self._wait_rpc_timeout(timeout)
        ).call_one(list(keys), timeout)

    async def wait_for_change(
        self, key: str, last_gen: int = 0, timeout: Optional[float] = None
    ) -> dict:
        """Block until ``key``'s update generation exceeds ``last_gen``;
        returns {"gen", "state"} (state: missing|partial|committed). The
        substrate for version subscriptions (see weight_channel)."""
        await self._ensure_setup()
        return await self._controller.wait_for_change.with_timeout(
            self._wait_rpc_timeout(timeout)
        ).call_one(key, last_gen, timeout)

    # ------------------------------------------------------------------
    # layer-streamed sync (see torchstore_tpu/stream_sync.py)
    # ------------------------------------------------------------------

    async def stream_begin(self, key: str, quant: Optional[dict] = None) -> int:
        """Open the next streamed publish of ``key``; returns the assigned
        stream version. ``quant`` registers static quantization meta on the
        record so readers can decode layer blobs before the seal."""
        await self._ensure_setup()
        return await self._controller.stream_begin.call_one(key, quant)

    async def stream_seal(self, key: str, version: int) -> None:
        await self._ensure_setup()
        await self._controller.stream_seal.call_one(key, version)

    async def stream_mark_unchanged(
        self, key: str, version: int, aliases: dict
    ) -> None:
        """Watermark unchanged keys of a streamed delta publish whose
        fragment landed no bytes (every key aliased to the previous
        version's committed bytes)."""
        await self._ensure_setup()
        await self._controller.stream_mark_unchanged.call_one(
            key, version, aliases
        )

    async def stream_state(self, key: str) -> Optional[dict]:
        """Snapshot of ``key``'s stream record, or None when never
        streamed. Always validate served keys through the blessed helpers
        in :mod:`torchstore_tpu.stream_sync` (tslint ``stream-discipline``)."""
        await self._ensure_setup()
        return await self._controller.stream_state.call_one(key)

    async def wait_for_stream(
        self,
        key: str,
        version: int,
        known: int = 0,
        timeout: Optional[float] = None,
        volume_id: Optional[str] = None,
    ) -> dict:
        """Long-poll streamed-publish progress (see
        Controller.wait_for_stream); the substrate for layer-by-layer
        acquires — woken by the notify that commits each layer, no spin.
        ``volume_id`` gates readiness on this subscriber's RELAY copy: keys
        report ready only once the broadcast tree landed them on that
        volume (ignored when the volume is not a live relay member).

        Both gate-less AND relay-gated polls serve from the stamped stream
        snapshot (same-host segment or this host's metadata mirror) with
        ZERO controller RPCs when attached: the controller publishes the
        relay-gate picture into the snapshot, so a gated poll applies the
        exact wait_for_stream formula against the local replica. The RPC
        long-poll stays the loud fallback (unattached, torn, stale, or
        mirror past its lag bound)."""
        await self._ensure_setup()
        served = await self._controller.stamped_wait_stream(
            key, version, known, timeout, volume_id=volume_id
        )
        if served is not None:
            return served
        return await self._controller.wait_for_stream.with_timeout(
            self._wait_rpc_timeout(timeout)
        ).call_one(key, version, known, timeout, volume_id)

    # ------------------------------------------------------------------
    # broadcast relay distribution (torchstore_tpu/relay.py)
    # ------------------------------------------------------------------

    async def relay_subscribe(
        self, channel: str, volume_id: Optional[str] = None
    ) -> dict:
        """Join ``channel``'s broadcast tree: the controller assigns (or
        adopts, via ``volume_id``) this host's relay volume — published
        versions flow to it volume-to-volume and local acquires read the
        one host-local copy. Returns ``{"volume_id", "epoch", "fanout"}``;
        ``{"volume_id": None, "disabled": True}`` when
        TORCHSTORE_TPU_RELAY_ENABLED is off."""
        await self._ensure_setup()
        if not self._config.relay_enabled:
            return {"volume_id": None, "disabled": True}
        from torchstore_tpu.observability.ledger import local_host

        return await self._controller.relay_subscribe.call_one(
            channel, local_host(), volume_id
        )

    async def relay_unsubscribe(self, channel: str, volume_id: str) -> dict:
        """Leave ``channel``'s broadcast tree (elastic membership: the last
        subscriber on a host removes its member and live runs re-parent
        around it). Idempotent."""
        await self._ensure_setup()
        return await self._controller.relay_unsubscribe.call_one(
            channel, volume_id
        )

    async def stream_ack(
        self, key: str, version: int, subscriber: str
    ) -> None:
        """Record this subscriber's acquire completion on the stream's
        generation timeline (telemetry for ``ts.sync_timeline``; advisory,
        bounded controller-side)."""
        await self._ensure_setup()
        await self._controller.stream_ack.call_one(key, version, subscriber)

    # ------------------------------------------------------------------
    # tiered capacity & multi-version serving (torchstore_tpu/tiering/)
    # ------------------------------------------------------------------

    async def lease_acquire(
        self,
        cohort: str,
        channel: str,
        version: int,
        ttl_s: Optional[float] = None,
    ) -> dict:
        """Pin (channel, version) for ``cohort`` against GC and spill
        (TTL'd; renew to keep it past the TTL). Returns the lease
        description — carry ``lease_id`` to renew/release."""
        await self._ensure_setup()
        return await self._controller.lease_acquire.call_one(
            cohort, channel, version, ttl_s
        )

    async def lease_renew(
        self, lease_id: str, ttl_s: Optional[float] = None
    ) -> dict:
        await self._ensure_setup()
        return await self._controller.lease_renew.call_one(lease_id, ttl_s)

    async def lease_release(self, lease_id: str) -> bool:
        await self._ensure_setup()
        return await self._controller.lease_release.call_one(lease_id)

    async def lease_list(
        self, channel: Optional[str] = None
    ) -> dict[str, dict[int, list[str]]]:
        """{channel: {version: [cohort, ...]}} over live leases."""
        await self._ensure_setup()
        return await self._controller.lease_list.call_one(channel)

    async def version_catalog(
        self, channel: Optional[str] = None
    ) -> dict[str, dict[int, dict]]:
        """Per-channel versions × tier × leases × bytes (see
        Controller.version_catalog)."""
        await self._ensure_setup()
        return await self._controller.version_catalog.call_one(channel)

    async def tier_sweep(self) -> dict[str, dict]:
        """Run one fleet spill pass now; returns per-volume summaries."""
        await self._ensure_setup()
        return await self._controller.tier_sweep.call_one()
