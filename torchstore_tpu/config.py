"""Central configuration object.

The reference configures itself through ~12 scattered env vars (SURVEY §5,
"config/flag system"; an author comment at
/root/reference/torchstore/transport/torchcomms/buffer.py:30-33 wishes for
strategy-level config). This build provides a real config object from day
one: every knob lives on ``StoreConfig``, env vars are read once as defaults,
and user code can override programmatically via ``initialize(config=...)``.
"""

from __future__ import annotations

import os
import random
import time
from dataclasses import dataclass, field, replace
from typing import Optional


@dataclass(frozen=True)
class EnvVar:
    """One operator knob: the single source of truth the env-registry lint
    (torchstore_tpu/analysis/checkers/env_registry.py) and the generated
    docs/API.md table are derived from. ``default=None`` means unset /
    computed dynamically (the doc string says how)."""

    name: str
    type: str  # "bool" | "int" | "float" | "str" | "path"
    default: object
    doc: str


# Every TORCHSTORE_TPU_* variable the tree reads. Adding a read site without
# an entry here fails `python scripts/tslint.py` (env-registry rule); after
# editing, regenerate the docs table with `scripts/tslint.py --regen-env-docs`.
ENV_REGISTRY: tuple[EnvVar, ...] = (
    # --- transports ---------------------------------------------------------
    EnvVar("TORCHSTORE_TPU_SHM_ENABLED", "bool", True,
           "Enable the shared-memory transport rung (same-host transfers "
           "through /dev/shm segments)."),
    EnvVar("TORCHSTORE_TPU_BULK_TCP_ENABLED", "bool", True,
           "Enable the bulk TCP transport rung (cross-host striped "
           "transfers over DCN)."),
    EnvVar("TORCHSTORE_TPU_ICI_ENABLED", "bool", True,
           "Enable the device (ICI) transfer rung for on-device arrays."),
    EnvVar("TORCHSTORE_TPU_ZERO_COPY_GET", "bool", True,
           "Same-host gets without an in-place destination return read-only "
           "snapshot views of SHM segments instead of copies."),
    EnvVar("TORCHSTORE_TPU_SHM_POOL_MAX_BYTES", "int", None,
           "Cap on the volume-side recycled SHM segment pool, bytes. "
           "Default: a quarter of /dev/shm's available space at startup, "
           "clamped to [4 GB, 64 GB]."),
    EnvVar("TORCHSTORE_TPU_USE_NATIVE", "bool", True,
           "Use the native C++ data-path library (libtsnative) when built."),
    # --- steady-state sync pipeline -----------------------------------------
    EnvVar("TORCHSTORE_TPU_LANDING_THREADS", "int", 0,
           "Size of the shared landing-copy thread pool that overlaps "
           "per-request segment copies with the event loop (0 = auto: one "
           "per core, capped at 4 — fast_copy is already internally "
           "threaded for large arrays, so the pool budgets against cores)."),
    EnvVar("TORCHSTORE_TPU_ARENA_MAX_BYTES", "int", 262144,
           "Tensors at or below this many bytes are packed into one shared "
           "arena segment per put batch (one handshake entry + one "
           "volume-side index pass instead of per-key segments); the bulk "
           "transport packs the same set into a single framed payload. "
           "0 disables packing."),
    EnvVar("TORCHSTORE_TPU_TRANSFER_QUANT", "str", "none",
           "Default wire quantization for state-dict publishes "
           "(none|int8|int8_block|int4_block): floating leaves ship as "
           "fused blockwise blobs (packed codes + f32 scale table in the "
           "SAME arena segment) instead of full-precision tensors. An "
           "explicit transfer_quant/transfer_dtype argument overrides "
           "this default per call."),
    EnvVar("TORCHSTORE_TPU_TRANSFER_QUANT_BLOCK", "int", 256,
           "Elements per quantization block for the blockwise modes "
           "(finer blocks: better accuracy, proportionally more scale "
           "bytes — 256 costs ~1.6% overhead at int8). Must be even for "
           "int4_block. Part of the plan signature: changing it is a "
           "restructure."),
    EnvVar("TORCHSTORE_TPU_DELTA_KEYFRAME", "int", 8,
           "Delta wire tier (WeightPublisher delta publishes): a full "
           "keyframe ships every this-many versions per key, bounding the "
           "chain a joining/lagging reader must walk. The publisher "
           "enforces channel keep >= this cadence so the chain is always "
           "retained."),
    EnvVar("TORCHSTORE_TPU_DELTA_SKIP_EPS", "float", 0.0,
           "Delta wire tier: extra absolute slack on the per-block skip "
           "threshold. A block skips (ships nothing) when its residual "
           "max|w_t - baseline| is at or below half the block's keyframe "
           "scale step (the representation's own noise floor) plus this "
           "slack. Residuals are measured against the live weights, so "
           "skipped error never compounds: served weights stay within "
           "~half a keyframe step of the true ones at every version."),
    EnvVar("TORCHSTORE_TPU_PLAN_CACHE", "bool", True,
           "Cache put/get_state_dict transfer plans per (store, size "
           "signature), invalidated by the controller's placement epoch, "
           "so repeated RL-sync iterations skip re-validation and "
           "re-locate."),
    EnvVar("TORCHSTORE_TPU_STREAM_POLL_S", "float", 10.0,
           "Layer-streamed sync: per-round long-poll window, seconds, for "
           "wait_for_stream on the controller (the acquire side re-polls "
           "after each window to refresh its lag gauge and deadline; "
           "wakeups are notify-driven, never a spin)."),
    EnvVar("TORCHSTORE_TPU_STREAM_RETRIES", "int", 2,
           "Layer-streamed sync: how many times a streamed acquire "
           "restarts after observing a superseded or mixed-generation "
           "stream (a newer publish overwrote keys mid-acquire) before "
           "failing loudly."),
    EnvVar("TORCHSTORE_TPU_BULK_EMULATE_GBPS", "float", 0,
           "Bench/test DCN emulation: when > 0, every bulk payload frame "
           "send adds the wall time a link of this bandwidth (GB/s) would "
           "need on top of the real transfer, so single-host benches "
           "measure the cross-host regime the bulk transport targets "
           "(bench.py delta_sync uses it). 0 (production default) "
           "disables pacing entirely."),
    EnvVar("TORCHSTORE_TPU_PUSH_SESSIONS", "bool", True,
           "Push-on-publish bulk sessions: a client that caches a "
           "doorbell plan also registers a persistent push subscription; "
           "the volume then streams freshly committed layers into the "
           "client's staging arena AT WATERMARK TIME, so the next warm "
           "get's first byte is a local memcpy (validated against the "
           "mirrored write generations before serving). Unsubscribed or "
           "lagging sessions fall back loudly to the doorbell ring."),
    EnvVar("TORCHSTORE_TPU_PUSH_STAGING_MAX_BYTES", "int", 1073741824,
           "Per-client cap on push-staged arena bytes; staging past the "
           "cap evicts oldest-staged plans first, and a single frame "
           "larger than the cap is never staged (its reads stay on the "
           "doorbell ring). Floor: 1 MiB."),
    EnvVar("TORCHSTORE_TPU_BULK_STRIPE_THRESHOLD", "int", 67108864,
           "Bulk transport payloads above this many bytes are striped "
           "across the pre-opened stripe connection set (puts, get "
           "replies, and IDX_PACKED doorbell replies)."),
    EnvVar("TORCHSTORE_TPU_RELAY_ENABLED", "bool", True,
           "Broadcast weight distribution: allow relay-tree fan-out of "
           "weight_channel versions (controller-driven volume-to-volume "
           "forwarding; subscribers opt in per channel via "
           "WeightSubscriber(relay=True) / client.relay_subscribe). "
           "0 disables relay subscription fleet-wide: acquires fall back "
           "to point-to-point reads from the origin volumes."),
    EnvVar("TORCHSTORE_TPU_RELAY_FANOUT", "int", 2,
           "Interior out-degree of the relay tree each published version "
           "flows down. The root (origin volume) always forwards to "
           "exactly ONE child so trainer-host egress stays O(1) however "
           "many fleets subscribe; 1 makes the whole tree a chain."),
    EnvVar("TORCHSTORE_TPU_RELAY_REPARENT_TIMEOUT_S", "float", 5.0,
           "How long a relay edge keeps retrying a failing parent before "
           "the controller re-parents the orphaned subtree onto the "
           "nearest healthy ancestor (the health supervisor's quarantine "
           "re-parents immediately, independent of this window)."),
    EnvVar("TORCHSTORE_TPU_ONE_SIDED", "bool", True,
           "One-sided data plane for warm gets: same-host readers with a "
           "cached plan read stamped (seqlock-validated) bytes directly "
           "from pre-attached SHM segments with zero RPCs; cross-host "
           "readers ring a bulk doorbell frame against a volume-cached "
           "get plan instead of issuing the get RPC. Torn/stale reads "
           "fall back loudly to the RPC path."),
    # --- scale-out metadata plane (torchstore_tpu/metadata/) ----------------
    EnvVar("TORCHSTORE_TPU_CONTROLLER_SHARDS", "int", 1,
           "Partition the controller's key->volume index across this many "
           "ControllerShard actors by stable key hash (1 = the classic "
           "single controller). Fleet-scoped state (placement epoch, "
           "health, streams, relay, leases) stays on the coordinator; "
           "clients fan batched metadata ops out per shard. An explicit "
           "ts.initialize(controller_shards=) overrides this default."),
    EnvVar("TORCHSTORE_TPU_META_STAMPED", "bool", True,
           "One-sided metadata reads: every index host publishes its "
           "committed index (and the coordinator its stream watermarks + "
           "placement epoch) into seqlock-stamped shm segments, so "
           "same-host clients resolve locations, validate cached plans, "
           "and poll streamed publishes with ZERO controller RPCs. "
           "Torn/stale reads fall back loudly to the RPC path."),
    EnvVar("TORCHSTORE_TPU_META_PUBLISH_MS", "float", 10,
           "Debounce interval for stamped metadata publishes, "
           "milliseconds: index/stream changes coalesce to at most one "
           "segment rewrite per interval (staleness is bounded by it; "
           "readers under-see progress, never the reverse)."),
    EnvVar("TORCHSTORE_TPU_META_SEGMENT_BYTES", "int", 8388608,
           "Size of each stamped metadata segment. A pickled view that "
           "outgrows it tombstones the segment (readers fall back to "
           "RPCs, loudly) rather than growing under attached readers."),
    EnvVar("TORCHSTORE_TPU_META_MIRROR", "bool", True,
           "Cross-host metadata mirroring: the coordinator runs a "
           "metadata feed that pushes the stamped segment images over "
           "persistent subscriptions (fanned through a relay tree, so "
           "index-host egress stays O(1) in subscriber count); each "
           "remote host's MetadataMirror republishes them into LOCAL shm "
           "replicas, extending the zero-RPC warm metadata paths across "
           "hosts. Off: remote clients use the RPC metadata plane only."),
    EnvVar("TORCHSTORE_TPU_META_MIRROR_INTERVAL_MS", "float", 20,
           "Feed pump poll interval, milliseconds: how often the root "
           "feed re-reads the local stamped segments and pushes changed "
           "images to subscribers (bounds mirror replica staleness "
           "alongside the publish debounce)."),
    EnvVar("TORCHSTORE_TPU_META_MIRROR_HEARTBEAT_S", "float", 0.2,
           "Feed heartbeat period, seconds: subscribers receive at least "
           "one frame per period even when no image changed, so a quiet "
           "feed is distinguishable from a dead parent."),
    EnvVar("TORCHSTORE_TPU_META_MIRROR_LAG_S", "float", 1.5,
           "Mirror staleness bound, seconds: a replica whose feed has "
           "been silent longer than this reports unfresh — every stamped "
           "read on that host falls back LOUDLY to the RPC plane "
           "(reason=mirror_lag) and the subscription re-parents around "
           "the dead feed (the down-set re-subscribe)."),
    # --- tiered capacity & multi-version serving (torchstore_tpu/tiering) ---
    EnvVar("TORCHSTORE_TPU_TIER_ENABLED", "bool", False,
           "Enable the disk spill tier: per-volume spill writers demote "
           "cold version groups from the memory/tmpfs tier to disk under "
           "the watermark policy, and gets on spilled keys fault back in "
           "through the normal transport ladder. Off: the store is "
           "memory-capacity-bound exactly as before (warm path pays one "
           "attribute check)."),
    EnvVar("TORCHSTORE_TPU_TIER_DIR", "path", None,
           "Root directory for the disk spill tier (one subdirectory per "
           "volume id). Default: <tmpdir>/torchstore_tpu_tier. Spill "
           "writes are crash-safe (write-temp, fsync, rename)."),
    EnvVar("TORCHSTORE_TPU_TIER_BUDGET_BYTES", "int", None,
           "Memory-tier pool budget, bytes, the spill watermarks apply "
           "to. Default: the SHM pool cap "
           "(TORCHSTORE_TPU_SHM_POOL_MAX_BYTES or its derived default)."),
    EnvVar("TORCHSTORE_TPU_TIER_HIGH_PCT", "float", 0.85,
           "Spill HIGH watermark: a volume whose resident bytes exceed "
           "this fraction of the pool budget starts demoting cold "
           "version groups (LRU by access; leased versions exempt)."),
    EnvVar("TORCHSTORE_TPU_TIER_LOW_PCT", "float", 0.65,
           "Spill LOW watermark: demotion stops once resident bytes drop "
           "under this fraction of the pool budget."),
    EnvVar("TORCHSTORE_TPU_TIER_SWEEP_INTERVAL_S", "float", 2.0,
           "Controller tier-sweep period, seconds: every interval the "
           "controller runs each volume's spill pass with the current "
           "lease pins and folds tier transitions into the index. <= 0 "
           "disables the background sweeper (ts.tier_sweep() still runs "
           "one on demand)."),
    EnvVar("TORCHSTORE_TPU_LEASE_TTL_S", "float", 30.0,
           "Default TTL, seconds, for cohort retention leases "
           "(lease_acquire without an explicit ttl_s; renew to keep a "
           "version pinned past it). A crashed cohort's pin expires "
           "instead of retaining capacity forever."),
    # --- control plane (torchstore_tpu/control/) ----------------------------
    EnvVar("TORCHSTORE_TPU_CONTROL_INTERVAL_S", "float", 0,
           "Placement policy engine reconcile period, seconds: every "
           "interval the controller snapshots fleet telemetry, runs the "
           "pure solver, and applies/audits the resulting actions "
           "(migrations, hot-key splits, relay re-ordering, frequency-"
           "aware demotions). <= 0 (the default) disables the periodic "
           "loop; ts.rebalance() / ts.control_plan() still serve on "
           "demand."),
    EnvVar("TORCHSTORE_TPU_CONTROL_OVERLOAD_RATIO", "float", 2.0,
           "Solver: a volume whose rolling-window traffic exceeds this "
           "multiple of the fleet mean counts as overloaded and sheds "
           "keys (migrations stop once it projects under the settle "
           "ratio)."),
    EnvVar("TORCHSTORE_TPU_CONTROL_MIN_WINDOW_BYTES", "int", 65536,
           "Solver: volumes whose rolling window moved fewer than this "
           "many bytes are ignored entirely — an idle fleet must plan "
           "zero actions."),
    EnvVar("TORCHSTORE_TPU_CONTROL_HOT_KEY_MIN_BYTES", "int", 1048576,
           "Solver: a key must move at least this many bytes in the "
           "window before it is hot enough to split across an additional "
           "replica."),
    EnvVar("TORCHSTORE_TPU_CONTROL_MIN_EDGE_BYTES", "int", 1048576,
           "Solver: relay trees re-order members by measured edge "
           "proximity only when the dominant consumer edge carried at "
           "least this many bytes."),
    EnvVar("TORCHSTORE_TPU_CONTROL_COOLDOWN_S", "float", 30.0,
           "Solver hysteresis: a subject acted on (or attempted) within "
           "this window is not acted on again, and a reversal of a prior "
           "action is damped for twice the window — the engine must "
           "converge, not oscillate."),
    EnvVar("TORCHSTORE_TPU_CONTROL_MAX_ACTIONS", "int", 8,
           "Solver: cap on actions per reconcile round (highest-impact "
           "first); convergence happens over rounds, not in one "
           "stop-the-world batch."),
    EnvVar("TORCHSTORE_TPU_CONTROL_ADMISSION", "bool", False,
           "Per-tenant admission control: client put/get batches reserve "
           "a token per logical op from a tenant-labeled bucket and "
           "sleep out any deficit BEFORE touching a volume. The refill "
           "rate scales down while overload signals (per-shard metadata "
           "RPC inflight, per-volume landing_inflight) exceed "
           "TORCHSTORE_TPU_CONTROL_OVERLOAD_INFLIGHT."),
    EnvVar("TORCHSTORE_TPU_CONTROL_ADMIT_RATE", "float", 512.0,
           "Admission control: steady-state refill rate, logical ops per "
           "second per client."),
    EnvVar("TORCHSTORE_TPU_CONTROL_ADMIT_BURST", "float", None,
           "Admission control: bucket depth, ops (how far a tenant may "
           "burst above the steady rate before queuing at its own "
           "bucket). Default: 2x the admit rate."),
    EnvVar("TORCHSTORE_TPU_CONTROL_OVERLOAD_INFLIGHT", "int", 16,
           "Admission control: overload knee. While the deepest observed "
           "inflight signal exceeds this, the refill factor scales down "
           "proportionally (knee/depth, floored at 0.1); throttle "
           "engage/release transitions are recorded as flight-recorder "
           "decision events."),
    EnvVar("TORCHSTORE_TPU_CONTROL_REPLICA_SPREAD", "bool", False,
           "Hot-key read spreading: clients rotate which replica they "
           "read first by a stable per-client salt instead of every "
           "client draining the same deterministic first choice — the "
           "read-side half of the policy engine's hot-key splits."),
    EnvVar("TORCHSTORE_TPU_TENANT", "str", "",
           "Tenant/cohort label this process's client carries: admission "
           "buckets, loadgen op records, and scoreboard rows are keyed "
           "by it (empty reads as 'default')."),
    # --- elastic fleet autoscaling (torchstore_tpu/autoscale/) --------------
    EnvVar("TORCHSTORE_TPU_AUTOSCALE_INTERVAL_S", "float", 0,
           "Elastic-fleet autoscaler reconcile period, seconds: every "
           "interval the controller snapshots fleet telemetry, runs the "
           "pure autoscale solver, and applies/audits scale decisions "
           "(drain, retire, blob demotion; scale-out spawns defer to "
           "ts.autoscale() client-side). <= 0 (the default) disables the "
           "periodic loop; ts.autoscale() / ts.autoscale_plan() still "
           "serve on demand."),
    EnvVar("TORCHSTORE_TPU_AUTOSCALE_MIN_VOLUMES", "int", 1,
           "Autoscale solver: never drain the fleet below this many live "
           "volumes (scale-in floor)."),
    EnvVar("TORCHSTORE_TPU_AUTOSCALE_MAX_VOLUMES", "int", 8,
           "Autoscale solver: never scale the fleet above this many live "
           "volumes (scale-out ceiling)."),
    EnvVar("TORCHSTORE_TPU_AUTOSCALE_OUT_INFLIGHT", "int", 8,
           "Autoscale solver: any volume holding at least this many open "
           "landing brackets in the snapshot counts as saturated and "
           "votes for scale-out (a sustained landing-inflight trend from "
           "the history detectors votes the same way)."),
    EnvVar("TORCHSTORE_TPU_AUTOSCALE_OUT_WINDOW_BYTES", "int", 33554432,
           "Autoscale solver: mean rolling-window bytes per live volume "
           "at or above this threshold votes for scale-out (sustained "
           "fleet-wide pressure, not one hot volume — that is the "
           "placement engine's job)."),
    EnvVar("TORCHSTORE_TPU_AUTOSCALE_IDLE_WINDOW_BYTES", "int", 65536,
           "Autoscale solver: the fleet counts as idle only when EVERY "
           "live volume's rolling window moved fewer than this many "
           "bytes (and no landing brackets are open, and no sustained "
           "overload trend is active)."),
    EnvVar("TORCHSTORE_TPU_AUTOSCALE_IDLE_ROUNDS", "int", 3,
           "Autoscale hysteresis: scale-in (drain entry) requires this "
           "many CONSECUTIVE idle reconcile rounds first — one quiet "
           "snapshot between bursts must not start retiring capacity."),
    EnvVar("TORCHSTORE_TPU_AUTOSCALE_DRAIN_KEYS_PER_ROUND", "int", 64,
           "Autoscale: resident keys migrated off a draining volume per "
           "reconcile round (graceful drain is incremental; the volume "
           "retires only when its index entry count reaches zero)."),
    EnvVar("TORCHSTORE_TPU_AUTOSCALE_BLOB_KEYS_PER_ROUND", "int", 32,
           "Autoscale: spilled (disk-tier) keys demoted to the blob cold "
           "tier per volume per reconcile round when the blob tier is "
           "enabled."),
    EnvVar("TORCHSTORE_TPU_AUTOSCALE_COOLDOWN_S", "float", 60.0,
           "Autoscale hysteresis: a subject acted on (or attempted) "
           "within this window is not acted on again, and a reversal "
           "(scale-out after scale-in, or vice versa) is damped for "
           "twice the window — the fleet must converge, not flap."),
    EnvVar("TORCHSTORE_TPU_AUTOSCALE_MAX_ACTIONS", "int", 4,
           "Autoscale solver: cap on actions per reconcile round "
           "(retire/drain continuations first); convergence happens over "
           "rounds, not in one stop-the-world batch."),
    # --- blob cold tier (torchstore_tpu/tiering/blob.py) --------------------
    EnvVar("TORCHSTORE_TPU_BLOB_ENABLED", "bool", False,
           "Enable the object-storage-style blob cold tier: volumes "
           "archive cold spilled entries below the disk tier, fault them "
           "back in through the get-RPC bracket, and the fleet gains "
           "scale-to-zero (ts.blob_checkpoint() + ts.blob_restore())."),
    EnvVar("TORCHSTORE_TPU_BLOB_DIR", "path", None,
           "Blob store root directory (shared by every volume — it "
           "emulates one bucket). Default: <tmpdir>/torchstore_tpu_blob. "
           "Objects persist across fleet restarts; point tests at a "
           "per-run directory for isolation."),
    EnvVar("TORCHSTORE_TPU_BLOB_LATENCY_MS", "float", 0,
           "Injected per-operation latency, milliseconds, on every blob "
           "store op (put/get/list/delete) — emulates object-storage "
           "round-trip time so benches and chaos runs exercise realistic "
           "cold-tier economics."),
    EnvVar("TORCHSTORE_TPU_BLOB_RATE_MBPS", "float", 0,
           "Blob store throughput cap, MiB/s: data-bearing ops stall to "
           "stay under it (an emulated egress/ingress rate limit). <= 0 "
           "(the default) disables the cap."),
    # --- cold-start provisioning (prewarm) ----------------------------------
    EnvVar("TORCHSTORE_TPU_PREWARM_AUTO", "bool", True,
           "put_state_dict derives a manifest and provisions pools/dials "
           "before the first data-plane puts of a large working set."),
    EnvVar("TORCHSTORE_TPU_PREWARM_AUTO_MIN_BYTES", "int", 33554432,
           "Working sets below this many bytes skip the automatic prewarm "
           "hint."),
    EnvVar("TORCHSTORE_TPU_PREWARM_HUGEPAGES", "bool", True,
           "madvise(MADV_HUGEPAGE) on provisioned segments while untouched "
           "(fail-open to plain pages)."),
    EnvVar("TORCHSTORE_TPU_PREWARM_THREADS", "int", 0,
           "Threads for the native prefault of provisioned segments "
           "(0 = auto, one per 16 MiB)."),
    # --- security -----------------------------------------------------------
    EnvVar("TORCHSTORE_TPU_AUTH_SECRET", "str", "",
           "Shared secret for HMAC challenge-response connection auth on "
           "every listener; empty disables auth (loopback-only deployments)."),
    # --- timeouts (seconds) -------------------------------------------------
    EnvVar("TORCHSTORE_TPU_RPC_TIMEOUT", "float", 120,
           "Default control-plane RPC deadline in seconds (<= 0 disables); "
           "data-plane RPCs scale it with payload size."),
    EnvVar("TORCHSTORE_TPU_HANDSHAKE_TIMEOUT", "float", 60,
           "Transport handshake deadline, seconds."),
    EnvVar("TORCHSTORE_TPU_DIRECT_SETTLE_TIMEOUT", "float", 30,
           "How long a direct weight-sync pull waits for the source seqlock "
           "generation to settle (even), seconds."),
    # --- logging / observability --------------------------------------------
    EnvVar("TORCHSTORE_TPU_LOG_LEVEL", "str", "WARNING",
           "Root level for torchstore loggers."),
    EnvVar("TORCHSTORE_TPU_TRACE", "path", None,
           "Write Chrome-trace span events to this file (pid-suffixed per "
           "process); merge with ts.collect_trace() / scripts/merge_traces.py."),
    EnvVar("TORCHSTORE_TPU_TRACE_RUN", "str", None,
           "Internal: per-run id the spawner stamps so reused trace OUTDIRs "
           "can arbitrate file ownership. Set automatically; do not set by "
           "hand."),
    EnvVar("TORCHSTORE_TPU_METRICS_DUMP", "path", None,
           "Every process periodically rewrites this file with its metrics "
           "registry (.json, or .prom for Prometheus text)."),
    EnvVar("TORCHSTORE_TPU_METRICS_INTERVAL_S", "float", 60,
           "Metrics dump period, seconds."),
    EnvVar("TORCHSTORE_TPU_METRICS_PORT", "int", None,
           "Serve live /metrics + /metrics.json + /healthz on this port "
           "from every process (ephemeral-port fallback on sibling "
           "conflicts, published via the ts_metrics_http_port gauge)."),
    EnvVar("TORCHSTORE_TPU_METRICS_HOST", "str", "127.0.0.1",
           "Bind address for the metrics HTTP exporter."),
    EnvVar("TORCHSTORE_TPU_SLOW_OP_MS", "float", None,
           "Client ops / volume puts+gets slower than this many "
           "milliseconds log a warning with the trace id and count "
           "ts_slow_ops_total."),
    EnvVar("TORCHSTORE_TPU_LEDGER", "bool", True,
           "Traffic ledger: per-(peer host, volume, transport, direction) "
           "byte/op accounting with per-key rolling windows, recorded at "
           "every transport choke point (incl. the zero-RPC one-sided "
           "paths) and merged fleet-wide by ts.traffic_matrix()."),
    EnvVar("TORCHSTORE_TPU_LEDGER_WINDOW_S", "float", 300,
           "Rolling per-key traffic-window width, seconds (the ledger "
           "keeps the current + previous window; a key that stops moving "
           "decays out within two)."),
    EnvVar("TORCHSTORE_TPU_FLIGHT_RECORDER", "bool", True,
           "Always-on flight recorder: a bounded per-process ring of "
           "recent ops/transfers/faults/errors, auto-dumped as a JSON "
           "post-mortem on quarantine, repair, wedged streams, injected "
           "deaths, and unclean exits; merged on demand via "
           "ts.flight_record()."),
    EnvVar("TORCHSTORE_TPU_FLIGHT_EVENTS", "int", 4096,
           "Flight-recorder ring capacity (events per process)."),
    EnvVar("TORCHSTORE_TPU_FLIGHT_DIR", "path", None,
           "Directory for flight-recorder post-mortem dumps (default: "
           "<tmpdir>/torchstore_tpu_flight; one file per trigger per "
           "pid, atomically replaced)."),
    EnvVar("TORCHSTORE_TPU_FLIGHT_MIN_INTERVAL_S", "float", 30,
           "Per-trigger-kind flight-dump rate limit: under a sustained "
           "fault storm at most one post-mortem per kind per this many "
           "seconds is written (the rest are counted in "
           "ts_flight_dumps_dropped_total). 0 disables the limit."),
    EnvVar("TORCHSTORE_TPU_HISTORY", "bool", True,
           "Time-series history: a background sampler sweeps every "
           "registry instrument into bounded multi-resolution rings "
           "(1s x 300 / 10s x 360 / 60s x 360, min/max/last per bucket; "
           "counters also derive :rate series), queried locally via "
           "observability.history() and fleet-wide via ts.history()."),
    EnvVar("TORCHSTORE_TPU_HISTORY_INTERVAL_S", "float", 1,
           "History sampling period, seconds. The measured sweep cost "
           "may stretch the effective period (see "
           "TORCHSTORE_TPU_HISTORY_BUDGET_PCT)."),
    EnvVar("TORCHSTORE_TPU_HISTORY_MAX_SERIES", "int", 256,
           "Hard cap on distinct series a process's history store will "
           "track; overflow series are counted in "
           "ts_history_series_dropped_total, never allocated."),
    EnvVar("TORCHSTORE_TPU_HISTORY_BUDGET_PCT", "float", 1,
           "CPU budget for the history sampler as a percent of one core: "
           "the effective interval is raised to sweep_cost / budget so "
           "sampling can never exceed this fraction, however many series "
           "the registry grows."),
    EnvVar("TORCHSTORE_TPU_HISTORY_DUMP_SERIES", "str", None,
           "Comma-separated series globs embedded in flight-recorder "
           "post-mortems (default: a curated vitals set — op quantiles, "
           "landing inflight, client op counters, doorbell residency, "
           "metadata queue depth, SLO breach counts)."),
    EnvVar("TORCHSTORE_TPU_TREND_SUSTAIN_SAMPLES", "int", 5,
           "Consecutive history samples at/over threshold before a "
           "sustained-kind trend detector fires (burst vs regime-change "
           "discrimination for slo_report()['trends'] and the control "
           "snapshot's sustained_overload signal)."),
    EnvVar("TORCHSTORE_TPU_TREND_INFLIGHT", "int", None,
           "Landing-inflight threshold for the sustained/ramp trend "
           "detectors (default: TORCHSTORE_TPU_CONTROL_OVERLOAD_INFLIGHT "
           "— 'the solver's own overload line, held')."),
    # --- SLOs (TORCHSTORE_TPU_SLO_* is a registered dynamic family:
    # operators may add their own; these are the shipped, wired-up bars.
    # Unset = disabled; breaches log + count ts_slo_violations_total) ----
    EnvVar("TORCHSTORE_TPU_SLO_PUT_P99_MS", "float", None,
           "SLO: rolling-window put p99 above this many milliseconds is "
           "a violation."),
    EnvVar("TORCHSTORE_TPU_SLO_GET_P99_MS", "float", None,
           "SLO: rolling-window get p99 above this many milliseconds is "
           "a violation."),
    EnvVar("TORCHSTORE_TPU_SLO_VERSION_LAG", "float", None,
           "SLO: a subscriber acquiring with more than this many "
           "published-but-never-pulled versions behind is a violation."),
    EnvVar("TORCHSTORE_TPU_SLO_FIRST_LAYER_MS", "float", None,
           "SLO: stream begin to a subscriber's first served layer above "
           "this many milliseconds is a violation."),
    EnvVar("TORCHSTORE_TPU_SLO_OVERLAP_MIN", "float", None,
           "SLO: a streamed acquire overlapping LESS than this fraction "
           "of the publish window is a violation."),
    # --- runtime / fleet ----------------------------------------------------
    EnvVar("TORCHSTORE_TPU_BIND_HOST", "str", "127.0.0.1",
           "Bind address for actor, bulk, and device-transfer listeners "
           "(set 0.0.0.0 for multi-host DCN)."),
    EnvVar("TORCHSTORE_TPU_ADVERTISE_HOST", "str", None,
           "Reachable address advertised in actor refs and bulk endpoints "
           "when binding 0.0.0.0/:: (default: the real hostname)."),
    EnvVar("TORCHSTORE_TPU_MP_CONTEXT", "str", "forkserver",
           "Multiprocessing start method for actor children (forkserver "
           "amortizes interpreter startup; spawn remains available)."),
    EnvVar("TORCHSTORE_TPU_HOSTNAME", "str", None,
           "Override the hostname strategies use for same-host transport "
           "selection (tests / containers with unstable hostnames)."),
    EnvVar("TORCHSTORE_TPU_VOLUME_ID", "str", None,
           "Force a spawned storage volume's id (volume replacement and "
           "repair flows)."),
    EnvVar("TORCHSTORE_TPU_STORAGE_DIR", "path", None,
           "Durable backend directory for storage volumes (unset = "
           "in-memory only)."),
    EnvVar("TORCHSTORE_TPU_RECLAIM_DELAYS", "str", None,
           "Comma-separated backoff delays, seconds, for the controller's "
           "stale-replica reclaim drainer (default 1,5,15,60; malformed "
           "values fall back). Parsed into an explicit-delays RetryPolicy."),
    # --- self-healing: health supervisor + retry/failover -------------------
    EnvVar("TORCHSTORE_TPU_HEALTH_INTERVAL_S", "float", 2.0,
           "Controller heartbeat period, seconds: every interval the health "
           "supervisor pings every volume. <= 0 disables the supervisor "
           "(quarantine and auto-repair never trigger)."),
    EnvVar("TORCHSTORE_TPU_HEALTH_MISS_THRESHOLD", "int", 3,
           "Consecutive missed heartbeats that quarantine a volume; the "
           "same count of consecutive successful pings reinstates a "
           "quarantined volume through probation."),
    EnvVar("TORCHSTORE_TPU_AUTO_REPAIR", "bool", True,
           "Quarantining a volume automatically re-replicates every key it "
           "held that still has a healthy copy onto healthy volumes "
           "(volume-to-volume, no client involvement). Off: quarantine "
           "only, redundancy stays degraded until ts.repair()."),
    EnvVar("TORCHSTORE_TPU_FAULTPOINTS", "str", None,
           "Arm deterministic fault injection at named sites, e.g. "
           "'volume.put=raise:count=2;actor.ping=wedge'. Parsed at process "
           "start (and after fork) in every store process; see "
           "torchstore_tpu/faults.py for the site registry and actions. "
           "Test/chaos tooling only — leave unset in production."),
    EnvVar("TORCHSTORE_TPU_RETRY_BASE_S", "float", 0.05,
           "Unified RetryPolicy: first backoff delay, seconds."),
    EnvVar("TORCHSTORE_TPU_RETRY_MAX_S", "float", 2.0,
           "Unified RetryPolicy: backoff ceiling, seconds."),
    EnvVar("TORCHSTORE_TPU_RETRY_MULTIPLIER", "float", 2.0,
           "Unified RetryPolicy: exponential backoff multiplier."),
    EnvVar("TORCHSTORE_TPU_RETRY_JITTER", "float", 0.1,
           "Unified RetryPolicy: fraction of each delay randomized "
           "(de-synchronizes fleet-wide retry storms)."),
    EnvVar("TORCHSTORE_TPU_RETRY_DEADLINE_S", "float", 30.0,
           "Unified RetryPolicy: total retry budget per logical operation, "
           "seconds; the first failure after the deadline surfaces."),
    # --- bench --------------------------------------------------------------
    EnvVar("TORCHSTORE_TPU_BENCH_COLD_MB", "int", None,
           "bench.py cold-path working-set size in MB (default scales with "
           "the bench tensor set)."),
)

# Dynamic families: names extending these prefixes are per-instance handles
# (one per store) or operator-extensible knob families (custom SLOs), not
# individually registrable entries.
ENV_PREFIXES: tuple[str, ...] = (
    "TORCHSTORE_TPU_STORE_",
    "TORCHSTORE_TPU_SLO_",
)


def env_registry_entry(name: str) -> EnvVar | None:
    for entry in ENV_REGISTRY:
        if entry.name == name:
            return entry
    return None


def _env_bool(name: str, default: bool) -> bool:
    val = os.environ.get(name)
    if val is None:
        return default
    return val.strip().lower() not in ("0", "false", "no", "off", "")


def _env_int(name: str, default: int) -> int:
    val = os.environ.get(name)
    return int(val) if val is not None else default


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name, default)


def _env_float(name: str, default: float) -> float:
    val = os.environ.get(name)
    return float(val) if val is not None else default


@dataclass(frozen=True)
class RetryPolicy:
    """The ONE retry/backoff vocabulary for the whole store.

    Every layer that retries — client get failover, non-replicated put
    transport demotion, weight-channel publish/acquire survival, the
    controller's stale-replica reclaim drainer — derives its schedule from
    an instance of this type instead of inventing env-list parsing or
    hardcoded deadlines (enforced by the ``retry-discipline`` tslint rule).

    Delay for attempt ``i`` (0-based) is ``min(max_s, base_s *
    multiplier**i)`` with ``jitter`` fraction of it randomized, unless
    ``delays`` pins an explicit schedule (then the schedule IS the attempt
    budget). ``deadline_s`` bounds the TOTAL time spent retrying one
    logical operation: the first failure after the deadline surfaces.
    Frozen + picklable: it rides StoreConfig through actor RPCs."""

    base_s: float = 0.05
    max_s: float = 2.0
    multiplier: float = 2.0
    jitter: float = 0.1
    deadline_s: float = 30.0
    # Explicit delay schedule (seconds). When set, backoff() indexes into it
    # and attempts are capped at len(delays); the reclaim drainer's
    # TORCHSTORE_TPU_RECLAIM_DELAYS compatibility rides this.
    delays: Optional[tuple[float, ...]] = None

    @classmethod
    def from_env(cls) -> "RetryPolicy":
        return cls(
            base_s=_env_float("TORCHSTORE_TPU_RETRY_BASE_S", 0.05),
            max_s=_env_float("TORCHSTORE_TPU_RETRY_MAX_S", 2.0),
            multiplier=_env_float("TORCHSTORE_TPU_RETRY_MULTIPLIER", 2.0),
            jitter=_env_float("TORCHSTORE_TPU_RETRY_JITTER", 0.1),
            deadline_s=_env_float("TORCHSTORE_TPU_RETRY_DEADLINE_S", 30.0),
        )

    @classmethod
    def from_delays(
        cls, delays, deadline_s: Optional[float] = None
    ) -> "RetryPolicy":
        delays = tuple(float(d) for d in delays)
        if not delays:
            raise ValueError("explicit delay schedule must not be empty")
        return cls(
            deadline_s=sum(delays) * 2 if deadline_s is None else deadline_s,
            delays=delays,
        )

    @property
    def max_attempts(self) -> Optional[int]:
        """Bound on RETRIES (not first attempts): None = deadline-limited."""
        return len(self.delays) if self.delays is not None else None

    def backoff(self, attempt: int) -> float:
        """Delay before retry ``attempt`` (0-based), jittered."""
        if self.delays is not None:
            delay = self.delays[min(attempt, len(self.delays) - 1)]
        else:
            delay = min(self.max_s, self.base_s * self.multiplier**attempt)
        if self.jitter:
            delay *= 1.0 + self.jitter * (2.0 * random.random() - 1.0)
        return max(0.0, delay)

    def start(self) -> float:
        """Monotonic deadline for one logical operation's retry budget."""
        return time.monotonic() + self.deadline_s

    def should_retry(self, attempt: int, deadline: float) -> bool:
        """Whether retry ``attempt`` (0-based) may still run: within both
        the attempt cap (explicit schedules) and the time budget."""
        if self.delays is not None and attempt >= len(self.delays):
            return False
        return time.monotonic() < deadline


def _default_shm_pool_cap() -> int:
    """Quarter of /dev/shm's AVAILABLE space at startup, clamped to
    [4 GB, 64 GB]. Available (not total) leaves room for live + retired
    segments and other tenants; the 64 GB ceiling bounds how many written
    tmpfs pages recycled segments may pin on huge hosts. Model-scale syncs
    (16 GB for Llama-3-8B bf16) need the pool to hold roughly one working
    set or puts fall back to cold tmpfs allocation."""
    try:
        stat = os.statvfs("/dev/shm")
        avail = stat.f_frsize * stat.f_bavail
    except OSError:
        return 4 << 30
    return max(4 << 30, min(avail // 4, 64 << 30))


@dataclass
class StoreConfig:
    """All tunables for one store instance. Field defaults come from env vars
    (prefix ``TORCHSTORE_TPU_``) so operator overrides keep working, but the
    object is the source of truth once a store is initialized."""

    # --- transports ---------------------------------------------------------
    shm_enabled: bool = field(
        default_factory=lambda: _env_bool("TORCHSTORE_TPU_SHM_ENABLED", True)
    )
    bulk_tcp_enabled: bool = field(
        default_factory=lambda: _env_bool("TORCHSTORE_TPU_BULK_TCP_ENABLED", True)
    )
    ici_enabled: bool = field(
        default_factory=lambda: _env_bool("TORCHSTORE_TPU_ICI_ENABLED", True)
    )
    # Zero-copy SHM gets: same-host fetches without an in-place destination
    # return read-only snapshot views of the volume's segments instead of
    # copies. Safe by default: the volume lease-counts served views and
    # retires (never overwrites) a viewed segment on the next put, so a held
    # view is always an immutable snapshot.
    zero_copy_get: bool = field(
        default_factory=lambda: _env_bool("TORCHSTORE_TPU_ZERO_COPY_GET", True)
    )
    # Cap on the volume-side pool of recycled SHM segments (bytes). Released
    # segments beyond the cap are unlinked oldest-first. Default: a quarter
    # of /dev/shm's AVAILABLE space at startup, clamped to [4 GB, 64 GB]
    # (see _default_shm_pool_cap). Size it to hold at least one working set
    # — a model-scale sync (16 GB for Llama-3-8B bf16) collapses to cold
    # tmpfs allocation if the pool can't retain it.
    shm_pool_max_bytes: int = field(
        default_factory=lambda: _env_int(
            "TORCHSTORE_TPU_SHM_POOL_MAX_BYTES", _default_shm_pool_cap()
        )
    )
    # Use the native C++ data-path library when built.
    use_native: bool = field(
        default_factory=lambda: _env_bool("TORCHSTORE_TPU_USE_NATIVE", True)
    )

    # --- steady-state sync pipeline -----------------------------------------
    # Landing-copy pool: client/volume-side segment copies fan out to this
    # many threads so they overlap each other and the event loop's RPC work
    # (0 = auto, one per core capped at 4; fast_copy already threads
    # internally for large arrays, so the pool budgets against cores).
    landing_threads: int = field(
        default_factory=lambda: _env_int("TORCHSTORE_TPU_LANDING_THREADS", 0)
    )
    # Small-key arena packing threshold: tensors at or below this many bytes
    # share one arena segment per put batch (0 disables).
    arena_max_bytes: int = field(
        default_factory=lambda: _env_int(
            "TORCHSTORE_TPU_ARENA_MAX_BYTES", 256 << 10
        )
    )
    # Default wire quantization for state-dict publishes (none|int8|
    # int8_block|int4_block) and the blockwise scale granularity. See
    # state_dict_utils' quant tier: fused blobs, scales in the payload's
    # arena segment, plan-cacheable.
    transfer_quant: str = field(
        default_factory=lambda: _env_str("TORCHSTORE_TPU_TRANSFER_QUANT", "none")
    )
    quant_block: int = field(
        default_factory=lambda: _env_int(
            "TORCHSTORE_TPU_TRANSFER_QUANT_BLOCK", 256
        )
    )
    # Delta wire tier cadence/threshold (weight_channel delta publishes).
    delta_keyframe: int = field(
        default_factory=lambda: _env_int("TORCHSTORE_TPU_DELTA_KEYFRAME", 8)
    )
    delta_skip_eps: float = field(
        default_factory=lambda: _env_float("TORCHSTORE_TPU_DELTA_SKIP_EPS", 0.0)
    )
    # Iteration-stable transfer-plan cache for put/get_state_dict.
    plan_cache: bool = field(
        default_factory=lambda: _env_bool("TORCHSTORE_TPU_PLAN_CACHE", True)
    )
    # One-sided data plane: warm same-host gets are seqlock-stamped direct
    # segment reads (zero RPCs); warm cross-host gets ring a bulk doorbell
    # against a volume-cached plan. Stale/torn reads fail over loudly to
    # the RPC path and bump ts_one_sided_fallbacks_total.
    one_sided: bool = field(
        default_factory=lambda: _env_bool("TORCHSTORE_TPU_ONE_SIDED", True)
    )
    # Layer-streamed sync: long-poll window per wait_for_stream round and
    # the mixed-generation/superseded re-acquire budget (stream_sync.py).
    stream_poll_s: float = field(
        default_factory=lambda: _env_float("TORCHSTORE_TPU_STREAM_POLL_S", 10.0)
    )
    stream_retries: int = field(
        default_factory=lambda: _env_int("TORCHSTORE_TPU_STREAM_RETRIES", 2)
    )
    # Broadcast distribution: whether this client may join relay trees
    # (per-channel opt-in still required — WeightSubscriber(relay=True)).
    # Fanout and the re-parent window are CONTROLLER-side knobs read from
    # env in the controller process; they live in the registry above.
    relay_enabled: bool = field(
        default_factory=lambda: _env_bool("TORCHSTORE_TPU_RELAY_ENABLED", True)
    )
    # Scale-out metadata plane: controller shard count (1 = classic single
    # controller; initialize(controller_shards=) overrides) and whether
    # this client attaches same-host stamped metadata segments for
    # zero-RPC warm locates / plan validation / stream polling.
    controller_shards: int = field(
        default_factory=lambda: max(
            1, _env_int("TORCHSTORE_TPU_CONTROLLER_SHARDS", 1)
        )
    )
    meta_stamped: bool = field(
        default_factory=lambda: _env_bool("TORCHSTORE_TPU_META_STAMPED", True)
    )

    # --- control plane (client-side half) -----------------------------------
    # Per-tenant admission control: when on, put/get batches reserve tokens
    # from a tenant-labeled bucket whose refill scales down under fleet
    # overload signals (see torchstore_tpu/control/admission.py). The
    # solver/engine knobs are CONTROLLER-side env reads (control/engine.py).
    control_admission: bool = field(
        default_factory=lambda: _env_bool(
            "TORCHSTORE_TPU_CONTROL_ADMISSION", False
        )
    )
    admit_rate_hz: float = field(
        default_factory=lambda: _env_float(
            "TORCHSTORE_TPU_CONTROL_ADMIT_RATE", 512.0
        )
    )
    # None: the bucket defaults to 2x the rate (AdmissionController).
    admit_burst: Optional[float] = field(
        default_factory=lambda: (
            float(v)
            if (v := os.environ.get("TORCHSTORE_TPU_CONTROL_ADMIT_BURST"))
            else None
        )
    )
    overload_inflight: int = field(
        default_factory=lambda: _env_int(
            "TORCHSTORE_TPU_CONTROL_OVERLOAD_INFLIGHT", 16
        )
    )
    # Hot-key read spreading: rotate first-replica choice by a stable
    # per-client salt so split replicas actually share the read load.
    replica_spread: bool = field(
        default_factory=lambda: _env_bool(
            "TORCHSTORE_TPU_CONTROL_REPLICA_SPREAD", False
        )
    )
    # Tenant/cohort label for admission buckets and loadgen attribution.
    tenant: str = field(
        default_factory=lambda: _env_str("TORCHSTORE_TPU_TENANT", "")
    )

    # --- cold-start provisioning (prewarm) ----------------------------------
    # Automatic hint path: put_state_dict derives a manifest from the state
    # dict and provisions pools/dials BEFORE the data-plane puts, so the very
    # first sync of a working set draws pre-faulted segments instead of
    # allocating cold on the critical path. Only fires for working sets of
    # prewarm_auto_min_bytes or more (tiny dicts would pay RPC overhead for
    # nothing) and at most once per distinct size-signature per client.
    prewarm_auto: bool = field(
        default_factory=lambda: _env_bool("TORCHSTORE_TPU_PREWARM_AUTO", True)
    )
    prewarm_auto_min_bytes: int = field(
        default_factory=lambda: _env_int(
            "TORCHSTORE_TPU_PREWARM_AUTO_MIN_BYTES", 32 << 20
        )
    )
    # madvise(MADV_HUGEPAGE) on provisioned segments so tmpfs backs them with
    # transparent huge pages where the kernel allows (fewer TLB misses on the
    # hot memcpy; fail-open — plain pages otherwise).
    prewarm_hugepages: bool = field(
        default_factory=lambda: _env_bool("TORCHSTORE_TPU_PREWARM_HUGEPAGES", True)
    )
    # Threads for the native prefault of provisioned segments (0 = auto).
    prewarm_threads: int = field(
        default_factory=lambda: _env_int("TORCHSTORE_TPU_PREWARM_THREADS", 0)
    )

    # --- security -----------------------------------------------------------
    # Shared secret for connection auth (HMAC challenge-response on every
    # actor/rendezvous/bulk/peer-read listener). Empty = auth disabled; set
    # it (same value on every host) for any non-loopback deployment — these
    # protocols unpickle peer payloads and must not accept strangers.
    auth_secret: str = field(
        default_factory=lambda: _env_str("TORCHSTORE_TPU_AUTH_SECRET", "")
    )

    # --- timeouts (seconds) -------------------------------------------------
    rpc_timeout: float = field(
        default_factory=lambda: float(_env_str("TORCHSTORE_TPU_RPC_TIMEOUT", "120"))
    )
    handshake_timeout: float = field(
        default_factory=lambda: float(
            _env_str("TORCHSTORE_TPU_HANDSHAKE_TIMEOUT", "60")
        )
    )
    # How long a direct pull waits for a source's seqlock generation to
    # settle (even) before giving up. Model-scale refreshes / fallback
    # stagings legitimately hold the generation odd for seconds.
    direct_settle_timeout: float = field(
        default_factory=lambda: float(
            _env_str("TORCHSTORE_TPU_DIRECT_SETTLE_TIMEOUT", "30")
        )
    )

    # --- retry / failover ---------------------------------------------------
    # The unified retry policy every layer derives backoff schedules from
    # (client failover, put transport demotion, publish/acquire survival).
    retry: RetryPolicy = field(default_factory=RetryPolicy.from_env)

    # --- logging ------------------------------------------------------------
    log_level: str = field(
        default_factory=lambda: _env_str("TORCHSTORE_TPU_LOG_LEVEL", "WARNING")
    )

    def merged(self, **overrides) -> "StoreConfig":
        return replace(self, **overrides)


_default_config: StoreConfig | None = None


def default_config() -> StoreConfig:
    global _default_config
    if _default_config is None:
        _default_config = StoreConfig()
    return _default_config
