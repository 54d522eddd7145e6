"""Public module-level async API.

TPU-native equivalent of /root/reference/torchstore/api.py:27-438: a store
registry keyed by ``store_name``, ``initialize`` spawning volumes + the
controller, and module-level ``put/get/...`` delegating to a cached
``LocalClient``. Store handles are published through an env var
(``TORCHSTORE_TPU_STORE_<name>``) so actor processes spawned afterwards
discover the controller the way Monarch's global actor naming served the
reference (/root/reference/torchstore/api.py:118-123).
"""

from __future__ import annotations

import asyncio
import base64
import os
import pickle
import time
from dataclasses import dataclass
from typing import Any, Optional

from torchstore_tpu.client import LocalClient, Shard
from torchstore_tpu.config import StoreConfig, default_config
from torchstore_tpu.controller import Controller
from torchstore_tpu.logging import get_logger, set_log_level
from torchstore_tpu.observability import metrics as obs_metrics
from torchstore_tpu.runtime import (
    ActorMesh,
    ActorRef,
    get_or_spawn_singleton,
    spawn_actors,
    stop_singleton,
)
from torchstore_tpu.storage_volume import StorageVolume
from torchstore_tpu.strategy import (
    LocalRankStrategy,
    SingletonStrategy,
    StoreStrategy,
)

logger = get_logger("torchstore_tpu.api")

ENV_STORE_PREFIX = "TORCHSTORE_TPU_STORE_"
DEFAULT_STORE = "default"


@dataclass
class _StoreHandle:
    controller: ActorRef
    volume_mesh: Optional[ActorMesh]  # only in the initializing process
    client: Optional[LocalClient]
    config: StoreConfig
    owner: bool
    inproc_volume: Any = None  # (server, ref) when colocated
    volume_env: dict = None  # env the volumes were spawned with (repair)
    repair_meshes: list = None  # replacement volumes spawned by repair()
    shard_mesh: Any = None  # ControllerShard actors (sharded metadata plane)
    retired_shard_meshes: list = None  # pre-reshard meshes (stopped at shutdown)
    autoscale_meshes: list = None  # [{"vid", "mesh"}] spawned by ts.autoscale()
    volume_env_fn: Any = None  # per-rank env overrides (reused by autoscale)


# Per-process store registry: forked actor children never reuse the parent's
# handles — they rebuild from the TORCHSTORE_TPU_STORE_* env their spawner
# passes explicitly (see spawn_actors' env forwarding).
_stores: dict[str, _StoreHandle] = {}  # tslint: disable=fork-safety


def _publish_handle(store_name: str, controller: ActorRef) -> None:
    payload = base64.b64encode(pickle.dumps(controller)).decode()
    os.environ[ENV_STORE_PREFIX + store_name] = payload


def _discover_handle(store_name: str) -> Optional[ActorRef]:
    payload = os.environ.get(ENV_STORE_PREFIX + store_name)
    if not payload:
        return None
    return pickle.loads(base64.b64decode(payload))


async def initialize(
    num_storage_volumes: int = 1,
    strategy: Optional[StoreStrategy] = None,
    store_name: str = DEFAULT_STORE,
    config: Optional[StoreConfig] = None,
    storage_dir: Optional[str] = None,
    recover: bool = False,
    colocated: bool = False,
    volume_env_fn: Optional[Any] = None,
    controller_shards: Optional[int] = None,
) -> ActorRef:
    """Boot a store: spawn volume actors, the singleton controller, wire them
    (/root/reference/torchstore/api.py:33-81). With ``storage_dir`` the
    volumes persist entries to disk; ``recover=True`` additionally rebuilds
    the metadata index from what the directory already holds (crash/restart
    recovery — beyond the reference, whose store is memory-only).

    ``volume_env_fn(rank) -> dict`` adds per-volume env overrides on top of
    the store's base volume env — e.g. a distinct
    ``TORCHSTORE_TPU_HOSTNAME`` per volume to emulate a multi-host fleet on
    one box (the relay fanout bench / tests measure per-host egress this
    way). Ignored for ``colocated`` stores (the single volume lives in this
    process).

    ``colocated=True`` hosts the (single) storage volume IN THIS PROCESS:
    local endpoint calls become direct method invocations — no RPC hop, no
    serialization — which drops same-process small-op latency to the tens
    of microseconds (the VERDICT r1 colocated-volume fast path). Remote
    processes still reach the volume over its real actor server, which
    serves as long as this process's event loop runs.

    ``controller_shards`` (default: ``TORCHSTORE_TPU_CONTROLLER_SHARDS``,
    1) partitions the metadata plane: the key->volume index is split
    across that many ControllerShard actors by stable key hash, with
    fleet-scoped state (placement epoch, health, streams, relay, leases)
    on the coordinator — locate/notify throughput scales with the shard
    count instead of funneling through one actor queue."""
    if store_name in _stores:
        raise RuntimeError(f"store {store_name!r} already initialized")
    config = config or default_config()
    if recover and not storage_dir:
        raise ValueError("recover=True requires storage_dir")
    if colocated and num_storage_volumes != 1:
        raise ValueError("colocated=True hosts exactly one volume")
    set_log_level(config.log_level)
    if config.use_native:
        from torchstore_tpu import native

        native.get_lib()  # build/load once at bootstrap, not mid-transfer
    if strategy is None:
        strategy = (
            SingletonStrategy() if num_storage_volumes == 1 else LocalRankStrategy()
        )
    if getattr(strategy, "replication", 1) > num_storage_volumes:
        raise ValueError(
            f"replication={strategy.replication} needs at least that many "
            f"storage volumes (have {num_storage_volumes})"
        )
    # Per-spawn env (NOT process-global os.environ: a failure mid-initialize
    # or a concurrent initialize must not leak the dir into other stores).
    volume_env = (
        {"TORCHSTORE_TPU_STORAGE_DIR": storage_dir} if storage_dir else {}
    )
    if config.auth_secret:
        # Volume processes must present/verify the same secret. A
        # programmatically-set secret is also exported to this process's env
        # (and the cached default config refreshed) so module-level client
        # paths — connection pool, rendezvous — see it too. Auth is
        # process-global: one secret per process, so a second store with a
        # DIFFERENT secret would silently break the first one's connections
        # — reject that instead.
        existing = os.environ.get("TORCHSTORE_TPU_AUTH_SECRET")
        if existing and existing != config.auth_secret:
            raise ValueError(
                "a different TORCHSTORE_TPU_AUTH_SECRET is already active "
                "in this process; auth secrets are per-process, not "
                "per-store"
            )
        volume_env["TORCHSTORE_TPU_AUTH_SECRET"] = config.auth_secret
        if existing != config.auth_secret:
            os.environ["TORCHSTORE_TPU_AUTH_SECRET"] = config.auth_secret
            from torchstore_tpu import config as config_mod

            config_mod._default_config = None
    inproc_volume = None
    if colocated:
        volume_mesh, inproc_volume = await _host_colocated_volume(
            store_name, strategy, volume_env
        )
    else:
        volume_mesh = await spawn_actors(
            num_storage_volumes,
            StorageVolume,
            f"ts_{store_name}_volume",
            strategy,
            env_fn=lambda rank: {
                **volume_env,
                **((volume_env_fn(rank) or {}) if volume_env_fn else {}),
            },
        )
    n_shards = (
        controller_shards
        if controller_shards is not None
        else config.controller_shards
    )
    shard_mesh = None
    try:
        controller = await get_or_spawn_singleton(
            f"ts_{store_name}_controller", Controller
        )
        await controller.init.call_one(strategy, volume_mesh.refs)
        if n_shards and n_shards > 1:
            # Sharded metadata plane: spawn the shard actors and hand each
            # its slot BEFORE any key is indexed (recover included — the
            # rebuild below partitions survivors to their owning shards).
            from torchstore_tpu.metadata.shards import ControllerShard

            shard_mesh = await spawn_actors(
                int(n_shards),
                ControllerShard,
                f"ts_{store_name}_ctrlshard",
            )
            await controller.attach_shards.call_one(
                controller, shard_mesh.refs
            )
        if recover:
            recovered = await controller.rebuild_index.call_one()
            logger.info(
                "recovered %d entries from %s", recovered, storage_dir
            )
    except BaseException:
        # Failed bootstrap must not leak volume/shard processes.
        if inproc_volume is not None:
            await _stop_colocated_volume(inproc_volume)
        else:
            await volume_mesh.stop()
        if shard_mesh is not None:
            await shard_mesh.stop()
        await stop_singleton(f"ts_{store_name}_controller")
        raise
    _publish_handle(store_name, controller)
    _stores[store_name] = _StoreHandle(
        controller=controller,
        volume_mesh=None if colocated else volume_mesh,
        client=None,
        config=config,
        owner=True,
        inproc_volume=inproc_volume,
        volume_env=dict(volume_env),
        repair_meshes=[],
        shard_mesh=shard_mesh,
        retired_shard_meshes=[],
        autoscale_meshes=[],
        volume_env_fn=volume_env_fn,
    )
    return controller


async def _host_colocated_volume(store_name: str, strategy, volume_env: dict):
    """Host one StorageVolume in THIS process: real actor server (remote
    clients reach it over RPC) + in-process registration (local endpoint
    calls dispatch directly)."""
    import socket as _socket

    from torchstore_tpu.runtime.actors import ActorServer, register_inproc

    old_env = {k: os.environ.get(k) for k in volume_env}
    os.environ.update(volume_env)  # StorageVolume reads STORAGE_DIR etc.
    try:
        volume = StorageVolume(strategy)
    finally:
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    name = f"ts_{store_name}_volume_0"
    server = ActorServer()
    server.register(name, volume)
    bind_host = os.environ.get("TORCHSTORE_TPU_BIND_HOST", "127.0.0.1")
    port = await server.start(bind_host)
    advertise = os.environ.get("TORCHSTORE_TPU_ADVERTISE_HOST")
    if advertise is None:
        advertise = (
            _socket.gethostname() if bind_host in ("0.0.0.0", "::") else bind_host
        )
    ref = ActorRef(name, advertise, port)
    register_inproc(advertise, port, name, volume)
    mesh = ActorMesh([ref], [])
    return mesh, (server, ref, volume)


async def _stop_colocated_volume(inproc_volume) -> None:
    from torchstore_tpu.runtime.actors import unregister_inproc

    server, ref, volume = inproc_volume
    unregister_inproc(ref.host, ref.port, ref.name)
    # A process-hosted volume's /dev/shm segments outlive ts.shutdown()
    # unless released here: the orphan reaper keys on dead creator pids,
    # and THIS process stays alive (normal volumes are reclaimed by
    # process exit). Idempotent after controller teardown already reset.
    try:
        volume.store.reset()
        volume.ctx.clear()
    except Exception:
        logger.exception("colocated volume cleanup failed")
    await server.close()


async def initialize_spmd(
    strategy: Optional[StoreStrategy] = None,
    store_name: str = DEFAULT_STORE,
    config: Optional[StoreConfig] = None,
    storage_dir: Optional[str] = None,
    recover: bool = False,
) -> None:
    """Collective bootstrap from torchrun-style env — call on every rank
    (/root/reference/torchstore/spmd.py:246-362). ``storage_dir``/``recover``
    enable durable volumes + index recovery, as in ``initialize``."""
    from torchstore_tpu import spmd as spmd_mod

    await spmd_mod.initialize(
        strategy=strategy,
        store_name=store_name,
        config=config,
        storage_dir=storage_dir,
        recover=recover,
    )


def client(store_name: str = DEFAULT_STORE) -> LocalClient:
    """The per-process cached LocalClient
    (/root/reference/torchstore/api.py:141-153)."""
    handle = _stores.get(store_name)
    if handle is None:
        controller = _discover_handle(store_name)
        if controller is None:
            raise RuntimeError(
                f"store {store_name!r} is not initialized in this process and "
                "no published handle was found; call ts.initialize() first"
            )
        handle = _StoreHandle(
            controller=controller,
            volume_mesh=None,
            client=None,
            config=default_config(),
            owner=False,
        )
        _stores[store_name] = handle
    if handle.client is None:
        handle.client = LocalClient(handle.controller, handle.config)
    return handle.client


def reset_client(store_name: str = DEFAULT_STORE) -> None:
    handle = _stores.get(store_name)
    if handle is not None:
        handle.client = None


async def put(key: str, value: Any, store_name: str = DEFAULT_STORE) -> None:
    await client(store_name).put(key, value)


async def put_batch(items: dict[str, Any], store_name: str = DEFAULT_STORE) -> None:
    await client(store_name).put_batch(items)


async def get(key: str, like: Any = None, store_name: str = DEFAULT_STORE) -> Any:
    return await client(store_name).get(key, like)


async def get_batch(
    items, store_name: str = DEFAULT_STORE
) -> dict[str, Any]:
    """Batched get: ``items`` is a list of keys or {key: target_or_None}."""
    return await client(store_name).get_batch(items)


async def delete(key: str, store_name: str = DEFAULT_STORE) -> None:
    await client(store_name).delete(key)


async def delete_batch(keys: list[str], store_name: str = DEFAULT_STORE) -> None:
    await client(store_name).delete_batch(keys)


async def delete_prefix(prefix: str, store_name: str = DEFAULT_STORE) -> int:
    return await client(store_name).delete_prefix(prefix)


async def keys(
    prefix: Optional[str] = None, store_name: str = DEFAULT_STORE
) -> list[str]:
    return await client(store_name).keys(prefix)


async def exists(key: str, store_name: str = DEFAULT_STORE) -> bool:
    return await client(store_name).exists(key)


async def wait_for(
    keys, timeout: Optional[float] = None, store_name: str = DEFAULT_STORE
) -> None:
    """Block until every key (str or list of str) exists and is fully
    committed (sharded keys: all mesh coordinates landed). Raises
    TimeoutError on expiry. Replaces the reference's poll-in-try/except
    consumer idiom with a push notification from the controller."""
    await client(store_name).wait_for(keys, timeout=timeout)


async def put_state_dict(
    key: str,
    state_dict: Any,
    transfer_dtype=None,
    transfer_quant: Optional[str] = None,
    direct: bool = False,
    rank: int = 0,
    num_ranks: int = 1,
    store_name: str = DEFAULT_STORE,
) -> None:
    from torchstore_tpu import state_dict_utils

    await state_dict_utils.put_state_dict(
        client(store_name),
        key,
        state_dict,
        transfer_dtype=transfer_dtype,
        transfer_quant=transfer_quant,
        direct=direct,
        rank=rank,
        num_ranks=num_ranks,
    )


def direct_staging_buffers(key: str, store_name: str = DEFAULT_STORE) -> Any:
    """Registered staging buffers for a direct-pushed state dict (write
    weights straight into them to make later direct puts copy-free); None
    when unavailable. See state_dict_utils.direct_staging_buffers."""
    from torchstore_tpu import state_dict_utils

    return state_dict_utils.direct_staging_buffers(client(store_name), key)


async def get_state_dict(
    key: str,
    user_state_dict: Any = None,
    direct: bool = False,
    strict: bool = True,
    key_order: Optional[list] = None,
    on_layer: Any = None,
    stream: bool = False,
    store_name: str = DEFAULT_STORE,
) -> Any:
    from torchstore_tpu import state_dict_utils

    return await state_dict_utils.get_state_dict(
        client(store_name),
        key,
        user_state_dict,
        direct=direct,
        strict=strict,
        key_order=key_order,
        on_layer=on_layer,
        stream=stream,
    )


def state_dict_stream(
    key: str,
    transfer_dtype=None,
    transfer_quant: Optional[str] = None,
    store_name: str = DEFAULT_STORE,
):
    """Open an incremental (layer-streamed) publish of ``key``: push
    fragments with ``await stream.put(...)`` as tensors become ready, then
    ``await stream.seal()`` — each batch is watermarked per key so
    streaming consumers (``get_state_dict(stream=True)`` /
    ``WeightSubscriber.acquire_streamed``) serve it immediately, while
    barrier readers still wake only on the sealed, complete dict.
    ``transfer_quant`` ships floating layers as fused blockwise blobs
    (delta encoding is a weight_channel feature — see
    ``WeightPublisher(delta=True)``). See
    :mod:`torchstore_tpu.stream_sync`."""
    from torchstore_tpu import state_dict_utils

    return state_dict_utils.stream_state_dict(
        client(store_name),
        key,
        transfer_dtype=transfer_dtype,
        transfer_quant=transfer_quant,
    )


async def get_state_dict_streamed(
    key: str,
    user_state_dict: Any = None,
    key_order: Optional[list] = None,
    on_layer: Any = None,
    strict: bool = True,
    timeout: Optional[float] = None,
    wait_for_stream_s: Optional[float] = None,
    relay_volume: Optional[str] = None,
    store_name: str = DEFAULT_STORE,
) -> Any:
    """Acquire a streamed publish layer by layer (long-poll, no spin):
    each key is served the moment its watermark lands, in ``key_order``
    when given, with ``on_layer(flat_key, value)`` per served leaf.
    ``wait_for_stream_s`` waits for a publisher that hasn't begun yet.
    ``relay_volume`` gates + routes the acquire through this host's
    broadcast relay copy (see ``WeightSubscriber(relay=True)``, which
    manages the subscription for you). Never mixes generations — see
    torchstore_tpu/stream_sync.py."""
    from torchstore_tpu import stream_sync

    return await stream_sync.get_state_dict_streamed(
        client(store_name),
        key,
        user_state_dict=user_state_dict,
        key_order=key_order,
        on_layer=on_layer,
        strict=strict,
        timeout=timeout,
        wait_for_stream_s=wait_for_stream_s,
        relay_volume=relay_volume,
    )


async def repair(store_name: str = DEFAULT_STORE) -> dict:
    """Elastic recovery: replace dead storage volumes with fresh actors and
    re-replicate every key a surviving replica still holds (the recovery
    story the reference lacks entirely — SURVEY §5 "no elasticity").

    Must run in the process that initialized the store. Returns
    ``{"replaced": [vids], "rereplicated": n_keys, "lost": [keys],
    "failed": [keys], "wedged": [vids]}``. Keys with no surviving copy are
    reported lost and dropped from the index (reads fail loudly with
    missing); keys whose re-replication read failed are reported in
    ``failed`` (their surviving copies stay indexed — run repair again).
    All dead volumes are REPLACED FIRST, then re-replication runs, so a
    multi-volume failure repairs whatever any survivor holds. Wedged
    (alive-but-stuck) volumes are NOT replaced — they may recover; kill
    the process first if replacement is wanted. Durable stores
    (``storage_dir``) can instead restart the volume and use
    ``recover=True`` to reload from disk."""
    from torchstore_tpu.runtime import spawn_actors as _spawn
    from torchstore_tpu.transport.types import Request

    handle = _stores.get(store_name)
    if handle is None or not handle.owner or handle.volume_mesh is None:
        raise RuntimeError(
            "repair must run in the process that initialized the store "
            "(with process-backed volumes)"
        )
    c = client(store_name)
    statuses = await handle.controller.check_volumes.call_one()
    dead = sorted(v for v, s in statuses.items() if s.startswith("dead"))
    wedged = sorted(v for v, s in statuses.items() if s.startswith("wedged"))
    if dead or wedged:
        # Repair is a postmortem-grade moment: capture the last seconds of
        # local history BEFORE replacement scrambles the fleet.
        from torchstore_tpu.observability import recorder as obs_recorder

        obs_recorder.record("health", "repair", dead=dead, wedged=wedged)
        obs_recorder.dump_postmortem("repair")
    report = {
        "replaced": [],
        "rereplicated": 0,
        "lost": [],
        "failed": [],
        "wedged": wedged,
    }
    strategy = await handle.controller.get_strategy.call_one()
    # Phase 1: replace EVERY dead volume before any re-replication read —
    # a key whose listed survivor is another dead volume would otherwise
    # abort the whole repair mid-way.
    recoverable_by_vid: dict[str, dict] = {}
    for vid in dead:
        gen = len(handle.repair_meshes)
        mesh = await _spawn(
            1,
            StorageVolume,
            f"ts_{store_name}_volume_repair{gen}",
            strategy,
            env_fn=lambda rank, _vid=vid: {
                **handle.volume_env,
                "TORCHSTORE_TPU_VOLUME_ID": _vid,
            },
        )
        handle.repair_meshes.append(mesh)
        new_ref = mesh.refs[0]
        info = await new_ref.get_id.call_one()
        result = await handle.controller.replace_volume.call_one(
            vid, new_ref, info["hostname"]
        )
        report["replaced"].append(vid)
        report["lost"].extend(result["lost"])
        recoverable_by_vid[vid] = result["recoverable"]
    await c.refresh_volumes()
    # Phase 2: re-replicate, grouped by KEY ("rereplicated" counts keys,
    # matching the report's documentation) with each payload fetched ONCE
    # however many replacements need it — but replicated per volume with
    # the exact slices THAT volume held (different dead volumes may have
    # held different shards of one key). A key whose read fails (e.g. its
    # survivor was itself among the dead) is reported, never aborts the
    # others.
    plan: dict[str, dict[str, Any]] = {}  # key -> {vid: slices | None}
    for vid, recoverable in recoverable_by_vid.items():
        for key, slices in recoverable.items():
            if key in report["lost"]:
                continue  # its last copy died in a later replacement
            plan.setdefault(key, {})[vid] = slices
    for key, by_vid in plan.items():
        try:
            whole_requests = None
            slice_cache: dict = {}
            for vid, slices in by_vid.items():
                if slices is None:
                    if whole_requests is None:
                        value = await c.get(key)
                        whole_requests = LocalClient._value_to_requests(
                            key, value
                        )
                    requests = whole_requests
                else:
                    requests = []
                    for ts in slices:
                        ckey = (ts.offsets, ts.local_shape)
                        arr = slice_cache.get(ckey)
                        if arr is None:
                            arr = await c.get(key, like=ts)
                            slice_cache[ckey] = arr
                        requests.append(
                            Request.from_tensor_slice(key, ts, arr)
                        )
                await c.replicate_to(vid, requests)
            report["rereplicated"] += 1
        except Exception as exc:  # noqa: BLE001 - reported, not fatal
            logger.warning(
                "repair: re-replicating %r onto %s failed: %s",
                key,
                sorted(by_vid),
                exc,
            )
            report["failed"].append(key)
    if dead:
        logger.info(
            "repair(%s): replaced %s, re-replicated %d key(s), lost %s",
            store_name,
            report["replaced"],
            report["rereplicated"],
            report["lost"] or "none",
        )
    return report


async def prewarm(
    state_dict_or_manifest: Any,
    store_name: str = DEFAULT_STORE,
    transfer_dtype=None,
    direct: bool = False,
    acquire_key: Optional[str] = None,
) -> dict:
    """Cold-start provisioning: size and warm every layer the first sync of
    this working set will touch, BEFORE the first byte moves.

    Accepts a state dict (nested; jax/numpy/torch/ShapeDtypeStruct leaves —
    only metadata is read, no device->host copies) or a prebuilt
    :class:`~torchstore_tpu.provision.StateDictManifest`. The planner fans
    the manifest out over the strategy's put volumes (replication included),
    reserves tmpfs capacity through the controller (concurrent prewarms
    can't oversubscribe /dev/shm), then provisions per transport rung:
    SHM volumes pre-create hugepage-advised, prefaulted pool segments; bulk
    volumes pre-dial the promoted connection (+ stripe set for payloads
    above the striping threshold); device-resident working sets start the
    ICI transfer server.

    ``direct=True`` additionally pre-creates the client-local staging
    segments a direct-source ``register`` will draw. ``acquire_key`` (with
    the state dict as the ACQUIRE targets) precomputes the direct-dest
    transfer plan for an already-published direct key: plan build, source
    dials, and same-host segment attaches all happen now, so iteration 0 of
    ``get_state_dict(direct=True)`` / ``WeightSubscriber.acquire`` starts at
    the data movement.

    ADVISORY by contract for every host stage: those never raise and never
    fail the subsequent sync — stage failures are logged, counted in
    ``ts_prewarm_errors_total``, reported in the returned dict, and the
    lazy path serves exactly as before. Only a transfer server that cannot
    start raises: the device rung has no lazy path to fall back on.
    Returns the provisioning report
    (``segments``, ``bytes``, ``dials``, ``granted_bytes``, ``errors``,
    ...)."""
    from torchstore_tpu import provision

    def _advisory_failure(stage: str, exc: Exception) -> dict:
        logger.warning(
            "prewarm %s failed: %s; lazy path will serve", stage, exc
        )
        obs_metrics.counter(
            "ts_prewarm_errors_total",
            "Prewarm stage failures (lazy path proceeded)",
        ).inc(stage=stage)
        return {"ok": False, "errors": {stage: str(exc)}}

    try:
        c = client(store_name)
    except Exception as exc:  # noqa: BLE001 - advisory, never raises
        return _advisory_failure("client", exc)
    if acquire_key is not None:
        from torchstore_tpu import state_dict_utils

        try:
            return await state_dict_utils.preplan_direct(
                c, acquire_key, state_dict_or_manifest
            )
        except Exception as exc:  # noqa: BLE001 - advisory
            return _advisory_failure("preplan", exc)
    try:
        arrays = None
        if isinstance(state_dict_or_manifest, provision.StateDictManifest):
            manifest = state_dict_or_manifest
        else:
            # ONE flatten serves both the manifest and the registration
            # scan (flattening an already-flat dict is a shallow pass).
            import numpy as _np

            from torchstore_tpu.state_dict_utils import flatten_state_dict

            flat, _ = flatten_state_dict(state_dict_or_manifest)
            manifest = provision.StateDictManifest.from_state_dict(
                flat, transfer_dtype=transfer_dtype
            )
            if transfer_dtype is None:
                # Real source buffers in hand: feed the bulk registration
                # cache too (numpy leaves only; a transfer-dtype cast
                # produces fresh arrays at put time, which the put
                # registers itself).
                arrays = [
                    v for v in flat.values() if isinstance(v, _np.ndarray)
                ]
    except Exception as exc:  # noqa: BLE001 - manifest derivation is
        # advisory too (e.g. flatten's duplicate-key ValueError): the sync
        # itself will surface real problems loudly.
        return _advisory_failure("manifest", exc)
    return await provision.prewarm_manifest(
        c, manifest, direct=direct, arrays=arrays
    )


def metrics_snapshot() -> dict:
    """This process's observability registry: every counter/gauge/histogram
    the store's layers emit (client ops, per-transport bytes, SHM pool
    economics, ...), as ``{name: {"kind", "help", "series": [...]}}`` —
    JSON-serializable. Metrics are PROCESS-LOCAL (Prometheus client-library
    semantics): volume and controller processes expose their registries
    through their ``stats()`` endpoints
    (``controller.stats.call_one(include_volumes=True)`` collects the whole
    fleet), and ``TORCHSTORE_TPU_METRICS_DUMP=/path`` makes every process
    periodically write its own dump. For the MERGED fleet view, see
    :func:`fleet_snapshot`."""
    return obs_metrics.metrics_snapshot()


async def fleet_snapshot(
    store_name: str = DEFAULT_STORE, render: Optional[str] = None
) -> Any:
    """One merged, process-labeled registry for the whole store fleet.

    Scrapes the controller's registry and — through the controller's
    ``stats(include_volumes=True)`` fan-out — every live volume's, merges
    them with this process's own (the client), and labels every series with
    ``process="client" | "controller" | "volume"`` (volumes additionally
    carry ``volume_id``; pre-existing colliding labels are preserved under
    an ``exported_`` prefix). Unreachable volumes land in ``errors`` instead
    of failing the scrape (heartbeat tolerance), and kind conflicts are
    dropped into ``conflicts`` rather than corrupting the document.

    Returns ``{"ts", "scraper_pid", "processes", "errors", "conflicts",
    "hot_keys", "metrics"}`` (JSON-serializable; ``hot_keys`` maps
    ``client``/volume ids to their rolling top-K keys by bytes).
    ``render="prometheus"`` returns one Prometheus-text document instead;
    ``render="json"`` a JSON string."""
    from torchstore_tpu.observability import aggregate, profile
    from torchstore_tpu.observability import ledger as obs_ledger

    c = client(store_name)
    stats = await c.controller.stats.call_one(include_volumes=True)
    entries: list[tuple[dict, dict]] = [
        ({"process": "client"}, obs_metrics.metrics_snapshot()),
        ({"process": "controller"}, stats.get("metrics") or {}),
    ]
    errors: dict[str, str] = {}
    hot: dict[str, list] = {"client": profile.hot_keys(10)}
    one_sided_hot = profile.hot_keys(10, source="one_sided")
    if one_sided_hot:
        # The labeled zero-RPC view: bytes these keys moved never touched
        # any volume, so no volume's hot_keys can account for them.
        hot["client:one_sided"] = one_sided_hot
    ledgers: dict[str, dict] = {"client": obs_ledger.snapshot()}
    for vid, vstats in sorted((stats.get("volumes") or {}).items()):
        if "metrics" not in vstats:
            errors[vid] = str(vstats.get("error", "no metrics in stats()"))
            continue
        entries.append(
            ({"process": "volume", "volume_id": vid}, vstats["metrics"])
        )
        if vstats.get("hot_keys"):
            hot[f"volume:{vid}"] = vstats["hot_keys"]
        if vstats.get("ledger"):
            ledgers[f"volume:{vid}"] = vstats["ledger"]
    doc = aggregate.fleet_doc(
        entries, errors=errors, hot_keys=hot, ledgers=ledgers
    )
    if render == "prometheus":
        return aggregate.render_prometheus(doc["metrics"])
    if render == "json":
        return aggregate.render_json(doc)
    return doc


async def traffic_matrix(store_name: str = DEFAULT_STORE) -> dict:
    """Fleet traffic matrix — the placement solver's input (ROADMAP item
    5) and the O(1)-egress measurement for broadcast trees (item 1).

    Scrapes every process's traffic ledger (``fleet_snapshot`` under the
    hood) and folds the cells into ``{"edges": {src_host: {dst_host:
    {"bytes", "ops"}}}, "egress": {host: bytes}, "ingress": {host: bytes},
    "volumes": {vid: {"bytes_in", "bytes_out"}}, "unattributed": ...,
    "keys": {process: top-K rolling-window keys}}``. Every transfer is
    counted exactly once, at the side that can attribute both endpoints
    (see observability/ledger.py)."""
    from torchstore_tpu.observability import ledger as obs_ledger

    doc = await fleet_snapshot(store_name)
    ledgers = doc.get("ledgers") or {}
    matrix = obs_ledger.traffic_matrix(ledgers)
    matrix["keys"] = {
        label: snap.get("keys", []) for label, snap in ledgers.items()
    }
    return matrix


async def flight_record(store_name: Optional[str] = DEFAULT_STORE) -> dict:
    """The merged fleet flight-recorder timeline: this process's ring plus
    the controller's and every reachable volume's, time-sorted — the
    on-demand post-mortem (``store_name=None`` returns the local ring
    only). Unreachable processes land in ``errors`` instead of failing
    the merge. See observability/recorder.py for what gets recorded and
    which faults auto-dump."""
    from torchstore_tpu.observability import recorder as obs_recorder

    events = [
        {**event, "process": "client"}
        for event in obs_recorder.snapshot()
    ]
    errors: dict[str, str] = {}
    if store_name is not None:
        try:
            c = client(store_name)
            await c._ensure_setup()
        except Exception as exc:  # noqa: BLE001 - local ring still serves
            errors["fleet"] = f"{type(exc).__name__}: {exc}"
        else:
            try:
                for event in await c.controller.flight_record.call_one():
                    events.append({**event, "process": "controller"})
            except Exception as exc:  # noqa: BLE001 - dead controller
                errors["controller"] = f"{type(exc).__name__}: {exc}"
            for vid in sorted(c._volume_refs or {}):
                try:
                    remote = await c._volume_refs[
                        vid
                    ].actor.flight_record.call_one()
                except Exception as exc:  # noqa: BLE001 - dead volume
                    errors[f"volume:{vid}"] = f"{type(exc).__name__}: {exc}"
                    continue
                for event in remote:
                    events.append({**event, "process": f"volume:{vid}"})
    events.sort(key=lambda e: e.get("ts") or 0)
    return {"events": events, "errors": errors}


async def history(
    series: Optional[Any] = None,
    since: Optional[float] = None,
    store_name: Optional[str] = DEFAULT_STORE,
) -> dict:
    """Fleet time-series history: every process's retained metric rings.

    Each torchstore process samples its own registry into bounded
    multi-resolution rings (observability/history.py). This collects
    them — this client's, the controller's, and every reachable
    volume's, riding the ``stats()`` endpoints the way ledgers and
    hot_keys do — without merging (label-identical series from different
    processes are different series; ``observability.history.merge_points``
    folds them when a consumer wants fleet totals).

    ``series`` is a glob or list of globs over series ids
    (``name{k="v"}``; a bare name also matches its labeled variants);
    ``since`` is a lookback in seconds (default 300) or an absolute wall
    timestamp. ``store_name=None`` returns the local view only.

    Returns ``{"generated_ts", "processes": {"client" | "controller" |
    "volume:<vid>": <SeriesStore.query() doc>}, "errors": {...}}`` —
    unreachable processes land in ``errors``, never fail the scrape."""
    from torchstore_tpu.observability import history as obs_history

    request = {"series": series, "since": since}
    doc: dict = {
        "generated_ts": time.time(),
        "processes": {
            "client": obs_history.history(series=series, since=since)
        },
        "errors": {},
    }
    if store_name is None:
        return doc
    try:
        c = client(store_name)
        await c._ensure_setup()
    except Exception as exc:  # noqa: BLE001 - no fleet: local view serves
        doc["errors"]["fleet"] = f"{type(exc).__name__}: {exc}"
        return doc
    try:
        stats = await c.controller.stats.call_one(history=request)
        if stats.get("history"):
            doc["processes"]["controller"] = stats["history"]
    except Exception as exc:  # noqa: BLE001 - dead controller
        doc["errors"]["controller"] = f"{type(exc).__name__}: {exc}"[:200]

    async def scrape(vid: str) -> None:
        try:
            st = await c._volume_refs[vid].actor.stats.call_one(
                history=request
            )
        except Exception as exc:  # noqa: BLE001 - dead volume: report it
            doc["errors"][f"volume:{vid}"] = f"{type(exc).__name__}: {exc}"[:200]
            return
        if st.get("history"):
            doc["processes"][f"volume:{vid}"] = st["history"]

    await asyncio.gather(*(scrape(vid) for vid in sorted(c._volume_refs or {})))
    return doc


async def sync_timeline(
    key: str, store_name: str = DEFAULT_STORE
) -> Optional[dict]:
    """One weight-sync generation's reconstructed lifecycle: stream begin
    -> per-key watermark landings -> seal -> per-subscriber acquire
    completions, with publish-window / first-layer / completion-lag
    figures (observability.timeline.reconstruct). None when ``key`` was
    never streamed (or its record was evicted)."""
    from torchstore_tpu.observability import timeline as obs_timeline

    state = await client(store_name).stream_state(key)
    return obs_timeline.reconstruct(state)


async def slo_report(store_name: Optional[str] = DEFAULT_STORE) -> dict:
    """The live SLO scoreboard: every configured ``TORCHSTORE_TPU_SLO_*``
    threshold with its current value, violation count, violated flag, and
    — per violated SLO — the dominant stage (plan / d2h / h2d / transport /
    landing / stamp_verify / watermark_wait / notify) with the full
    per-stage wall-time breakdown, so "p99 blew the budget" comes with "and THIS
    stage ate it".

    With a ``store_name`` (default store when omitted) the report also
    carries fleet ``overload`` signals — per-volume inflight landings,
    resident doorbell plans, rolling-window transfer totals, each
    volume's OWN per-stage digests (its landing bracket / serve legs:
    read these next to the client's dominant stage — a client
    "transport" verdict whose wall time is rivaled by a volume's
    "landing" row means the landing pool, not the wire, is the stall),
    and this client's per-shard metadata-RPC inflight — the inputs
    admission control (ROADMAP item 3) consumes. ``store_name=None``
    returns the process-local scoreboard only (what loadgen drivers ship
    home; see ``loadgen.report.merge_slo_reports`` for the fleet fold).

    Returns ``{"slos": {name: {"env", "threshold", "current",
    "violations", "violated", "op", "dominant_stage"?, "stages"?}},
    "stages": {op: {stage: {...}}}, "overload": {"volumes": {vid: {...}},
    "metadata_rpc_inflight": {...}, "errors": {...}}, "generated_ts"}``."""
    from torchstore_tpu.observability import timeline as obs_timeline

    report = obs_timeline.slo_report()
    if store_name is None:
        return report
    overload: dict = {
        "volumes": {},
        "metadata_rpc_inflight": {},
        "errors": {},
    }
    report["overload"] = overload
    try:
        c = client(store_name)
        await c._ensure_setup()
    except Exception as exc:  # noqa: BLE001 - no fleet: local view serves
        overload["errors"]["fleet"] = f"{type(exc).__name__}: {exc}"
        return report
    snapshot_fn = getattr(c.controller, "inflight_snapshot", None)
    if snapshot_fn is not None:
        overload["metadata_rpc_inflight"] = snapshot_fn()

    async def scrape(vid: str) -> None:
        try:
            st = await c._volume_refs[vid].actor.stats.call_one()
        except Exception as exc:  # noqa: BLE001 - dead volume: report it
            overload["errors"][vid] = f"{type(exc).__name__}: {exc}"[:200]
            return
        entry = dict(st.get("overload") or {})
        window = (st.get("ledger") or {}).get("window") or {}
        entry["window_ops"] = window.get("ops", 0)
        entry["window_bytes"] = window.get("bytes", 0)
        # The volume's OWN per-stage digests ride the report next to the
        # client-side attribution. They are NOT summed into the client's
        # stage table: the client's "transport" span CONTAINS the
        # volume's "landing" bracket (nested wall time — summing would
        # double-count and can never flip the vote), so a wedged landing
        # pool is diagnosed by reading the volume rows — e.g. put.landing
        # p99 here rivaling the client's put.transport p99.
        if st.get("stages"):
            entry["stages"] = st["stages"]
        if st.get("trends"):
            entry["trends"] = st["trends"]
        overload["volumes"][vid] = entry

    await asyncio.gather(*(scrape(vid) for vid in sorted(c._volume_refs or {})))
    # Active volume-side trends surface at top level next to the client's
    # own (report["trends"], from timeline.slo_report) so "which process
    # is in a regime change" needs no drill-down: keys are
    # volume:<vid>:<detector>.
    trends = report.setdefault("trends", {})
    for vid, entry in overload["volumes"].items():
        for name, result in (entry.get("trends") or {}).items():
            if result.get("active"):
                trends[f"volume:{vid}:{name}"] = result
    return report


async def inject_fault(
    name: str,
    action: str,
    count: Optional[int] = None,
    prob: Optional[float] = None,
    delay_ms: Optional[float] = None,
    scope: str = "volumes",
    store_name: str = DEFAULT_STORE,
) -> dict:
    """Arm a deterministic faultpoint across the fleet (test/chaos control
    plane; see ``torchstore_tpu/faults.py`` for sites and actions).

    ``scope``: ``"client"`` (this process), ``"controller"``, ``"volumes"``
    (every volume), ``"shards"`` (every controller shard) or
    ``"shard:<i>"`` (one of them, by index), a specific volume id, or
    ``"all"``. Arming rides the ``inject_fault`` control RPC, so it
    reaches ALREADY-RUNNING forked actor processes — the capability the
    old monkeypatch-per-test idiom never had. Returns
    ``{target: armed spec}``."""
    from torchstore_tpu import faults

    c = client(store_name)
    await c._ensure_setup()
    kwargs = {"count": count, "prob": prob, "delay_ms": delay_ms}
    out: dict[str, dict] = {}
    if scope in ("client", "all"):
        out["client"] = faults.arm(name, action, **kwargs)
    if scope in ("controller", "all"):
        out["controller"] = await c.controller.inject_fault.call_one(
            name, action, **kwargs
        )
    shard_refs = c.controller.shard_refs
    if scope in ("shards", "all"):
        for i, ref in enumerate(shard_refs):
            out[f"shard:{i}"] = await ref.inject_fault.call_one(
                name, action, **kwargs
            )
    elif scope.startswith("shard:"):
        try:
            i = int(scope.split(":", 1)[1])
            ref = shard_refs[i]
        except (ValueError, IndexError):
            raise ValueError(
                f"unknown fault scope {scope!r}: this store has "
                f"{len(shard_refs)} controller shard(s)"
            ) from None
        out[f"shard:{i}"] = await ref.inject_fault.call_one(
            name, action, **kwargs
        )
    if scope in ("volumes", "all"):
        targets = list(c._volume_refs)
    elif scope in c._volume_refs:
        targets = [scope]
    elif scope in ("client", "controller", "shards") or scope.startswith(
        "shard:"
    ):
        targets = []
    else:
        raise ValueError(
            f"unknown fault scope {scope!r}; expected 'client', "
            f"'controller', 'volumes', 'shards', 'shard:<i>', 'all', or a "
            f"volume id ({sorted(c._volume_refs)})"
        )
    for vid in targets:
        out[f"volume:{vid}"] = await c._volume_refs[
            vid
        ].actor.inject_fault.call_one(name, action, **kwargs)
    return out


async def clear_faults(
    name: Optional[str] = None, store_name: str = DEFAULT_STORE
) -> int:
    """Disarm ``name`` (or ALL faultpoints when None) in every reachable
    fleet process; returns how many armed specs were dropped. Unreachable
    processes are skipped — a volume a test killed cannot answer."""
    from torchstore_tpu import faults

    cleared = faults.disarm(name)
    try:
        c = client(store_name)
        await c._ensure_setup()
    except Exception:  # noqa: BLE001 - no fleet: local disarm is all there is
        return cleared
    try:
        cleared += await c.controller.clear_faults.call_one(name)
    except Exception:  # noqa: BLE001 - best-effort cleanup
        pass
    for ref in list(c.controller.shard_refs):
        try:
            cleared += await ref.clear_faults.call_one(name)
        except Exception:  # noqa: BLE001 - a killed shard can't disarm
            pass
    for vid in list(c._volume_refs):
        try:
            cleared += await c._volume_refs[vid].actor.clear_faults.call_one(
                name
            )
        except Exception:  # noqa: BLE001 - dead volumes can't disarm
            pass
    return cleared


async def relay_topology(store_name: str = DEFAULT_STORE) -> dict:
    """The current broadcast relay topology, per channel: members (with
    subscriber refcounts), topology epoch, configured fanout, and every
    live run's tree + per-member landed progress — the operator view of
    the fan-out shape (each re-parenting decision is additionally recorded
    in the flight recorder as a ``health`` event). See
    torchstore_tpu/relay.py."""
    c = client(store_name)
    await c._ensure_setup()
    return await c.controller.relay_topology.call_one()


async def volume_health(store_name: str = DEFAULT_STORE) -> dict:
    """The health supervisor's per-volume view:
    ``{volume_id: {"state": "ok"|"probation"|"quarantined", "misses",
    "oks"}}``."""
    c = client(store_name)
    await c._ensure_setup()
    return await c.controller.volume_health.call_one()


async def version_catalog(
    channel: Optional[str] = None, store_name: str = DEFAULT_STORE
) -> dict:
    """Per-channel version inventory (torchstore_tpu/tiering/): for every
    ``{channel}/v{n}`` group the store holds — keys, logical bytes, replica
    volumes, tier split (resident vs spilled-to-disk), and the live cohort
    leases pinning it. The operator's answer to "which cohort is holding
    which version where, and what is it costing"."""
    return await client(store_name).version_catalog(channel)


async def lease_acquire(
    cohort: str,
    channel: str,
    version: int,
    ttl_s: Optional[float] = None,
    store_name: str = DEFAULT_STORE,
) -> dict:
    """Pin ``(channel, version)`` for ``cohort``: the version is exempt
    from the publisher's GC (and the controller refuses deletes under it)
    and from the spill tier's demotion while the lease lives. TTL'd
    (default ``TORCHSTORE_TPU_LEASE_TTL_S``) — renew to keep. Returns the
    lease description; pass its ``lease_id`` to renew/release.
    ``WeightSubscriber.acquire(version=...)`` manages a read-scoped lease
    for you; use this directly for long-lived cohort pins."""
    return await client(store_name).lease_acquire(
        cohort, channel, version, ttl_s
    )


async def lease_renew(
    lease_id: str,
    ttl_s: Optional[float] = None,
    store_name: str = DEFAULT_STORE,
) -> dict:
    """Extend a live lease; raises KeyError when it already expired (the
    cohort must re-acquire and re-validate the version still exists)."""
    return await client(store_name).lease_renew(lease_id, ttl_s)


async def lease_release(
    lease_id: str, store_name: str = DEFAULT_STORE
) -> bool:
    """Drop a lease (idempotent). The version becomes GC- and
    spill-eligible again once its LAST lease is gone."""
    return await client(store_name).lease_release(lease_id)


async def lease_list(
    channel: Optional[str] = None, store_name: str = DEFAULT_STORE
) -> dict:
    """Live pins as ``{channel: {version: [cohort, ...]}}``."""
    return await client(store_name).lease_list(channel)


async def tier_sweep(store_name: str = DEFAULT_STORE) -> dict:
    """Run one spill pass across the fleet NOW (instead of waiting for the
    background ``TORCHSTORE_TPU_TIER_SWEEP_INTERVAL_S`` cadence); returns
    per-volume ``{spilled, fault_ins, resident_bytes, spilled_bytes}``
    summaries. A no-op reporting ``enabled: False`` per volume when
    ``TORCHSTORE_TPU_TIER_ENABLED`` is unset."""
    return await client(store_name).tier_sweep()


async def _control_signals(
    store_name: str,
) -> tuple[Optional[dict], Optional[dict]]:
    """Fleet-wide signals only a client can fully assemble — the traffic
    matrix (every process's ledger) and the SLO overload view — shipped to
    the controller's policy engine alongside its own volume scrape. Either
    half degrades to None on scrape failure: the engine solves on what it
    has rather than refusing to plan."""
    traffic = overload = None
    try:
        traffic = await traffic_matrix(store_name)
    except Exception as exc:  # noqa: BLE001 - partial signals still solve
        logger.warning("control signals: traffic matrix scrape failed: %s", exc)
    try:
        overload = (await slo_report(store_name)).get("overload")
    except Exception as exc:  # noqa: BLE001 - partial signals still solve
        logger.warning("control signals: slo report scrape failed: %s", exc)
    return traffic, overload


async def control_plan(store_name: str = DEFAULT_STORE) -> dict:
    """Dry run of the placement policy engine: assemble the same telemetry
    snapshot a reconcile round would (fleet traffic matrix + SLO overload
    signals + per-volume stats), run the pure solver, and return the
    actions it WOULD take — applying nothing, recording nothing. The
    inspection surface for "what does the control plane think right now":
    ``{"actions": [{kind, subject, reason, ...}], "snapshot": {...}}``."""
    c = client(store_name)
    await c._ensure_setup()
    traffic, overload = await _control_signals(store_name)
    return await c.controller.control_plan.call_one(
        traffic=traffic, overload=overload
    )


async def rebalance(
    store_name: str = DEFAULT_STORE, shards: Optional[int] = None
) -> dict:
    """Manual control-plane trigger.

    Without ``shards``: run ONE reconcile round now — snapshot, solve,
    apply, audit — and return ``{"actions": [...], "applied": N}``. Safe
    alongside the periodic loop (``TORCHSTORE_TPU_CONTROL_INTERVAL_S``):
    per-subject cooldowns keep back-to-back rounds from thrashing.

    With ``shards=N``: elastically reshard the metadata plane at runtime —
    spawn a new ControllerShard mesh (N==1 merges back onto the
    coordinator), freeze-export-replay the whole index onto it, bump the
    placement epoch, retire the old mesh. Zero lost keys, zero failed
    client ops: in-flight mutations park during the swap and stale-topology
    errors are retried by the metadata router after a topology reload.
    Must run in the process that initialized the store (it owns actor
    spawning). Returns the controller's reshard summary
    ``{"shards", "was", "keys", "reindexed", "epoch"}``."""
    c = client(store_name)
    await c._ensure_setup()
    if shards is None:
        traffic, overload = await _control_signals(store_name)
        return await c.controller.control_reconcile.call_one(
            traffic=traffic, overload=overload
        )
    shards = int(shards)
    if shards < 1:
        raise ValueError(f"rebalance(shards={shards}): need >= 1")
    handle = _stores.get(store_name)
    if handle is None:
        raise RuntimeError(
            "rebalance(shards=N) spawns controller-shard actors and must "
            f"run in the process that initialized store {store_name!r}"
        )
    new_mesh = None
    if shards > 1:
        from torchstore_tpu.metadata.shards import ControllerShard

        generation = len(handle.retired_shard_meshes or ()) + 1
        new_mesh = await spawn_actors(
            shards,
            ControllerShard,
            f"ts_{store_name}_ctrlshard_g{generation}",
        )
    try:
        result = await handle.controller.reshard.call_one(
            handle.controller, new_mesh.refs if new_mesh is not None else []
        )
    except BaseException:
        # The old authority thawed controller-side; don't leak the new mesh.
        if new_mesh is not None:
            await new_mesh.stop()
        raise
    # Old shards are retired (they still drain scheduled reclaims); their
    # processes stop with the store.
    if handle.shard_mesh is not None:
        if handle.retired_shard_meshes is None:
            handle.retired_shard_meshes = []
        handle.retired_shard_meshes.append(handle.shard_mesh)
    handle.shard_mesh = new_mesh
    # Re-route this client onto the new mesh immediately (other clients
    # recover through the stale-topology retry + epoch confirmation).
    await c.controller.load_topology()
    return result


async def autoscale_plan(store_name: str = DEFAULT_STORE) -> dict:
    """Dry run of the elastic-fleet policy engine: assemble the autoscale
    telemetry snapshot (fleet traffic + SLO overload + per-volume stats
    with spilled-key counts), run the pure solver, and return the actions
    it WOULD take — applying nothing, recording nothing, not even
    advancing the idle-round hysteresis counter. Returns ``{"actions":
    [{kind, subject, reason, ...}], "snapshot": {...}, "fleet": {...}}``."""
    c = client(store_name)
    await c._ensure_setup()
    traffic, overload = await _control_signals(store_name)
    return await c.controller.autoscale_plan.call_one(
        traffic=traffic, overload=overload
    )


async def autoscale(store_name: str = DEFAULT_STORE) -> dict:
    """Run ONE autoscale round now — snapshot, solve, apply, audit — and
    execute any deferred ``scale_out`` actions by actually spawning fresh
    volume actors (actor spawning is client-side, so the controller defers
    spawns exactly like ``rebalance(shards=N)`` defers resharding).

    Drain / retire / blob-demote actions apply controller-side inside the
    round. Scale-out spawns happen HERE, in the process that initialized
    the store: each new volume gets a unique forced volume id, the store's
    base volume env (plus ``volume_env_fn`` overrides at a fresh rank),
    and is attached through ``controller.attach_volume`` — then one
    control-plane reconcile runs so hot-key splits can seed placement onto
    the new capacity immediately. Retired autoscale-spawned volumes have
    their actor processes stopped (fixed fleet volumes retire from the
    placement maps but their processes stop with the store).

    Safe alongside the periodic loop
    (``TORCHSTORE_TPU_AUTOSCALE_INTERVAL_S``): per-subject cooldowns and
    reversal damping keep back-to-back rounds from thrashing. Returns the
    round report with ``spawned``/``stopped`` volume-id lists merged in."""
    c = client(store_name)
    await c._ensure_setup()
    traffic, overload = await _control_signals(store_name)
    result = await c.controller.autoscale_reconcile.call_one(
        traffic=traffic, overload=overload
    )
    handle = _stores.get(store_name)
    actions = result.get("actions", [])
    wants = sum(
        int(a.get("count") or 1)
        for a in actions
        if a.get("kind") == "scale_out"
        and str(a.get("outcome", "")).startswith("deferred")
    )
    spawned: list[str] = []
    stopped: list[str] = []
    if wants:
        if handle is None or not handle.owner:
            # Only the initializing process owns actor spawning; other
            # processes surface the deferral for it to pick up.
            result["spawn_deferred"] = wants
        else:
            spawned = await _autoscale_spawn(store_name, handle, wants)
            if spawned:
                # Seed placement onto the new capacity immediately: one
                # control round can split hot keys / rebalance replicas
                # instead of waiting for the next interval.
                try:
                    await c.controller.control_reconcile.call_one(
                        traffic=traffic, overload=overload
                    )
                except Exception as exc:  # noqa: BLE001 - placement seeding
                    # is best-effort; the periodic loop converges anyway
                    logger.warning(
                        "autoscale: placement seeding reconcile failed: %s",
                        exc,
                    )
            await c.refresh_volumes()
    retired = {
        str(a.get("subject"))
        for a in actions
        if a.get("kind") == "retire_volume"
        and str(a.get("outcome", "")).startswith("applied")
    }
    if (
        handle is not None
        and handle.owner
        and any(rec["mesh"] is not None for rec in handle.autoscale_meshes or [])
    ):
        # Reclaim the processes of autoscale-spawned volumes no longer
        # attached to the fleet — THIS is what makes scale-in save
        # volume-seconds. Reconciling against the controller's live
        # volume map (not just this round's retire actions) also sweeps
        # volumes the periodic loop retired between manual rounds, whose
        # processes would otherwise idle until shutdown.
        attached = set(await c.controller.get_volume_map.call_one())
        for rec in handle.autoscale_meshes:
            if rec["mesh"] is not None and rec["vid"] not in attached:
                await rec["mesh"].stop()
                rec["mesh"] = None
                stopped.append(rec["vid"])
    if retired or stopped:
        await c.refresh_volumes()
    result["spawned"] = spawned
    result["stopped"] = stopped
    return result


async def _autoscale_spawn(
    store_name: str, handle: _StoreHandle, count: int
) -> list[str]:
    """Spawn ``count`` fresh storage volumes and attach them to the live
    fleet (the actuator half of a ``scale_out`` decision). Each spawn
    crosses the ``autoscale.spawn`` faultpoint; a failed spawn stops the
    batch and reports what DID attach rather than raising away the round."""
    from torchstore_tpu import faults

    strategy = await handle.controller.get_strategy.call_one()
    if handle.autoscale_meshes is None:
        handle.autoscale_meshes = []
    spawned: list[str] = []
    for _ in range(count):
        gen = len(handle.autoscale_meshes)
        vid = f"scale-{gen}"
        try:
            await faults.afire("autoscale.spawn")
            mesh = await spawn_actors(
                1,
                StorageVolume,
                f"ts_{store_name}_volume_{vid}",
                strategy,
                env_fn=lambda rank, _vid=vid, _gen=gen: {
                    **handle.volume_env,
                    **(
                        (handle.volume_env_fn(_gen) or {})
                        if handle.volume_env_fn
                        else {}
                    ),
                    "TORCHSTORE_TPU_VOLUME_ID": _vid,
                },
            )
        except Exception as exc:  # noqa: BLE001 - partial scale-out is
            # still progress; the next round retries the remainder
            logger.warning("autoscale: spawning %s failed: %s", vid, exc)
            break
        handle.autoscale_meshes.append({"vid": vid, "mesh": mesh})
        new_ref = mesh.refs[0]
        try:
            info = await new_ref.get_id.call_one()
            await handle.controller.attach_volume.call_one(
                vid, new_ref, info["hostname"]
            )
        except Exception as exc:  # noqa: BLE001 - an unattachable volume
            # must not leak its process
            logger.warning("autoscale: attaching %s failed: %s", vid, exc)
            await mesh.stop()
            handle.autoscale_meshes[-1]["mesh"] = None
            break
        spawned.append(vid)
    if spawned:
        logger.info(
            "autoscale(%s): spawned + attached %s", store_name, spawned
        )
    return spawned


async def blob_checkpoint(store_name: str = DEFAULT_STORE) -> dict:
    """Archive every live volume's committed payloads into the blob cold
    tier and write the durable fleet manifest — the prerequisite for
    scale-to-zero. After this returns, the whole fleet can be killed and a
    fresh one cold-started with ``ts.blob_restore()`` recovering every
    committed generation from the blob tier. Requires
    ``TORCHSTORE_TPU_BLOB_ENABLED=1``. Returns ``{"outcome", "keys",
    "volumes", "errors"}``."""
    c = client(store_name)
    await c._ensure_setup()
    return await c.controller.blob_checkpoint.call_one()


async def blob_restore(store_name: str = DEFAULT_STORE) -> dict:
    """Cold-start restore: read the durable fleet manifest from the blob
    tier, decode each archived object, and land every committed key into
    the (fresh) fleet via the targeted-replication path — byte-for-byte
    the payloads the last ``ts.blob_checkpoint()`` captured. Keys restore
    round-robin across live volumes and are indexed with fresh write
    generations (reclaim tokens stay sound on the new fleet). Failed keys
    are reported, never abort the rest. Returns ``{"restored", "failed",
    "keys", "seconds"}`` and audits the round as an
    ``autoscale/blob_restore`` decision."""
    from torchstore_tpu.observability import recorder as obs_recorder
    from torchstore_tpu.tiering import blob as blob_mod
    from torchstore_tpu.transport.types import Request

    if not blob_mod.enabled():
        raise RuntimeError(
            "blob tier disabled; set TORCHSTORE_TPU_BLOB_ENABLED=1"
        )
    store = blob_mod.BlobStore()
    doc = blob_mod.read_fleet_manifest(store)
    if doc is None:
        raise RuntimeError(
            "no fleet manifest in the blob tier; run ts.blob_checkpoint() "
            "on a live fleet first"
        )
    c = client(store_name)
    await c._ensure_setup()
    vmap = await c.controller.get_volume_map.call_one()
    vids = sorted(
        vid
        for vid, info in vmap.items()
        if info.get("health") not in ("quarantined", "draining")
    )
    if not vids:
        raise RuntimeError("no live volumes to restore onto")
    t0 = time.perf_counter()
    restored: list[str] = []
    failed: list[str] = []
    for i, (key, info) in enumerate(sorted(doc.get("keys", {}).items())):
        try:
            metas, values = blob_mod.BlobTier.decode_entry(
                store.get(info["object"])
            )
            requests = []
            for idx, meta in enumerate(metas):
                val = values[idx]
                if meta.is_object:
                    requests.append(Request(key=key, is_object=True, objects=val))
                elif meta.tensor_slice is not None:
                    requests.append(
                        Request.from_tensor_slice(key, meta.tensor_slice, val)
                    )
                else:
                    requests.append(Request.from_tensor(key, val))
            await c.replicate_to(vids[i % len(vids)], requests)
            restored.append(key)
        except Exception as exc:  # noqa: BLE001 - reported, not fatal
            logger.warning("blob_restore: %r failed: %s", key, exc)
            failed.append(key)
    seconds = time.perf_counter() - t0
    obs_recorder.record(
        "decision",
        "autoscale/blob_restore",
        subject="fleet",
        reason="cold restore from the blob-tier fleet manifest",
        outcome="applied" if not failed else "applied: %d failed" % len(failed),
        restored=len(restored),
        failed=len(failed),
        seconds=round(seconds, 3),
    )
    logger.info(
        "blob_restore(%s): %d key(s) restored, %d failed, %.2fs",
        store_name,
        len(restored),
        len(failed),
        seconds,
    )
    return {
        "restored": len(restored),
        "failed": failed,
        "keys": len(doc.get("keys", {})),
        "seconds": seconds,
    }


def collect_trace(out_path: Optional[str] = None) -> Optional[dict]:
    """Merge every process's Chrome-trace file (``TORCHSTORE_TPU_TRACE``
    base + pid-suffixed siblings) into ONE Perfetto-loadable timeline with
    labeled process tracks and cross-process trace ids. Call after
    ``ts.shutdown()`` so actor processes have flushed their atexit dumps.
    Returns ``{"path", "files", "events", "trace_ids"}`` or None when
    tracing is disabled. Default output: ``<root>.merged<ext>``."""
    from torchstore_tpu.observability import tracing

    return tracing.collect_trace(out_path)


async def barrier(
    name: str, store_name: str = DEFAULT_STORE, timeout: float = 300.0
) -> None:
    """Collective barrier across the SPMD world that initialized this store
    (put-barrier-get is the canonical exchange pattern). Requires
    ``initialize_spmd``."""
    from torchstore_tpu import spmd as spmd_mod

    session = spmd_mod._spmd_sessions.get(store_name)
    if session is None:
        raise RuntimeError(
            f"barrier requires an SPMD-initialized store (none for "
            f"{store_name!r}); call ts.initialize_spmd() first"
        )
    await session.client.barrier(name, session.env.world_size, timeout=timeout)


async def shutdown(store_name: str = DEFAULT_STORE) -> None:
    """Tear down a store. Routes to the SPMD session when one owns this
    store; otherwise, in the initializing process this resets + stops the
    volume/controller actors, elsewhere it only drops local caches
    (/root/reference/torchstore/api.py:100-109)."""
    from torchstore_tpu import sharding as shd
    from torchstore_tpu import spmd as spmd_mod

    # Recycled device->host landing buffers are a cache of this process:
    # nothing of a store that is going should keep host memory.
    shd.host_pool().clear()
    if await spmd_mod.shutdown(store_name):
        return
    handle = _stores.pop(store_name, None)
    if handle is None:
        return
    if handle.client is not None:
        from torchstore_tpu import state_dict_utils

        await state_dict_utils.close_direct_caches(handle.client)
    # Cross-host metadata mirrors subscribe per (process, feed root);
    # once the LAST store is gone their feeds are dead — close them so
    # the receiver tasks and local replica segments don't outlive the
    # fleet (they would spin re-subscribing against nothing).
    if not _stores:
        from torchstore_tpu.metadata import mirror as mirror_mod

        mirror_mod.close_mirrors()
    # Release prewarmed-but-undrawn direct staging segments once the LAST
    # store is gone (the pool is process-local and advisory; another live
    # store may have prewarmed it, so a per-store shutdown must not discard
    # its segments — but without this, segments a register() never took
    # would pin tmpfs until process exit).
    if not _stores:
        from torchstore_tpu.provision.pool import local_pool

        local_pool().clear()
    if handle.owner:
        try:
            await handle.controller.teardown.call_one()
        except Exception:
            logger.exception("controller teardown failed")
        if handle.volume_mesh is not None:
            await handle.volume_mesh.stop()
        if handle.shard_mesh is not None:
            await handle.shard_mesh.stop()
        for mesh in handle.retired_shard_meshes or []:
            await mesh.stop()
        for mesh in handle.repair_meshes or []:
            await mesh.stop()
        for rec in handle.autoscale_meshes or []:
            if rec["mesh"] is not None:
                await rec["mesh"].stop()
        if handle.inproc_volume is not None:
            await _stop_colocated_volume(handle.inproc_volume)
        await stop_singleton(f"ts_{store_name}_controller")
        os.environ.pop(ENV_STORE_PREFIX + store_name, None)


__all__ = [
    "DEFAULT_STORE",
    "Shard",
    "autoscale",
    "autoscale_plan",
    "barrier",
    "blob_checkpoint",
    "blob_restore",
    "client",
    "collect_trace",
    "control_plan",
    "delete",
    "delete_batch",
    "delete_prefix",
    "exists",
    "fleet_snapshot",
    "flight_record",
    "get",
    "get_batch",
    "get_state_dict",
    "get_state_dict_streamed",
    "initialize",
    "initialize_spmd",
    "keys",
    "lease_acquire",
    "lease_list",
    "lease_release",
    "lease_renew",
    "metrics_snapshot",
    "prewarm",
    "put",
    "put_batch",
    "direct_staging_buffers",
    "put_state_dict",
    "rebalance",
    "relay_topology",
    "repair",
    "reset_client",
    "shutdown",
    "state_dict_stream",
    "slo_report",
    "sync_timeline",
    "tier_sweep",
    "traffic_matrix",
    "version_catalog",
    "wait_for",
]
