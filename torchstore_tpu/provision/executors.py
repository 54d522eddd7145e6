"""Prewarm orchestrator: drive a ProvisionPlan through the live stack.

``prewarm_manifest`` is the engine behind ``ts.prewarm`` and the automatic
``put_state_dict`` hint path. Contract (ISSUE acceptance): it NEVER raises —
every stage failure (volume down, tmpfs full, dial refused) is logged,
counted in ``ts_prewarm_errors_total``, reported in the returned dict, and
the subsequent sync proceeds on the lazy path exactly as before.

Stages, each its own span under ``provision.prewarm``:

1. plan      — manifest + strategy fan-out + per-volume transport rung
2. reserve   — controller capacity reservation (concurrent prewarms can't
               oversubscribe tmpfs); grants clamp the plan
3. shm       — per-volume pool pre-sizing (hugepage + native prefault)
4. bulk      — connection pre-dial (+ stripe set) and registration prewarm
5. device    — ICI transfer-server start when the working set is on device
6. release   — drop the reservation (the pool itself now holds the bytes)
"""

from __future__ import annotations

import asyncio
import uuid
import weakref
from typing import Any, Optional

from torchstore_tpu.logging import get_logger
from torchstore_tpu.observability import context as obs_context
from torchstore_tpu.observability import metrics as obs_metrics
from torchstore_tpu.observability.tracing import span
from torchstore_tpu.provision import planner
from torchstore_tpu.provision.manifest import StateDictManifest

logger = get_logger("torchstore_tpu.provision")

_RUNS = obs_metrics.counter(
    "ts_prewarm_runs_total", "Prewarm invocations (explicit + auto-hint)"
)
_BYTES = obs_metrics.counter(
    "ts_prewarm_bytes_total",
    "Bytes pre-faulted into pools/staging by prewarm, by leg",
)
_SEGMENTS = obs_metrics.counter(
    "ts_prewarm_segments_total", "Segments pre-created by prewarm, by leg"
)
_DIALS = obs_metrics.counter(
    "ts_prewarm_dials_total", "Connections pre-opened by prewarm, by leg"
)
_ERRORS = obs_metrics.counter(
    "ts_prewarm_errors_total", "Prewarm stage failures (lazy path proceeded)"
)
_CLAMPED = obs_metrics.counter(
    "ts_prewarm_clamped_bytes_total",
    "Plan bytes dropped by capacity grants (tmpfs headroom)",
)


def _fail(report: dict, stage: str, exc: BaseException) -> None:
    _ERRORS.inc(stage=stage)
    report["ok"] = False
    report["errors"][stage] = f"{type(exc).__name__}: {exc}"
    logger.warning(
        "prewarm stage %s failed (%s: %s); lazy path will serve",
        stage,
        type(exc).__name__,
        exc,
    )


def as_manifest(
    state_dict_or_manifest: Any,
    transfer_dtype=None,
    transfer_quant: Optional[str] = None,
    quant_block: int = 256,
) -> StateDictManifest:
    if isinstance(state_dict_or_manifest, StateDictManifest):
        return state_dict_or_manifest
    return StateDictManifest.from_state_dict(
        state_dict_or_manifest,
        transfer_dtype=transfer_dtype,
        transfer_quant=transfer_quant,
        quant_block=quant_block,
    )


async def prewarm_manifest(
    client,
    manifest: StateDictManifest,
    direct: bool = False,
    arrays: Optional[list] = None,
) -> dict:
    """Provision every layer a sync of ``manifest`` will touch. Returns a
    report dict. Host legs never raise (the lazy path serves what they
    could not warm); the device leg does, because the transfer server has
    no lazy stand-in — if it cannot start here it cannot start at the
    direct register either. ``direct=True`` additionally pre-creates the
    client-local staging segments a direct-source ``register`` will draw.
    ``arrays`` (optional, real source buffers) feed the bulk registration
    cache."""
    report: dict[str, Any] = {
        "ok": True,
        "manifest_bytes": manifest.total_bytes,
        "entries": len(manifest.entries),
        "planned_bytes": 0,
        "clamped_bytes": 0,
        "granted_bytes": {},
        "segments": 0,
        "bytes": 0,
        "dials": 0,
        "local_segments": 0,
        "device_server": None,
        "errors": {},
    }
    _RUNS.inc()
    plan = None
    try:
        with obs_context.ensure_root(), span(
            "provision.prewarm",
            nbytes=manifest.total_bytes,
            entries=len(manifest.entries),
        ):
            plan = await _build_plan(client, manifest, report)
            if plan is not None:
                reservation = await _reserve(client, plan, report)
                await _run_volume_legs(client, plan, report)
                if reservation is not None:
                    try:
                        await client.controller.release_prewarm.call_one(
                            reservation
                        )
                    except Exception:  # noqa: BLE001 - TTL expires it anyway
                        pass
            if direct:
                await _run_local_staging_leg(client, manifest, report)
            if arrays:
                _run_registration_leg(client, plan, arrays, report)
    except Exception as exc:  # noqa: BLE001 - prewarm must never raise.
        # Exception, NOT BaseException: cancellation (the auto hint runs on
        # the put_state_dict path — a caller's wait_for timeout must still
        # cancel it) and interpreter exits propagate.
        _fail(report, "prewarm", exc)
    if plan is not None and plan.device_server:
        from torchstore_tpu.transport import device_transfer as dt

        report["device_server"] = dt.prewarm_engine()
    return report


async def _build_plan(client, manifest, report):
    try:
        with span("provision.plan", entries=len(manifest.entries)):
            await client._ensure_setup()
            strategy = client._strategy
            volume_ids = sorted(client._volume_refs or ())
            if not volume_ids:
                raise RuntimeError("no storage volumes")
            try:
                client_id = strategy.get_client_id()
            except Exception:  # noqa: BLE001 - strategy without env context
                client_id = volume_ids[0]
            put_ids = strategy.select_put_volume_ids(client_id, volume_ids)
            from torchstore_tpu.transport.factory import create_transport_buffer

            transports = {
                vid: create_transport_buffer(
                    client._volume_refs[vid], client._config
                ).transport_name
                for vid in put_ids
            }
            plan = planner.plan_provisioning(
                manifest,
                put_ids,
                transports,
                ici_available=client._config.ici_enabled,
                arena_max_bytes=client._config.arena_max_bytes,
            )
            # Plan-cache handoff: hand the provisioned arena layout to the
            # client so even the FIRST put_state_dict of this working set
            # adopts it verbatim instead of re-deriving the packing.
            plan_cache = getattr(client, "plan_cache", None)
            if plan_cache is not None:
                hint = manifest.arena_hint(client._config.arena_max_bytes)
                if hint is not None:
                    plan_cache.seed(hint["sizes"], hint)
            report["transports"] = transports
            report["planned_bytes"] = plan.planned_bytes
            return plan
    except Exception as exc:  # noqa: BLE001 - cancellation propagates
        _fail(report, "plan", exc)
        return None


async def _reserve(client, plan, report) -> Optional[str]:
    asks = {
        vid: vp.planned_bytes
        for vid, vp in plan.volumes.items()
        if vp.transport == "shm" and vp.planned_bytes
    }
    if not asks:
        return None
    reservation = uuid.uuid4().hex
    try:
        with span("provision.reserve", volumes=len(asks)):
            result = await client.controller.reserve_prewarm.call_one(
                reservation, asks, config=client._config
            )
        grants = result.get("grants", {})
        report["granted_bytes"] = grants
        for vid, reason in (result.get("errors") or {}).items():
            _ERRORS.inc(stage="reserve")
            report["errors"][f"reserve:{vid}"] = reason
        for vid, vp in plan.volumes.items():
            planner.clamp_to_grant(vp, grants.get(vid))
        report["clamped_bytes"] = plan.clamped_bytes
        if plan.clamped_bytes:
            _CLAMPED.inc(plan.clamped_bytes)
            logger.info(
                "prewarm clamped %d bytes to fit capacity grants "
                "(tmpfs headroom)",
                plan.clamped_bytes,
            )
        return reservation
    except Exception as exc:  # noqa: BLE001 - proceed unclamped:
        # the volume-side provision clamps to its own pool cap regardless.
        _fail(report, "reserve", exc)
        return None


async def _run_volume_legs(client, plan, report) -> None:
    async def one(vid: str, vp) -> None:
        volume = client._volume_refs[vid]
        if vp.transport == "shm" and vp.segment_sizes:
            with span(
                "provision.shm", volume=vid, nbytes=vp.planned_bytes
            ):
                result = await volume.actor.provision_shm.call_one(
                    vp.segment_sizes, client._config
                )
            if result.get("error"):
                raise RuntimeError(f"volume {vid}: {result['error']}")
            report["segments"] += result.get("created", 0)
            report["bytes"] += result.get("bytes", 0)
            # The volume clamps to its own pool cap too (its config may be
            # stricter than the controller's grant) — surface both clamps.
            if result.get("clamped_bytes"):
                report["clamped_bytes"] += result["clamped_bytes"]
                _CLAMPED.inc(result["clamped_bytes"])
            _SEGMENTS.inc(result.get("created", 0), leg="shm")
            _BYTES.inc(result.get("bytes", 0), leg="shm")
            names = result.get("names") or []
            if names:
                # Client-side half of the SHM leg: attach the provisioned
                # segments NOW (populate=True) so the first put's offers hit
                # the attachment cache — page-table wiring off the hot path.
                from torchstore_tpu.transport import shared_memory as shm_mod

                with span(
                    "provision.pre_attach", volume=vid, segments=len(names)
                ):
                    # Await into a local FIRST: reading report[...] before
                    # the suspension would lose concurrent legs' updates
                    # under the multi-volume gather.
                    attached = await shm_mod.pre_attach_segments(volume, names)
                report["pre_attached"] = (
                    report.get("pre_attached", 0) + attached
                )
        elif vp.transport == "bulk" and vp.dials:
            from torchstore_tpu.transport import bulk

            with span("provision.bulk", volume=vid, dials=vp.dials):
                n = await bulk.prewarm_connection(
                    volume, client._config, stripes=vp.dials - 1
                )
            report["dials"] += n
            _DIALS.inc(n, leg="bulk")

    items = sorted(plan.volumes.items())
    results = await asyncio.gather(
        *(one(vid, vp) for vid, vp in items), return_exceptions=True
    )
    for (vid, _), result in zip(items, results):
        if isinstance(result, BaseException):
            if not isinstance(result, Exception):
                raise result  # cancellation: propagate, don't report
            _fail(report, f"volume:{vid}", result)


async def _run_local_staging_leg(client, manifest, report) -> None:
    """Pre-create the client-local staging segments a direct-source
    register() will draw (one exact-size segment per request). The creation
    + prefault runs on an executor thread — a model-scale prefault inline
    on the event loop would stall every concurrent RPC/sync."""
    try:
        from torchstore_tpu.provision.pool import local_pool

        config = getattr(client, "_config", None)
        loop = asyncio.get_running_loop()
        with span("provision.local_staging", nbytes=manifest.total_bytes):
            result = await loop.run_in_executor(
                None,
                lambda: local_pool().provision(
                    manifest.segment_sizes(),
                    hugepages=getattr(config, "prewarm_hugepages", True),
                    nthreads=getattr(config, "prewarm_threads", 0),
                ),
            )
        if result.get("error"):
            raise RuntimeError(result["error"])
        report["local_segments"] = result.get("created", 0)
        if result.get("clamped_bytes"):
            report["clamped_bytes"] += result["clamped_bytes"]
            _CLAMPED.inc(result["clamped_bytes"])
        _SEGMENTS.inc(result.get("created", 0), leg="local")
        _BYTES.inc(result.get("bytes", 0), leg="local")
    except Exception as exc:  # noqa: BLE001
        _fail(report, "local_staging", exc)


def _run_registration_leg(client, plan, arrays, report) -> None:
    try:
        from torchstore_tpu.transport import bulk

        registered = 0
        for vid, vp in (plan.volumes if plan is not None else {}).items():
            if vp.transport == "bulk":
                registered += bulk.prewarm_registrations(
                    client._volume_refs[vid], arrays
                )
        report["registrations"] = registered
    except Exception as exc:  # noqa: BLE001
        _fail(report, "registrations", exc)


# ---------------------------------------------------------------------------
# automatic hint path (put_state_dict)
# ---------------------------------------------------------------------------

# Per-client size-signatures already prewarmed this process lifetime: the
# hint fires once per distinct working-set shape, not once per publish.
# Weak client keys cannot survive a fork (children build fresh clients), so
# inherited entries are unreachable garbage at worst, never stale hits.
_auto_seen: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()  # tslint: disable=fork-safety


async def maybe_auto_prewarm(client, flat: dict) -> Optional[dict]:
    """The put_state_dict hint path: derive a manifest from the already-
    flattened dict and provision ahead of the first commit. Gated by
    ``config.prewarm_auto`` and ``prewarm_auto_min_bytes``; fires at most
    once per distinct size-signature per client. Like ``prewarm_manifest``
    it raises only from the device leg."""
    try:
        config = getattr(client, "_config", None)
        if config is None or not getattr(config, "prewarm_auto", False):
            return None
        # Cheap pre-checks BEFORE any manifest construction: an RL loop
        # republishing the same working set every step must pay only this
        # signature computation on its critical path, not per-leaf manifest
        # derivation.
        signature = tuple(
            sorted(
                (key, int(nbytes))
                for key, value in flat.items()
                if isinstance((nbytes := getattr(value, "nbytes", 0)), int)
                and nbytes
            )
        )
        if sum(n for _, n in signature) < config.prewarm_auto_min_bytes:
            return None
        seen = _auto_seen.get(client)
        if seen is None:
            seen = _auto_seen[client] = set()
        if signature in seen:
            return None
        seen.add(signature)
        manifest = StateDictManifest.from_state_dict(flat)
    except Exception as exc:  # noqa: BLE001 - the put must proceed
        _fail(
            {"ok": False, "errors": {}},
            "auto",
            exc,
        )
        return None
    report = await prewarm_manifest(client, manifest)
    logger.info(
        "auto-prewarm: %d entries / %d bytes -> %d segment(s), "
        "%d dial(s)%s",
        report["entries"],
        report["manifest_bytes"],
        report["segments"],
        report["dials"],
        " (with errors)" if report["errors"] else "",
    )
    return report
