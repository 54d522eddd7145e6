"""Transport buffer contract + client-side transport caches.

TPU-native equivalent of /root/reference/torchstore/transport/buffers.py:20-361.
The same five-phase lifecycle makes transports pluggable and independently
testable (SURVEY §5 "distributed communication backend"):

    client                                server (storage volume)
    ------                                -----------------------
    perform_handshake ──RPC──────────────▶ recv_handshake
    _pre_put_hook / _pre_get_hook
    volume.put/get(buffer, metas) ──RPC──▶ handle_put_request /
                                           handle_get_request
    _handle_storage_volume_response ◀─────(buffer rides the response)
    _post_request_success; drop() in finally

The buffer object itself is serialized into the RPC both ways; client-only
references (live arrays, caches) are stripped in ``__getstate__`` by each
implementation.
"""

from __future__ import annotations

import time
from abc import ABC, abstractmethod
from typing import TYPE_CHECKING, Any, Optional

import numpy as np

from torchstore_tpu.logging import get_logger
from torchstore_tpu.observability import ledger as obs_ledger
from torchstore_tpu.observability import metrics as obs_metrics
from torchstore_tpu.observability import recorder as obs_recorder
from torchstore_tpu.observability import timeline as obs_timeline
from torchstore_tpu.observability import tracing
from torchstore_tpu.transport.types import Request
from torchstore_tpu.utils import maybe_await

if TYPE_CHECKING:
    from torchstore_tpu.strategy import StorageVolumeRef

logger = get_logger("torchstore_tpu.transport")

# Per-transport data-plane instruments (client side — where the bytes are
# handed to / received from the wire). Labeled by transport rung + op so one
# snapshot answers "where did the bytes go".
_OPS = obs_metrics.counter(
    "ts_transport_ops_total", "Data-plane transfers by transport and op"
)
_BYTES = obs_metrics.counter(
    "ts_transport_bytes_total",
    "Logical payload bytes handed to / received from each transport",
)
_ERRORS = obs_metrics.counter(
    "ts_transport_errors_total", "Failed transfers by transport and op"
)
_OP_SECONDS = obs_metrics.histogram(
    "ts_transport_op_seconds", "Wall time of one transfer by transport and op"
)

# Data-plane RPCs carry (or wait on) tensor bytes: their deadline must scale
# with payload size or a transfer slower than config.rpc_timeout spuriously
# fails. 50 MB/s is a conservative DCN floor.
MIN_TRANSFER_RATE_BPS = 50e6


def transfer_timeout(base: Optional[float], nbytes: int) -> Optional[float]:
    if base is None or base <= 0:
        return base  # timeouts disabled
    return base + nbytes / MIN_TRANSFER_RATE_BPS


class TransportCache:
    """Base class for per-volume client-side caches (connections, segments,
    registrations). Reference: /root/reference/torchstore/transport/buffers.py:20-38."""

    def delete_key(self, key: str) -> None:  # noqa: B027 - optional hook
        pass

    def clear(self) -> None:  # noqa: B027 - optional hook
        pass


class TransportContext:
    """Type-keyed lazy registry of ``TransportCache`` instances, one per
    client (and one per storage volume server side). Reference:
    /root/reference/torchstore/transport/buffers.py:39-69."""

    def __init__(self) -> None:
        self._caches: dict[type, TransportCache] = {}

    def get_cache(self, cache_cls: type, *args, **kwargs) -> Any:
        cache = self._caches.get(cache_cls)
        if cache is None:
            cache = cache_cls(*args, **kwargs)
            self._caches[cache_cls] = cache
        return cache

    def peek(self, cache_cls: type) -> Any:
        """The cache of this type if one was ever created, else None (stats
        paths must not instantiate caches as a side effect)."""
        return self._caches.get(cache_cls)

    def delete_key(self, key: str) -> None:
        for cache in self._caches.values():
            cache.delete_key(key)

    def clear(self) -> None:
        for cache in self._caches.values():
            cache.clear()
        self._caches.clear()


class TransportBuffer(ABC):
    """One instance per request batch; orchestrates the transfer lifecycle.

    Subclasses implement the hooks; this base drives ordering, error
    propagation and guaranteed resource release (``drop()`` runs in a
    ``finally`` for both success and failure — reference invariant,
    /root/reference/torchstore/transport/buffers.py:196-257).
    """

    requires_handshake: bool = False
    # Rung label for metrics/spans ("shm" | "bulk" | "rpc" | ...).
    transport_name: str = "unknown"
    # Which ops actually need the handshake RPC; transports whose gets are
    # self-describing (SHM descriptors ride the get response) skip the extra
    # round trip by narrowing this to ("put",).
    handshake_ops: tuple = ("put", "get")
    supports_inplace: bool = True
    requires_contiguous_inplace: bool = False
    supports_batch_puts: bool = True
    supports_batch_gets: bool = True
    # Per-key write generations the volume assigned to the last put this
    # buffer carried (set by put_to_storage_volume; forwarded by the client
    # to the controller so stale-replica reclaims can delete conditionally).
    write_gens: "Optional[dict[str, int]]" = None
    # Optional transfer-plan hint from the iteration-stable plan cache
    # (client.put_batch plumbs it): e.g. a precomputed arena layout the
    # transport may adopt instead of recomputing. Transports MUST validate
    # the hint against the actual requests before trusting it.
    plan_hint: "Optional[dict]" = None

    # ---- client-side lifecycle ------------------------------------------

    async def put_to_storage_volume(
        self, volume: "StorageVolumeRef", requests: list[Request]
    ) -> None:
        for req in requests:
            if not req.is_object and req.tensor_val is None:
                raise ValueError(
                    f"put of key {req.key!r} carries no tensor data "
                    "(Shard.data must not be None on puts)"
                )
        nbytes = sum(r.nbytes for r in requests)
        t0 = time.perf_counter()
        try:
            with tracing.span(
                "transport.put",
                transport=self.transport_name,
                volume=volume.volume_id,
                keys=len(requests),
                nbytes=nbytes,
            ):
                if self.requires_handshake and "put" in self.handshake_ops:
                    # Self time (outside what _post_handshake spans) is the
                    # handshake RPC as the client waits for it.
                    with tracing.span("transport.handshake", keys=len(requests)):
                        await self._perform_handshake(
                            volume, requests, op="put"
                        )
                await self._pre_put_hook(volume, requests)
                metas = [r.meta_only() for r in requests]
                put = volume.actor.put
                with tracing.span("transport.put_rpc", keys=len(requests)):
                    reply = await put.with_timeout(
                        transfer_timeout(put._effective_timeout(), nbytes)
                    ).call_one(self, metas)
                if isinstance(reply, dict) and "write_gens" in reply:
                    self.write_gens = reply["write_gens"]
                    reply = reply["reply"]
                self._handle_put_reply(volume, reply, requests)
                self._post_request_success(volume)
            _OPS.inc(transport=self.transport_name, op="put")
            _BYTES.inc(nbytes, transport=self.transport_name, op="put")
            dur = time.perf_counter() - t0
            _OP_SECONDS.observe(
                dur, transport=self.transport_name, op="put"
            )
            # Stage attribution: this lifecycle (handshake -> frames/RPC ->
            # reply) IS the wire leg of a put; replicated puts record one
            # segment per replica, so the stage total carries the real
            # aggregate wire time.
            obs_timeline.observe_stage("put", "transport", dur)
            # Traffic ledger + flight recorder (decision telemetry): the
            # client side of every put knows BOTH endpoints, so this is the
            # count-once choke point the traffic matrix is built from.
            # The enabled check lives HERE (not just inside record) so a
            # disabled ledger skips even the per-key items build.
            if obs_ledger.ledger().enabled:
                obs_ledger.record(
                    self.transport_name,
                    obs_ledger.EGRESS,
                    nbytes,
                    peer_host=volume.hostname or "",
                    volume=volume.volume_id,
                    items=[(r.key, r.nbytes) for r in requests],
                )
            obs_recorder.record(
                "transfer",
                f"put/{self.transport_name}",
                volume=volume.volume_id,
                keys=len(requests),
                nbytes=nbytes,
            )
        except BaseException as exc:
            _ERRORS.inc(transport=self.transport_name, op="put")
            obs_recorder.record(
                "error",
                f"put/{self.transport_name}",
                volume=volume.volume_id,
                error=f"{type(exc).__name__}: {exc}"[:200],
            )
            raise
        finally:
            self.drop()

    async def get_from_storage_volume(
        self, volume: "StorageVolumeRef", requests: list[Request]
    ) -> list[np.ndarray]:
        t0 = time.perf_counter()
        try:
            with tracing.span(
                "transport.get",
                transport=self.transport_name,
                volume=volume.volume_id,
                keys=len(requests),
            ) as sp:
                if self.requires_handshake and "get" in self.handshake_ops:
                    await self._perform_handshake(volume, requests, op="get")
                await self._pre_get_hook(volume, requests)
                metas = [r.meta_only() for r in requests]
                nbytes = sum(
                    m.tensor_meta.nbytes for m in metas if m.tensor_meta is not None
                )
                sp.set(nbytes=nbytes)
                get = volume.actor.get
                remote = await get.with_timeout(
                    transfer_timeout(get._effective_timeout(), nbytes)
                ).call_one(self, metas)
                results = await maybe_await(
                    self._handle_storage_volume_response(volume, remote, requests)
                )
                self._post_request_success(volume)
            _OPS.inc(transport=self.transport_name, op="get")
            _BYTES.inc(nbytes, transport=self.transport_name, op="get")
            dur = time.perf_counter() - t0
            _OP_SECONDS.observe(
                dur, transport=self.transport_name, op="get"
            )
            obs_timeline.observe_stage("get", "transport", dur)
            if obs_ledger.ledger().enabled:
                obs_ledger.record(
                    self.transport_name,
                    obs_ledger.INGRESS,
                    nbytes,
                    peer_host=volume.hostname or "",
                    volume=volume.volume_id,
                    items=[
                        (
                            m.key,
                            m.tensor_meta.nbytes
                            if m.tensor_meta is not None
                            else 0,
                        )
                        for m in metas
                    ],
                )
            obs_recorder.record(
                "transfer",
                f"get/{self.transport_name}",
                volume=volume.volume_id,
                keys=len(requests),
                nbytes=nbytes,
            )
            return results
        except BaseException as exc:
            _ERRORS.inc(transport=self.transport_name, op="get")
            obs_recorder.record(
                "error",
                f"get/{self.transport_name}",
                volume=volume.volume_id,
                error=f"{type(exc).__name__}: {exc}"[:200],
            )
            raise
        finally:
            self.drop()

    async def _perform_handshake(
        self, volume: "StorageVolumeRef", requests: list[Request], op: str
    ) -> None:
        self._pre_handshake(volume, requests, op)
        metas = [r.meta_only() for r in requests]
        reply = await volume.actor.handshake.call_one(self, metas, op)
        # May be a coroutine: the SHM buffer lands its post-handshake
        # segment copies through the overlap pool instead of serially on
        # the event loop thread.
        await maybe_await(self._post_handshake(volume, requests, reply, op))

    # ---- hooks (client) --------------------------------------------------

    def _pre_handshake(self, volume, requests, op) -> None:  # noqa: B027
        pass

    def _post_handshake(self, volume, requests, reply, op) -> None:  # noqa: B027
        pass

    async def _pre_put_hook(self, volume, requests) -> None:  # noqa: B027
        pass

    async def _pre_get_hook(self, volume, requests) -> None:  # noqa: B027
        pass

    @abstractmethod
    def _handle_storage_volume_response(
        self, volume, remote: "TransportBuffer", requests: list[Request]
    ) -> list[np.ndarray]:
        """Land fetched data: into destination views when attached, else
        return fresh arrays, in request order."""

    def _handle_put_reply(self, volume, reply, requests) -> None:  # noqa: B027
        """Process the server's (small, picklable) put reply — e.g. segment
        renames a client cache must adopt. ``reply`` is ``put_reply()``'s
        return value from the server-side buffer instance."""

    def _post_request_success(self, volume) -> None:  # noqa: B027
        """Promote any handshake-scoped resources to the reusable cache —
        only reached on success, so failed requests cannot poison caches
        (reference invariant 5, SURVEY §2.2)."""

    def drop(self) -> None:  # noqa: B027
        """Release pinned/staged resources; safe to call multiple times."""

    # ---- hooks (server side, run inside the storage-volume process) ------

    def recv_handshake(
        self, ctx: TransportContext, metas: list[Request], existing: dict, op: str
    ) -> Any:
        """Server-side handshake step; returns a (picklable) reply. May be a
        coroutine (socket-backed transports await IO inside the volume's
        event loop)."""
        return None

    @abstractmethod
    def handle_put_request(
        self, ctx: TransportContext, metas: list[Request], existing: dict[str, Any]
    ) -> dict[int, np.ndarray]:
        """Materialize incoming data server-side: returns {request_index:
        host array} for the store to keep (may be a coroutine). ``existing``
        maps request index -> previously stored array for in-place reuse
        (invariant 6)."""

    def put_reply(self):
        """Small picklable reply returned to the client after a put lands
        (rides the put RPC response; must never carry tensor bytes)."""
        return None

    @abstractmethod
    def handle_get_request(
        self, ctx: TransportContext, metas: list[Request], entries: list[Any]
    ) -> None:
        """Load outgoing data into this buffer server-side (may be a
        coroutine). ``entries`` are the store's arrays/objects in request
        order."""
