"""Direct (one-hop) weight sync: dest pulls straight from the source's
registered buffers — the store carries only metadata handles.

TPU re-architecture of /root/reference/torchstore/direct_weight_sync.py
(:46-350). The reference rides ibverbs one-sided RDMA reads of source GPU
memory; TPUs expose no such primitive (SURVEY §7.3), so the same API —
register -> publish handles -> cached transfer plan -> concurrent pull ->
refresh — is kept, with the data path re-based on a source-side **peer
buffer engine**:

- same host: staging buffers live in /dev/shm segments; the dest attaches
  and copies directly (true one-hop, zero intermediary).
- cross host: the source process runs a tiny read server; dests issue
  ranged reads over cached TCP connections (DCN path).

Handles published under ``{key}/rank_{r}`` + ``{key}/num_ranks`` exactly like
the reference (state_dict_utils.py:217-275), so discovery flows through the
normal store.
"""

from __future__ import annotations

import asyncio
import struct
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from torchstore_tpu import sharding as shd
from torchstore_tpu.logging import LatencyTracker, get_logger
from torchstore_tpu.native import copy_into
from torchstore_tpu.observability import metrics as obs_metrics
from torchstore_tpu.observability.tracing import span, trace_enabled
from torchstore_tpu.state_dict_utils import flatten_state_dict
from torchstore_tpu.transport import shared_memory as shm
from torchstore_tpu.transport.types import TensorMeta, TensorSlice
from torchstore_tpu.utils import (
    Box,
    boxes_cover,
    get_destination_view,
    get_hostname,
    intersect_boxes,
)

logger = get_logger("torchstore_tpu.direct")

# Cold-start observability: a first pull that reuses a plan built by
# ``ts.prewarm`` (DirectWeightSyncDest.preplan) counts here — the signal
# that iteration 0 skipped plan construction.
_PLAN_PREWARM_HITS = obs_metrics.counter(
    "ts_prewarm_plan_cache_hits_total",
    "Direct-sync pulls that hit a prewarm-built transfer plan",
)


class PullRaceError(RuntimeError):
    """A direct pull lost its race with concurrent source activity (seqlock
    generation never settled, or tore on both attempts). Transient by
    nature — the state-dict layer retries once with fresh handles."""

_READ_REQ = struct.Struct("<QQQ")  # buffer_id, offset, length
_READ_RESP = struct.Struct("<Q")  # length (0xFFFF.. = error)
_ERR = (1 << 64) - 1
# buffer_id sentinel: "stage the registered device arrays for one pull and
# reply with the transfer uuid" (the ICI rung's control op — each staging
# serves exactly one jax.experimental.transfer pull).
_STAGE_DEVICE = (1 << 64) - 2
# buffer_id sentinel: "materialize the current device arrays into host
# buffers and reply with pickled WeightHandles" — the graceful-degradation
# rung for dests that cannot reconstruct our device shardings (disjoint jax
# worlds / non-coinciding device ids).
_STAGE_HOST = (1 << 64) - 3
# buffer_id sentinel: "reply with the source's current weight generation"
# (seqlock: ODD while a refresh is overwriting the staging buffers, even at
# rest; bumped +2 per publish). Dests read it before and after a host-path
# pull and retry once on change — tear detection for pulls concurrent with
# refreshes (VERDICT r2 item 4).
_GET_GEN = (1 << 64) - 4
_U64 = struct.Struct("<Q")
_2U64 = struct.Struct("<QQ")


# --------------------------------------------------------------------------
# handles
# --------------------------------------------------------------------------


@dataclass
class WeightHandle:
    """Picklable pointer to one registered source shard (the reference's
    RDMAWeightHandle, direct_weight_sync.py:46-58)."""

    buffer_id: int
    hostname: str
    port: int
    shm_name: Optional[str]
    meta: TensorMeta
    tensor_slice: TensorSlice
    source_rank: int


@dataclass
class DeviceEntry:
    """One staged device array in a rank's device-mode publication: where it
    sits in the global tensor (``tensor_slice``) plus how to pull it
    (``spec``). The per-rank analog of the reference's per-rank RDMA handle
    list (/root/reference/torchstore/state_dict_utils.py:217-275) with the
    handle re-based on the XLA transfer engine."""

    flat_key: str
    spec: Any  # transport.device_transfer.DeviceSpec
    tensor_slice: TensorSlice


# --------------------------------------------------------------------------
# source side
# --------------------------------------------------------------------------


class _PeerReadServer:
    """Serves ranged reads of registered buffers over TCP (cross-host path)
    and the device-staging control op (ICI rung)."""

    def __init__(self) -> None:
        self.buffers: dict[int, np.ndarray] = {}
        # Set by the source when device mode is on: () -> transfer uuid.
        self.stage_device_fn = None
        # Set alongside: () -> pickled {flat_key: [WeightHandle]} after
        # materializing current device arrays into host buffers (fallback
        # for dests outside this source's jax world).
        self.stage_host_fn = None
        # () -> current weight generation (seqlock; see _GET_GEN).
        self.gen_fn = lambda: 0
        self._server: Optional[asyncio.AbstractServer] = None
        self.port: Optional[int] = None
        self._writers: set = set()

    async def ensure_started(self) -> int:
        if self._server is None:
            import os

            # Loopback by default; cross-host deployments set
            # TORCHSTORE_TPU_BIND_HOST=0.0.0.0 (+ ADVERTISE_HOST).
            bind = os.environ.get("TORCHSTORE_TPU_BIND_HOST", "127.0.0.1")
            self._server = await asyncio.start_server(self._handle, bind, 0)
            self.port = self._server.sockets[0].getsockname()[1]
        return self.port

    async def _handle(self, reader, writer) -> None:
        from torchstore_tpu.runtime.auth import server_authenticate

        if not await server_authenticate(reader, writer):
            try:
                writer.close()
            except Exception:
                pass
            return
        self._writers.add(writer)
        try:
            while True:
                req = await reader.readexactly(_READ_REQ.size)
                buffer_id, offset, length = _READ_REQ.unpack(req)
                if buffer_id == _GET_GEN:
                    writer.write(
                        _READ_RESP.pack(_U64.size) + _U64.pack(self.gen_fn())
                    )
                    await writer.drain()
                    continue
                if buffer_id == _STAGE_DEVICE:
                    if self.stage_device_fn is None:
                        writer.write(_READ_RESP.pack(_ERR))
                    else:
                        try:
                            uid = self.stage_device_fn()
                        except Exception:
                            # Stage-time failures (e.g. resharded republish
                            # guard) must reach the dest as a refusal, not
                            # a dropped connection.
                            logger.exception("device staging failed")
                            writer.write(_READ_RESP.pack(_ERR))
                        else:
                            # uid + the generation the staged snapshot was
                            # taken at (cross-rank consistency check).
                            writer.write(
                                _READ_RESP.pack(_2U64.size)
                                + _2U64.pack(uid, self.gen_fn())
                            )
                    await writer.drain()
                    continue
                if buffer_id == _STAGE_HOST:
                    if self.stage_host_fn is None:
                        writer.write(_READ_RESP.pack(_ERR))
                    else:
                        try:
                            # D2H of a whole model: off the event loop, or
                            # it would stall every concurrent read/stage op.
                            payload = await asyncio.get_running_loop().run_in_executor(
                                None, self.stage_host_fn
                            )
                        except Exception:
                            logger.exception("host-fallback staging failed")
                            writer.write(_READ_RESP.pack(_ERR))
                        else:
                            writer.write(_READ_RESP.pack(len(payload)))
                            writer.write(payload)
                    await writer.drain()
                    continue
                arr = self.buffers.get(buffer_id)
                if arr is None:
                    writer.write(_READ_RESP.pack(_ERR))
                    await writer.drain()
                    continue
                flat = arr.reshape(-1).view(np.uint8)
                chunk = flat[offset : offset + length]
                writer.write(_READ_RESP.pack(chunk.nbytes))
                writer.write(memoryview(chunk))
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass
        finally:
            self._writers.discard(writer)
            try:
                writer.close()
            except Exception:
                pass

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            # Close live client connections first: py3.12's wait_closed()
            # waits for handlers, which would otherwise block forever.
            for writer in list(self._writers):
                try:
                    writer.close()
                except Exception:
                    pass
            try:
                await asyncio.wait_for(self._server.wait_closed(), timeout=2.0)
            except asyncio.TimeoutError:
                pass
            self._server = None


def _stage_copy(staged: np.ndarray, host_arr: np.ndarray) -> None:
    """One host copy into a published staging buffer."""
    with span("direct.stage_copy", nbytes=host_arr.nbytes):
        copy_into(staged, host_arr)


class DirectWeightSyncSource:
    """Registers a state dict's shards into pull-able staging buffers.

    ``register`` stages every shard once (device->host copy + optional dtype
    cast, reference staging-buffer pattern direct_weight_sync.py:99-156);
    ``refresh`` re-copies current values into the SAME buffers so published
    handles stay valid across training steps (direct_weight_sync.py:158-169).
    """

    def __init__(self, use_shm: bool = True, config=None, device: Optional[bool] = None):
        from torchstore_tpu.config import default_config

        self.use_shm = use_shm and shm.is_available()
        self.config = config or default_config()
        # None = auto (device path when eligible); False pins the host path.
        self.device = device
        self.server = _PeerReadServer()
        self.segments: dict[int, shm.ShmSegment] = {}
        self.handles: dict[str, list[WeightHandle]] = {}
        self._sources: dict[str, Any] = {}  # flat_key -> live array/jax ref
        self._transfer_dtype = None
        self._next_id = 0
        self._registered = False
        self._mapping: Optional[dict] = None
        self._flat_template: dict[str, Any] = {}
        # Device (ICI) mode state: ordered flat keys + current jax arrays.
        self.device_info: Optional[dict] = None
        self._device_keys: list[str] = []
        self._device_arrays: dict[str, Any] = {}
        self._device_counts: dict[str, int] = {}
        # entry index -> reusable host-fallback buffer id (_stage_host_handles).
        self._host_fallback_ids: dict[int, int] = {}
        self._advertise: tuple[str, int] = ("", 0)
        # _stage_host_handles runs in the server's executor (off the event
        # loop); concurrent fallback pulls must not race id allocation or
        # buffer refreshes.
        import threading

        self._host_fallback_lock = threading.Lock()
        # Weight generation (seqlock). _gen is the CONTENT generation: even
        # always, +2 per publish (refresh). _busy counts in-flight buffer
        # overwrites (host refresh, fallback staging); gen_fn reports
        # _gen+1 (odd) while any overwrite runs, so dests wait out
        # overwrites and retry when content moved mid-pull. Fallback
        # staging itself never advances _gen — N dests pulling the same
        # content concurrently see one stable generation (no spurious
        # retries / "torn twice"). Mutated from the event loop (refresh)
        # AND the server executor (_stage_host_handles): every access goes
        # through _gen_lock — an unsynchronized `_gen += n` can lose a
        # bump and wedge the parity.
        self._gen = 0
        self._busy = 0
        self._gen_lock = threading.Lock()
        # Host-fallback staging cache: the pickled handle payload + the
        # content generation it materialized. Re-materialization happens
        # only when _gen advanced — concurrent cross-world dests share one
        # D2H staging per publish instead of re-copying the model per pull.
        self._staged_gen: Optional[int] = None
        self._staged_payload: Optional[bytes] = None
        self.server.gen_fn = self._read_gen_locked

    def _read_gen_locked(self) -> int:
        with self._gen_lock:
            return self._gen + 1 if self._busy else self._gen

    def _bump_gen(self, n: int = 2) -> None:
        with self._gen_lock:
            self._gen += n

    def _set_busy(self, on: bool) -> None:
        with self._gen_lock:
            self._busy += 1 if on else -1

    def _device_mode_eligible(self, flat: dict) -> bool:
        """Device path engages when every tensor leaf lives on a device the
        transfer engine serves (``device_transfer.serves`` — not a TPU, on
        this installation): plain jax arrays, or rank-local ``Shard``
        wrappers whose data is a jax array. Rank-independent: each rank of
        a multi-rank SPMD source registers its own per-shard device entries
        (``register``'s rank param — the reference's per-rank handle
        publication pattern, state_dict_utils.py:217-275)."""
        if self.device is False:
            return False
        if not self.config.ici_enabled:
            return False
        from torchstore_tpu.transport import device_transfer as dt

        tensorish = [
            _unwrap_shard(v) for v in flat.values() if _is_tensor_leaf(v)
        ]
        return bool(tensorish) and all(
            shd.is_jax_array(x) and dt.serves(x) for x in tensorish
        )

    async def register(
        self,
        state_dict: Any,
        rank: int = 0,
        transfer_dtype=None,
        num_ranks: int = 1,
    ) -> dict[str, list[WeightHandle]]:
        import os

        port = await self.server.ensure_started()
        self._transfer_dtype = transfer_dtype
        flat, mapping = flatten_state_dict(state_dict)
        self._mapping = mapping
        # Only NON-tensor leaves are kept (staging_state_dict fills tensor
        # keys from the registered buffers); keeping tensor leaves would pin
        # a full copy of the registration-time weights forever.
        self._flat_template = {
            k: v for k, v in flat.items() if not _is_tensor_leaf(v)
        }
        # Advertise the same reachable name the actor runtime uses.
        hostname = os.environ.get("TORCHSTORE_TPU_ADVERTISE_HOST", get_hostname())
        if self._device_mode_eligible(flat):
            return self._register_device(flat, hostname, port, transfer_dtype, rank)
        for flat_key, value in flat.items():
            if (
                transfer_dtype is not None
                and shd.is_jax_array(value)
                and _is_floating(value)
            ):
                # Cast on device (ops.device_cast: one fused XLA kernel)
                # so the HBM->host copy moves the transfer dtype's bytes.
                from torchstore_tpu.ops import device_cast

                value = device_cast(value, transfer_dtype)
            shards = self._shards_of(value)
            if shards is None:
                continue  # non-tensor leaves don't take the direct path
            self._sources[flat_key] = value
            handle_list: list[WeightHandle] = []
            for ts_slice, host_arr in shards:
                if (
                    transfer_dtype is not None
                    and _is_floating(host_arr)
                    and host_arr.dtype != np.dtype(transfer_dtype)
                ):
                    host_arr = host_arr.astype(transfer_dtype)
                host_arr = np.ascontiguousarray(host_arr)
                buffer_id = self._next_id
                self._next_id += 1
                shm_name = None
                if self.use_shm:
                    # Prewarmed staging: an exact-size pre-faulted segment
                    # from the client-local pool (ts.prewarm direct=True)
                    # skips the cold create+zero on the first publish.
                    from torchstore_tpu.provision.pool import local_pool

                    seg = local_pool().take(max(host_arr.nbytes, 1))
                    if seg is None:
                        seg = shm.ShmSegment.create(max(host_arr.nbytes, 1))
                    # WRITER side: this module publishes the generation
                    # seqlock that brackets these staging writes (readers
                    # validate against it) — not an unstamped read.
                    staged = seg.view(TensorMeta.of(host_arr))  # tslint: disable=one-sided-discipline
                    _stage_copy(staged, host_arr)
                    self.segments[buffer_id] = seg
                    self.server.buffers[buffer_id] = staged
                    shm_name = seg.name
                else:
                    self.server.buffers[buffer_id] = host_arr.copy()
                handle_list.append(
                    WeightHandle(
                        buffer_id=buffer_id,
                        hostname=hostname,
                        port=port,
                        shm_name=shm_name,
                        meta=TensorMeta.of(host_arr),
                        tensor_slice=ts_slice,
                        source_rank=rank,
                    )
                )
            self.handles[flat_key] = handle_list
        self._registered = True
        return self.handles

    def _register_device(
        self, flat: dict, hostname: str, port: int, transfer_dtype, rank: int
    ) -> dict:
        """ICI rung registration: no host staging at all. Arrays stay on
        device; every dest pull stages the CURRENT arrays through the XLA
        transfer server (device-to-device over ICI/DCN — the reference's
        one-sided GPU read, monarch_rdma.py:158-219, without host bounce).
        Each rank of a multi-rank SPMD source registers independently and
        publishes its own entries under ``key/rank_{r}``; the dest's plan
        merges all ranks' parts."""
        from torchstore_tpu.transport import device_transfer as dt

        engine = dt.DeviceTransferEngine.get()
        self._device_keys = []
        self._device_arrays = {}
        self._device_counts = {}
        entries: list[DeviceEntry] = []
        for flat_key, value in flat.items():
            if not _is_tensor_leaf(value):
                continue
            self._device_keys.append(flat_key)
            self._device_arrays[flat_key] = value  # uncast; cast at stage time
            parts = _device_parts(_cast_device_value(value, transfer_dtype))
            self._device_counts[flat_key] = len(parts)
            for ts_slice, arr in parts:
                entries.append(
                    DeviceEntry(
                        flat_key=flat_key,
                        spec=dt.DeviceSpec.of(arr),
                        tensor_slice=ts_slice,
                    )
                )
        address = engine.ensure_server()
        self.server.stage_device_fn = self._stage_current
        self.server.stage_host_fn = self._stage_host_handles
        self._advertise = (hostname, port)
        self.device_info = {
            "address": address,
            "hostname": hostname,
            "control_port": port,
            "keys": list(self._device_keys),
            "entries": entries,
            "source_rank": rank,
        }
        self._registered = True
        self.handles = {}
        logger.info(
            "direct sync rank %d registered %d tensors (%d device entries) "
            "on the device (ICI) path",
            rank,
            len(self._device_keys),
            len(entries),
        )
        return self.handles

    def _current_device_parts(self) -> list[tuple[str, TensorSlice, Any]]:
        """(flat_key, global slice, device array) for the CURRENT values, in
        registration order — validated one-to-one against the PUBLISHED
        entries (spec AND placement, not just count): a republish that
        reshards a param without re-registering would otherwise stage
        arrays the dest lands at stale offsets — silent corruption."""
        from torchstore_tpu.transport import device_transfer as dt

        out: list[tuple[str, TensorSlice, Any]] = []
        entries = self.device_info["entries"]
        idx = 0
        # Local ref: update_sources swaps the dict atomically; holding one
        # reference keeps this pass consistent even from an executor thread.
        arrays = self._device_arrays
        for key in self._device_keys:
            parts = _device_parts(
                _cast_device_value(arrays[key], self._transfer_dtype)
            )
            if len(parts) != self._device_counts[key]:
                raise ValueError(
                    f"device refresh of {key!r}: value now decomposes into "
                    f"{len(parts)} parts but {self._device_counts[key]} were "
                    "registered — re-register after changing a param's "
                    "sharding"
                )
            for ts_slice, arr in parts:
                reg = entries[idx]
                idx += 1
                if (
                    reg.tensor_slice != ts_slice
                    or reg.spec != dt.DeviceSpec.of(arr)
                ):
                    raise ValueError(
                        f"device refresh of {key!r}: current value's "
                        "sharding/placement differs from the published "
                        "entries — re-register (publish under a fresh key "
                        "or restart the source) after changing a param's "
                        "sharding"
                    )
                out.append((key, ts_slice, arr))
        return out

    def _stage_current(self) -> int:
        from torchstore_tpu.transport import device_transfer as dt

        engine = dt.DeviceTransferEngine.get()
        return engine.stage([arr for _, _, arr in self._current_device_parts()])

    def _stage_host_handles(self) -> bytes:
        """Materialize the current device arrays into host buffers and return
        pickled ``{flat_key: [WeightHandle]}`` — serves dests whose jax world
        does not contain our device ids (they then read over the normal host
        TCP path). Runs in the server's executor; _host_fallback_lock
        serializes concurrent fallback pulls (unlocked, two threads could
        allocate the same buffer id for different tensors — silent weight
        swaps for same-shape params).

        The staging is cached per content generation: concurrent dests at
        the same generation share ONE D2H materialization and observe a
        stable (even) generation throughout — staging never bumps _gen, so
        N generators fanning out over one source cannot trip each other's
        tear detection. Buffers are only overwritten after a publish
        advanced _gen; a dest mid-read then sees the busy (odd) marker or
        the new generation and retries, exactly as for a host-path
        refresh."""
        with self._host_fallback_lock:
            for _ in range(3):
                with self._gen_lock:
                    gen0 = self._gen
                if self._staged_gen == gen0 and self._staged_payload is not None:
                    return self._staged_payload
                self._set_busy(True)
                try:
                    payload = self._materialize_host_handles()
                finally:
                    self._set_busy(False)
                with self._gen_lock:
                    settled = self._gen == gen0
                self._staged_gen = gen0
                self._staged_payload = payload
                if settled:
                    return payload
                # A publish landed mid-materialization: the staged snapshot
                # is a consistent view of SOME step but tagged stale — loop
                # to restage the fresh content (bounded; a publisher hotter
                # than the loop still gets a consistent, slightly stale
                # payload, which the dest-side gen check resolves).
            return payload

    def _materialize_host_handles(self) -> bytes:
        import pickle

        hostname, port = self._advertise
        handles: dict[str, list[WeightHandle]] = {}
        for idx, (flat_key, ts_slice, arr) in enumerate(
            self._current_device_parts()
        ):
            with span("d2h.wait", nbytes=arr.nbytes):
                host_arr = np.ascontiguousarray(np.asarray(arr))
            buffer_id = self._host_fallback_ids.get(idx)
            if buffer_id is None:
                buffer_id = self._next_id
                self._next_id += 1
                self._host_fallback_ids[idx] = buffer_id
            # Staging-buffer reuse across generations: land the new bytes in
            # the SAME published buffer when layout is unchanged — its pages
            # are already faulted and any warm reader connection keeps
            # serving one stable address (refresh-in-place, like the host
            # path's registered buffers). Seqlock busy/gen markers already
            # fence readers during the overwrite.
            staged = self.server.buffers.get(buffer_id)
            if (
                staged is not None
                and staged.shape == host_arr.shape
                and staged.dtype == host_arr.dtype
            ):
                _stage_copy(staged, host_arr)
                host_arr = staged
            else:
                # np.asarray of a jax array is a READ-ONLY view of the
                # array's cached host copy: the staging buffer the next
                # generation lands in must be a writable copy of its own.
                host_arr = self.server.buffers[buffer_id] = host_arr.copy()
            handles.setdefault(flat_key, []).append(
                WeightHandle(
                    buffer_id=buffer_id,
                    hostname=hostname,
                    port=port,
                    shm_name=None,
                    meta=TensorMeta.of(host_arr),
                    tensor_slice=ts_slice,
                    source_rank=self.device_info["source_rank"],
                )
            )
        return pickle.dumps(handles)

    @staticmethod
    def _shards_of(value) -> Optional[list[tuple[TensorSlice, np.ndarray]]]:
        from torchstore_tpu.client import Shard as _Shard

        if isinstance(value, _Shard):
            # Rank-local shard with explicit global placement (SPMD sources):
            # decompose the data, then re-base its slices into the global
            # space the wrapper describes.
            inner = DirectWeightSyncSource._shards_of(value.data)
            if inner is None:
                return None
            return [
                (_rebase_slice(ts_slice, value.tensor_slice), arr)
                for ts_slice, arr in inner
            ]
        if shd.is_jax_array(value):
            reqs = shd.put_requests("_", value)
            out = []
            for req in reqs:
                if req.tensor_slice is not None:
                    out.append((req.tensor_slice, np.asarray(req.tensor_val)))
                else:
                    arr = np.asarray(req.tensor_val)
                    out.append((_full_slice(arr.shape), arr))
            return out
        if isinstance(value, np.ndarray):
            return [(_full_slice(value.shape), value)]
        return None

    async def refresh(self) -> None:
        """Re-stage current param values into the registered buffers.

        Device (ICI) mode needs no work here: staging happens per pull, so
        dests always read the arrays ``update_sources`` last installed."""
        if not self._registered:
            raise RuntimeError("register() must run before refresh()")
        with span("direct.refresh", device=self.device_info is not None):
            if self.device_info is not None:
                # Device staging snapshots per pull; publish = one stable
                # bump (which also invalidates the host-fallback staging
                # cache).
                self._bump_gen(2)
                return
            self._set_busy(True)  # reported odd while buffers are overwritten
            try:
                await self._refresh_host()
            finally:
                self._bump_gen(2)
                self._set_busy(False)

    async def _refresh_host(self) -> None:
        for flat_key, value in self._sources.items():
            if (
                self._transfer_dtype is not None
                and shd.is_jax_array(value)
                and _is_floating(value)
            ):
                from torchstore_tpu.ops import device_cast

                value = device_cast(value, self._transfer_dtype)
            shards = self._shards_of(value)
            handles = self.handles[flat_key]
            if shards is None or len(shards) != len(handles):
                raise ValueError(
                    f"refresh of {flat_key!r}: value now produces "
                    f"{0 if shards is None else len(shards)} shards but "
                    f"{len(handles)} buffers were registered — re-register "
                    "after changing a param's sharding"
                )
            for (_, host_arr), handle in zip(shards, handles):
                if (
                    self._transfer_dtype is not None
                    and _is_floating(host_arr)
                    and host_arr.dtype != np.dtype(self._transfer_dtype)
                ):
                    host_arr = host_arr.astype(self._transfer_dtype)
                staged = self.server.buffers[handle.buffer_id]
                if _aliases(staged, host_arr):
                    # Registered-buffer sources (staging_state_dict) write
                    # weights straight into the published buffers — the
                    # refresh copy vanishes, matching RDMA's register-once
                    # read-live semantics.
                    continue
                _stage_copy(staged, np.ascontiguousarray(host_arr))

    def staging_state_dict(self) -> Optional[Any]:
        """The registered staging buffers in the ORIGINAL state-dict
        structure (host path, unsharded sources only). A trainer that
        writes its weights directly into these arrays makes every
        subsequent direct put a pure metadata publish — zero source-side
        copies, the host analog of RDMA registered memory
        (/root/reference/torchstore/direct_weight_sync.py:99-156 registers
        buffers once; here the caller may adopt them as its own weight
        storage). Returns None when any source is sharded/device-resident
        (device sources already sync copy-free via the ICI path)."""
        if (
            not self._registered
            or self.device_info is not None
            or self._mapping is None
        ):
            return None
        from torchstore_tpu.state_dict_utils import unflatten_state_dict

        flat = dict(self._flat_template)  # non-tensor leaves as registered
        for flat_key, handles in self.handles.items():
            if len(handles) != 1 or not handles[0].tensor_slice.is_full():
                return None
            flat[flat_key] = self.server.buffers[handles[0].buffer_id]
        return unflatten_state_dict(flat, self._mapping)

    def update_sources(self, state_dict: Any) -> None:
        """Point refresh() at new param objects (jax arrays are immutable, so
        each train step produces fresh arrays — functional-update analog of
        the reference's in-place staging refresh)."""
        flat, _ = flatten_state_dict(state_dict)
        for key in self._sources:
            self._sources[key] = flat[key]
        if self._device_keys:
            # Atomic whole-dict swap: _stage_host_handles reads this from an
            # executor thread; per-key mutation could hand it a torn
            # old/new mix across keys.
            self._device_arrays = {
                key: flat[key] for key in self._device_keys
            }

    async def close(self) -> None:
        await self.server.stop()
        for seg in self.segments.values():
            seg.unlink()
        self.segments.clear()
        self.server.buffers.clear()


def _full_slice(shape) -> TensorSlice:
    return TensorSlice(
        offsets=(0,) * len(shape),
        local_shape=tuple(shape),
        global_shape=tuple(shape),
        coordinates=(),
        mesh_shape=(),
    )


def _rebase_slice(inner: TensorSlice, base: TensorSlice) -> TensorSlice:
    """``inner`` (a slice of the rank-local data) re-based into the global
    space ``base`` places that data in."""
    return TensorSlice(
        offsets=tuple(o + bo for o, bo in zip(inner.offsets, base.offsets)),
        local_shape=inner.local_shape,
        global_shape=base.global_shape,
        coordinates=inner.coordinates,
        mesh_shape=inner.mesh_shape,
    )


def _unwrap_shard(value):
    from torchstore_tpu.client import Shard as _Shard

    return value.data if isinstance(value, _Shard) else value


def _cast_device_value(value, transfer_dtype):
    """On-device cast of a device-mode leaf (or its Shard data) to the
    transfer dtype; identity when no cast applies."""
    if transfer_dtype is None:
        return value
    from torchstore_tpu.client import Shard as _Shard

    if isinstance(value, _Shard):
        data = _cast_device_value(value.data, transfer_dtype)
        return value if data is value else _Shard(data, value.tensor_slice)
    if shd.is_jax_array(value) and _is_floating(value):
        from torchstore_tpu.ops import device_cast

        return device_cast(value, transfer_dtype)
    return value


def _device_parts(value) -> list[tuple[TensorSlice, Any]]:
    """Decompose one device-mode leaf into (global TensorSlice, device
    array) staging parts:

    - fully-addressable jax array: ONE part, the array itself (whole-array
      staging keeps its mesh sharding — the single-controller fast shape);
    - non-fully-addressable (true multi-controller SPMD): one part per
      addressable shard, each a committed single-device array placed by its
      shard index in the global space;
    - ``Shard`` wrapper: the data's parts re-based into the wrapper's global
      space (mp.spawn-style SPMD where each rank owns a disjoint device
      subset)."""
    from torchstore_tpu.client import Shard as _Shard

    if isinstance(value, _Shard):
        return [
            (_rebase_slice(ts_slice, value.tensor_slice), arr)
            for ts_slice, arr in _device_parts(value.data)
        ]
    if value.is_fully_addressable:
        return [(_full_slice(value.shape), value)]
    global_shape = tuple(int(s) for s in value.shape)
    out = []
    seen: set[tuple[int, ...]] = set()
    for shard in value.addressable_shards:
        offsets = tuple(int(sl.start or 0) for sl in shard.index)
        if offsets in seen:
            continue  # replicated-across-local-devices: stage one copy
        seen.add(offsets)
        out.append(
            (
                TensorSlice(
                    offsets=offsets,
                    local_shape=tuple(int(s) for s in shard.data.shape),
                    global_shape=global_shape,
                    coordinates=(),
                    mesh_shape=(),
                ),
                shard.data,
            )
        )
    return out


def _aliases(a: np.ndarray, b: np.ndarray) -> bool:
    """Same memory AND same interpretation. Layout must match too: a
    transposed/reinterpreted view of the staging buffer is a real publish
    request (the transform must be materialized), not an alias to skip."""
    try:
        return (
            a.__array_interface__["data"][0] == b.__array_interface__["data"][0]
            and a.nbytes == b.nbytes
            and a.shape == b.shape
            and a.dtype == b.dtype
            and a.strides == b.strides
        )
    except (AttributeError, TypeError):
        return False


def _is_floating(arr) -> bool:
    return np.issubdtype(np.asarray(arr).dtype, np.floating) or "bfloat16" in str(
        getattr(arr, "dtype", "")
    )


# --------------------------------------------------------------------------
# dest side
# --------------------------------------------------------------------------


@dataclass
class _TransferOp:
    """One planned read: pull ``handle``'s bytes, slice-copy into every dest
    region it overlaps (reference plan semantics,
    direct_weight_sync.py:221-317)."""

    flat_key: str
    handle: WeightHandle
    region: Box  # global region this op covers


class DirectWeightSyncDest:
    def __init__(self, pool_size: int = 4) -> None:
        self.pool_size = pool_size
        self._plan: Optional[list[_TransferOp]] = None
        self._plan_sig: Optional[tuple] = None
        self._conns: dict[tuple[str, int], dict] = {}
        self._segments: dict[str, shm.ShmSegment] = {}
        self._lock = asyncio.Lock()
        # Set by preplan() (the ts.prewarm transfer-plan precompute); the
        # first pull that reuses the preplanned plan counts a cache hit.
        self._preplanned = False

    # ---- plan -------------------------------------------------------------

    def _build_plan(
        self,
        all_handles: dict[str, list[WeightHandle]],
        dest_flat: dict[str, Any],
    ) -> list[_TransferOp]:
        plan: list[_TransferOp] = []
        for flat_key, target in dest_flat.items():
            if not _is_tensor_like(target):
                continue
            handles = all_handles.get(flat_key)
            if handles is None:
                raise KeyError(
                    f"dest state dict expects {flat_key!r} but the source "
                    "published no handle for it"
                )
            for want in _target_slices(target):
                covered: set[Box] = set()
                covered_elems = 0
                for handle in handles:
                    inter = intersect_boxes(handle.tensor_slice.box, want.box)
                    if inter is None or inter in covered:
                        continue  # replicated-shard dedup (reference :247-261)
                    covered.add(inter)
                    covered_elems += inter.size
                    plan.append(_TransferOp(flat_key, handle, inter))
                if covered_elems < want.box.size:
                    # Returning np.empty garbage for uncovered regions would
                    # silently corrupt weights — fail loudly instead.
                    raise ValueError(
                        f"source shards cover only {covered_elems} of "
                        f"{want.box.size} elements of {flat_key!r} region "
                        f"{want.box}"
                    )
        return plan

    # ---- pull -------------------------------------------------------------

    async def pull(
        self,
        all_handles: dict[str, list[WeightHandle]],
        dest_state_dict: Any,
        key_order: Optional[list] = None,
        on_layer=None,
    ) -> Any:
        """Concurrently pull every planned region and rebuild the dest dict,
        seqlock-validated against concurrent source refreshes: source
        generations are read before and after the data moves, and the pull
        retries ONCE when any source refreshed mid-flight (a retry fully
        overwrites in-place landings). The plan is cached and reused while
        the handle/dest signature is unchanged (reference cached-plan
        invariant).

        ``key_order`` (model-forward order) serializes the pull into
        per-key waves so the FIRST layers land first, with
        ``on_layer(flat_key, value)`` (sync or async) invoked as each key
        completes — the consumer's forward pass starts before the last
        layer lands. Note the seqlock re-check still happens at the END of
        the full pull: on_layer consumers must treat served layers as
        tentative until pull returns (a raced refresh retries the whole
        pull and re-serves every layer)."""
        endpoints = sorted(
            {
                (h.hostname, h.port)
                for handle_list in all_handles.values()
                for h in handle_list
            }
        )
        async def pull_once(attempt: int) -> Any:
            with span("direct.pull", attempt=attempt):
                return await self._pull_once(
                    all_handles, dest_state_dict, key_order, on_layer
                )

        gens0 = None
        for attempt in (0, 1):
            try:
                gens0 = await self._stable_gens(endpoints)
            except KeyError:
                # Pre-generation source (or server without the op): serve
                # the pull unchecked rather than failing it.
                return await pull_once(attempt)
            result = await pull_once(attempt)
            gens1 = list(
                await asyncio.gather(
                    *(self._read_gen(h, p) for h, p in endpoints)
                )
            )
            if gens1 == gens0:
                return result
            logger.info(
                "direct pull raced a source refresh (gens %s -> %s); "
                "retrying once",
                gens0,
                gens1,
            )
        raise PullRaceError(
            "direct pull torn twice by concurrent source refreshes — "
            "throttle publishes or pull between refreshes"
        )

    async def _read_gen(self, hostname: str, port: int) -> int:
        (gen,) = _U64.unpack(
            await self._control_op(hostname, port, _GET_GEN)
        )
        return gen

    async def _stable_gens(self, endpoints) -> list:
        """Every source's generation once none is mid-overwrite (odd).

        The wait scales to ``config.direct_settle_timeout`` (default 30 s,
        env ``TORCHSTORE_TPU_DIRECT_SETTLE_TIMEOUT``): a model-scale host
        refresh or another dest's fallback D2H staging legitimately holds
        the generation odd for seconds."""
        import time

        from torchstore_tpu.config import default_config

        deadline = time.monotonic() + default_config().direct_settle_timeout
        delay = 0.02
        while True:
            gens = list(
                await asyncio.gather(
                    *(self._read_gen(h, p) for h, p in endpoints)
                )
            )
            if all(g % 2 == 0 for g in gens):
                return gens
            if time.monotonic() >= deadline:
                raise PullRaceError(
                    "source refresh never settled (generation stayed odd "
                    f"for {default_config().direct_settle_timeout:.0f}s) — "
                    "source wedged mid-refresh?"
                )
            await asyncio.sleep(delay)
            delay = min(delay * 1.5, 0.25)

    @staticmethod
    def _plan_signature(
        all_handles: dict[str, list[WeightHandle]], dest_flat: dict[str, Any]
    ) -> tuple:
        # The signature must cover the dest layouts, not just key names — a
        # changed target sharding must rebuild the plan (and re-run its
        # coverage validation), never reuse a stale one.
        target_sig = tuple(
            sorted(
                (
                    k,
                    tuple(
                        (ts.offsets, ts.local_shape, ts.global_shape)
                        for ts in _target_slices(v)
                    ),
                )
                for k, v in dest_flat.items()
                if _is_tensor_like(v)
            )
        )
        handle_sig = tuple(
            sorted(
                (
                    k,
                    tuple(
                        sorted(
                            (h.tensor_slice.offsets, h.tensor_slice.local_shape)
                            for h in v
                        )
                    ),
                )
                for k, v in all_handles.items()
            )
        )
        return (handle_sig, target_sig)

    def _ensure_plan(
        self,
        all_handles: dict[str, list[WeightHandle]],
        dest_flat: dict[str, Any],
    ) -> bool:
        """Build (or reuse) the transfer plan for this handle/target pair;
        returns True when the cached plan was reused."""
        sig = self._plan_signature(all_handles, dest_flat)
        if self._plan is not None and self._plan_sig == sig:
            return True
        self._plan = self._build_plan(all_handles, dest_flat)
        self._plan_sig = sig
        return False

    async def preplan(
        self,
        all_handles: dict[str, list[WeightHandle]],
        dest_state_dict: Any,
    ) -> dict:
        """Transfer-plan precompute (the ts.prewarm hook for the direct
        path): build + cache the plan, pre-dial every source endpoint's
        first connection, and pre-attach same-host SHM staging segments —
        so iteration 0 of acquire() pays only the data movement. Failures
        are per-resource and advisory (the lazy path re-dials/attaches as
        before); the plan itself raises on genuine coverage errors so a
        misconfigured dest fails at prewarm time rather than mid-sync."""
        dest_flat, _ = flatten_state_dict(dest_state_dict)
        reused = self._ensure_plan(all_handles, dest_flat)
        self._preplanned = True
        dials = 0
        dial_errors = 0
        endpoints = sorted(
            {
                (h.hostname, h.port)
                for handle_list in all_handles.values()
                for h in handle_list
            }
        )
        for hostname, port in endpoints:
            host = "127.0.0.1" if hostname == get_hostname() else hostname
            try:
                await self._get_conn(host, port)
                dials += 1
            except Exception:  # noqa: BLE001 - advisory; lazy path re-dials
                dial_errors += 1
        attached = 0
        for handle_list in all_handles.values():
            for h in handle_list:
                if (
                    h.shm_name is None
                    or h.hostname != get_hostname()
                    or h.shm_name in self._segments
                ):
                    continue
                try:
                    self._segments[h.shm_name] = shm.ShmSegment.attach(
                        h.shm_name, max(h.meta.nbytes, 1), populate=True
                    )
                    attached += 1
                except OSError:
                    pass  # source gone/re-registered; lazy path resolves
        return {
            "plan_ops": len(self._plan or ()),
            "plan_reused": reused,
            "dials": dials,
            "dial_errors": dial_errors,
            "segments_attached": attached,
        }

    async def _pull_once(
        self,
        all_handles: dict[str, list[WeightHandle]],
        dest_state_dict: Any,
        key_order: Optional[list] = None,
        on_layer=None,
    ) -> Any:
        tracker = LatencyTracker("direct_pull")
        dest_flat, mapping = flatten_state_dict(dest_state_dict)
        reused = self._ensure_plan(all_handles, dest_flat)
        if reused and self._preplanned:
            # Iteration-0 hit on a prewarm-built plan: the cold/steady gap's
            # plan component was paid at prewarm time.
            _PLAN_PREWARM_HITS.inc()
            self._preplanned = False
        tracker.track_step("plan")

        # Host landing buffers per (flat_key, target slice). A numpy target
        # with one full-array slice IS its own landing buffer — ops write
        # straight into destination memory (the reference's exact-match
        # zero-extra-copy path, direct_weight_sync.py:221-247).
        landings: dict[str, list[tuple[TensorSlice, np.ndarray]]] = {}
        inplace_targets: set[str] = set()
        from torchstore_tpu.client import Shard as _Shard

        for flat_key, target in dest_flat.items():
            if not _is_tensor_like(target):
                continue
            wants = _target_slices(target)
            # Shard targets land into their provided buffer; plain ndarray
            # targets into themselves (both in place, no extra copy).
            buf = target.data if isinstance(target, _Shard) else target
            if (
                isinstance(buf, np.ndarray)
                and len(wants) == 1
                and tuple(buf.shape) == wants[0].local_shape
                and buf.flags["C_CONTIGUOUS"]
                and buf.flags["WRITEABLE"]
            ):
                landings[flat_key] = [(wants[0], buf)]
                inplace_targets.add(flat_key)
            else:
                if isinstance(target, _Shard) and target.data is None:
                    # Buffer-less region pull: dtype comes from the source.
                    dtype = all_handles[flat_key][0].meta.np_dtype
                else:
                    dtype = _np_dtype_of(target)
                landings[flat_key] = [
                    (want, np.empty(want.local_shape, dtype)) for want in wants
                ]

        # Each source shard is read ONCE per pull, however many dest regions
        # overlap it — and only the row range its ops actually need (ranged
        # reads cut DCN bytes when a pull touches part of a shard). Keyed by
        # (host, port, buffer_id): buffer ids are per-SOURCE counters, so two
        # ranks' shards share ids and a bare-id key would collapse them.
        by_handle: dict[tuple, tuple[WeightHandle, list[_TransferOp]]] = {}
        for op in self._plan:
            hkey = (op.handle.hostname, op.handle.port, op.handle.buffer_id)
            by_handle.setdefault(hkey, (op.handle, []))[1].append(op)
        row_ranges = {
            hkey: _row_range(handle, ops)
            for hkey, (handle, ops) in by_handle.items()
        }
        out_flat = dict(dest_flat)
        if key_order is not None or on_layer is not None:
            # Ordered per-key waves (layer-streamed consumers): each flat
            # key's shard reads + landings complete before the next key
            # starts, so forward-order consumers see layer k before k+1.
            # A shard feeding several keys is still read ONCE (cached by
            # handle key); keys outside the order are appended after it.
            from torchstore_tpu.utils import maybe_await

            ops_by_key: dict[str, list[_TransferOp]] = {}
            for op in self._plan:
                ops_by_key.setdefault(op.flat_key, []).append(op)
            order = [k for k in (key_order or []) if k in ops_by_key]
            tail = [k for k in ops_by_key if k not in set(order)]
            shard_raws: dict[tuple, tuple] = {}
            ops_bytes = 0
            for flat_key in order + tail:
                need = []
                for op in ops_by_key[flat_key]:
                    hkey = (
                        op.handle.hostname,
                        op.handle.port,
                        op.handle.buffer_id,
                    )
                    if hkey not in shard_raws and hkey not in need:
                        need.append(hkey)
                with span("direct.read", shards=len(need)):
                    reads = await asyncio.gather(
                        *(
                            self._read_shard(by_handle[hk][0], row_ranges[hk])
                            for hk in need
                        )
                    )
                for hk, read in zip(need, reads):
                    shard_raws[hk] = read
                    ops_bytes += read[0].nbytes
                with span("direct.land", key=flat_key) as sp:
                    if trace_enabled():
                        sp.set(
                            nbytes=sum(
                                buf.nbytes for _, buf in landings[flat_key]
                            )
                        )
                    for op in ops_by_key[flat_key]:
                        hkey = (
                            op.handle.hostname,
                            op.handle.port,
                            op.handle.buffer_id,
                        )
                        arr, row0 = shard_raws[hkey]
                        self._apply_op(op, arr, row0, landings)
                parts = landings[flat_key]
                if flat_key in inplace_targets:
                    out_flat[flat_key] = parts[0][1]
                else:
                    out_flat[flat_key] = _rebuild(
                        dest_flat[flat_key], parts
                    )
                if on_layer is not None:
                    await maybe_await(
                        on_layer(flat_key, out_flat[flat_key])
                    )
            tracker.track_step("reads", ops_bytes)
            tracker.track_step("rebuild")
        else:
            with span("direct.read", shards=len(by_handle)):
                reads = await asyncio.gather(
                    *(
                        self._read_shard(handle, row_ranges[hkey])
                        for hkey, (handle, _) in by_handle.items()
                    )
                )
            shard_raws = dict(zip(by_handle.keys(), reads))
            ops_bytes = 0
            with span("direct.land") as sp:
                for hkey, (arr, row0) in shard_raws.items():
                    ops_bytes += arr.nbytes
                    for op in by_handle[hkey][1]:
                        self._apply_op(op, arr, row0, landings)
                sp.set(nbytes=ops_bytes)
            tracker.track_step("reads", ops_bytes)

            for flat_key, parts in landings.items():
                if flat_key in inplace_targets:
                    out_flat[flat_key] = parts[0][1]  # the target array
                else:
                    out_flat[flat_key] = _rebuild(dest_flat[flat_key], parts)
            tracker.track_step("rebuild")
        tracker.log_summary(level=20)
        from torchstore_tpu.state_dict_utils import unflatten_state_dict

        return unflatten_state_dict(out_flat, mapping)

    def _apply_op(
        self, op: _TransferOp, shard_arr: np.ndarray, row0: int, landings
    ) -> None:
        """``shard_arr`` covers shard rows [row0, row0+len) of the handle's
        slice (row0 > 0 for ranged reads)."""
        for want, buf in landings[op.flat_key]:
            inter = intersect_boxes(op.region, want.box)
            if inter is None:
                continue
            shard_offsets = op.handle.tensor_slice.offsets
            rel_src = tuple(
                slice(
                    o - so - (row0 if d == 0 else 0),
                    o - so - (row0 if d == 0 else 0) + s,
                )
                for d, (o, so, s) in enumerate(
                    zip(inter.offsets, shard_offsets, inter.shape)
                )
            )
            view = get_destination_view(
                buf, want.box, inter, require_contiguous=False
            )
            copy_into(view, shard_arr[rel_src])

    async def _get_conn(self, host: str, port: int):
        """A pooled (reader, writer, lock) to a source's peer server — a
        small pool per source so concurrent reads overlap on the wire
        instead of serializing behind one connection."""
        key = (host, port)
        async with self._lock:
            pool = self._conns.get(key)
            if pool is None:
                pool = {"conns": [], "rr": 0}
                self._conns[key] = pool
            if len(pool["conns"]) < self.pool_size:
                reader, writer = await asyncio.wait_for(
                    asyncio.open_connection(host, port), timeout=30
                )
                from torchstore_tpu.runtime.auth import client_authenticate

                await client_authenticate(reader, writer)
                conn = (reader, writer, asyncio.Lock())
                pool["conns"].append(conn)
            else:
                conn = pool["conns"][pool["rr"] % len(pool["conns"])]
                pool["rr"] += 1
        return conn

    # ---- device (ICI) path ------------------------------------------------

    async def pull_device(
        self, device_infos: list[dict], dest_state_dict: Any
    ) -> Any:
        """One-hop device pull across every source rank: ask each rank to
        stage its current arrays, pull them device-to-device through the
        transfer engine, merge the per-rank parts, then land into the dest
        targets (resharding locally where the target sharding differs — XLA
        moves the shards over ICI). Falls back to each rank's host-staging
        control op when the published device shardings reference device ids
        this process cannot see (disjoint jax worlds)."""
        from torchstore_tpu.transport import device_transfer as dt

        tracker = LatencyTracker("direct_pull_device")
        dest_flat, mapping = flatten_state_dict(dest_state_dict)
        # Build every rank's pull specs BEFORE staging anything: a
        # staged-but-never-pulled uuid would pin source arrays in its
        # transfer server. The built shardings are reused for the pull
        # itself (one Mesh construction per entry, not two).
        try:
            built_specs = [
                [e.spec.to_jax() for e in info["entries"]]
                for info in device_infos
            ]
        except ValueError as exc:
            logger.warning(
                "device path unavailable (%s); falling back to source-side "
                "host staging",
                exc,
            )
            all_handles: dict[str, list[WeightHandle]] = {}
            # Ranks materialize independently — fetch concurrently (each
            # rank's D2H staging overlaps instead of serializing).
            fetched = await asyncio.gather(
                *(self._fetch_host_handles(info) for info in device_infos)
            )
            for rank_handles in fetched:
                for flat_key, hl in rank_handles.items():
                    all_handles.setdefault(flat_key, []).extend(hl)
            return await self.pull(all_handles, dest_state_dict)

        engine = dt.DeviceTransferEngine.get()
        parts_by_key: dict[str, list[tuple[TensorSlice, Any]]] = {}
        pulled_bytes = 0
        # Each staged snapshot is internally consistent (immutable arrays
        # captured in one event-loop call), but ranks refresh independently
        # — a pull mixing rank A at step N with rank B at N+1 is torn.
        # Every rank's stage op reports its generation; mixed gens retry
        # the whole pull once. Stage each rank immediately before pulling
        # it: on a mid-sequence failure at most ONE staged uuid is left
        # un-pulled (the engine has no un-stage op).
        for attempt in (0, 1):
            parts_by_key.clear()
            pulled_bytes = 0
            gens = []
            for info, specs in zip(device_infos, built_specs):
                uid, gen = await self._stage_remote(info)
                gens.append(gen)
                entries = info["entries"]
                arrays = engine.pull_built(info["address"], uid, specs)
                for entry, arr in zip(entries, arrays):
                    parts_by_key.setdefault(entry.flat_key, []).append(
                        (entry.tensor_slice, arr)
                    )
                    pulled_bytes += int(np.prod(entry.spec.shape)) * TensorMeta(
                        shape=(), dtype=entry.spec.dtype
                    ).np_dtype.itemsize
            if len(set(gens)) <= 1:
                break
            logger.info(
                "device pull mixed source generations %s; retrying once",
                gens,
            )
        else:
            raise PullRaceError(
                f"device pull mixed source generations twice ({gens}) — "
                "source ranks are publishing out of lockstep"
            )
        tracker.track_step("pull", pulled_bytes)
        out_flat = dict(dest_flat)
        for flat_key, target in dest_flat.items():
            if not _is_tensor_like(target):
                continue
            parts = parts_by_key.get(flat_key)
            if parts is None:
                raise KeyError(
                    f"dest state dict expects {flat_key!r} but no source "
                    "rank published a device entry for it"
                )
            if len(parts) == 1 and parts[0][0].is_full():
                out_flat[flat_key] = _land_device(target, parts[0][1])
            else:
                out_flat[flat_key] = _assemble_device(flat_key, target, parts)
        tracker.track_step("land")
        tracker.log_summary(level=20)
        from torchstore_tpu.state_dict_utils import unflatten_state_dict

        return unflatten_state_dict(out_flat, mapping)

    async def _control_op(self, hostname: str, port: int, opcode: int) -> bytes:
        """One control op against a source's peer server: send the sentinel
        ``opcode``, return the response payload (all control ops share the
        length-prefixed reply shape)."""
        host = "127.0.0.1" if hostname == get_hostname() else hostname
        reader, writer, lock = await self._get_conn(host, port)
        async with lock:
            writer.write(_READ_REQ.pack(opcode, 0, 0))
            await writer.drain()
            (length,) = _READ_RESP.unpack(await reader.readexactly(_READ_RESP.size))
            if length == _ERR:
                raise KeyError(
                    "source refused to stage: no device-mode "
                    "registration, or stage-time validation failed "
                    "(check source logs)"
                )
            return await reader.readexactly(length)

    async def _control_request(self, device_info: dict, opcode: int) -> bytes:
        return await self._control_op(
            device_info["hostname"], device_info["control_port"], opcode
        )

    async def _stage_remote(self, device_info: dict) -> tuple[int, int]:
        """Ask one source rank to stage its current arrays; returns the
        transfer uuid serving exactly this pull plus the source's weight
        generation at staging time (the snapshot's step identity)."""
        uid, gen = _2U64.unpack(
            await self._control_request(device_info, _STAGE_DEVICE)
        )
        return uid, gen

    async def _fetch_host_handles(
        self, device_info: dict
    ) -> dict[str, list[WeightHandle]]:
        """Ask one source rank to materialize its device arrays into host
        buffers; returns the WeightHandles serving them over TCP."""
        import pickle

        return pickle.loads(
            await self._control_request(device_info, _STAGE_HOST)
        )

    async def _read_shard(
        self, handle: WeightHandle, row_range: Optional[tuple[int, int]] = None
    ) -> tuple[np.ndarray, int]:
        """One-hop read of a source buffer: SHM attach on the same host, TCP
        (ranged when ``row_range`` is set) across hosts. Returns
        ``(shard-shaped array rows, first_row)``."""
        shape = handle.meta.shape
        if handle.shm_name is not None and handle.hostname == get_hostname():
            # Attach is free — no transfer to range. The blessed one-sided
            # accessor: the surrounding pull() brackets this read with the
            # source's generation seqlock (_stable_gens before, gens
            # re-read after), so a torn read is detected and retried.
            seg = self._segments.get(handle.shm_name)
            if seg is None:
                seg = shm.ShmSegment.attach(
                    handle.shm_name, max(handle.meta.nbytes, 1), populate=True
                )
                self._segments[handle.shm_name] = seg
            view = shm.segment_read_view(seg, handle.meta)
            return np.asarray(view).reshape(shape), 0
        # Same-host TCP reads dial loopback (the container hostname may not
        # route back to this process); cross-host uses the advertised name.
        host = (
            "127.0.0.1" if handle.hostname == get_hostname() else handle.hostname
        )
        reader, writer, lock = await self._get_conn(host, handle.port)
        row_bytes = (
            handle.meta.nbytes // shape[0] if shape and shape[0] else handle.meta.nbytes
        )
        if row_range is not None and shape:
            r0, r1 = row_range
            offset, want_len = r0 * row_bytes, (r1 - r0) * row_bytes
            out_shape = (r1 - r0,) + tuple(shape[1:])
        else:
            r0, offset, want_len = 0, 0, handle.meta.nbytes
            out_shape = tuple(shape)
        async with lock:
            writer.write(_READ_REQ.pack(handle.buffer_id, offset, want_len))
            await writer.drain()
            (length,) = _READ_RESP.unpack(await reader.readexactly(_READ_RESP.size))
            if length == _ERR:
                raise KeyError(
                    f"source no longer has buffer {handle.buffer_id} "
                    f"(rank {handle.source_rank})"
                )
            raw = await reader.readexactly(length)
        arr = np.frombuffer(bytearray(raw), dtype=handle.meta.np_dtype)
        return arr.reshape(out_shape), r0

    async def close(self) -> None:
        # Under the pool lock: close racing a _get_conn mid-dial would
        # otherwise leak the freshly opened connection past the clear().
        async with self._lock:
            for pool in self._conns.values():
                for _, writer, _ in pool["conns"]:
                    try:
                        writer.close()
                    except Exception:
                        pass
            self._conns.clear()
        for seg in self._segments.values():
            seg.close()
        self._segments.clear()


# --------------------------------------------------------------------------
# helpers shared by plan/pull
# --------------------------------------------------------------------------


def _row_range(
    handle: WeightHandle, ops: list[_TransferOp]
) -> Optional[tuple[int, int]]:
    """Shard-local dim-0 row range covering every op, or None for a full
    read. Ranging applies only when each op's region spans the shard's full
    extent in every trailing dim (then rows are a contiguous byte range —
    the protocol's offset/length supports it directly)."""
    ts = handle.tensor_slice
    if not ts.local_shape:
        return None
    lo, hi = None, None
    for op in ops:
        for d in range(1, len(ts.local_shape)):
            if (
                op.region.offsets[d] != ts.offsets[d]
                or op.region.shape[d] != ts.local_shape[d]
            ):
                return None
        r0 = op.region.offsets[0] - ts.offsets[0]
        r1 = r0 + op.region.shape[0]
        lo = r0 if lo is None else min(lo, r0)
        hi = r1 if hi is None else max(hi, r1)
    if lo == 0 and hi == ts.local_shape[0]:
        return None  # full shard anyway
    return lo, hi


def _is_tensor_like(value) -> bool:
    from torchstore_tpu.client import Shard

    return (
        isinstance(value, (np.ndarray, Shard))
        or shd.is_jax_array(value)
        or shd.is_sharded_spec(value)
        or shd.is_plain_spec(value)
    )


def _is_tensor_leaf(value) -> bool:
    """Source-side leaf classification (register): array-valued leaves,
    including rank-local Shard wrappers (SPMD sources)."""
    from torchstore_tpu.client import Shard as _Shard

    return (
        isinstance(value, (np.ndarray, _Shard)) or shd.is_jax_array(value)
    )


def _assemble_region_on_device(want, parts, dtype, device):
    """Assemble global region ``want`` from overlapping ``parts`` as a
    single-device array on ``device``: each overlap is sliced out of its
    part ON the part's devices (lax.slice), moved with device_put (ICI on
    real hardware), and placed with dynamic_update_slice — peak memory is
    one region plus one overlap piece, never the dense global tensor."""
    import jax
    import jax.numpy as jnp

    out = jax.device_put(jnp.zeros(want.local_shape, dtype), device)
    for ts_slice, arr in parts:
        inter = intersect_boxes(ts_slice.box, want.box)
        if inter is None:
            continue
        starts = [o - so for o, so in zip(inter.offsets, ts_slice.offsets)]
        piece = jax.lax.slice(
            arr, starts, [s + sz for s, sz in zip(starts, inter.shape)]
        )
        piece = jax.device_put(piece, device)
        if piece.dtype != dtype:
            piece = piece.astype(dtype)
        out = jax.lax.dynamic_update_slice(
            out,
            piece,
            tuple(o - wo for o, wo in zip(inter.offsets, want.offsets)),
        )
    return out


def _assemble_device(flat_key: str, target, parts):
    """Assemble a multi-part device pull (per-rank / per-shard entries) into
    one dest target. jax-ish targets assemble ON DEVICE, one target shard
    at a time (no dense single-device copy of the global tensor is ever
    materialized); host targets land each part into its destination
    region. Coverage is validated by exact box union — overlapping or
    replicated parts cannot mask a hole."""
    import jax
    import jax.numpy as jnp

    from torchstore_tpu.client import Shard as _Shard

    # Replicated source shards publish identical regions; pull cost was
    # already paid upstream (dedup at publication), this guards merged
    # multi-rank duplicates.
    seen: set[tuple] = set()
    deduped = []
    for ts_slice, arr in parts:
        sig = (ts_slice.offsets, ts_slice.local_shape)
        if sig in seen:
            continue
        seen.add(sig)
        deduped.append((ts_slice, arr))
    parts = deduped
    global_shape = tuple(parts[0][0].global_shape)
    global_box = Box((0,) * len(global_shape), global_shape)
    if not boxes_cover(global_box, [ts_slice.box for ts_slice, _ in parts]):
        raise ValueError(
            f"source ranks do not cover all of {flat_key!r} "
            f"{global_shape} — missing regions would silently read as zeros"
        )
    if (
        shd.is_jax_array(target)
        or shd.is_sharded_spec(target)
        or shd.is_plain_spec(target)
    ):
        if tuple(target.shape) != global_shape:
            raise ValueError(
                f"pulled global shape {global_shape} != target shape "
                f"{tuple(target.shape)} for {flat_key!r}"
            )
        dtype = jnp.dtype(str(target.dtype))
        sharding = getattr(target, "sharding", None)
        if sharding is not None and not shd._is_demotable(sharding):
            # Shard-wise assembly straight into the target layout.
            shard_list = shd.target_slices(target)
            locals_ = [
                _assemble_region_on_device(want, parts, dtype, dev)
                for dev, want in shard_list
            ]
            return jax.make_array_from_single_device_arrays(
                global_shape, sharding, locals_
            )
        full = _full_slice(global_shape)
        out = _assemble_region_on_device(full, parts, dtype, jax.devices()[0])
        if sharding is not None:
            out = jax.device_put(out, sharding)
        return out
    # Host targets: one want region (Shard → its slice, numpy → full);
    # copy every overlapping part into the destination view.
    (want,) = _target_slices(target)
    buf = target.data if isinstance(target, _Shard) else target
    if buf is None:
        dtype = TensorMeta(shape=(), dtype=parts[0][1].dtype.name).np_dtype
        buf = np.empty(want.local_shape, dtype)
    touched = []
    for ts_slice, arr in parts:
        inter = intersect_boxes(ts_slice.box, want.box)
        if inter is None:
            continue
        host = np.asarray(arr)
        rel_src = tuple(
            slice(o - so, o - so + s)
            for o, so, s in zip(inter.offsets, ts_slice.offsets, inter.shape)
        )
        view = get_destination_view(buf, want.box, inter, require_contiguous=False)
        copy_into(view, host[rel_src])
        touched.append(inter)
    if not boxes_cover(want.box, touched):
        raise ValueError(
            f"source ranks do not cover region {want.box} of {flat_key!r}"
        )
    return buf


def _land_device(target, arr):
    """Land a pulled device array into a dest target: reshard on device for
    jax targets (device_put compiles to ICI collectives), copy to host
    memory for numpy/Shard targets."""
    import jax

    from torchstore_tpu.client import Shard as _Shard

    if (
        shd.is_jax_array(target)
        or shd.is_sharded_spec(target)
        or shd.is_plain_spec(target)
    ):
        if tuple(arr.shape) != tuple(target.shape):
            raise ValueError(
                f"pulled shape {tuple(arr.shape)} != target shape "
                f"{tuple(target.shape)} (source re-published under a "
                "different shape?)"
            )
        want_dtype = getattr(target, "dtype", None)
        if want_dtype is not None and arr.dtype != want_dtype:
            arr = arr.astype(want_dtype)
        sharding = getattr(target, "sharding", None)
        if sharding is not None and sharding != arr.sharding:
            with span("h2d.dispatch", nbytes=arr.nbytes, parts=1):
                arr = jax.device_put(arr, sharding)
        return arr
    if isinstance(target, _Shard):
        region = tuple(
            slice(o, o + s)
            for o, s in zip(target.tensor_slice.offsets, target.tensor_slice.local_shape)
        )
        part = np.asarray(arr[region])
        if target.data is not None:
            copy_into(target.data, part)
            return target.data
        return part
    # numpy target: full copy in place.
    copy_into(target, np.asarray(arr))
    return target


def _np_dtype_of(value) -> np.dtype:
    from torchstore_tpu.client import Shard

    if isinstance(value, Shard):
        value = value.data
    # Avoids materializing jax arrays on host just to learn their dtype.
    return TensorMeta(shape=(), dtype=str(value.dtype)).np_dtype


def _target_slices(value) -> list[TensorSlice]:
    from torchstore_tpu.client import Shard

    if isinstance(value, Shard):
        # Explicit region target: pull only this slice of the global space
        # (SPMD ranks syncing their own shard).
        return [value.tensor_slice]
    if shd.is_jax_array(value) or shd.is_sharded_spec(value):
        return [ts for _, ts in shd.target_slices(value)]
    # numpy arrays and sharding-less ShapeDtypeStructs: one full slice.
    return [_full_slice(value.shape)]


def _rebuild(target, parts: list[tuple[TensorSlice, np.ndarray]]):
    from torchstore_tpu.client import Shard

    if isinstance(target, Shard):
        ((_, arr),) = parts
        if target.data is not None:
            copy_into(target.data, arr)
            return target.data
        return arr
    if shd.is_jax_array(target) or shd.is_sharded_spec(target):
        devs = [dev for dev, _ in shd.target_slices(target)]
        return shd.build_array(target, [(d, arr) for d, (_, arr) in zip(devs, parts)])
    if shd.is_plain_spec(target):
        import jax.numpy as jnp

        ((_, arr),) = parts
        with span("h2d.dispatch", nbytes=arr.nbytes, parts=1):
            return jnp.asarray(arr, dtype=target.dtype)
    # numpy target: single full slice, filled in place.
    ((_, arr),) = parts
    copy_into(target, arr)
    return target
