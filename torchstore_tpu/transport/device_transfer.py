"""Device-path weight sync: the ICI rung.

TPU-native answer to the reference's one-sided RDMA device reads
(/root/reference/torchstore/transport/monarch_rdma.py:158-219, ibverbs reads
of source GPU memory). TPUs expose no raw one-sided read primitive to user
code, but the XLA runtime does: ``jax.experimental.transfer`` starts a
per-process *transfer server* attached to the local backend, and a remote
process can pull staged device arrays directly — device-to-device over the
accelerator fabric (ICI within a pod, DCN across), never touching host
staging buffers. This module wraps that engine as the store's device
transport rung, gated by ``StoreConfig.ici_enabled``.

Protocol (one-shot staging is the engine's contract — each ``await_pull``
uuid serves exactly ONE ``pull``):

    source: engine.ensure_server() -> address; publish handles via the store
    dest:   asks the source to stage a fresh generation (tiny TCP control
            op, see direct_weight_sync) -> uuid
    dest:   conn.pull(uuid, specs_with_source_sharding) -> device arrays
    dest:   reshards locally (jax.device_put) — XLA moves shards over ICI

Because staging happens per pull request, a dest always receives the
source's CURRENT weights with zero host copies on either side.

Scope: single-controller sources stage whole (mesh-sharded) arrays;
multi-rank SPMD sources each run their own transfer server and publish
per-shard entries the dest merges (direct_weight_sync._device_parts).
Sharding descriptors reconstruct by GLOBAL device id, so source and dest
must share a jax world (jax.distributed) or have coinciding device ids
(same-topology slices). When they don't, the dest falls back to the
source-side host-staging control op (_STAGE_HOST) and reads over TCP.

Shardings cannot be pickled across processes (they hold live Device
objects); ``ShardingDescriptor`` round-trips NamedSharding /
SingleDeviceSharding by mesh shape + axis names + device ids, reconstructed
over the destination process's view of the same global device set.
"""

from __future__ import annotations

import os
import uuid as uuid_mod
from dataclasses import dataclass
from typing import Any, Optional

from torchstore_tpu.logging import get_logger
from torchstore_tpu.observability import metrics as obs_metrics
from torchstore_tpu.observability import tracing

logger = get_logger("torchstore_tpu.transport.ici")

_STAGED = obs_metrics.counter(
    "ts_device_staged_total", "Device arrays staged for one-shot remote pulls"
)
_PULL_OPS = obs_metrics.counter(
    "ts_device_pull_ops_total", "Device-to-device pulls through the ICI rung"
)
# Same instruments the host transports feed (transport/buffers.py) — the
# ICI rung reports under transport="ici" so one query covers every rung.
_OPS = obs_metrics.counter(
    "ts_transport_ops_total", "Data-plane transfers by transport and op"
)
_PULL_BYTES = obs_metrics.counter(
    "ts_transport_bytes_total",
    "Logical payload bytes handed to / received from each transport",
)
_OP_SECONDS = obs_metrics.histogram(
    "ts_transport_op_seconds", "Wall time of one transfer by transport and op"
)


# Platforms whose buffers the transfer engine of THIS installation (jax
# 0.9.0, jaxlib 0.9.0, libtpu 0.0.34) serves — decided by runs, not by
# whether the module imports. On a TPU v5 lite chip (my chip run, PR 21) a
# server's pulls complete until 2 GiB have passed through it (16 pulls of a
# 117 MB array; max_num_parallel_copies 8 x transfer_size 256 MiB), then the
# arrays of the next pull never become ready and the caller blocks forever —
# Llama-3-8B-width weights (3.85 GB at 4 layers) hang in their first pull.
# ``use_raw_buffers=True`` changes nothing, and ``supports_pinned_allocator=
# True`` fails at server start ("NOT_FOUND: tcp-zero-copy-mmap: No such
# device"). The CPU client serves without such a limit (tier 1). A TPU
# source therefore takes direct sync's host-staged path.
SERVED_PLATFORMS = frozenset({"cpu"})


def serves(arr) -> bool:
    """Whether the device rung can serve the jax.Array ``arr``: every device
    holding it is of a platform the transfer engine serves."""
    return all(d.platform in SERVED_PLATFORMS for d in arr.sharding.device_set)


# --------------------------------------------------------------------------
# sharding descriptors (picklable)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class ShardingDescriptor:
    """Picklable description of a NamedSharding/SingleDeviceSharding."""

    kind: str  # "named" | "single"
    mesh_shape: tuple[int, ...] = ()
    axis_names: tuple[str, ...] = ()
    device_ids: tuple[int, ...] = ()  # mesh devices flattened, or [device]
    spec: tuple = ()  # PartitionSpec entries (None | str | tuple[str, ...])
    memory_kind: Optional[str] = None

    @classmethod
    def of(cls, sharding) -> "ShardingDescriptor":
        import jax

        if isinstance(sharding, jax.sharding.SingleDeviceSharding):
            (dev,) = sharding.device_set
            return cls(kind="single", device_ids=(dev.id,))
        if isinstance(sharding, jax.sharding.NamedSharding):
            mesh = sharding.mesh
            spec = tuple(
                tuple(p) if isinstance(p, (list, tuple)) else p
                for p in sharding.spec
            )
            return cls(
                kind="named",
                mesh_shape=tuple(mesh.devices.shape),
                axis_names=tuple(mesh.axis_names),
                device_ids=tuple(d.id for d in mesh.devices.flat),
                spec=spec,
                memory_kind=sharding.memory_kind,
            )
        raise TypeError(f"unsupported sharding type {type(sharding).__name__}")

    def build(self):
        """Reconstruct the sharding over THIS process's devices."""
        import numpy as np

        import jax

        by_id = {d.id: d for d in jax.devices()}
        try:
            devices = [by_id[i] for i in self.device_ids]
        except KeyError as exc:
            raise ValueError(
                f"device id {exc} in sharding descriptor is not visible in "
                "this process (device-path sync requires a shared jax world)"
            ) from None
        if self.kind == "single":
            return jax.sharding.SingleDeviceSharding(devices[0])
        mesh = jax.sharding.Mesh(
            np.array(devices, dtype=object).reshape(self.mesh_shape),
            self.axis_names,
        )
        spec = jax.sharding.PartitionSpec(*self.spec)
        if self.memory_kind is not None:
            return jax.sharding.NamedSharding(
                mesh, spec, memory_kind=self.memory_kind
            )
        return jax.sharding.NamedSharding(mesh, spec)


@dataclass(frozen=True)
class DeviceSpec:
    """Shape/dtype/placement of one staged array (pull-spec ingredients)."""

    shape: tuple[int, ...]
    dtype: str
    sharding: ShardingDescriptor

    @classmethod
    def of(cls, arr) -> "DeviceSpec":
        return cls(
            shape=tuple(arr.shape),
            dtype=str(arr.dtype),
            sharding=ShardingDescriptor.of(arr.sharding),
        )

    def to_jax(self):
        import jax
        import jax.numpy as jnp

        return jax.ShapeDtypeStruct(
            self.shape, jnp.dtype(self.dtype), sharding=self.sharding.build()
        )


# --------------------------------------------------------------------------
# the engine (per-process singleton)
# --------------------------------------------------------------------------


class DeviceTransferEngine:
    """Owns this process's transfer server + cached peer connections."""

    _instance: Optional["DeviceTransferEngine"] = None

    def __init__(self) -> None:
        self._server = None
        self._conns: dict[str, Any] = {}
        # uuids must be unique per (source process, staging); random base +
        # counter keeps restarted sources from colliding with stale pulls.
        self._next_uuid = uuid_mod.uuid4().int & ((1 << 62) - 1)

    @classmethod
    def get(cls) -> "DeviceTransferEngine":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def ensure_server(self, client=None) -> str:
        """Start (once) the transfer server on the local backend; returns its
        reachable address."""
        if self._server is None:
            import jax
            from jax.experimental import transfer

            if client is None:
                client = jax.devices()[0].client
            bind = os.environ.get("TORCHSTORE_TPU_BIND_HOST", "127.0.0.1")
            if bind in ("0.0.0.0", "::"):
                bind = "[::]" if bind == "::" else "0.0.0.0"
            self._server = transfer.start_transfer_server(
                client, f"{bind}:0", [f"{bind}:0"]
            )
            logger.info("device transfer server at %s", self._server.address())
        return self._server.address()

    def stage(self, arrays: list) -> int:
        """Schedule ``arrays`` (device jax.Arrays) for ONE remote pull;
        returns the uuid the peer must pull with."""
        self.ensure_server()
        self._next_uuid += 1
        uid = self._next_uuid
        self._server.await_pull(uid, list(arrays))
        _STAGED.inc(len(arrays))
        return uid

    def pull(self, address: str, uid: int, specs: list[DeviceSpec]) -> list:
        """Pull staged arrays from a peer server, landing them with the
        source's sharding (reshard afterwards with jax.device_put)."""
        return self.pull_built(address, uid, [s.to_jax() for s in specs])

    def pull_built(self, address: str, uid: int, jax_specs: list) -> list:
        """Pull with pre-built jax ShapeDtypeStructs (callers that validate
        sharding reconstruction up front reuse the same objects here)."""
        self.ensure_server()
        conn = self._conns.get(address)
        if conn is None:
            conn = self._server.connect(address)
            self._conns[address] = conn
        import time

        import numpy as np

        nbytes = sum(
            int(np.prod(s.shape)) * s.dtype.itemsize for s in jax_specs
        )
        t0 = time.perf_counter()
        with tracing.span(
            "transport.pull_device",
            transport="ici",
            peer=address,
            arrays=len(jax_specs),
            nbytes=nbytes,
        ):
            out = conn.pull(uid, jax_specs)
        _PULL_OPS.inc()
        _OPS.inc(transport="ici", op="get")
        _PULL_BYTES.inc(nbytes, transport="ici", op="get")
        _OP_SECONDS.observe(time.perf_counter() - t0, transport="ici", op="get")
        return out

    def reset(self) -> None:
        """Drop connections (tests); the server itself is process-lifetime."""
        self._conns.clear()


def finalize_stamped(uploaded, recheck) -> bool:
    """Settle a device upload that consumed a BORROWED stamped SHM view
    (``shared_memory.stamped_read(..., borrow=True)``): block until the
    device has fully read the mapped bytes, then re-check the seqlock.
    True -> the upload holds one consistent generation; False -> a landing
    raced the read and the arrays may mix generations — the caller MUST
    discard them and fall back to the RPC path."""
    import jax

    jax.block_until_ready(uploaded)
    return bool(recheck())


def upload_stamped(view, recheck, dtype=None, sharding=None):
    """One-sided host->device upload: hand the borrowed stamped segment
    view straight to the device runtime (``jax.device_put`` reads the
    mmapped bytes itself — no intermediate host staging copy, the staging
    buffer IS the stamped segment), then :func:`finalize_stamped`. With an
    ICI-capable backend the very same call pulls over the accelerator
    fabric; on host-only backends it is still the zero-extra-copy path.
    Returns the device array, or None when the upload tore (the caller
    falls back to the RPC path, which serves a consistent snapshot)."""
    import jax

    import numpy as np

    devices = (
        list(sharding.device_set) if sharding is not None else jax.devices()
    )
    if all(d.platform == "cpu" for d in devices):
        # Host-only backend: device_put of an aligned C-contiguous host
        # array may SHARE the buffer instead of copying — the "device"
        # array would alias recyclable segment memory and mutate under
        # the caller after a later landing. Materialize a private copy
        # first (the cost real accelerators pay in the H2D DMA anyway);
        # the recheck below still validates it was not torn.
        view = np.asarray(view).copy()
    with tracing.span("h2d.dispatch", nbytes=view.nbytes, parts=1):
        out = (
            jax.device_put(view, sharding)
            if sharding is not None
            else jax.device_put(view)
        )
    if dtype is not None and str(out.dtype) != str(dtype):
        out = out.astype(dtype)  # on-device; depends on the H2D transfer
    if not finalize_stamped(out, recheck):
        from torchstore_tpu.transport.shared_memory import ONE_SIDED_TORN

        ONE_SIDED_TORN.inc(transport="device")
        return None
    return out


def prewarm_engine() -> str:
    """Cold-start provisioning for the ICI rung: start this process's
    transfer server BEFORE the first publish/pull needs it (server startup
    binds a listener and initializes the backend's transfer machinery — paid
    once, and without prewarm it lands on iteration 0's critical path).
    Returns the server address. Staging itself stays per-pull (the engine's
    one-shot contract); dest-side staging buffers are the pull targets the
    caller provides."""
    with tracing.span("provision.device_server"):
        return DeviceTransferEngine.get().ensure_server()
