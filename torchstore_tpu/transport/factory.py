"""Transport selection ladder.

Equivalent of /root/reference/torchstore/transport/__init__.py:38-108. The
reference ladder (SHM -> uniflow RDMA/NVLink -> legacy RDMA -> ibverbs ->
Gloo -> RPC) maps to TPU rungs:

    ici   device-to-device via the XLA transfer engine
          (``transport/device_transfer.py``, gated by ``ici_enabled``) —
          the direct weight-sync path rides it for all-jax state dicts;
          volume-backed store entries are host memory, so this rung serves
          the direct path, not the volume ladder (the reference's device
          rung, monarch_rdma.py, likewise serves weight sync)
    shm   same-host POSIX shared memory between client and volume
          (zero-copy snapshot reads)
    bulk  dedicated-socket bulk transfer (host staging within a pod;
          DCN across pods)
    rpc   payload rides the actor-RPC frames (always available)

Selection is per-volume at request time: forced type on the
``StorageVolumeRef``/strategy wins, else the best available rung probes in.
"""

from __future__ import annotations

from enum import Enum
from typing import TYPE_CHECKING, Optional

from torchstore_tpu.config import StoreConfig, default_config
from torchstore_tpu.logging import get_logger
from torchstore_tpu.transport.buffers import TransportBuffer
from torchstore_tpu.transport.rpc import RPCTransportBuffer

if TYPE_CHECKING:
    from torchstore_tpu.strategy import StorageVolumeRef

logger = get_logger("torchstore_tpu.transport")


class TransportType(str, Enum):
    UNSET = "unset"
    RPC = "rpc"
    SHM = "shm"
    BULK = "bulk"


def shm_available(volume: "StorageVolumeRef", config: StoreConfig) -> bool:
    if not config.shm_enabled or not volume.is_same_host():
        return False
    try:
        from torchstore_tpu.transport import shared_memory  # noqa: F401

        return shared_memory.is_available()
    except ImportError:
        return False


def bulk_available(volume: "StorageVolumeRef", config: StoreConfig) -> bool:
    if not config.bulk_tcp_enabled:
        return False
    try:
        from torchstore_tpu.transport import bulk  # noqa: F401

        return bulk.is_available()
    except ImportError:
        return False


_logged_resolution = False


def demotion_ladder(
    volume: "StorageVolumeRef", config: Optional[StoreConfig] = None
) -> list[TransportType]:
    """The rungs a put retry may walk DOWN, best first, STARTING at the
    rung the volume actually uses (``ladder[0]`` is what a plain
    ``create_transport_buffer`` call resolves to): a broken shm handshake
    or reset bulk socket demotes to the next rung instead of surfacing —
    rpc (always last) rides the actor channel itself, so if it fails too
    the volume is gone, not the transport. A volume whose
    ``transport_type`` is pinned never retries ABOVE the pinned rung:
    rungs the operator excluded (e.g. shm known-broken in a deployment
    that forced rpc) stay excluded."""
    config = config or default_config()
    forced = volume.transport_type
    if forced in (None, TransportType.UNSET, TransportType.UNSET.value):
        start = None
    else:
        start = TransportType(forced)
    order = (TransportType.SHM, TransportType.BULK, TransportType.RPC)
    available = {
        TransportType.SHM: shm_available(volume, config),
        TransportType.BULK: bulk_available(volume, config),
        TransportType.RPC: True,
    }
    rungs: list[TransportType] = []
    for rung in order:
        if start is not None and not rungs:
            if rung != start:
                continue  # a rung above the pin was deliberately excluded
            rungs.append(rung)  # the pin itself: what the failure used
            continue
        if available[rung]:
            rungs.append(rung)
    return rungs or [TransportType.RPC]


def create_transport_buffer(
    volume: "StorageVolumeRef",
    config: Optional[StoreConfig] = None,
    force: "Optional[TransportType | str]" = None,
) -> TransportBuffer:
    config = config or default_config()
    forced = force if force is not None else volume.transport_type
    if forced in (None, TransportType.UNSET, TransportType.UNSET.value):
        chosen = _auto_select(volume, config)
    else:
        chosen = TransportType(forced)
    global _logged_resolution
    if not _logged_resolution:
        # One line listing every rung's availability (reference behavior,
        # /root/reference/torchstore/transport/__init__.py:70-81).
        logger.info(
            "transport resolution: volume=%s same_host=%s -> %s "
            "[ici(direct)=%s shm=%s bulk=%s rpc=True]",
            volume.volume_id,
            volume.is_same_host(),
            chosen.value,
            config.ici_enabled,
            shm_available(volume, config),
            bulk_available(volume, config),
        )
        _logged_resolution = True
    try:
        if chosen == TransportType.SHM:
            from torchstore_tpu.transport.shared_memory import (
                SharedMemoryTransportBuffer,
            )

            return SharedMemoryTransportBuffer(
                config, inproc_copy=volume.is_inproc()
            )
        if chosen == TransportType.BULK:
            from torchstore_tpu.transport.bulk import BulkTransportBuffer

            return BulkTransportBuffer(
                config, inproc_copy=volume.is_inproc()
            )
    except ImportError as exc:
        raise RuntimeError(
            f"transport {chosen.value!r} was forced but is not available "
            f"in this build: {exc}"
        ) from exc
    return RPCTransportBuffer(inproc_copy=volume.is_inproc())


def _auto_select(volume: "StorageVolumeRef", config: StoreConfig) -> TransportType:
    if shm_available(volume, config):
        return TransportType.SHM
    if bulk_available(volume, config):
        return TransportType.BULK
    return TransportType.RPC
