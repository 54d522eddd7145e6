"""Versioned weight channel: the RL weight-sync steady state as one object.

The reference leaves the publish/consume loop to users: trainers invent
version-numbered keys ("v0", "v1", ...) and generators poll
``get_state_dict`` in try/except loops (reference example/torchstore_rl.py).
This layer packages the whole pattern:

- ``WeightPublisher.publish(sd)`` writes the state dict under
  ``name/v{n}``, atomically advances the ``name/LATEST`` pointer, and
  garbage-collects versions older than ``keep`` — unbounded-memory-free by
  construction.
- ``WeightSubscriber.acquire()`` BLOCKS until a version newer than the last
  one it returned is committed (woken by the controller's update
  notification, no polling), pulls it — optionally in place into
  ``user_state_dict`` targets, resharding as usual — and returns
  ``(state_dict, version)``.

Ordering guarantee: ``LATEST`` is written only after the version's commit
marker, so a subscriber woken by the pointer update always finds a complete
state dict. GC trails ``keep`` versions behind, so a subscriber mid-pull on
version n is safe while n+1 publishes (keep >= 2).
"""

from __future__ import annotations

import asyncio
import contextlib
from typing import Any, Optional

from torchstore_tpu.logging import get_logger
from torchstore_tpu.observability import metrics as obs_metrics
from torchstore_tpu.observability import recorder as obs_recorder
from torchstore_tpu.observability import timeline as obs_timeline
from torchstore_tpu.observability.tracing import span
from torchstore_tpu.state_dict_utils import NoMatchingPush

logger = get_logger("torchstore_tpu.weight_channel")

# Publisher side and subscriber side each run in their own process; gauges
# are labeled by channel so one scrape of both processes yields the
# publish→subscribe version lag (published_version - acquired_version).
_PUBLISHES = obs_metrics.counter(
    "ts_weight_channel_publishes_total", "Versions published, per channel"
)
_PUBLISHED_VERSION = obs_metrics.gauge(
    "ts_weight_channel_published_version", "Latest version published"
)
_ACQUIRED_VERSION = obs_metrics.gauge(
    "ts_weight_channel_acquired_version", "Latest version a subscriber pulled"
)
_VERSION_LAG = obs_metrics.gauge(
    "ts_weight_channel_version_lag",
    "Versions between the channel pointer and what this subscriber last "
    "acquired, measured at wakeup (0 = consuming every publish)",
)
_SKIPPED = obs_metrics.counter(
    "ts_weight_channel_versions_skipped_total",
    "Published versions a subscriber never pulled (lagged past)",
)
_PINNED_ACQUIRES = obs_metrics.counter(
    "ts_weight_channel_pinned_acquires_total",
    "Version-pinned acquires served under a cohort lease, per channel",
)

_LATEST = "LATEST"
# In-flight streamed-publish announce: written when a ChannelStream's first
# layer opens, BEFORE any seal — the streaming subscriber's wakeup pointer.
_STREAM_PTR = "STREAM"


def _version_key(name: str, version: int) -> str:
    return f"{name}/v{version}"


def _parse_pointer(value) -> tuple[int, int]:
    """(version, epoch) from a LATEST pointer. Plain ints (pre-epoch
    pointers recovered from a durable store) read as epoch 0."""
    if isinstance(value, (tuple, list)):
        return int(value[0]), int(value[1])
    return int(value), 0


class WeightPublisher:
    """Trainer side of a versioned weight channel."""

    def __init__(
        self,
        name: str,
        store_name: str = "default",
        keep: int = 2,
        client: Any = None,
        transfer_quant: Optional[str] = None,
        delta: bool = False,
        keyframe_every: Optional[int] = None,
    ) -> None:
        if keep < 1:
            raise ValueError("keep must be >= 1 (the latest version must live)")
        self.name = name
        self.keep = keep
        self._store_name = store_name
        self._client = client
        self._next_version: Optional[int] = None
        # Wire-tier defaults for this publisher: ``transfer_quant`` (None =
        # the TORCHSTORE_TPU_TRANSFER_QUANT default) and ``delta=True`` for
        # delta encoding between consecutive versions (requires a blockwise
        # mode; the publisher keeps the last-shipped baseline per key and
        # ships sparse residuals, re-keyframing every ``keyframe_every``
        # versions — default TORCHSTORE_TPU_DELTA_KEYFRAME).
        self._transfer_quant = transfer_quant
        self._delta = delta
        self._keyframe_every = keyframe_every
        self._codec = None
        # Channel epoch: minted when this publisher CREATES the channel,
        # inherited when it resumes one. Lets subscribers distinguish a
        # deleted-then-recreated channel (fresh epoch, numbering restarts)
        # from a duplicate wakeup of the same publish (ADVICE r2).
        self._epoch: Optional[int] = None

    def _resolve_client(self):
        if self._client is None:
            from torchstore_tpu import api

            self._client = api.client(self._store_name)
        return self._client

    async def register(
        self, state_dict: Any, transfer_dtype=None, direct: bool = False
    ) -> dict:
        """Provision the store for this channel's working set BEFORE the
        first publish (the cold-start hint path): derives a manifest from
        the state dict (metadata only — no bytes move) and prewarms volume
        pools, transport connections, and — with ``direct=True`` — the
        client-local staging segments the direct source will draw. Call it
        during model setup, while the trainer is still compiling/loading,
        so the first publish lands in pre-faulted segments. Advisory:
        failures are reported in the returned dict, never raised, and the
        first publish falls back to the lazy path."""
        from torchstore_tpu import provision

        try:
            client = self._resolve_client()
            from torchstore_tpu.config import default_config

            cfg = getattr(client, "_config", None) or default_config()
            # Quantized channels prewarm pools sized for the fused blobs
            # (scale-bearing arena segments), not full-precision tensors.
            # The "none" sentinel (explicitly disabled) must not reach the
            # manifest, which treats any non-None value as a quant format.
            quant = None
            if transfer_dtype is None:
                quant = self._resolve_quant(client, None)
                if quant == "none":
                    quant = None
            manifest = provision.as_manifest(
                state_dict,
                transfer_dtype=transfer_dtype,
                transfer_quant=quant,
                quant_block=cfg.quant_block,
            )
        except Exception as exc:  # noqa: BLE001 - advisory: the first
            # publish surfaces real problems loudly; register never does.
            logger.warning(
                "channel %s register failed (%s); first publish will take "
                "the lazy path",
                self.name,
                exc,
            )
            return {"ok": False, "errors": {"register": str(exc)}}
        with span(
            "weight_channel.register",
            channel=self.name,
            nbytes=manifest.total_bytes,
        ):
            return await provision.prewarm_manifest(
                client, manifest, direct=direct
            )

    async def _resolve_next_version(self, client) -> int:
        """Resume after the channel's existing LATEST (a restarted publisher
        must not clobber live versions) — and reclaim any PARTIAL version a
        crashed predecessor left beyond the pointer: an abandoned stream's
        layer keys (never sealed, so never pointed at) would otherwise leak
        until their version number is reused and GC'd.

        Versions that SURVIVE the reclaim (pinned by live cohort leases —
        including versions of a closed-and-recreated channel, whose fresh
        epoch restarts numbering at 0) advance the counter past them: a
        publish must never land in a retained version's directory, where
        its keys would mix with the survivor's into a two-generation dict.
        Skipping the numbers also routes the survivors into ``_gc``'s
        retention window once their leases lapse, so a skipped partial is
        reclaimed by a later publish instead of leaking forever."""
        if self._next_version is None:
            try:
                current, epoch = _parse_pointer(
                    await client.get(f"{self.name}/{_LATEST}")
                )
                self._next_version = current + 1
                self._epoch = epoch
            except KeyError:
                import secrets

                self._next_version = 0
                self._epoch = secrets.randbits(62) or 1
                current = -1
            survivors = await self._reclaim_partials(client, current)
            if survivors:
                self._next_version = max(
                    self._next_version - 1, max(survivors)
                ) + 1
        return self._next_version

    async def _commit(self, client, version: int) -> None:
        """The ONE commit tail for a published version, shared by the
        barrier ``publish`` and ``ChannelStream.seal``: advance the LATEST
        pointer (subscribers woken by it always find a committed dict —
        callers must have finished the data/seal writes first), step the
        version counter, and publish the channel metrics."""
        await client.put(f"{self.name}/{_LATEST}", (version, self._epoch))
        self._next_version = version + 1
        _PUBLISHES.inc(channel=self.name)
        _PUBLISHED_VERSION.set(version, channel=self.name)
        obs_recorder.record(
            "stream", "publish", channel=self.name, version=version
        )

    async def _leased_versions(self, client) -> Optional[set[int]]:
        """Versions of this channel pinned by live cohort leases — GC and
        partial-reclaim skip them. Advisory here (a skip avoids pointless
        delete RPCs): the HARD guarantee is the controller's
        notify_delete_batch lease guard, which refuses the delete however
        it is issued, so a lease-plane hiccup degrades to noise, never to
        a reaped pinned version. Returns None when the lease plane is
        unreachable — callers fall back to the guard and, where it
        matters, verify their deletes actually removed keys."""
        try:
            pins = await client.lease_list(self.name)
        except Exception:  # noqa: BLE001 - advisory; the controller guard
            # still enforces retention
            logger.warning(
                "channel %s: lease_list failed; relying on the "
                "controller's delete guard for pinned versions",
                self.name,
            )
            return None
        return {int(v) for v in pins.get(self.name, {})}

    async def _reclaim_partials(self, client, current: int) -> set[int]:
        """Delete every version directory BEYOND the committed pointer
        (keys a crashed publisher streamed but never sealed). Runs once per
        publisher lifetime, on resume. LEASED versions survive — a canary
        cohort may legitimately pin an experimental version published past
        the main pointer — and are returned so the caller can advance the
        version counter past them instead of publishing into them."""
        stale: set[int] = set()
        for key in await client.keys(self.name):
            seg = key[len(self.name) + 1 :].split("/", 1)[0]
            if seg.startswith("v") and seg[1:].isdigit() and int(seg[1:]) > current:
                stale.add(int(seg[1:]))
        survivors: set[int] = set()
        if stale:
            survivors = (await self._leased_versions(client) or set()) & stale
            stale -= survivors
        for v in sorted(stale):
            removed = await client.delete_prefix(_version_key(self.name, v))
            if await client.keys(_version_key(self.name, v)):
                # Keys remain after the delete: the controller's lease
                # guard refused it (the version is pinned, but lease_list
                # failed above so we did not know). A survivor is a
                # survivor however we learn of it — numbering must still
                # advance past it, never publish into its directory.
                survivors.add(v)
                logger.warning(
                    "channel %s: v%d survived reclaim (lease-guarded "
                    "delete refused); resuming numbering past it",
                    self.name,
                    v,
                )
            elif removed:
                logger.warning(
                    "channel %s: reclaimed partial v%d (%d keys) left by a "
                    "crashed publisher",
                    self.name,
                    v,
                    removed,
                )
        return survivors

    def _resolve_quant(self, client, override: Optional[str]) -> Optional[str]:
        from torchstore_tpu import state_dict_utils as sdu

        explicit = override if override is not None else self._transfer_quant
        mode = sdu.resolve_transfer_quant(
            explicit, None, getattr(client, "_config", None)
        )
        if mode is None and explicit is not None:
            # Explicitly disabled ("none") at the publisher/call level:
            # keep the sentinel so put_state_dict does not re-apply the
            # TORCHSTORE_TPU_TRANSFER_QUANT default.
            return "none"
        return mode

    def _ensure_codec(self, client, mode: str):
        """The publisher's DeltaEncoder (lazy; one per publisher lifetime —
        a restarted publisher has no baselines and re-keyframes naturally).
        Enforces keep >= keyframe cadence: a fresh reader chain-walks back
        to the newest keyframe, which must still be retained."""
        from torchstore_tpu import state_dict_utils as sdu
        from torchstore_tpu.config import default_config

        if self._codec is None:
            cfg = getattr(client, "_config", None) or default_config()
            kf = int(self._keyframe_every or cfg.delta_keyframe)
            if kf > self.keep:
                raise ValueError(
                    f"delta publishing on channel {self.name!r} needs "
                    f"keep >= keyframe cadence ({kf}): readers chain-walk "
                    "deltas back to the newest keyframe, which must still "
                    "be retained — raise keep or lower keyframe_every / "
                    "TORCHSTORE_TPU_DELTA_KEYFRAME"
                )
            self._codec = sdu.DeltaEncoder(
                mode, cfg.quant_block, kf, cfg.delta_skip_eps
            )
        return self._codec

    def _delta_ctx_for(
        self, client, version: int, transfer_quant: Optional[str],
        delta: Optional[bool],
    ) -> tuple[Optional[str], Optional[dict]]:
        """(effective quant mode, delta_ctx) for one publish."""
        mode = self._resolve_quant(client, transfer_quant)
        use_delta = self._delta if delta is None else delta
        if not use_delta:
            return mode, None
        if mode not in ("int8_block", "int4_block"):
            raise ValueError(
                "delta publishing requires a blockwise transfer_quant "
                f"(int8_block/int4_block), got {mode!r}"
            )
        return mode, {
            "codec": self._ensure_codec(client, mode),
            "version": int(version),
            "channel": self.name,
        }

    def stream(
        self,
        transfer_dtype=None,
        transfer_quant: Optional[str] = None,
        delta: Optional[bool] = None,
    ) -> "ChannelStream":
        """Open a LAYER-STREAMED publish of the next version: push
        fragments with ``await cs.put(...)`` as the trainer produces them,
        then ``await cs.seal()`` to advance LATEST/GC exactly like
        ``publish``. Streaming subscribers (``acquire_streamed``) wake on
        the in-flight announce and start pulling layers before the seal;
        barrier subscribers (``acquire``) still wake only on the sealed
        pointer. ``transfer_quant``/``delta`` override the publisher's
        wire-tier defaults for this version. See
        torchstore_tpu/stream_sync.py."""
        return ChannelStream(
            self,
            transfer_dtype=transfer_dtype,
            transfer_quant=transfer_quant,
            delta=delta,
        )

    async def publish(
        self,
        state_dict: Any,
        transfer_dtype=None,
        transfer_quant: Optional[str] = None,
        direct: bool = False,
        delta: Optional[bool] = None,
    ) -> int:
        """Write the next version, advance LATEST, GC old versions. Returns
        the published version number. A restarted publisher resumes after
        the channel's existing LATEST instead of clobbering live versions.

        ``direct=True`` publishes through the one-hop path under a single
        STABLE key (``name/direct``): the first publish registers staging
        buffers, later ones are refreshes — no per-version registrations to
        leak, and the version number is purely the subscriber wakeup
        ordinal. A pull concurrent with a refresh is detected by the
        source's seqlock generation and retried, so the returned dict is
        always internally consistent (one step's weights, never a mix)."""
        from torchstore_tpu import state_dict_utils

        with span("weight_channel.resolve_version", channel=self.name):
            client = self._resolve_client()
            version = await self._resolve_next_version(client)
            data_key = (
                f"{self.name}/direct"
                if direct
                else _version_key(self.name, version)
            )
            if direct:
                quant_mode, delta_ctx = None, None
            else:
                quant_mode, delta_ctx = self._delta_ctx_for(
                    client, version, transfer_quant, delta
                )
        with span(
            "weight_channel.publish",
            channel=self.name,
            version=version,
            direct=direct,
        ):
            await state_dict_utils.put_state_dict(
                client,
                data_key,
                state_dict,
                transfer_dtype=transfer_dtype,
                transfer_quant=quant_mode if not direct else transfer_quant,
                direct=direct,
                delta_ctx=delta_ctx,
            )
            # Pointer write LAST: subscribers woken by it see a committed dict.
            await self._commit(client, version)
        if not direct:
            with span("weight_channel.gc", channel=self.name, version=version):
                await self._gc(client, version)
        return version

    async def _gc(self, client, version: int) -> None:
        """Retain the newest ``keep`` versions at or below the one just
        published and delete EVERY other version still present — not just
        the one this publish expires — so versions orphaned by a crash
        between pointer write and GC, or by restarting with a smaller
        ``keep``, are reclaimed on the next publish rather than leaking
        forever. The window counts EXISTING versions, not ``version -
        keep`` arithmetic: a publisher that resumed past a leased
        survivor publishes with a numbering gap, and a numeric cutoff
        would leap across it and reap the previous LATEST out from under
        a mid-pull subscriber. Versions beyond ``version`` (beyond-pointer
        partials a lease retained) are never touched here — they fall
        into the window once numbering passes them.

        Lease-aware (torchstore_tpu/tiering/): versions pinned by live
        cohort leases are skipped — an evaluation cohort on v_{t−k} keeps
        its weights however far LATEST advances — and reaped by a later
        publish's GC once the last lease expires or is released. Old
        retained versions cost tmpfs nothing in a tiered store: the spill
        writer demotes them to disk and reads fault them back in."""
        present: set[int] = set()
        for key in await client.keys(self.name):
            # Keys look like "{name}/v{n}/..." — prefix filtering is
            # segment-bounded, so list the channel root and parse.
            seg = key[len(self.name) + 1 :].split("/", 1)[0]
            if seg.startswith("v") and seg[1:].isdigit():
                present.add(int(seg[1:]))
        window = sorted(v for v in present if v <= version)
        stale = set(window[: -self.keep])
        lease_plane_ok = True
        if stale:
            leased = await self._leased_versions(client)
            lease_plane_ok = leased is not None
            leased = (leased or set()) & set(window)
            if leased:
                # Leased versions are exempt AND excluded from the window:
                # a pinned survivor must neither be reaped nor consume a
                # retention slot (pushing the previous LATEST out of the
                # keep window while a subscriber may still be pulling it).
                stale = set(
                    [v for v in window if v not in leased][: -self.keep]
                )
                logger.debug(
                    "channel %s: GC retaining leased version(s) %s",
                    self.name,
                    sorted(leased),
                )
        for v in sorted(stale):
            removed = await client.delete_prefix(_version_key(self.name, v))
            if not removed:
                continue
            if not lease_plane_ok and await client.keys(
                _version_key(self.name, v)
            ):
                # With lease_list down we could not exempt pinned
                # versions up front; the controller guard refused this
                # delete — retained, not GC'd.
                continue
            logger.debug("channel %s: GC'd v%d (%d keys)", self.name, v, removed)

    async def close(self, delete: bool = False) -> None:
        """Optionally remove every key the channel owns. Versions pinned
        by live cohort leases SURVIVE this delete (the controller's lease
        guard refuses them) and are reaped by a future publisher's GC on
        this channel once the leases lapse — a close racing a pinned read
        must never win; if the channel is truly done, release the leases
        (or let their TTLs expire) and close again."""
        if delete:
            client = self._resolve_client()
            await client.delete_prefix(self.name)


class ChannelStream:
    """One layer-streamed publish of a channel version (see
    :meth:`WeightPublisher.stream`). The first ``put`` resolves the next
    version number, opens the stream, and announces it on the channel's
    ``STREAM`` pointer so streaming subscribers wake immediately;
    ``seal()`` commits the marker, advances ``LATEST`` (barrier
    subscribers wake here), and GCs old versions. An abandoned stream
    (publisher crash before seal) never advances a pointer — the previous
    version stays fully acquirable, and the next publisher's resume
    reclaims the partial keys."""

    def __init__(
        self,
        publisher: WeightPublisher,
        transfer_dtype=None,
        transfer_quant: Optional[str] = None,
        delta: Optional[bool] = None,
    ) -> None:
        self._pub = publisher
        self._transfer_dtype = transfer_dtype
        self._transfer_quant = transfer_quant
        self._delta = delta
        self._stream = None
        self.version: Optional[int] = None

    async def put(self, fragment: Any) -> int:
        from torchstore_tpu import stream_sync

        if self._stream is None:
            pub = self._pub
            with span("weight_channel.resolve_version", channel=pub.name):
                client = pub._resolve_client()
                self.version = await pub._resolve_next_version(client)
                quant_mode, delta_ctx = pub._delta_ctx_for(
                    client, self.version, self._transfer_quant, self._delta
                )
            self._stream = stream_sync.stream_state_dict(
                client,
                _version_key(pub.name, self.version),
                transfer_dtype=self._transfer_dtype,
                transfer_quant=quant_mode,
                delta_ctx=delta_ctx,
            )
            await self._stream.begin()
            # Announce the IN-FLIGHT version before any layer lands:
            # streaming subscribers wake on this pointer and long-poll the
            # stream's watermarks — decode starts before the seal. A
            # regular put, so a crashed publisher leaves at worst a stale
            # announce that the next subscriber wakeup skips.
            await client.put(
                f"{pub.name}/{_STREAM_PTR}", (self.version, pub._epoch)
            )
        return await self._stream.put(fragment)

    async def seal(self) -> int:
        if self._stream is None:
            raise RuntimeError("seal() before any put(): nothing published")
        pub = self._pub
        client = pub._resolve_client()
        version = self.version
        with span(
            "weight_channel.publish",
            channel=pub.name,
            version=version,
            streamed=True,
        ):
            await self._stream.seal()
            # Pointer write LAST: barrier subscribers woken by it always
            # see a committed (sealed) dict, exactly like publish().
            await pub._commit(client, version)
        with span("weight_channel.gc", channel=pub.name, version=version):
            await pub._gc(client, version)
        return version


class WeightSubscriber:
    """Consumer side: blocks for fresh versions instead of polling.

    ``relay=True`` joins the channel's BROADCAST tree (torchstore_tpu/
    relay.py): the controller assigns this host's relay volume, published
    versions flow to it volume-to-volume, and streamed acquires are gated
    on + routed to that one host-local copy — K generator fleets cost O(1)
    trainer-host egress instead of K×. ``relay_volume`` pins an explicit
    member volume (tests/benches emulating multi-host fleets). Membership
    is elastic: the subscription happens lazily on the first streamed
    acquire and ``unsubscribe_relay()`` leaves mid-run (the tree re-parents
    around the departed host)."""

    def __init__(
        self,
        name: str,
        store_name: str = "default",
        client: Any = None,
        relay: bool = False,
        relay_volume: Optional[str] = None,
        cohort: Optional[str] = None,
    ) -> None:
        import os as _os

        self.name = name
        self._store_name = store_name
        self._client = client
        # Cohort identity for version-pinned acquires: the lease owner in
        # ts.version_catalog() / the flight recorder. Defaults to a
        # process-unique id; name it (e.g. "eval-fleet-2") so retention is
        # attributable.
        self.cohort = cohort or f"sub-{_os.getpid()}-{id(self):x}"
        # Lease-owner prefix for pinned acquires: ALWAYS process- and
        # instance-unique, even under a shared named cohort ("eval-fleet-2"
        # across a fleet) — the registry coalesces same-owner pins, so two
        # subscribers reusing an owner string would share one lease the
        # first finisher releases under the second. The cohort stays the
        # prefix for attribution in ts.version_catalog()/telemetry.
        self._lease_owner = f"{self.cohort}:{_os.getpid()}:{id(self):x}"
        # Monotonic per-subscriber read counter: each pinned acquire's
        # lease owner is "{_lease_owner}:r{n}" (see _pinned_lease).
        self._read_seq = 0
        self._last_gen = 0
        self._last_stream_gen = 0
        self.last_version: Optional[int] = None
        self._last_epoch: Optional[int] = None
        self._relay = relay or relay_volume is not None
        self._relay_volume = relay_volume
        self._relay_home: Optional[str] = None
        # Delta wire tier: this subscriber's accumulated per-key state.
        # Lazily built, shared across acquires so consecutive versions
        # accumulate (and unchanged-key layers serve with zero
        # re-transfer); empty-cost for unquantized channels.
        self._decoder = None
        self._decoder_epoch: Optional[int] = None

    def _delta_decoder(self, epoch: Optional[int] = None):
        from torchstore_tpu import state_dict_utils as sdu

        if self._decoder is None:
            self._decoder = sdu.DeltaDecoder()
            self._decoder_epoch = epoch
        elif epoch is not None and epoch != self._decoder_epoch:
            # A deleted-then-recreated channel restarts version numbering
            # under a fresh epoch: accumulated state from the OLD epoch
            # could collide with the new numbering (same version ints,
            # different weights) and silently serve stale accumulations —
            # drop it so the new epoch's first acquire re-keyframes/
            # chain-walks from real bytes.
            self._decoder.drop()
            self._decoder_epoch = epoch
        return self._decoder

    def _resolve_client(self):
        if self._client is None:
            from torchstore_tpu import api

            self._client = api.client(self._store_name)
        return self._client

    async def _ensure_relay(self, client) -> Optional[str]:
        """Join the channel's relay tree once (lazy, idempotent); returns
        the assigned home volume id, or None when relay is off/disabled."""
        if not self._relay:
            return None
        if self._relay_home is None:
            res = await client.relay_subscribe(
                self.name, volume_id=self._relay_volume
            )
            self._relay_home = res.get("volume_id")
            if self._relay_home is None:
                # Disabled fleet-wide (TORCHSTORE_TPU_RELAY_ENABLED=0):
                # stop retrying the control RPC on every acquire.
                self._relay = False
            else:
                obs_recorder.record(
                    "stream",
                    "relay_join",
                    channel=self.name,
                    volume=self._relay_home,
                )
        return self._relay_home

    async def unsubscribe_relay(self) -> None:
        """Elastic leave: drop this subscriber from the channel's broadcast
        tree (live runs re-parent around the host). Idempotent."""
        if self._relay_home is None:
            return
        client = self._resolve_client()
        await client.relay_unsubscribe(self.name, self._relay_home)
        self._relay_home = None

    async def _pinned_lease(self, client, version: int):
        """Acquire the read-scoped retention lease for a pinned acquire:
        while it lives, the version can be neither GC'd (controller delete
        guard) nor demoted off the warm path by the next spill sweep.

        The lease owner is a per-READ identity
        (``{cohort}:{pid}:{instance}:r{n}``), never the bare cohort: the
        registry coalesces same-owner pins, so a read under a shared name
        would RENEW — and its release DROP — a pin another read (or a
        long-lived cohort lease) still depends on. The pid/instance parts
        keep owners unique across subscribers SHARING a named cohort and
        across a restarted process whose read counter resets within a
        live lease's TTL; should an acquire still coalesce
        (``renewed: True``), :meth:`_pinned_read` leaves the shared pin
        live instead of releasing it under the other holder."""
        self._read_seq += 1
        owner = f"{self._lease_owner}:r{self._read_seq}"
        # Bracket contract lives in the CALLER: _pinned_read releases in
        # its finally; the normal return here hands the lease over open by
        # design, and the renewed-pin KeyError path deliberately leaves a
        # COALESCED lease to its other holder (releasing it would strip a
        # live read's GC protection).
        lease = await client.lease_acquire(owner, self.name, version)  # tslint: disable=bracket-discipline
        if lease.get("resident_keys") == 0:
            # Nothing indexed under this version: GC'd or never published.
            # Fail BEFORE the pull with a precise error (the pull's
            # NoMatchingPush would be indistinguishable from a torn push).
            if not lease.get("renewed"):
                await client.lease_release(lease["lease_id"])
            raise KeyError(
                f"channel {self.name!r} does not retain v{version} (GC'd "
                "or never published); pin versions with a cohort lease "
                "before LATEST advances past keep"
            )
        return lease

    async def _renew_pinned(self, client, lease: dict) -> None:
        """Heartbeat a pinned read's lease while the pull is in flight:
        state dicts routinely take longer than the default 30 s TTL to
        transfer, and a lease that lapses mid-read would hand the version
        back to GC/spill. Renews at a third of the TTL; a failed renewal
        (transient RPC blip, controller restart, lease expired under a
        long stall) falls back to RE-ACQUIRING the same owner's pin — one
        hiccup must not strip a long pull's protection for its remaining
        duration. Only when the re-acquire also fails does the heartbeat
        stop: the read degrades to best-effort, it never errors."""
        interval = max(0.1, float(lease.get("ttl_s") or 1.0) / 3.0)
        while True:
            await asyncio.sleep(interval)
            try:
                await client.lease_renew(lease["lease_id"])
            except Exception as renew_exc:  # noqa: BLE001 - degrade,
                # never fail the read: the pin is advisory protection,
                # the pull is the deliverable.
                try:
                    fresh = await client.lease_acquire(
                        lease["cohort"],
                        lease["channel"],
                        lease["version"],
                        lease.get("ttl_s"),
                    )
                    # Same owner: the registry coalesces onto the live
                    # lease when it still exists, or mints a replacement.
                    # Keep the ORIGINAL "renewed" flag — whether release
                    # is ours to do was decided at the first acquire.
                    lease["lease_id"] = fresh["lease_id"]
                    logger.info(
                        "channel %s: pinned-read lease renewal failed "
                        "(%s); re-acquired as %s",
                        self.name,
                        renew_exc,
                        fresh["lease_id"],
                    )
                except Exception as exc:  # noqa: BLE001
                    logger.warning(
                        "channel %s: pinned-read lease %s renewal and "
                        "re-acquire both failed (%s); read continues "
                        "without GC/spill protection",
                        self.name,
                        lease["lease_id"],
                        exc,
                    )
                    return

    @contextlib.asynccontextmanager
    async def _pinned_read(self, client, version: int):
        """Hold the read-scoped lease for the duration of a pinned pull:
        acquires it, renews it in the background (long pulls stay
        protected past the TTL), and on exit releases it — unless the
        acquire merely coalesced with an existing same-owner pin
        (``renewed: True``), which must survive for its other holder."""
        lease = await self._pinned_lease(client, version)
        renewer = asyncio.ensure_future(self._renew_pinned(client, lease))
        try:
            yield lease
        finally:
            renewer.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await renewer
            if lease.get("renewed"):
                logger.warning(
                    "channel %s: pinned-read lease owner collided with a "
                    "live pin (lease %s); leaving the shared lease to its "
                    "other holder",
                    self.name,
                    lease["lease_id"],
                )
            else:
                try:
                    await client.lease_release(lease["lease_id"])
                except Exception as exc:  # noqa: BLE001 - best-effort:
                    # the pull already succeeded (or raised its own
                    # error); the TTL reaps an unreleased pin anyway.
                    logger.warning(
                        "channel %s: pinned-read lease %s release failed "
                        "(%s); its TTL will expire it",
                        self.name,
                        lease["lease_id"],
                        exc,
                    )

    async def acquire(
        self,
        user_state_dict: Any = None,
        timeout: Optional[float] = None,
        direct: bool = False,
        strict: bool = True,
        version: Optional[int] = None,
    ) -> tuple[Any, int]:
        """Block until a version is published that this subscriber has not
        yet acquired, pull it, and return (state_dict, version). The first
        call returns the channel's current version immediately when one
        exists; each publish is delivered at most once (a deleted-then-
        recreated channel restarts numbering and delivers its v0). Raises
        TimeoutError if nothing new arrives in ``timeout`` seconds.

        ``version=N`` PINS the read instead (multi-version serving,
        torchstore_tpu/tiering/): a cohort retention lease is held — and
        renewed in the background, so pulls longer than the lease TTL stay
        protected — for the read's duration: the version cannot be GC'd
        mid-read, and spilled segments fault back in through the normal
        transport ladder. ``(state_dict, N)`` returns without touching
        this subscriber's LATEST tracking; ``timeout`` bounds the pull
        itself (there is no wait phase) and raises TimeoutError —
        cancelling a pull mid-flight, so after a timeout an IN-PLACE
        ``user_state_dict`` may hold a mix of its old leaves and
        already-landed v``N`` leaves: treat its contents as undefined.
        Raises KeyError when the channel no longer retains ``N``."""
        import time

        from torchstore_tpu import state_dict_utils

        client = self._resolve_client()
        if version is not None:
            if direct:
                raise ValueError(
                    "acquire(version=...) is incompatible with direct=True "
                    "(the direct path serves one stable key, not versions)"
                )
            version = int(version)
            async with self._pinned_read(client, version):
                with span(
                    "weight_channel.acquire_pinned",
                    channel=self.name,
                    version=version,
                ):
                    pull = state_dict_utils.get_state_dict(
                        client,
                        _version_key(self.name, version),
                        user_state_dict=user_state_dict,
                        strict=strict,
                        delta_state=self._delta_decoder(),
                    )
                    if timeout is None:
                        sd = await pull
                    else:
                        try:
                            sd = await asyncio.wait_for(pull, timeout)
                        except asyncio.TimeoutError:
                            raise TimeoutError(
                                f"pinned acquire of {self.name}/v{version} "
                                f"did not complete within {timeout}s"
                            ) from None
            _PINNED_ACQUIRES.inc(channel=self.name)
            obs_recorder.record(
                "tier",
                "pinned_acquire",
                channel=self.name,
                version=version,
                cohort=self.cohort,
            )
            return sd, version
        pointer = f"{self.name}/{_LATEST}"
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            remaining = None
            if deadline is not None:
                remaining = max(0.0, deadline - time.monotonic())
            change = await client.wait_for_change(
                pointer, self._last_gen, timeout=remaining
            )
            self._last_gen = change["gen"]
            if change["state"] != "committed":
                continue  # deleted channel or mid-rewrite; wait for the next
            data_key = None
            try:
                version, epoch = _parse_pointer(await client.get(pointer))
                if (
                    version == self.last_version
                    and epoch == self._last_epoch
                ):
                    # Duplicate wakeup: the gen we woke for belongs to a
                    # publish whose successor we ALREADY returned (the
                    # pointer is read in a later RPC than the gen, so a
                    # publish landing in between makes the next wake see
                    # the same version again). Each publish is delivered
                    # at most once — wait for a genuinely new one. A
                    # deleted-then-recreated channel mints a fresh epoch,
                    # so its restarted numbering still delivers (ADVICE r2).
                    continue
                data_key = (
                    f"{self.name}/direct"
                    if direct
                    else _version_key(self.name, version)
                )
                # Lag at wakeup: versions published since this subscriber's
                # last acquire that it will never pull (same epoch only — a
                # recreated channel restarts numbering). Consuming every
                # publish means waking at last_version + 1, i.e. lag 0.
                if (
                    self.last_version is not None
                    and epoch == self._last_epoch
                ):
                    skipped = version - self.last_version - 1
                    _VERSION_LAG.set(max(0, skipped), channel=self.name)
                    if skipped > 0:
                        _SKIPPED.inc(skipped, channel=self.name)
                    obs_timeline.check_slo(
                        obs_timeline.SLO_VERSION_LAG,
                        max(0, skipped),
                        channel=self.name,
                    )
                with span(
                    "weight_channel.acquire",
                    channel=self.name,
                    version=version,
                    direct=direct,
                ):
                    sd = await state_dict_utils.get_state_dict(
                        client,
                        data_key,
                        user_state_dict=user_state_dict,
                        direct=direct,
                        strict=strict,
                        delta_state=(
                            None if direct else self._delta_decoder(epoch)
                        ),
                    )
            except (NoMatchingPush, KeyError):
                # The pointer or version vanished between wakeup and pull
                # (channel deleted, or we lagged > keep versions behind);
                # wait for the next publish.
                logger.info(
                    "channel %s: %s vanished before pull (deleted channel "
                    "or lagging subscriber); waiting for next version",
                    self.name,
                    data_key or pointer,
                )
                continue
            self.last_version = version
            self._last_epoch = epoch
            _ACQUIRED_VERSION.set(version, channel=self.name)
            return sd, version

    async def acquire_streamed(
        self,
        user_state_dict: Any = None,
        key_order: Optional[list] = None,
        on_layer: Any = None,
        timeout: Optional[float] = None,
        strict: bool = True,
        version: Optional[int] = None,
    ) -> tuple[Any, int]:
        """Like :meth:`acquire`, but against layer-streamed publishes
        (:meth:`WeightPublisher.stream`): wakes on the channel's IN-FLIGHT
        announce (written before any layer lands) and pulls layer by layer
        as watermarks land — with ``key_order`` (model-forward order, e.g.
        ``models.generate.forward_key_order`` or
        ``StateDictManifest.key_order``) and an ``on_layer`` callback,
        generation starts before the publisher seals. The returned dict is
        always a single version's weights (stream_sync's watermark
        consistency ladder), and versions are delivered at most once.
        Requires streamed publishes; raises TimeoutError when nothing is
        announced within ``timeout``.

        ``version=N`` PINS the acquire to a retained historical version
        under a read-scoped cohort lease (see :meth:`acquire`); a sealed
        stream serves its layers immediately (in ``key_order`` when
        given), and a version whose stream record is gone falls back to
        the barrier read inside stream_sync."""
        import time

        from torchstore_tpu import stream_sync

        client = self._resolve_client()
        if version is not None:
            version = int(version)
            async with self._pinned_read(client, version):
                with span(
                    "weight_channel.acquire_pinned",
                    channel=self.name,
                    version=version,
                    streamed=True,
                ):
                    sd = await stream_sync.get_state_dict_streamed(
                        client,
                        _version_key(self.name, version),
                        user_state_dict=user_state_dict,
                        key_order=key_order,
                        on_layer=on_layer,
                        strict=strict,
                        timeout=timeout,
                        delta_state=self._delta_decoder(),
                    )
            _PINNED_ACQUIRES.inc(channel=self.name)
            obs_recorder.record(
                "tier",
                "pinned_acquire",
                channel=self.name,
                version=version,
                cohort=self.cohort,
            )
            return sd, version
        relay_home = await self._ensure_relay(client)
        pointer = f"{self.name}/{_STREAM_PTR}"
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            remaining = None
            if deadline is not None:
                remaining = max(0.0, deadline - time.monotonic())
            change = await client.wait_for_change(
                pointer, self._last_stream_gen, timeout=remaining
            )
            self._last_stream_gen = change["gen"]
            if change["state"] != "committed":
                continue  # deleted channel mid-rewrite; wait for the next
            try:
                version, epoch = _parse_pointer(await client.get(pointer))
            except KeyError:
                continue
            if version == self.last_version and epoch == self._last_epoch:
                continue  # duplicate wakeup: delivered at most once
            data_key = _version_key(self.name, version)
            if self.last_version is not None and epoch == self._last_epoch:
                skipped = version - self.last_version - 1
                _VERSION_LAG.set(max(0, skipped), channel=self.name)
                if skipped > 0:
                    _SKIPPED.inc(skipped, channel=self.name)
                obs_timeline.check_slo(
                    obs_timeline.SLO_VERSION_LAG,
                    max(0, skipped),
                    channel=self.name,
                )
            with span(
                "weight_channel.acquire",
                channel=self.name,
                version=version,
                streamed=True,
            ):
                try:
                    sd = await stream_sync.get_state_dict_streamed(
                        client,
                        data_key,
                        user_state_dict=user_state_dict,
                        key_order=key_order,
                        on_layer=on_layer,
                        strict=strict,
                        timeout=(
                            None
                            if deadline is None
                            else max(0.0, deadline - time.monotonic())
                        ),
                        relay_volume=relay_home,
                        delta_state=self._delta_decoder(epoch),
                    )
                except (NoMatchingPush, KeyError):
                    # The announced version vanished before the pull (GC'd
                    # under a lagging subscriber, or a crashed publisher's
                    # partial was reclaimed); wait for the next announce.
                    logger.info(
                        "channel %s: streamed %s vanished before pull; "
                        "waiting for next version",
                        self.name,
                        data_key,
                    )
                    continue
            self.last_version = version
            self._last_epoch = epoch
            _ACQUIRED_VERSION.set(version, channel=self.name)
            return sd, version
