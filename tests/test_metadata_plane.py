"""Scale-out metadata plane (ISSUE 14): sharded controller index +
one-sided stamped metadata reads.

Covers the whole stack: the stable key->shard hash and the router's
partition/merge vocabulary (pure units), a sharded fleet end-to-end
(puts/gets/keys/exists/delete/waits and a streamed publish whose
watermarks route through the coordinator AFTER the owning shards index
the batch), the zero-RPC warm-path acceptance (plan validation, same-host
locate, stream polling all measured at ZERO controller RPCs in
``ts.traffic_matrix()["metadata"]``), the stamped seqlock machinery
(torn-write fallback, tombstones), the deterministic chaos leg (one
controller shard killed mid-put-storm via the ``controller.shard_dispatch``
faultpoint: clients fail loudly, coordinator-scoped state survives, no
committed key on a surviving shard is lost), and the regression tests for
the single-controller-ref assumptions in ``_raise_with_diagnosis`` and
the health supervisor (both route through the coordinator now).
"""

import asyncio
import pickle

import numpy as np
import pytest

import torchstore_tpu as ts
from torchstore_tpu.metadata import INDEX_OPS, shard_of
from torchstore_tpu.metadata import stamped as stamped_mod
from torchstore_tpu.metadata.shards import (
    partition_keys,
    partition_metas,
    slice_write_gens,
)
from torchstore_tpu.runtime import ActorDiedError
from torchstore_tpu.transport.types import Request

pytestmark = pytest.mark.anyio


# --------------------------------------------------------------------------
# units: hashing + partitioning
# --------------------------------------------------------------------------


def test_shard_of_is_stable_and_total():
    """crc32 sharding: deterministic across processes/runs (clients,
    coordinator, and shards must all agree), total over any string, and
    identity at 1 shard."""
    keys = [f"ns/k{i}" for i in range(500)] + ["", "a/b/c", "é"]
    for key in keys:
        assert shard_of(key, 1) == 0
        s = shard_of(key, 4)
        assert 0 <= s < 4
        assert shard_of(key, 4) == s  # stable on repeat
    # All shards actually used at this scale (hash spreads).
    assert len({shard_of(k, 4) for k in keys}) == 4


def test_partition_vocabulary():
    keys = [f"k{i}" for i in range(64)]
    parts = partition_keys(keys, 4)
    assert sorted(k for ks in parts.values() for k in ks) == sorted(keys)
    for i, ks in parts.items():
        assert all(shard_of(k, 4) == i for k in ks)
    metas = [Request.from_tensor(k, np.zeros(2, np.float32)).meta_only()
             for k in keys]
    mparts = partition_metas(metas, 4)
    assert sum(len(ms) for ms in mparts.values()) == len(metas)
    gens = {"v0": {k: i for i, k in enumerate(keys)}}
    sliced = slice_write_gens(gens, set(parts[0]))
    assert set(sliced["v0"]) == set(parts[0])
    assert slice_write_gens(None, {"x"}) is None


# --------------------------------------------------------------------------
# unit: the stamped seqlock segment
# --------------------------------------------------------------------------


def test_stamped_writer_reader_roundtrip_and_tombstone():
    payload = {"hello": 1}
    writer = stamped_mod.MetaStampWriter(lambda: payload, size=64 << 10)
    try:
        writer.publish_now()
        reader = stamped_mod.MetaStampReader(
            writer.seg.name, writer.size
        )
        gen1, obj, epoch = reader.read()
        assert obj == {"hello": 1} and epoch == 0
        # Unchanged generation: header-only re-read serves the cache.
        gen2, obj2, _ = reader.read()
        assert gen2 == gen1 and obj2 is obj
        payload["hello"] = 2
        writer.publish_now()
        gen3, obj3, _ = reader.read()
        assert gen3 > gen1 and obj3 == {"hello": 2}
        # A payload outgrowing the segment tombstones it: readers get a
        # PERMANENT MetaUnavailable (they stand down to the RPC path).
        payload["big"] = b"x" * (128 << 10)
        writer.publish_now()
        with pytest.raises(stamped_mod.MetaUnavailable) as exc:
            reader.read()
        assert exc.value.reason == "tombstone"
    finally:
        writer.close()


def test_stamped_reader_never_published():
    writer = stamped_mod.MetaStampWriter(lambda: {}, size=64 << 10)
    try:
        reader = stamped_mod.MetaStampReader(writer.seg.name, writer.size)
        with pytest.raises(stamped_mod.MetaUnavailable):
            reader.read()
        assert reader.generation() is None
    finally:
        writer.close()


def test_stamped_torn_write_detected():
    """A write-in-flight (odd seqlock) or a publish racing the payload
    copy is detected and surfaces as a torn fallback, never bad bytes."""
    writer = stamped_mod.MetaStampWriter(lambda: {"v": 1}, size=64 << 10)
    try:
        writer.publish_now()
        reader = stamped_mod.MetaStampReader(writer.seg.name, writer.size)
        # Force the seqlock odd (writer mid-publish from the reader's view).
        writer.words[0] = int(writer.words[0]) + 1
        with pytest.raises(stamped_mod.MetaUnavailable) as exc:
            reader.read()
        assert exc.value.reason == "torn"
        writer.words[0] = int(writer.words[0]) + 1  # settle even again
        _, obj, _ = reader.read()
        assert obj == {"v": 1}
    finally:
        writer.close()


# --------------------------------------------------------------------------
# fleet: sharded metadata plane end-to-end
# --------------------------------------------------------------------------


async def test_sharded_store_end_to_end():
    """A 3-shard fleet serves the full core-op surface with classic
    semantics: batched puts/gets across shards, prefix keys, exists,
    deletes (through the coordinator's lease guard + stream retire),
    wait_for, and per-shard ownership actually spread."""
    await ts.initialize(
        num_storage_volumes=2, store_name="mp3", controller_shards=3
    )
    try:
        c = ts.client("mp3")
        items = {
            f"mp3k/{i}": np.full((16,), i, np.float32) for i in range(48)
        }
        await ts.put_batch(items, store_name="mp3")
        out = await ts.get_batch(list(items), store_name="mp3")
        for k, v in items.items():
            assert np.array_equal(out[k], v), k
        assert await ts.keys("mp3k", store_name="mp3") == sorted(items)
        assert await ts.exists("mp3k/3", store_name="mp3")
        assert not await ts.exists("mp3k/nope", store_name="mp3")
        await c.wait_for(list(items)[:5], timeout=10)
        # Ownership is spread: every shard holds a nonempty slice.
        router = c.controller
        assert len(router.shard_refs) == 3
        per_shard = await asyncio.gather(
            *(ref.summary.call_one() for ref in router.shard_refs)
        )
        assert all(s["num_keys"] > 0 for s in per_shard), per_shard
        assert sum(s["num_keys"] for s in per_shard) >= len(items)
        # Coordinator stats merge the shard rollups.
        stats = await router.stats.call_one()
        assert stats["num_keys"] >= len(items)
        assert stats["metadata_shards"] == 3
        assert stats["puts"] >= len(items)
        # Deletes: guard -> shard drop -> stream retire; idempotent.
        await ts.delete_batch(["mp3k/0", "mp3k/1"], store_name="mp3")
        assert not await ts.exists("mp3k/0", store_name="mp3")
        with pytest.raises(KeyError):
            await ts.get("mp3k/0", store_name="mp3")
        # wait_for_change routes to the owning shard.
        res = await c.wait_for_change("mp3k/2", 0, timeout=5)
        assert res["state"] == "committed"
    finally:
        await ts.shutdown("mp3")


async def test_sharded_streamed_publish_acquire():
    """Streamed publish under sharding: layer watermarks are recorded on
    the coordinator strictly AFTER the owning shards indexed each batch,
    and a streaming reader serves a consistent single-generation dict."""
    await ts.initialize(
        num_storage_volumes=1, store_name="mpst", controller_shards=2
    )
    try:
        served = []
        stream = ts.state_dict_stream("sd", store_name="mpst")
        await stream.put({"a": np.ones((64,), np.float32)})
        await stream.put({"b": np.full((64,), 2.0, np.float32)})
        await stream.seal()
        got = await ts.get_state_dict(
            "sd",
            stream=True,
            on_layer=lambda k, v: served.append(k),
            store_name="mpst",
        )
        assert np.array_equal(got["a"], np.ones((64,), np.float32))
        assert np.array_equal(got["b"], np.full((64,), 2.0, np.float32))
        assert sorted(served) == ["a", "b"]
    finally:
        await ts.shutdown("mpst")


# --------------------------------------------------------------------------
# acceptance: warm-path metadata is ZERO controller RPCs
# --------------------------------------------------------------------------


async def _metadata_counts():
    tm = await ts.traffic_matrix("mpz")
    return tm["metadata"]


async def test_warm_path_zero_metadata_rpcs():
    """The ISSUE-14 acceptance, measured: after warmup, same-host locate
    (fresh client, cold caches), cached-plan validation, and streamed
    wait_for_stream polling all run with ZERO controller RPCs — every
    one served from the stamped segments and counted as such in
    ``ts.traffic_matrix()["metadata"]``."""
    await ts.initialize(num_storage_volumes=1, store_name="mpz")
    try:
        c = ts.client("mpz")
        items = {
            f"wz/{i}": np.full((256,), i, np.float32) for i in range(8)
        }
        await ts.put_batch(items, store_name="mpz")
        # Let the debounced stamped publishes land.
        await asyncio.sleep(4 * stamped_mod.publish_interval_s() + 0.05)

        # --- same-host locate on a COLD client: zero RPCs ---------------
        ts.reset_client("mpz")
        c = ts.client("mpz")
        await c._ensure_setup()
        before = await _metadata_counts()
        out = await ts.get_batch(list(items), store_name="mpz")
        for k, v in items.items():
            assert np.array_equal(out[k], v)
        after = await _metadata_counts()
        assert after["rpcs"].get("locate_volumes", 0) == before["rpcs"].get(
            "locate_volumes", 0
        ), (before, after)
        assert after["stamped"].get("locate_volumes", 0) > before[
            "stamped"
        ].get("locate_volumes", 0)

        # --- warm plan validation: zero RPCs ----------------------------
        # Two identical batched gets: the second validates its cached plan
        # against the STAMPED epoch (confirmation fast path).
        await ts.get_batch(list(items), store_name="mpz")
        await c.placement_epoch()  # adopt the current epoch once (RPC ok)
        before = await _metadata_counts()
        epoch = await c.placement_epoch()
        after = await _metadata_counts()
        assert epoch > 0
        assert after["rpcs"].get("placement_epoch", 0) == before["rpcs"].get(
            "placement_epoch", 0
        ), (before, after)
        assert after["stamped"].get("placement_epoch", 0) > before[
            "stamped"
        ].get("placement_epoch", 0)

        # --- streamed wait_for_stream polling: zero RPCs ----------------
        stream = ts.state_dict_stream("zs", store_name="mpz")
        await stream.put({"l0": np.ones((64,), np.float32)})
        await stream.put({"l1": np.ones((64,), np.float32)})
        await stream.seal()
        await asyncio.sleep(4 * stamped_mod.publish_interval_s() + 0.05)
        before = await _metadata_counts()
        got = await ts.get_state_dict("zs", stream=True, store_name="mpz")
        assert set(got) == {"l0", "l1"}
        after = await _metadata_counts()
        assert after["rpcs"].get("wait_for_stream", 0) == before["rpcs"].get(
            "wait_for_stream", 0
        ), (before, after)
        assert after["stamped"].get("wait_for_stream", 0) > before[
            "stamped"
        ].get("wait_for_stream", 0)
    finally:
        await ts.shutdown("mpz")


async def test_stamped_disabled_falls_back_to_rpc(monkeypatch):
    """TORCHSTORE_TPU_META_STAMPED=0: no segments are attached, every
    metadata op is a counted RPC — the knob and the fallback ladder both
    work (and the RPC path is what the sharded bench measures)."""
    monkeypatch.setenv("TORCHSTORE_TPU_META_STAMPED", "0")
    from torchstore_tpu import config as config_mod

    config_mod._default_config = None
    try:
        await ts.initialize(num_storage_volumes=1, store_name="mpoff")
        try:
            c = ts.client("mpoff")
            await ts.put("offk", np.ones((32,), np.float32),
                         store_name="mpoff")
            ts.reset_client("mpoff")
            # The ledger is process-cumulative (earlier tests' stamped
            # reads persist): assert on DELTAS across this get only.
            before = (await ts.traffic_matrix("mpoff"))["metadata"]
            await ts.get("offk", store_name="mpoff")
            md = (await ts.traffic_matrix("mpoff"))["metadata"]
            assert md["rpcs"].get("locate_volumes", 0) > before["rpcs"].get(
                "locate_volumes", 0
            ), (before, md)
            assert md["stamped"] == before["stamped"], (before, md)
        finally:
            await ts.shutdown("mpoff")
    finally:
        config_mod._default_config = None


# --------------------------------------------------------------------------
# chaos: one controller shard dies mid-put-storm
# --------------------------------------------------------------------------


async def test_shard_kill_mid_put_storm_fails_loud_coordinator_survives():
    """Deterministic kill of one controller shard under load (the
    ``controller.shard_dispatch`` faultpoint, die action): puts whose keys
    hash to the dead shard fail LOUDLY (ActorDiedError — never silent
    loss, never wrong data), keys owned by surviving shards stay fully
    readable with correct bytes, and every coordinator-scoped subsystem
    (streams, leases, health, epoch) keeps answering."""
    await ts.initialize(
        num_storage_volumes=2, store_name="mpck", controller_shards=2
    )
    try:
        c = ts.client("mpck")
        router = c.controller
        n = 2
        keys = [f"ck/{i}" for i in range(40)]
        committed = {}
        for k in keys[:20]:
            v = np.full((64,), hash(k) % 97, np.float32)
            await ts.put(k, v, store_name="mpck")
            committed[k] = v
        # Arm the kill on shard 0 only: its NEXT dispatch dies.
        await ts.inject_fault(
            "controller.shard_dispatch", "die", scope="shard:0",
            store_name="mpck",
        )
        survivors = [k for k in committed if shard_of(k, n) == 1]
        dead_keys = [k for k in committed if shard_of(k, n) == 0]
        assert survivors and dead_keys  # both shards own committed keys
        # Put storm over fresh keys: everything routed to shard 0 fails
        # loudly once it dies; shard-1 keys keep landing.
        storm_ok, storm_dead = 0, 0
        for k in keys[20:]:
            try:
                await ts.put(
                    k, np.zeros((64,), np.float32), store_name="mpck"
                )
                storm_ok += 1
            except (ActorDiedError, ConnectionError, OSError):
                storm_dead += 1
        assert storm_dead >= 1, "the armed shard never died"
        assert storm_ok >= 1, "surviving shard stopped serving puts"
        # Committed keys on the SURVIVING shard: bytes intact, readable.
        got = await ts.get_batch(
            {k: None for k in survivors}, store_name="mpck"
        )
        for k in survivors:
            assert np.array_equal(got[k], committed[k]), k
        # Dead-shard keys fail loudly at locate — not wrong data. (The
        # stamped index may serve a pre-kill snapshot — also CORRECT data
        # — so force the RPC path via a fresh locate.)
        with pytest.raises((ActorDiedError, ConnectionError, OSError)):
            await router.locate_volumes.call_one([dead_keys[0]])
        # Coordinator-scoped state survives: health, epoch, streams,
        # leases all answer.
        health = await ts.volume_health("mpck")
        assert set(health)  # supervisor still tracking volumes
        assert await router.placement_epoch.call_one() > 0
        assert await router.lease_list.call_one() == {}
        assert await router.stream_state.call_one("never-streamed") is None
    finally:
        await ts.shutdown("mpck")


# --------------------------------------------------------------------------
# fix: diagnosis + health supervisor under sharding
# --------------------------------------------------------------------------


async def test_diagnosis_routes_through_coordinator_when_sharded():
    """``_raise_with_diagnosis`` fans the health check out through the
    COORDINATOR (never a shard): killing a volume under a sharded store
    still yields the controller-diagnosed error string, and the client's
    dead-volume memory comes from the coordinator's verdict. (Retry
    deadline 3 s, not the default 30: the get retries against the surviving
    volume until then, and tier 1 should not wait it out.)"""
    from torchstore_tpu.config import RetryPolicy, StoreConfig

    await ts.initialize(
        num_storage_volumes=2,
        store_name="mpdx",
        controller_shards=2,
        config=StoreConfig(retry=RetryPolicy(deadline_s=3.0)),
    )
    try:
        c = ts.client("mpdx")
        await ts.put("dxk", np.ones((32,), np.float32), store_name="mpdx")
        located = await c.controller.locate_volumes.call_one(["dxk"])
        vid = next(iter(located["dxk"]))
        # Kill the volume process holding the key.
        await ts.inject_fault(
            "volume.get", "die", scope=vid, store_name="mpdx"
        )
        with pytest.raises(ActorDiedError) as exc:
            # Bypass caches/one-sided so the fetch really dials the dead
            # volume (stamped/warm paths would serve the local copy).
            c._loc_cache.clear()
            await c.get("dxk")
        assert "controller diagnosis" in str(exc.value)
    finally:
        await ts.shutdown("mpdx")


async def test_quarantine_pushes_to_shards(monkeypatch):
    """The health supervisor's quarantine verdict reaches every shard
    (set_quarantined push): a sharded locate filters the quarantined
    replica exactly like the classic controller did."""
    monkeypatch.setenv("TORCHSTORE_TPU_HEALTH_INTERVAL_S", "0.25")
    monkeypatch.setenv("TORCHSTORE_TPU_HEALTH_MISS_THRESHOLD", "2")
    monkeypatch.setenv("TORCHSTORE_TPU_AUTO_REPAIR", "0")
    from torchstore_tpu.strategy import LocalRankStrategy

    await ts.initialize(
        num_storage_volumes=2,
        strategy=LocalRankStrategy(replication=2),
        store_name="mpq",
        controller_shards=2,
    )
    try:
        c = ts.client("mpq")
        await ts.put("qk", np.ones((32,), np.float32), store_name="mpq")
        located = await c.controller.locate_volumes.call_one(["qk"])
        assert len(located["qk"]) == 2  # replicated on both volumes
        victim = sorted(located["qk"])[0]
        await ts.inject_fault(
            "actor.ping", "wedge", scope=victim, store_name="mpq"
        )
        deadline = asyncio.get_event_loop().time() + 20
        while True:
            health = await ts.volume_health("mpq")
            if health.get(victim, {}).get("state") == "quarantined":
                break
            assert asyncio.get_event_loop().time() < deadline, health
            await asyncio.sleep(0.2)
        # Give the best-effort shard push a beat, then locate via the
        # owning SHARD: the quarantined replica is filtered.
        deadline = asyncio.get_event_loop().time() + 5
        while True:
            located = await c.controller.locate_volumes.call_one(["qk"])
            if victim not in located["qk"]:
                break
            assert asyncio.get_event_loop().time() < deadline, located
            await asyncio.sleep(0.1)
        assert len(located["qk"]) == 1
    finally:
        await ts.shutdown("mpq")


# --------------------------------------------------------------------------
# router plumbing
# --------------------------------------------------------------------------


async def test_router_counts_every_metadata_rpc():
    """Every controller RPC a client issues lands in the ledger's metadata
    cells per (op, shard) — the measurement the zero-RPC assertions and
    the metadata_scale bench both read."""
    await ts.initialize(
        num_storage_volumes=1, store_name="mprc", controller_shards=2
    )
    try:
        c = ts.client("mprc")
        await ts.put("rck", np.ones((16,), np.float32), store_name="mprc")
        await c.controller.locate_volumes.call_one(["rck"])
        await c.controller.keys.call_one(None)
        tm = await ts.traffic_matrix("mprc")
        md = tm["metadata"]
        assert md["rpcs"].get("notify_put_batch", 0) >= 1, md
        assert md["rpcs"].get("locate_volumes", 0) >= 1, md
        assert md["rpcs"].get("keys", 0) >= 2, md  # fanned to both shards
        shards = set(md["rpcs_by_shard"])
        assert {"s0", "s1"} <= shards or "coord" in shards, md
        # INDEX_OPS is the router's routing table: a new index op must be
        # added there deliberately (this keeps the set honest).
        assert "locate_volumes" in INDEX_OPS
        assert pickle.loads(pickle.dumps(shard_of))("x", 2) == shard_of(
            "x", 2
        )
    finally:
        await ts.shutdown("mprc")
