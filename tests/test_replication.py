"""k-way replication: puts land on the primary plus ring-successor
replicas, gets fail over transparently when a replica dies, deletes clean
every copy. Beyond the reference (which stores each key exactly once and
loses it with its volume)."""

import asyncio

import numpy as np
import pytest

import torchstore_tpu as ts
from torchstore_tpu.client import Shard
from torchstore_tpu.runtime import ActorDiedError
from torchstore_tpu.strategy import LocalRankStrategy
from torchstore_tpu.transport.types import TensorSlice


async def _kill_volume(store_name: str, volume_id: str) -> None:
    """Kill the process hosting ``volume_id`` (match refs by identity
    triple — pickled ActorRefs don't compare equal to the mesh's)."""
    from torchstore_tpu import api

    client = ts.client(store_name)
    vmap = await client.controller.get_volume_map.call_one()
    target = vmap[volume_id]["ref"]
    handle = api._stores[store_name]
    meshes = [handle.volume_mesh, *(handle.repair_meshes or [])]
    for mesh in meshes:
        for idx, ref in enumerate(mesh.refs):
            if (ref.host, ref.port, ref.name) == (
                target.host,
                target.port,
                target.name,
            ):
                proc = mesh._processes[idx]
                proc.kill()
                proc.join(5)
                return
    raise AssertionError(f"no process found for volume {volume_id!r}")


@pytest.fixture
async def store():
    await ts.initialize(
        num_storage_volumes=3,
        strategy=LocalRankStrategy(replication=2),
        store_name="repl",
    )
    yield "repl"
    await ts.shutdown("repl")


async def test_put_indexes_on_two_volumes(store):
    await ts.put("k", np.arange(8.0, dtype=np.float32), store_name=store)
    client = ts.client(store)
    located = await client.controller.locate_volumes.call_one(["k"])
    assert len(located["k"]) == 2  # primary + 1 replica
    out = await ts.get("k", store_name=store)
    np.testing.assert_array_equal(out, np.arange(8.0, dtype=np.float32))


async def test_ring_selection_is_deterministic():
    s = LocalRankStrategy(replication=2)
    vols = ["0", "1", "2"]
    assert s.select_put_volume_ids("1", vols) == ["1", "2"]
    assert s.select_put_volume_ids("2", vols) == ["2", "0"]  # wraps
    with pytest.raises(ValueError, match="replication=4"):
        LocalRankStrategy(replication=4).select_put_volume_ids("0", vols)


async def test_replication_exceeding_volumes_rejected():
    with pytest.raises(ValueError, match="replication=3"):
        await ts.initialize(
            num_storage_volumes=2,
            strategy=LocalRankStrategy(replication=3),
            store_name="repl_bad",
        )


async def test_get_survives_volume_death(store):
    src = np.random.rand(64, 64).astype(np.float32)
    await ts.put("w", src, store_name=store)
    client = ts.client(store)
    located = await client.controller.locate_volumes.call_one(["w"])
    primary = sorted(located["w"])[0]
    await _kill_volume(store, primary)
    # First get may pay a diagnosis round trip; it must SUCCEED from the
    # surviving replica, not raise.
    out = await ts.get("w", store_name=store)
    np.testing.assert_array_equal(out, src)
    # And keep succeeding (dead volume now deprioritized).
    out = await ts.get("w", store_name=store)
    np.testing.assert_array_equal(out, src)


async def test_unreplicated_key_on_dead_volume_still_fails():
    # replication=1 control: a volume death LOSES its keys; the error must
    # surface rather than silently serving stale/empty data. The get retries
    # against the surviving volume until the retry deadline: 3 s here, not
    # the default 30, so tier 1 does not wait it out.
    from torchstore_tpu.config import RetryPolicy, StoreConfig

    await ts.initialize(
        num_storage_volumes=2,
        strategy=LocalRankStrategy(replication=1),
        store_name="repl1",
        config=StoreConfig(retry=RetryPolicy(deadline_s=3.0)),
    )
    try:
        await ts.put("only", np.ones(4), store_name="repl1")
        client = ts.client("repl1")
        located = await client.controller.locate_volumes.call_one(["only"])
        (vid,) = located["only"]
        await _kill_volume("repl1", vid)
        with pytest.raises((ActorDiedError, ConnectionError, OSError)):
            await ts.get("only", store_name="repl1")
    finally:
        await ts.shutdown("repl1")


async def test_sharded_replicated_roundtrip(store):
    # Each shard of a sharded key replicates; a resharded read assembles
    # from whichever replicas answer.
    full = np.arange(32.0, dtype=np.float32).reshape(4, 8)
    for row in range(4):
        sl = TensorSlice(
            offsets=(row, 0),
            local_shape=(1, 8),
            global_shape=(4, 8),
            coordinates=(row,),
            mesh_shape=(4,),
        )
        await ts.put("sh", Shard(full[row : row + 1], sl), store_name=store)
    out = await ts.get("sh", store_name=store)
    np.testing.assert_array_equal(out, full)


async def test_state_dict_replicated_with_failover(store):
    sd = {"a": np.random.rand(32).astype(np.float32), "b": np.arange(4)}
    await ts.put_state_dict("ck", sd, store_name=store)
    # Kill the primary (client id "0" -> volume "0" under LocalRank).
    await _kill_volume(store, "0")
    out = await ts.get_state_dict("ck", store_name=store)
    np.testing.assert_array_equal(out["a"], sd["a"])
    np.testing.assert_array_equal(out["b"], sd["b"])


async def test_bulk_transport_failover():
    # Volume death on the bulk transport surfaces as ConnectionError, not
    # ActorDiedError — failover must normalize and still serve from the
    # surviving replica.
    await ts.initialize(
        num_storage_volumes=3,
        strategy=LocalRankStrategy(replication=2, default_transport_type="bulk"),
        store_name="replb",
    )
    try:
        src = np.random.rand(1024).astype(np.float32)
        await ts.put("w", src, store_name="replb")
        client = ts.client("replb")
        located = await client.controller.locate_volumes.call_one(["w"])
        await _kill_volume("replb", sorted(located["w"])[0])
        out = await ts.get("w", store_name="replb")
        np.testing.assert_array_equal(out, src)
    finally:
        await ts.shutdown("replb")


async def test_degraded_overwrite_stays_consistent(store):
    """An overwrite that lands on only SOME replicas must not leave the
    failed replica serving the old value under committed metadata: the put
    succeeds at degraded redundancy and the stale copy is detached."""
    v1 = np.full(16, 1.0, np.float32)
    v2 = np.full(16, 2.0, np.float32)
    await ts.put("k", v1, store_name=store)
    client = ts.client(store)
    located = await client.controller.locate_volumes.call_one(["k"])
    replicas = sorted(located["k"])
    assert len(replicas) == 2
    await _kill_volume(store, replicas[1])
    # Overwrite: one replica is dead — the put succeeds (degraded) and the
    # dead replica's stale entry is detached from the index.
    await ts.put("k", v2, store_name=store)
    located = await client.controller.locate_volumes.call_one(["k"])
    assert replicas[1] not in located["k"]
    # Every read sees v2 — no divergence window.
    for _ in range(4):
        out = await ts.get("k", store_name=store)
        np.testing.assert_array_equal(out, v2)


async def test_delete_cleans_every_replica(store):
    await ts.put("gone", np.ones(4), store_name=store)
    await ts.delete("gone", store_name=store)
    assert not await ts.exists("gone", store_name=store)
    client = ts.client(store)
    located = await client.controller.locate_volumes.call_one(
        ["gone"], missing_ok=True
    )
    assert located == {}


async def test_reclaim_never_deletes_a_put_that_raced_it():
    """ADVICE r3 (medium): a put landing on the volume while the reclaim's
    delete is in flight must keep its bytes. The reclaim delete is
    conditional on the stale write generation: a racing put bumps the
    volume's generation, so the volume reports the key fresh instead of
    deleting an acknowledged overwrite — even when this volume is the only
    replica (controller-level deterministic re-enactment of the race)."""
    from torchstore_tpu.controller import Controller
    from torchstore_tpu.transport.types import Request, TensorMeta

    c = Controller()

    class FakeVolume:
        """Volume ref exposing only what the reclaim drainer touches, with
        a write-generation table mirroring StorageVolume's."""

        def __init__(self):
            self.kv = {}
            self.gens = {}
            self.deleted = []

        class _Ep:
            def __init__(self, fn):
                self.call_one = fn

        def __getattr__(self, name):
            return self._Ep(getattr(self, f"_{name}"))

        async def _delete_batch_if(self, items):
            removed, kept, kept_gens = [], [], {}
            for key, stale_gen in items:
                cur = self.gens.get(key)
                if cur is not None and cur > stale_gen:
                    kept.append(key)
                    kept_gens[key] = cur
                    continue
                if self.kv.pop(key, None) is not None:
                    removed.append(key)
                    self.deleted.append(key)
                self.gens.pop(key, None)
            return {"removed": removed, "kept_fresh": kept, "kept_gens": kept_gens}

    vol = FakeVolume()
    c.volume_refs = {"v0": vol}

    def meta(key="k"):
        req = Request.from_tensor(key, np.ones(4, np.float32))
        req.tensor_meta = TensorMeta(shape=(4,), dtype="float32")
        return req.meta_only()

    # v1 lands on v0 at gen 100 and is indexed with that generation.
    vol.kv["k"] = "v1-bytes"
    vol.gens["k"] = 100
    await c.notify_put_batch([meta()], "v0", write_gens={"v0": {"k": 100}})
    # v2's data-plane write to v0 FAILS -> detach + reclaim scheduled with
    # stale_gen=100. (Indexed on another volume so the key survives.)
    await c.notify_put_batch(
        [meta()], "v1", detach_volume_ids=["v0"],
        write_gens={"v1": {"k": 200}},
    )
    assert c._pending_reclaims["v0"] == {"k": 100}

    # THE RACE: before the reclaim drainer fires, a NEW put (v3) lands on
    # v0 (data plane, gen 300) but its controller notify has NOT arrived.
    vol.kv["k"] = "v3-bytes"
    vol.gens["k"] = 300

    # Drain the reclaim directly (skip the 1s backoff sleep).
    for task in list(c._reclaim_tasks):
        task.cancel()
    c._reclaim_running.discard("v0")
    pending = c._pending_reclaims["v0"]
    result = await vol._delete_batch_if(sorted(pending.items()))
    assert result == {
        "removed": [], "kept_fresh": ["k"], "kept_gens": {"k": 300},
    }
    assert vol.kv["k"] == "v3-bytes"  # the acknowledged put survived
    assert vol.deleted == []

    # Counter-case: with NO racing put the stale copy IS reclaimed.
    vol.kv["stale"] = "old-bytes"
    vol.gens["stale"] = 50
    result = await vol._delete_batch_if([("stale", 50)])
    assert result["removed"] == ["stale"] and "stale" not in vol.kv


async def test_reclaim_drainer_uses_conditional_delete():
    """End-to-end through the real drainer task: the controller's reclaim
    calls delete_batch_if with the captured stale generation; re-indexed
    keys are skipped outright; deleted keys drain pending."""
    from torchstore_tpu.controller import Controller
    from torchstore_tpu.transport.types import Request, TensorMeta

    c = Controller()
    calls = []

    class FakeVolume:
        class _Ep:
            def __init__(self, fn):
                self.call_one = fn

        def __getattr__(self, name):
            return self._Ep(getattr(self, f"_{name}"))

        async def _delete_batch_if(self, items):
            calls.append(items)
            return {
                "removed": [k for k, _ in items], "kept_fresh": [],
                "kept_gens": {},
            }

    c.volume_refs = {"v0": FakeVolume()}

    def meta():
        req = Request.from_tensor("k", np.ones(4, np.float32))
        req.tensor_meta = TensorMeta(shape=(4,), dtype="float32")
        return req.meta_only()

    await c.notify_put_batch([meta()], "v0", write_gens={"v0": {"k": 7}})
    await c.notify_put_batch(
        [meta()], "v1", detach_volume_ids=["v0"],
        write_gens={"v1": {"k": 8}},
    )
    # Simulate the racing put's notify arriving before the drainer fires:
    # the key re-indexes on v0 and the drainer must skip it entirely.
    await c.notify_put_batch([meta()], "v0", write_gens={"v0": {"k": 9}})
    for task in list(c._reclaim_tasks):
        await task
    assert calls == []  # re-indexed -> no delete at all

    # And when the key stays detached, the conditional delete carries the
    # captured stale generation.
    await c.notify_put_batch(
        [meta()], "v1", detach_volume_ids=["v0"],
        write_gens={"v1": {"k": 10}},
    )
    for task in list(c._reclaim_tasks):
        await task
    assert calls == [[("k", 9)]]
    assert c._pending_reclaims == {}


async def test_reclaim_requeues_kept_fresh_until_indexed_or_orphaned():
    """kept_fresh is NOT terminal: the drainer requeues the volume's
    reported generation, so (a) a put whose notify arrives is confirmed by
    the re-index check, and (b) an ORPHANED put (client died between
    data-plane ack and notify) is reclaimed on a later round instead of
    leaking unindexed bytes forever (code-review r4 finding)."""
    from torchstore_tpu.controller import Controller
    from torchstore_tpu.transport.types import Request, TensorMeta

    c = Controller()
    calls = []
    state = {"gen": 300, "deleted": []}

    class FakeVolume:
        class _Ep:
            def __init__(self, fn):
                self.call_one = fn

        def __getattr__(self, name):
            return self._Ep(getattr(self, f"_{name}"))

        async def _delete_batch_if(self, items):
            calls.append(items)
            removed, kept, kept_gens = [], [], {}
            for key, stale_gen in items:
                if state["gen"] > stale_gen:
                    kept.append(key)
                    kept_gens[key] = state["gen"]
                else:
                    removed.append(key)
                    state["deleted"].append(key)
            return {
                "removed": removed, "kept_fresh": kept,
                "kept_gens": kept_gens,
            }

    c.volume_refs = {"v0": FakeVolume()}

    def meta():
        req = Request.from_tensor("k", np.ones(4, np.float32))
        req.tensor_meta = TensorMeta(shape=(4,), dtype="float32")
        return req.meta_only()

    # Indexed at gen 100; detach schedules reclaim at stale_gen 100. The
    # volume holds ORPHANED gen-300 bytes whose notify never arrives.
    await c.notify_put_batch([meta()], "v0", write_gens={"v0": {"k": 100}})
    await c.notify_put_batch(
        [meta()], "v1", detach_volume_ids=["v0"],
        write_gens={"v1": {"k": 200}},
    )
    for task in list(c._reclaim_tasks):
        await task
    # Round 1: kept (300 > 100) -> requeued at 300; round 2: 300 <= 300 ->
    # deleted. The orphan is reclaimed, not leaked.
    assert calls[0] == [("k", 100)]
    assert calls[1] == [("k", 300)]
    assert state["deleted"] == ["k"]
    assert c._pending_reclaims == {}


async def test_reclaim_collects_partial_landings_two_phase():
    """A detached volume with NO prior indexed copy may still hold bytes
    from a partial batch landing. The reclaim schedules it at generation
    -1 and resolves two-phase: read the volume's current generation, then
    conditionally delete exactly those bytes (code-review r4 finding)."""
    from torchstore_tpu.controller import Controller
    from torchstore_tpu.transport.types import Request, TensorMeta

    c = Controller()
    state = {"gens": {"k": 77}, "kv": {"k": "partial-bytes"}, "calls": []}

    class FakeVolume:
        class _Ep:
            def __init__(self, fn):
                self.call_one = fn

        def __getattr__(self, name):
            return self._Ep(getattr(self, f"_{name}"))

        async def _write_gens(self, keys):
            state["calls"].append(("write_gens", list(keys)))
            return {k: state["gens"][k] for k in keys if k in state["gens"]}

        async def _delete_batch_if(self, items):
            state["calls"].append(("delete_if", items))
            removed = []
            for key, stale_gen in items:
                cur = state["gens"].get(key)
                if cur is not None and cur > stale_gen:
                    continue
                if state["kv"].pop(key, None) is not None:
                    removed.append(key)
                state["gens"].pop(key, None)
            return {"removed": removed, "kept_fresh": [], "kept_gens": {}}

    c.volume_refs = {"v0": FakeVolume()}

    def meta():
        req = Request.from_tensor("k", np.ones(4, np.float32))
        req.tensor_meta = TensorMeta(shape=(4,), dtype="float32")
        return req.meta_only()

    # First-ever put of k: landed on v1 but FAILED on v0 after a partial
    # landing — v0 was never indexed, yet holds bytes at gen 77.
    await c.notify_put_batch(
        [meta()], "v1", detach_volume_ids=["v0"],
        write_gens={"v1": {"k": 200}},
    )
    assert c._pending_reclaims["v0"] == {"k": -1}
    for task in list(c._reclaim_tasks):
        await task
    assert state["calls"] == [
        ("write_gens", ["k"]),
        ("delete_if", [("k", 77)]),
    ]
    assert state["kv"] == {}  # partial landing reclaimed, not leaked
    assert c._pending_reclaims == {}


async def test_reclaim_reconciles_clobbered_index_entries():
    """Safety net for the residual notify-in-flight race: if the index
    claims the volume holds a key the reclaim just deleted, the entry is
    detached loudly instead of routing readers at missing bytes."""
    from torchstore_tpu.controller import Controller
    from torchstore_tpu.transport.types import Request, TensorMeta

    c = Controller()

    def meta():
        req = Request.from_tensor("k", np.ones(4, np.float32))
        req.tensor_meta = TensorMeta(shape=(4,), dtype="float32")
        return req.meta_only()

    class FakeVolume:
        class _Ep:
            def __init__(self, fn):
                self.call_one = fn

        def __getattr__(self, name):
            return self._Ep(getattr(self, f"_{name}"))

        async def _delete_batch_if(self, items):
            # The delete removes the bytes; meanwhile (before the drainer
            # processes the result) the racing put's notify indexes v0.
            await c.notify_put_batch(
                [meta()], "v0", write_gens={"v0": {"k": 500}}
            )
            return {
                "removed": [k for k, _ in items], "kept_fresh": [],
                "kept_gens": {},
            }

    c.volume_refs = {"v0": FakeVolume()}
    await c.notify_put_batch([meta()], "v0", write_gens={"v0": {"k": 7}})
    await c.notify_put_batch(
        [meta()], "v1", detach_volume_ids=["v0"],
        write_gens={"v1": {"k": 8}},
    )
    for task in list(c._reclaim_tasks):
        await task
    # The clobbered entry is detached: only v1 serves k now.
    located = await c.locate_volumes(["k"])
    assert set(located["k"]) == {"v1"}
    assert c._pending_reclaims == {}


async def test_detached_stale_copy_reclaimed_and_not_served():
    """ADVICE r2 (medium): after a degraded replicated overwrite, the
    failed-but-ALIVE replica still holds the OLD bytes, and clients with
    warm location caches would read them. The controller must best-effort
    delete the stale copy once the replica recovers, so stale-cache reads
    fail over to the fresh value instead of silently serving v1."""
    import os
    import signal

    from torchstore_tpu.client import LocalClient
    from torchstore_tpu.config import StoreConfig

    await ts.initialize(
        num_storage_volumes=2,
        strategy=LocalRankStrategy(replication=2),
        store_name="reclaim",
        config=StoreConfig(rpc_timeout=2.0),
    )
    stopped = []
    try:
        v1 = np.full(8, 1.0, np.float32)
        v2 = np.full(8, 2.0, np.float32)
        await ts.put("k", v1, store_name="reclaim")
        client = ts.client("reclaim")
        # A second client with a WARM location cache for k.
        cli2 = LocalClient(client.controller, client._config)
        out = await cli2.get("k")
        np.testing.assert_array_equal(out, v1)
        assert "k" in cli2._loc_cache and len(cli2._loc_cache["k"]) == 2

        # Wedge volume "1" (alive but stuck) and overwrite at degraded
        # redundancy.
        from torchstore_tpu import api

        handle = api._stores["reclaim"]
        vmap = await client.controller.get_volume_map.call_one()
        target = vmap["1"]["ref"]
        proc = None
        for idx, ref in enumerate(handle.volume_mesh.refs):
            if (ref.host, ref.port, ref.name) == (
                target.host, target.port, target.name,
            ):
                proc = handle.volume_mesh._processes[idx]
        assert proc is not None
        os.kill(proc.pid, signal.SIGSTOP)
        stopped.append(proc.pid)
        await ts.put("k", v2, store_name="reclaim")
        located = await client.controller.locate_volumes.call_one(["k"])
        assert set(located["k"]) == {"0"}  # detached from the index

        # Recover the wedged replica. Two safe outcomes converge on v2:
        # (a) the wedged put's buffered RPC lands late — the volume then
        #     holds v2 at a FRESH write generation and the conditional
        #     reclaim keeps it (deleting it would destroy good bytes);
        # (b) it never lands — the reclaim deletes the stale v1 copy and
        #     pinned reads fail over to volume "0".
        # Either way a warm-cached client pinned to "1" must converge to
        # v2 and never be left serving v1.
        os.kill(proc.pid, signal.SIGCONT)
        stopped.clear()
        stale_pin = cli2._loc_cache["k"]["1"]
        deadline = asyncio.get_event_loop().time() + 30
        while True:
            cli2._loc_cache["k"] = {"1": stale_pin}  # re-pin each probe
            out2 = await cli2.get("k")
            if (out2 == v2).all():
                break
            np.testing.assert_array_equal(out2, v1)  # only other legal value
            assert asyncio.get_event_loop().time() < deadline, (
                "pinned stale-cache read never converged to v2"
            )
            await asyncio.sleep(0.5)
        # And the reclaim machinery has fully drained (kept-fresh or
        # deleted, nothing pending).
        deadline = asyncio.get_event_loop().time() + 30
        while (await client.controller.stats.call_one()).get(
            "pending_reclaims"
        ):
            assert asyncio.get_event_loop().time() < deadline, (
                "reclaim never drained"
            )
            await asyncio.sleep(0.5)
    finally:
        for pid in stopped:
            try:
                os.kill(pid, signal.SIGCONT)
            except ProcessLookupError:
                pass
        await ts.shutdown("reclaim")
