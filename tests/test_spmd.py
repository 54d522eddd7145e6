"""SPMD bootstrap tests: env parsing/validation + full multi-process
lifecycle (rendezvous, per-host volume spawn, handle broadcast, cross-rank
put/get, two-phase shutdown) — reference tests/test_spmd.py mechanisms."""

import asyncio
import json
import multiprocessing as mp
import os

import numpy as np
import pytest

from torchstore_tpu.spmd import SPMDEnv
from torchstore_tpu.utils import get_free_port


class TestSPMDEnv:
    def _env(self, **kw):
        base = {
            "RANK": "1",
            "WORLD_SIZE": "4",
            "LOCAL_RANK": "1",
            "LOCAL_WORLD_SIZE": "4",
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": "29500",
        }
        base.update(kw)
        return base

    def test_parse(self, monkeypatch):
        for k, v in self._env().items():
            monkeypatch.setenv(k, v)
        env = SPMDEnv.from_env()
        assert env.rank == 1 and env.world_size == 4
        assert env.num_hosts == 1 and env.host_rank == 0

    def test_multi_host_derivation(self, monkeypatch):
        for k, v in self._env(
            RANK="5", WORLD_SIZE="8", LOCAL_RANK="1", LOCAL_WORLD_SIZE="4"
        ).items():
            monkeypatch.setenv(k, v)
        env = SPMDEnv.from_env()
        assert env.num_hosts == 2 and env.host_rank == 1

    def test_missing_vars(self, monkeypatch):
        monkeypatch.delenv("RANK", raising=False)
        monkeypatch.delenv("MASTER_ADDR", raising=False)
        with pytest.raises(RuntimeError, match="missing"):
            SPMDEnv.from_env()

    def test_rank_out_of_range(self, monkeypatch):
        for k, v in self._env(RANK="4").items():
            monkeypatch.setenv(k, v)
        with pytest.raises(ValueError, match="out of range"):
            SPMDEnv.from_env()

    def test_world_not_divisible(self, monkeypatch):
        for k, v in self._env(WORLD_SIZE="6", LOCAL_WORLD_SIZE="4", RANK="0", LOCAL_RANK="0").items():
            monkeypatch.setenv(k, v)
        with pytest.raises(ValueError, match="divisible"):
            SPMDEnv.from_env()


def _durable_worker(rank: int, world: int, port: int, result_dir: str, phase: str) -> None:
    os.environ.update(
        {
            "RANK": str(rank),
            "LOCAL_RANK": str(rank),
            "WORLD_SIZE": str(world),
            "LOCAL_WORLD_SIZE": str(world),
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
        }
    )
    result = {"rank": rank, "ok": False}
    try:
        asyncio.run(_durable_scenario(rank, world, result_dir, phase, result))
    except Exception as exc:  # noqa: BLE001
        import traceback

        result["error"] = f"{exc!r}\n{traceback.format_exc()}"
    with open(os.path.join(result_dir, f"{phase}_rank_{rank}.json"), "w") as f:
        json.dump(result, f)


async def _durable_scenario(rank, world, result_dir, phase, result):
    import torchstore_tpu as ts

    storage = os.path.join(result_dir, "storage")
    if phase == "write":
        await ts.initialize_spmd(store_name="dspmd", storage_dir=storage)
        await ts.put(f"r{rank}", np.full(4, float(rank)), store_name="dspmd")
        await ts.barrier("puts", store_name="dspmd")
        from torchstore_tpu.spmd import _spmd_sessions

        session = _spmd_sessions["dspmd"]
        # Drain ack: non-zero ranks confirm they have no in-flight
        # rendezvous requests before rank 0 (which HOSTS the rendezvous)
        # simulates its crash — otherwise killing the server races their
        # barrier replies.
        if rank != 0:
            await session.client.add("drained", 1)
        else:
            await session.client.wait_counter("drained", world - 1)
        # SIMULATED CRASH: exit without collective shutdown (volumes are
        # children and die with us; data must persist on disk).
        if session.volume_mesh is not None:
            for proc in session.volume_mesh._processes:
                proc.terminate()
        result["ok"] = True
        return
    # phase == "recover": fresh world over the same storage dir.
    await ts.initialize_spmd(store_name="dspmd", storage_dir=storage, recover=True)
    for other in range(world):
        out = await ts.get(f"r{other}", store_name="dspmd")
        assert out[0] == float(other), (other, out)
    await ts.barrier("reads", store_name="dspmd")
    await ts.shutdown("dspmd")
    result["ok"] = True


def test_spmd_durable_recovery(tmp_path):
    world = 2
    for phase in ("write", "recover"):
        port = get_free_port()
        ctx = mp.get_context("spawn")
        procs = [
            ctx.Process(
                target=_durable_worker,
                args=(r, world, port, str(tmp_path), phase),
                daemon=False,
            )
            for r in range(world)
        ]
        for p in procs:
            p.start()
        try:
            for p in procs:
                p.join(timeout=180)
                assert not p.is_alive(), f"{phase} worker hung"
        finally:
            for p in procs:
                if p.is_alive():
                    p.terminate()
        for r in range(world):
            result = json.loads((tmp_path / f"{phase}_rank_{r}.json").read_text())
            assert result["ok"], f"{phase} rank {r}: {result.get('error')}"


async def test_rendezvous_kv():
    from torchstore_tpu.runtime.rendezvous import RendezvousClient, RendezvousServer

    server = RendezvousServer()
    port = await server.start("127.0.0.1", 0)
    a = RendezvousClient("127.0.0.1", port)
    b = RendezvousClient("127.0.0.1", port)
    await a.connect()
    await b.connect()
    try:
        # Blocking get resolves once the other client sets.
        get_task = asyncio.ensure_future(b.get("k"))
        await asyncio.sleep(0.05)
        assert not get_task.done()
        await a.set("k", {"v": 1})
        assert await get_task == {"v": 1}
        assert await a.add("c", 2) == 2
        assert await b.add("c", 3) == 5
        await a.wait_counter("c", 5)
        assert await b.check("k") and not await b.check("nope")
        await asyncio.gather(a.barrier("x", 2), b.barrier("x", 2))
    finally:
        await a.close()
        await b.close()
        await server.stop()


def _spmd_worker(
    rank: int,
    world: int,
    port: int,
    result_dir: str,
    local_world: int = 0,
    secret: "str | None" = None,
) -> None:
    local_world = local_world or world
    env = {
        "RANK": str(rank),
        "LOCAL_RANK": str(rank % local_world),
        "WORLD_SIZE": str(world),
        "LOCAL_WORLD_SIZE": str(local_world),
        "MASTER_ADDR": "127.0.0.1",
        "MASTER_PORT": str(port),
    }
    if local_world != world:
        # Emulated multi-host on one machine: volumes bind 0.0.0.0; the
        # advertised address must still be reachable.
        env["TORCHSTORE_TPU_ADVERTISE_HOST"] = "127.0.0.1"
    if secret:
        env["TORCHSTORE_TPU_AUTH_SECRET"] = secret
    os.environ.update(env)
    result = {"rank": rank, "ok": False}
    try:
        asyncio.run(_spmd_scenario(rank, world, result))
    except Exception as exc:  # noqa: BLE001 - reported to parent
        import traceback

        result["error"] = f"{exc!r}\n{traceback.format_exc()}"
    with open(os.path.join(result_dir, f"rank_{rank}.json"), "w") as f:
        json.dump(result, f)


async def _spmd_scenario(rank: int, world: int, result: dict) -> None:
    import torchstore_tpu as ts

    await ts.initialize_spmd(store_name="spmdtest")
    # Each rank publishes its shard of a global array + a rank tensor.
    g = np.arange(float(world * 4), dtype=np.float32).reshape(world, 4)
    sl = ts.TensorSlice(
        offsets=(rank, 0), local_shape=(1, 4), global_shape=(world, 4),
        coordinates=(rank,), mesh_shape=(world,),
    )
    await ts.put("g", ts.Shard(g[rank : rank + 1], sl), store_name="spmdtest")
    await ts.put(f"r{rank}", np.full(2, float(rank)), store_name="spmdtest")
    await ts.barrier("puts_done", store_name="spmdtest")
    other = (rank + 1) % world
    peer = await ts.get(f"r{other}", store_name="spmdtest")
    assert peer[0] == float(other), peer
    full = await ts.get("g", store_name="spmdtest")
    np.testing.assert_array_equal(full, g)
    await ts.barrier("reads_done", store_name="spmdtest")
    await ts.shutdown("spmdtest")
    result["ok"] = True


def _channel_worker(rank: int, world: int, port: int, result_dir: str) -> None:
    os.environ.update(
        {
            "RANK": str(rank),
            "LOCAL_RANK": str(rank),
            "WORLD_SIZE": str(world),
            "LOCAL_WORLD_SIZE": str(world),
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
        }
    )
    result = {"rank": rank, "ok": False}
    try:
        asyncio.run(_channel_scenario(rank, world, result))
    except Exception as exc:  # noqa: BLE001 - reported to parent
        import traceback

        result["error"] = f"{exc!r}\n{traceback.format_exc()}"
    with open(os.path.join(result_dir, f"rank_{rank}.json"), "w") as f:
        json.dump(result, f)


async def _channel_scenario(rank: int, world: int, result: dict) -> None:
    """Versioned weight channel across SPMD ranks: rank 0 publishes, every
    other rank block-acquires each version (wait_for_change over real RPC,
    no polling) — the RL trainer/generator topology under torchrun."""
    import torchstore_tpu as ts

    await ts.initialize_spmd(store_name="chspmd")
    versions = 3
    if rank == 0:
        pub = ts.WeightPublisher("policy", store_name="chspmd", keep=versions)
        for v in range(versions):
            await pub.publish({"w": np.full(8, float(v), np.float32)})
            await asyncio.sleep(0.05)
    else:
        sub = ts.WeightSubscriber("policy", store_name="chspmd")
        got = []
        while len(got) < 1 or got[-1] < versions - 1:
            sd, v = await sub.acquire(timeout=60.0)
            assert sd["w"][0] == float(v), (v, sd["w"][0])
            got.append(v)
        assert got == sorted(got), got
    await ts.barrier("channel_done", store_name="chspmd")
    await ts.shutdown("chspmd")
    result["ok"] = True


def _device_sync_worker(rank: int, world: int, port: int, result_dir: str) -> None:
    os.environ.update(
        {
            "RANK": str(rank),
            "LOCAL_RANK": str(rank),
            "WORLD_SIZE": str(world),
            "LOCAL_WORLD_SIZE": str(world),
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        }
    )
    result = {"rank": rank, "ok": False}
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
        asyncio.run(_device_sync_scenario(rank, world, result))
    except Exception as exc:  # noqa: BLE001 - reported to parent
        import traceback

        result["error"] = f"{exc!r}\n{traceback.format_exc()}"
    with open(os.path.join(result_dir, f"rank_{rank}.json"), "w") as f:
        json.dump(result, f)


async def _device_sync_scenario(rank: int, world: int, result: dict) -> None:
    """Multi-rank SPMD DEVICE-path direct sync (VERDICT r2 item 1): two
    publisher processes each own a disjoint 4-device subset and publish
    their half of the model direct=True; the consumer (rank 0) pulls the
    merged dict over the device path — per-rank transfer servers, zero host
    staging on any source."""
    import jax

    import torchstore_tpu as ts

    await ts.initialize_spmd(store_name="devsync")
    w = np.arange(128.0, dtype=np.float32).reshape(16, 8)
    devs = jax.devices()
    if rank > 0:
        r = rank - 1  # publisher rank within the 2-rank source world
        sub = np.array(devs[4 * r : 4 * r + 4], dtype=object)
        mesh = jax.sharding.Mesh(sub.reshape(4), ("x",))
        sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("x"))
        local = jax.device_put(jax.numpy.asarray(w[8 * r : 8 * r + 8]), sh)
        sl = ts.TensorSlice(
            offsets=(8 * r, 0), local_shape=(8, 8), global_shape=(16, 8),
            coordinates=(r,), mesh_shape=(2,),
        )
        await ts.put_state_dict(
            "policy", {"w": ts.Shard(local, sl)}, direct=True,
            rank=r, num_ranks=2, store_name="devsync",
        )
        await ts.barrier("published", store_name="devsync")
        # Keep serving until the consumer confirms its pull.
        await ts.barrier("pulled", store_name="devsync")
    else:
        await ts.barrier("published", store_name="devsync")
        # Zero host staging: all-jax sources register on the device rung.
        for r in (0, 1):
            published = await ts.get(f"policy/rank_{r}", store_name="devsync")
            assert published["handles"] == {}, "host buffers on device path"
            assert published["device"] is not None
        mesh8 = jax.sharding.Mesh(
            np.array(devs, dtype=object).reshape(8), ("x",)
        )
        tgt = jax.sharding.NamedSharding(mesh8, jax.sharding.PartitionSpec("x"))
        out = await ts.get_state_dict(
            "policy",
            user_state_dict={
                "w": jax.ShapeDtypeStruct(
                    (16, 8), jax.numpy.float32, sharding=tgt
                )
            },
            direct=True,
            store_name="devsync",
        )
        assert out["w"].sharding == tgt
        np.testing.assert_array_equal(np.asarray(out["w"]), w)
        await ts.barrier("pulled", store_name="devsync")
    await ts.shutdown("devsync")
    result["ok"] = True


def test_spmd_multi_rank_device_sync(tmp_path):
    world = 3  # rank 0 consumes; ranks 1-2 publish as source ranks 0-1
    port = get_free_port()
    ctx = mp.get_context("spawn")
    procs = [
        ctx.Process(
            target=_device_sync_worker,
            args=(r, world, port, str(tmp_path)),
            daemon=False,
        )
        for r in range(world)
    ]
    for p in procs:
        p.start()
    try:
        for p in procs:
            p.join(timeout=180)
            assert not p.is_alive(), "device-sync worker hung"
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
    for r in range(world):
        path = tmp_path / f"rank_{r}.json"
        assert path.exists(), f"rank {r} produced no result"
        result = json.loads(path.read_text())
        assert result["ok"], f"rank {r} failed: {result.get('error')}"


def test_spmd_weight_channel(tmp_path):
    world = 3
    port = get_free_port()
    ctx = mp.get_context("spawn")
    procs = [
        ctx.Process(
            target=_channel_worker,
            args=(r, world, port, str(tmp_path)),
            daemon=False,
        )
        for r in range(world)
    ]
    for p in procs:
        p.start()
    try:
        for p in procs:
            p.join(timeout=180)
            assert not p.is_alive(), "channel worker hung"
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
    for r in range(world):
        path = tmp_path / f"rank_{r}.json"
        assert path.exists(), f"rank {r} produced no result"
        result = json.loads(path.read_text())
        assert result["ok"], f"rank {r} failed: {result.get('error')}"


@pytest.mark.parametrize(
    "world,local_world,secret",
    [
        (2, 2, None),
        (4, 4, None),
        (4, 2, None),
        # Multi-host WITH connection auth: every listener (rendezvous,
        # actors, bulk) requires the HMAC challenge end to end.
        (4, 2, "spmd-secret"),
    ],
    ids=["1host-2rank", "1host-4rank", "2hosts-2ranks", "2hosts-auth"],
)
def test_spmd_full_lifecycle(tmp_path, world, local_world, secret):
    port = get_free_port()
    ctx = mp.get_context("spawn")
    # Not daemonic: workers spawn their own volume actor children.
    procs = [
        ctx.Process(
            target=_spmd_worker,
            args=(r, world, port, str(tmp_path), local_world, secret),
            daemon=False,
        )
        for r in range(world)
    ]
    for p in procs:
        p.start()
    try:
        for p in procs:
            p.join(timeout=180)
            assert not p.is_alive(), "spmd worker hung"
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
    for r in range(world):
        path = tmp_path / f"rank_{r}.json"
        assert path.exists(), f"rank {r} produced no result"
        result = json.loads(path.read_text())
        assert result["ok"], f"rank {r} failed: {result.get('error')}"
