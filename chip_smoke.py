"""Chip smoke: the trainer -> store -> generator weight sync on the TPU.

Drives the system's main path once, through the entry points a user calls,
with every tensor at the published Llama-3-8B shape and the arrays resident
in HBM. ONE process owns the chip and plays both roles; the controller and
the storage volume are the usual host-only actor children of
``ts.initialize()``. The only cut is depth (``LAYERS`` of 32; weights are
random, from ``SEED``).

    python chip_smoke.py            # one chip: buffered sync, overwrite
                                    # hazard, direct (one-hop) sync
    python chip_smoke.py --chips 4  # four chips: ONLY the reshard across
                                    # meshes and its in-process comparison

There is no CPU mode: without a TPU the script exits non-zero and prints no
result. The phases are plain functions of a config and a device list, so
tests/test_chip_smoke.py runs them at ``LlamaConfig.tiny()`` on CPU devices.
Timings printed here are information, not claims. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

jax is imported inside functions only: the actor children re-import this
file as their ``__main__`` and must stay off the chip.
"""

import argparse
import asyncio
import dataclasses
import faulthandler
import functools
import json
import math
import os
import shutil
import signal
import sys
import threading
import time
import traceback

STORE = "chip_smoke"
SEED = 0
LAYERS = 4  # of 32: the only cut (reduced: layers)
TRAIN_STEPS = 3
TRAIN_SEQ = 512
LEARNING_RATE = 0.05
PROMPT_LEN = 16
NEW_TOKENS = 4
# A phase that blocks inside the runtime cannot be cancelled from Python:
# past this many seconds every thread's stack goes to stderr, every child
# process is killed and the process exits non-zero, inside the 1200 s a run
# is allowed.
WATCHDOG_S = 1100


def say(msg: str) -> None:
    print(msg, flush=True)


def smoke_config():
    """Llama-3-8B at its published widths, ``LAYERS`` deep, bf16 weights."""
    import jax.numpy as jnp

    from torchstore_tpu.models.llama import LlamaConfig

    return dataclasses.replace(
        LlamaConfig.llama3_8b(), num_layers=LAYERS, param_dtype=jnp.bfloat16
    )


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------


def _init_fn(cfg):
    import jax.numpy as jnp

    from torchstore_tpu.models.llama import Llama

    return lambda rng: Llama(cfg).init(rng, jnp.zeros((1, 8), jnp.int32))


def placements(cfg, devices, mesh_axes=None):
    """The sharding of every param (a tree shaped like the unboxed params):
    all on ``devices[0]``, or spread over ``make_mesh(mesh_axes, devices)``
    by the model's logical axes."""
    import jax

    from torchstore_tpu import parallel

    boxed = jax.eval_shape(_init_fn(cfg), jax.random.key(SEED))
    if mesh_axes is None:
        single = jax.sharding.SingleDeviceSharding(devices[0])
        return jax.tree.map(lambda _: single, parallel.unbox(boxed))
    return parallel.param_shardings(boxed, parallel.make_mesh(mesh_axes, devices))


def init_params(cfg, devices, mesh_axes=None):
    """Seeded params, created already placed. Returns ``(params,
    shardings)``."""
    import jax

    from torchstore_tpu import parallel

    init = _init_fn(cfg)
    shardings = placements(cfg, devices, mesh_axes)
    params = jax.jit(
        lambda rng: parallel.unbox(init(rng)), out_shardings=shardings
    )(jax.random.key(SEED))
    return params, shardings


def as_targets(tree, shardings):
    """Acquire targets that hold nothing on the device: one sharded
    ``ShapeDtypeStruct`` per leaf."""
    import jax

    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree,
        shardings,
    )


def tree_nbytes(tree) -> int:
    """Bytes of a tree of arrays (or of their shapes)."""
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


@functools.cache
def _same_bits():
    import jax
    import jax.numpy as jnp

    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}

    @jax.jit
    def same(a, b):
        as_bits = uint[a.dtype.itemsize]
        return jnp.array_equal(
            jax.lax.bitcast_convert_type(a, as_bits),
            jax.lax.bitcast_convert_type(b, as_bits),
        )

    return same


def mismatched_leaves(got, want) -> list:
    """Paths of the leaves of ``got`` that are not BITWISE equal to
    ``want``'s (compared on the device, as unsigned integers: a NaN equals
    itself, -0.0 differs from 0.0)."""
    import jax

    same = _same_bits()
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    if len(flat_got) != len(flat_want):
        raise AssertionError(
            f"{len(flat_got)} leaves acquired, {len(flat_want)} expected"
        )
    verdicts = [
        (path, a.shape == b.shape and a.dtype == b.dtype and same(a, b))
        for (path, a), b in zip(flat_got, flat_want)
    ]
    return [jax.tree_util.keystr(path) for path, ok in verdicts if not bool(ok)]


def require(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def require_on(tree, devices, what: str) -> None:
    """Every leaf is a ``jax.Array`` whose shards sit exactly on
    ``devices``, each shard of the shape its sharding prescribes."""
    import jax

    want = set(devices)
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        name = f"{what}{jax.tree_util.keystr(path)}"
        require(isinstance(x, jax.Array), f"{name} is {type(x).__name__}")
        on = [s.device for s in x.addressable_shards]
        require(
            len(on) == len(want) and set(on) == want,
            f"{name} sits on {sorted(d.id for d in on)}, "
            f"expected {sorted(d.id for d in want)}",
        )
        shard_shape = x.sharding.shard_shape(x.shape)
        for s in x.addressable_shards:
            require(
                s.data.shape == shard_shape,
                f"{name} shard on {s.device} is {s.data.shape}, "
                f"expected {shard_shape}",
            )


def counter(name: str, **labels) -> float:
    """This process's value of one store metric (0 before its first use)."""
    import torchstore_tpu as ts

    series = ts.metrics_snapshot().get(name, {}).get("series", [])
    return sum(
        s["value"]
        for s in series
        if all(s["labels"].get(k) == v for k, v in labels.items())
    )


async def timed(awaitable):
    """(result, seconds) of an awaitable whose device work has finished."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(await awaitable)
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


async def buffered_phase(cfg, devices) -> None:
    """Trainer steps on ``devices[0]``; after each one the weights go through
    the versioned channel — D2H, shm transport, a real volume — and come
    back into the generator's device-resident params, which then decode."""
    import jax
    import optax

    import torchstore_tpu as ts
    from torchstore_tpu import parallel
    from torchstore_tpu.models.generate import Decoder
    from torchstore_tpu.models.llama import Llama

    dev = devices[0]
    model = Llama(cfg)
    params, shardings = init_params(cfg, devices)
    nbytes = tree_nbytes(params)
    optimizer = optax.sgd(LEARNING_RATE)
    opt_state = optimizer.init(params)
    train_step = parallel.make_train_step(model, optimizer)
    forward = jax.jit(model.apply)
    decoder = Decoder(cfg, max_len=PROMPT_LEN + NEW_TOKENS)
    # One batch for every step: a loss that moves then shows the weights did.
    tokens = jax.device_put(
        jax.random.randint(
            jax.random.key(SEED + 1), (1, TRAIN_SEQ + 1), 0, cfg.vocab_size
        ),
        dev,
    )
    prompt = tokens[:, :PROMPT_LEN]

    publisher = ts.WeightPublisher("policy", store_name=STORE)
    subscriber = ts.WeightSubscriber("policy", store_name=STORE)
    # The first acquire lands in bare specs; every later one in the
    # generator's own resident params, as a running generator would.
    generator = as_targets(params, shardings)
    shm_put_bytes = functools.partial(
        counter, "ts_transport_bytes_total", transport="shm", op="put"
    )
    losses = []
    for step in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        params, opt_state, loss = train_step(params, opt_state, tokens)
        losses.append(float(loss))
        t_step = time.perf_counter() - t0
        if step:
            require(
                mismatched_leaves(generator, params),
                f"train step {step} left every weight unchanged",
            )
        shm_before = shm_put_bytes()
        version, t_pub = await timed(publisher.publish({"params": params}))
        moved = shm_put_bytes() - shm_before
        require(
            moved >= nbytes,
            f"publish moved {moved} B over shm, the weights are {nbytes} B",
        )
        (synced, got_version), t_acq = await timed(
            subscriber.acquire(
                user_state_dict={"params": generator}, timeout=600.0
            )
        )
        generator = synced["params"]
        require(got_version == version, f"acquired v{got_version}, not v{version}")
        require_on(generator, [dev], "generator")
        bad = mismatched_leaves(generator, params)
        require(not bad, f"v{version}: leaves differ from the trainer's: {bad}")

        logits = forward(generator, prompt)
        require(
            logits.shape == (1, PROMPT_LEN, cfg.vocab_size),
            f"logits shape {logits.shape}",
        )
        require(bool(jax.numpy.isfinite(logits).all()), "non-finite logits")
        require(
            not mismatched_leaves(logits, forward(params, prompt)),
            "generator logits differ from the trainer's forward",
        )
        out = decoder.generate(generator, prompt, NEW_TOKENS)
        new = out[0, PROMPT_LEN:].tolist()
        require(
            out.shape == (1, PROMPT_LEN + NEW_TOKENS)
            and all(0 <= t < cfg.vocab_size for t in new),
            f"bad generation {out.shape} {new}",
        )
        require(
            new == decoder.generate(params, prompt, NEW_TOKENS)[0, PROMPT_LEN:].tolist(),
            "generator decodes differently from the trainer's weights",
        )
        say(
            f"buffered step {step}: loss {losses[-1]:.4f} "
            f"(train step {t_step:.2f} s) | publish v{version} {t_pub:.2f} s"
            f" | acquire {t_acq:.2f} s | {nbytes} B each way, "
            f"bitwise equal | greedy tokens {new}"
        )
    require(all(map(math.isfinite, losses)), f"non-finite loss: {losses}")
    require(
        all(a != b for a, b in zip(losses, losses[1:])),
        f"loss did not change between steps: {losses}",
    )
    await publisher.close(delete=True)


async def overwrite_hazard_phase(cfg, devices) -> None:
    """Does the read lease of a zero-copy shm view outlive the H2D copy that
    reads it? ``jax.device_put`` returns before the DMA has read the host
    bytes, so: put A, get it into device targets WITHOUT waiting, overwrite
    the key with B twice (the volume's rotated segment gets recycled), and
    only then look at the first tree. It must still be exactly A."""
    import jax
    import numpy as np

    import torchstore_tpu as ts

    key = "policy/live"
    a, shardings = init_params(cfg, devices)
    targets = as_targets(a, shardings)
    b = jax.tree.map(lambda x: np.ones(x.shape, x.dtype), a)
    await ts.put_state_dict(key, {"params": a}, store_name=STORE)
    first = await ts.get_state_dict(
        key, user_state_dict={"params": targets}, store_name=STORE
    )
    for _ in range(2):
        await ts.put_state_dict(key, {"params": b}, store_name=STORE)
    jax.block_until_ready(first)
    bad = mismatched_leaves(first["params"], a)
    require(not bad, f"overwrite hazard: leaves of A changed under B: {bad}")
    del first
    second = await ts.get_state_dict(
        key, user_state_dict={"params": targets}, store_name=STORE
    )
    require_on(second["params"], devices[:1], "overwritten")
    bad = mismatched_leaves(second["params"], b)
    require(not bad, f"after the overwrite the key does not serve B: {bad}")
    await ts.delete_prefix(key, store_name=STORE)
    say(
        f"overwrite hazard: {tree_nbytes(a)} B read into device targets, key "
        "overwritten twice before the H2D was awaited: first tree still "
        "exactly A, key serves B: held"
    )


async def direct_phase(cfg, devices) -> None:
    """One-hop sync of the all-jax dict into device targets; a republish of
    new values is what the next pull sees. WHICH rung serves is the
    store's own decision (``device_transfer.serves``): the device rung
    (``jax.experimental.transfer``) where the runtime serves the platform —
    the CPU of tier 1 — and host staging buffers where it does not — the
    TPU, see ``device_transfer.SERVED_PLATFORMS``. Either way the phase
    holds the store to the rung it chose."""
    import jax

    import torchstore_tpu as ts
    from torchstore_tpu.transport import device_transfer

    key = "policy/direct"
    params, shardings = init_params(cfg, devices)
    targets = as_targets(params, shardings)
    nbytes = tree_nbytes(params)
    n_leaves = len(jax.tree.leaves(params))
    on_device_rung = all(map(device_transfer.serves, jax.tree.leaves(params)))
    rung = (
        "device (jax.experimental.transfer)"
        if on_device_rung
        else "host-staged (shm staging buffers)"
    )
    negate = jax.jit(
        lambda tree: jax.tree.map(jax.numpy.negative, tree), donate_argnums=0
    )
    for round_ in range(2):
        if round_:
            params = negate(params)  # new arrays: the second put REFRESHES
        _, t_put = await timed(
            ts.put_state_dict(key, {"params": params}, direct=True, store_name=STORE)
        )
        published = await ts.get(f"{key}/rank_0", store_name=STORE)
        require(
            (published.get("device") is not None) == on_device_rung
            and len(published["handles"]) == (0 if on_device_rung else n_leaves),
            f"expected the {rung} rung, but the source published "
            f"{len(published['handles'])} host handles and device info "
            f"{'present' if published.get('device') else 'absent'}",
        )
        pulls_before = counter("ts_device_pull_ops_total")
        out, t_get = await timed(
            ts.get_state_dict(
                key,
                user_state_dict={"params": targets},
                direct=True,
                store_name=STORE,
            )
        )
        require(
            (counter("ts_device_pull_ops_total") > pulls_before) == on_device_rung,
            f"ts_device_pull_ops_total disagrees with the {rung} rung",
        )
        require_on(out["params"], devices[:1], "direct")
        bad = mismatched_leaves(out["params"], params)
        require(not bad, f"direct round {round_}: leaves differ: {bad}")
        del out
        say(
            f"direct round {round_}: rung served = {rung} | put "
            f"{t_put:.2f} s | pull {t_get:.2f} s | {nbytes} B, bitwise equal"
        )


async def reshard_phase(cfg, devices) -> None:
    """Across meshes on four devices: fsdp-sharded trainer params go through
    the store and come back tensor-parallel, plus one 2x2 -> 1x4 array as in
    examples/reshard.py; both compared bitwise with the in-process
    ``parallel.reshard`` of the same arrays."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchstore_tpu as ts
    from torchstore_tpu import parallel

    require(len(devices) == 4, f"reshard phase needs 4 devices, got {len(devices)}")
    src, _ = init_params(cfg, devices, {"fsdp": 4})
    require_on(src, devices, "trainer")
    dst_shardings = placements(cfg, devices, {"tp": 4})
    _, t_put = await timed(
        ts.put_state_dict("policy/fsdp", {"params": src}, store_name=STORE)
    )
    got, t_get = await timed(
        ts.get_state_dict(
            "policy/fsdp",
            user_state_dict={"params": as_targets(src, dst_shardings)},
            store_name=STORE,
        )
    )
    require_on(got["params"], devices, "resharded")
    want = jax.tree.map(parallel.reshard, src, dst_shardings)
    for g, w in zip(jax.tree.leaves(got["params"]), jax.tree.leaves(want)):
        require(g.sharding == w.sharding, f"{g.sharding} != {w.sharding}")
    bad = mismatched_leaves(got["params"], want)
    require(not bad, f"fsdp -> tp through the store differs from reshard: {bad}")
    say(
        f"reshard fsdp:4 -> tp:4: {tree_nbytes(src)} B | put {t_put:.2f} s | "
        f"get {t_get:.2f} s | every leaf on 4 devices, bitwise equal to the "
        "in-process reshard"
    )
    del got, want, src

    # One MLP-matrix-sized array, 2x2 -> 1x4 with the spec transposed.
    rows, cols = cfg.hidden_size, cfg.intermediate_size
    w = jax.device_put(
        jax.random.normal(jax.random.key(SEED + 2), (rows, cols), jnp.bfloat16),
        NamedSharding(parallel.make_mesh({"x": 2, "y": 2}, devices), P("x", "y")),
    )
    dst = NamedSharding(parallel.make_mesh({"a": 1, "b": 4}, devices), P("b", "a"))
    await ts.put("policy/w", w, store_name=STORE)
    out = await ts.get(
        "policy/w",
        like=jax.ShapeDtypeStruct(w.shape, w.dtype, sharding=dst),
        store_name=STORE,
    )
    require_on(out, devices, "2x2->1x4")
    require(out.sharding == dst, f"{out.sharding} != {dst}")
    require(
        not mismatched_leaves(out, parallel.reshard(w, dst)),
        "2x2 -> 1x4 through the store differs from reshard",
    )
    say(
        f"reshard 2x2 -> 1x4: ({rows}, {cols}) bf16, shards "
        f"{dst.shard_shape(w.shape)} on 4 devices, bitwise equal"
    )


# --------------------------------------------------------------------------
# the process tree
# --------------------------------------------------------------------------


def descendants(root: int) -> dict[int, int]:
    """{pid: depth below ``root``} of every live descendant (Linux /proc)."""
    children: dict[int, list[int]] = {}
    for entry in os.listdir("/proc"):
        if entry.isdigit():
            try:
                with open(f"/proc/{entry}/stat") as f:
                    fields = f.read().rsplit(")", 1)[1].split()
            except OSError:
                continue  # exited while we looked
            if fields[0] != "Z":
                children.setdefault(int(fields[1]), []).append(int(entry))
    out: dict[int, int] = {}
    stack = [(root, 0)]
    while stack:
        pid, depth = stack.pop()
        for child in children.get(pid, []):
            out[child] = depth + 1
            stack.append((child, depth + 1))
    return out


def actor_pids() -> list[int]:
    """The store's actor processes: forked BY multiprocessing's fork server,
    so they sit two levels below this process."""
    return sorted(p for p, depth in descendants(os.getpid()).items() if depth >= 2)


def require_host_only(pids: list[int]) -> None:
    """No actor child may have initialised a jax backend: the chip belongs
    to this process. The TPU runtime is a shared library a process maps
    only when its backend starts."""
    require(pids, "ts.initialize() started no actor process")
    for pid in pids:
        with open(f"/proc/{pid}/maps") as f:
            require(
                "libtpu" not in f.read(),
                f"actor process {pid} loaded the TPU runtime",
            )


async def require_gone(pids: list[int]) -> None:
    deadline = time.monotonic() + 30.0
    while set(pids) & set(descendants(os.getpid())):
        require(
            time.monotonic() < deadline,
            f"ts.shutdown() left actor processes {pids} behind",
        )
        await asyncio.sleep(0.2)


def kill(pids) -> list[str]:
    """SIGKILL ``pids`` and wait until they are gone. Returns one
    ``pid: command line`` per process that was still there to kill."""
    killed = []
    for pid in pids:
        try:
            with open(f"/proc/{pid}/cmdline") as f:
                cmd = f.read().replace("\0", " ").strip()
            os.kill(pid, signal.SIGKILL)
        except OSError:
            continue  # exited while we looked
        killed.append(f"{pid}: {cmd}")
    deadline = time.monotonic() + 10.0
    while set(pids) & set(descendants(os.getpid())) and time.monotonic() < deadline:
        time.sleep(0.05)
    return killed


def leave_no_process() -> bool:
    """Nothing this script started may outlive it. ``ts.shutdown()`` stops
    the actors but keeps multiprocessing's fork server (and its resource
    tracker) warm for the next ``initialize()``; left alone they exit only
    AFTER this process has. Stop and reap them here, and kill whatever a
    failed phase left behind. False if anything had to be killed or is
    still there."""
    from torchstore_tpu.runtime import stop_spawn_helpers

    # Actors first: a live one holds the resource tracker's pipe open, and
    # stopping the tracker would wait for it.
    killed = kill(actor_pids())
    stop_spawn_helpers()
    killed += kill(list(descendants(os.getpid())))
    for line in killed:
        print(f"chip_smoke: had to kill {line}", file=sys.stderr)
    left = descendants(os.getpid())
    say(
        f"fork server and resource tracker stopped: {len(left)} child "
        f"processes left, {len(killed)} had to be killed"
    )
    return not killed and not left


def arm_watchdog() -> None:
    def abort() -> None:
        faulthandler.dump_traceback(all_threads=True)
        kill(list(descendants(os.getpid())))
        os._exit(1)

    timer = threading.Timer(WATCHDOG_S, abort)
    timer.daemon = True
    timer.start()
    # Should a call hold the GIL for ever, the timer never runs: this one
    # needs no GIL (but cannot stop the children).
    faulthandler.dump_traceback_later(WATCHDOG_S + 30, exit=True)


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------


async def run(chips: int, devices) -> None:
    import torchstore_tpu as ts
    from torchstore_tpu import native

    cfg = smoke_config()
    toolchain = bool(shutil.which("make") and shutil.which("g++"))
    await ts.initialize(store_name=STORE)
    pids = actor_pids()
    try:
        say(
            f"native.available() = {native.available()} (toolchain "
            f"{'present' if toolchain else 'absent'})"
        )
        require(
            native.available() or not toolchain,
            "g++ and make are here but native/libtsnative.so is not loaded",
        )
        if chips == 4:
            await reshard_phase(cfg, devices)
        else:
            await buffered_phase(cfg, devices)
            await overwrite_hazard_phase(cfg, devices)
            await direct_phase(cfg, devices)
        require_host_only(pids)
    finally:
        await ts.shutdown(STORE)
    await require_gone(pids)
    say(f"{len(pids)} actor processes, all host-only, none left after shutdown")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="4: run only the reshard-across-meshes phase, on four chips",
    )
    args = ap.parse_args()
    arm_watchdog()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: jax found no TPU (platform {devices[0].platform!r}); "
            "this script has no CPU mode",
            file=sys.stderr,
        )
        return 1
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but jax sees {len(devices)}",
            file=sys.stderr,
        )
        return 1
    devices = devices[: args.chips]
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }

    from torchstore_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    compiles = {"requests": 0, "hits": 0}

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            compiles["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            compiles["hits"] += 1

    jax.monitoring.register_event_listener(on_event)

    cfg = smoke_config()
    say(f"device: {device['kind']} x {device['count']} ({device['platform']})")
    say(f"compile cache: {cache_dir}")
    say(
        f"model: Llama-3-8B widths (vocab {cfg.vocab_size}, hidden "
        f"{cfg.hidden_size}, ffn {cfg.intermediate_size}, heads "
        f"{cfg.num_heads}/{cfg.num_kv_heads} x {cfg.head_dim}), bf16, seed {SEED}"
    )
    shapes = jax.eval_shape(_init_fn(cfg), jax.random.key(SEED))
    say(
        f"reduced: layers L={cfg.num_layers} of 32 -> "
        f"{tree_nbytes(shapes)} B of weights"
    )
    t0 = time.perf_counter()
    ok = True
    try:
        asyncio.run(run(args.chips, devices))
    except Exception:  # noqa: BLE001 - the boundary: report, then fail
        traceback.print_exc()
        ok = False
    ok = leave_no_process() and ok
    memory = devices[0].memory_stats() or {}
    say(
        f"peak HBM in use on device 0: {memory.get('peak_bytes_in_use')} of "
        f"{memory.get('bytes_limit')} B"
    )
    say(
        f"compiled programs: {compiles['requests']}, of which "
        f"{compiles['hits']} came from the cache and "
        f"{compiles['requests'] - compiles['hits']} were compiled anew"
    )
    say(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
