"""Big arrays leave the device in row-block chunks into recycled host
buffers (ISSUE 25; `sharding.chunk_plan`, `_host_copies`, `HostBufferPool`):
the host array is bitwise what the whole-array copy gives, whatever the
dtype, the rank or the tail; small and odd arrays go whole as before; a
second put compiles nothing and draws every buffer from the pool; a buffer
someone still holds is never written again; the counters add up.

CPU only, with the chunking constants patched small: nothing here is a
device number."""

import gc

import numpy as np
import pytest

from torchstore_tpu import sharding as shd
from torchstore_tpu.observability import metrics as obs_metrics

jax = pytest.importorskip("jax")
jnp = jax.numpy

CHUNK = 4 << 10


@pytest.fixture
def small_chunks(monkeypatch):
    """Chunks of 4 KiB from 12 KiB up, and a pool that starts empty."""
    monkeypatch.setattr(shd, "D2H_CHUNK_BYTES", CHUNK)
    monkeypatch.setattr(shd, "D2H_CHUNK_THRESHOLD", 3 * CHUNK)
    gc.collect()
    shd.host_pool().clear()
    yield
    gc.collect()
    shd.host_pool().clear()


def counter(name: str, **labels) -> float:
    series = obs_metrics.metrics_snapshot().get(name, {"series": []})["series"]
    return sum(s["value"] for s in series if s["labels"] == labels)


def device_array(shape, dtype, seed: int = 0):
    host = np.random.default_rng(seed).integers(-100, 100, size=shape)
    return jnp.asarray(host).astype(dtype)


def whole_copy(x) -> np.ndarray:
    """The parent's path: one ``np.asarray`` of (a copy of) the array."""
    return np.asarray(x + jnp.zeros((), x.dtype))


SHAPES = {
    "rows_divide": (128, 96),  # equal blocks, no tail
    "ragged_tail": (1000, 37),
    "stacked_experts": (8, 64, 96),  # a row of axis 0 is over a chunk
    "deep_ragged": (5, 3, 7, 11, 13),
    "one_row": (1, 40000),  # chunked along the second axis
    "vector": (40000,),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32", "int8"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_chunked_is_bitwise_the_whole_copy(small_chunks, shape, dtype):
    x = device_array(SHAPES[shape], dtype)
    plan = shd.chunk_plan(x)
    assert plan is not None
    axis, rows = plan
    assert rows * x.dtype.itemsize * int(np.prod(x.shape[axis + 1 :])) <= CHUNK
    chunked = counter("ts_d2h_bytes_total", path="chunked")
    chunks = counter("ts_d2h_chunks_total")
    (request,) = shd.put_requests("k", x)
    got, want = request.tensor_val, whole_copy(x)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.flags["C_CONTIGUOUS"] and not got.flags.writeable
    assert got.tobytes() == want.tobytes()
    assert counter("ts_d2h_bytes_total", path="chunked") - chunked == x.nbytes
    expected_chunks = int(np.prod(x.shape[:axis])) * -(-x.shape[axis] // rows)
    assert counter("ts_d2h_chunks_total") - chunks == expected_chunks > 1


@pytest.mark.parametrize(
    "make",
    [
        lambda: jnp.float32(3.5),  # 0-d
        lambda: device_array((1, 64), "float32"),  # one row, under the threshold
        lambda: device_array((32, 32), "bfloat16"),  # small
        lambda: jnp.ones((64, 1024), jnp.int4),  # sub-byte: whatever its size
    ],
    ids=["zero_d", "one_row", "small", "sub_byte"],
)
def test_small_and_odd_arrays_go_whole(small_chunks, make):
    x = make()
    assert shd.chunk_plan(x) is None
    whole = counter("ts_d2h_bytes_total", path="whole")
    misses = counter("ts_d2h_pool_misses_total")
    (request,) = shd.put_requests("k", x)
    assert request.tensor_val.tobytes() == np.asarray(x).tobytes()
    assert counter("ts_d2h_bytes_total", path="whole") - whole == request.tensor_val.nbytes
    assert counter("ts_d2h_pool_misses_total") == misses


def sharded_array(shape, spec, seed: int = 0):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    data = np.random.default_rng(seed).standard_normal(shape).astype(np.float32)
    return data, jax.device_put(data, NamedSharding(mesh, PartitionSpec(*spec)))


@pytest.mark.parametrize(
    "spec", [("x",), (None, "x"), ()], ids=["rows", "columns", "replicated"]
)
def test_a_sharded_array_on_four_devices(small_chunks, spec):
    data, x = sharded_array((64, 1024), spec)
    requests = shd.put_requests("k", x)
    assert len(requests) == (4 if spec else 1)
    for request in requests:
        if request.tensor_slice is None:
            want = data
        else:
            where = tuple(
                slice(o, o + n)
                for o, n in zip(
                    request.tensor_slice.offsets, request.tensor_slice.local_shape
                )
            )
            want = data[where]
        assert request.tensor_val.tobytes() == want.tobytes()
    # Every shard (16 KiB or more) left in chunks, each from its own device.
    assert counter("ts_d2h_pool_misses_total") >= len(requests)


def test_each_devices_window_is_bounded(small_chunks, monkeypatch):
    """At most D2H_WINDOW blocks of one array issued and not yet landed."""
    most = []
    real_issue = shd._ChunkedCopy.issue

    def issue(self, d2h):
        real_issue(self, d2h)
        most.append(len(self.pending))

    monkeypatch.setattr(shd._ChunkedCopy, "issue", issue)
    shd.put_requests("k", device_array((1000, 37), "float32"))
    assert max(most) == shd.D2H_WINDOW


def test_the_second_put_compiles_nothing_and_hits_the_pool(small_chunks):
    compiles = []
    jax.monitoring.register_event_listener(
        lambda event, **_: compiles.append(event)
        if event == "/jax/compilation_cache/compile_requests_use_cache"
        else None
    )
    tree = {
        "experts": device_array((8, 64, 96), "bfloat16", 1),
        "embed": device_array((1000, 37), "float32", 2),
        "norm": device_array((64,), "float32", 3),
    }
    first = [shd.put_requests("k", value) for value in tree.values()]
    del first  # the whole tree was out at once, as in a put_batch: all kept
    gc.collect()
    held = counter("ts_d2h_pool_bytes")
    assert held == tree["experts"].nbytes + tree["embed"].nbytes
    hits, misses = counter("ts_d2h_pool_hits_total"), counter("ts_d2h_pool_misses_total")
    for key, value in tree.items():
        tree[key] = value + jnp.ones((), value.dtype)  # a new version
    del compiles[:]  # the "+1" programs are the trainer's, not the put's
    kept = [shd.put_requests("k", value)[0].tensor_val for value in tree.values()]
    assert not compiles
    assert counter("ts_d2h_pool_hits_total") - hits == 2  # the two big leaves
    assert counter("ts_d2h_pool_misses_total") == misses
    for got, value in zip(kept, tree.values()):
        assert got.tobytes() == np.asarray(value).tobytes()
    assert counter("ts_d2h_pool_bytes") == 0  # all of it is out again
    del kept, got
    gc.collect()
    assert counter("ts_d2h_pool_bytes") == held


def test_one_buffer_serves_leaves_in_turn(small_chunks):
    """The direct refresh drops each leaf before it takes the next: a free
    buffer that is big enough serves a smaller leaf too."""
    shd.put_requests("k", device_array((8, 64, 96), "bfloat16"))
    misses = counter("ts_d2h_pool_misses_total")
    small = device_array((1000, 37), "int8")
    (request,) = shd.put_requests("k", small)
    assert counter("ts_d2h_pool_misses_total") == misses
    assert request.tensor_val.tobytes() == np.asarray(small).tobytes()


def test_the_pool_keeps_no_more_than_was_out_at_once(small_chunks):
    for seed, shape in enumerate([(64, 96), (128, 96), (256, 96)]):
        shd.put_requests("k", device_array(shape, "float32", seed))
        gc.collect()
    # One leaf out at a time: the pool never holds more than the largest.
    assert counter("ts_d2h_pool_bytes") <= 256 * 96 * 4


def test_put_batch_issues_no_whole_copy_of_a_chunked_leaf(small_chunks, monkeypatch):
    from jax._src.array import ArrayImpl

    issued = []
    real = ArrayImpl.copy_to_host_async

    def spy(self):
        issued.append(int(self.nbytes))
        return real(self)

    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", spy)
    big = device_array((1000, 37), "float32")
    small = device_array((8, 8), "float32")
    shd.issue_d2h([big, small])  # what client._put_batch calls up front
    assert issued == [small.nbytes]
    shd.put_requests("k", big)
    assert max(issued) <= CHUNK  # blocks only: the leaf never moved whole
    assert big._npy_value is None  # ... and caches no host copy of itself


STORES = {
    "shm": lambda ts: dict(strategy=ts.SingletonStrategy(default_transport_type="shm")),
    "rpc": lambda ts: dict(strategy=ts.SingletonStrategy(default_transport_type="rpc")),
    "colocated": lambda ts: dict(colocated=True),
}


@pytest.mark.parametrize("transport", sorted(STORES))
async def test_a_held_array_is_not_overwritten_by_the_next_put(
    small_chunks, monkeypatch, transport
):
    """Whoever still holds a request's tensor keeps its bytes: the buffer
    comes back to the pool only when the last reference has died."""
    import torchstore_tpu as ts

    held = []
    real = shd.put_requests

    def recording(key, x, d2h=None):
        requests = real(key, x, d2h)
        held.extend(r.tensor_val for r in requests)
        return requests

    monkeypatch.setattr(shd, "put_requests", recording)
    store = f"d2h_{transport}"
    # The rpc volume cannot overwrite a key it holds a device array's bytes
    # under (it keeps them read-only as they arrived, and `rpc.py` copies
    # in place: so on the parent commit too) - there each put has its key.
    keys = iter(["w0", "w1", "w2"] if transport == "rpc" else ["w"] * 3)
    first = device_array((1000, 37), "float32", 1)
    second = device_array((1000, 37), "float32", 2)
    tree_bytes = first.nbytes
    before = sum(
        counter("ts_d2h_bytes_total", path=path) for path in ("chunked", "whole")
    )
    await ts.initialize(store_name=store, **STORES[transport](ts))
    try:
        await ts.put(next(keys), first, store_name=store)
        old = held[0]
        assert old.tobytes() == np.asarray(first).tobytes()
        misses = counter("ts_d2h_pool_misses_total")
        key = next(keys)
        await ts.put(key, second, store_name=store)
        # The first put's array is still referenced: a fresh buffer, not its.
        assert counter("ts_d2h_pool_misses_total") - misses == 1
        assert not np.shares_memory(old, held[1])
        assert old.tobytes() == np.asarray(first).tobytes()
        got = await ts.get(key, store_name=store)
        assert np.asarray(got).tobytes() == np.asarray(second).tobytes()
        # Dropped, both come back; a third put takes one of them.
        del old, got
        held.clear()
        gc.collect()
        hits = counter("ts_d2h_pool_hits_total")
        key = next(keys)
        await ts.put(key, first, store_name=store)
        assert counter("ts_d2h_pool_hits_total") - hits == 1
        got = await ts.get(key, store_name=store)
        assert np.asarray(got).tobytes() == np.asarray(first).tobytes()
        del got
        after = sum(
            counter("ts_d2h_bytes_total", path=path) for path in ("chunked", "whole")
        )
        assert after - before == 3 * tree_bytes  # the counters add up to the bytes put
    finally:
        held.clear()
        await ts.shutdown(store)
    gc.collect()
    assert counter("ts_d2h_pool_bytes") == 0  # ts.shutdown emptied the pool
    assert not shd.host_pool()._free


def test_a_finalizer_inside_the_pools_lock_does_not_deadlock(small_chunks):
    """The collector may run a buffer's finalizer while this thread is
    inside ``take``: giving back must never wait for the lock."""
    pool = shd.HostBufferPool()
    owner = pool.take(1 << 12)
    with pool._lock:
        del owner  # the finalizer fires here, under the lock
        assert len(pool._returned) == 1
    again = pool.take(1 << 12)  # settles what came back, and reuses it
    assert not pool._returned and not pool._free
    assert again.nbytes == 1 << 12
