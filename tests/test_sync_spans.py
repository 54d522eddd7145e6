"""Spans where a weight sync's seconds are spent (ISSUE 23): every span of
the channel path and of the direct path is emitted, with the containment
the readers under `chipbench/layer_metrics/` rely on; the spans sit on the
profiler's host plane when (and only when) the process has imported jax;
a disabled span costs next to nothing; the `d2h` stage is booked apart from
`plan` and lands in a histogram that never decays.

CPU only: nothing here is a device number."""

import glob
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from torchstore_tpu.observability import metrics as obs_metrics
from torchstore_tpu.observability import timeline, tracing

jax = pytest.importorskip("jax")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Store tracing on for this process (and the actors it starts); gives a
    function that returns this process's events so far."""
    base = str(tmp_path / "trace.json")
    monkeypatch.setenv(tracing.ENV_TRACE, base)
    collector = tracing.collector()
    collector.reinit_after_fork()

    def events() -> list[dict]:
        tracing.flush_trace()
        return [
            e
            for path in tracing.trace_files(base)
            for e in tracing.load_trace_events(path)
            if e.get("ph") == "X" and e.get("pid") == os.getpid()
        ]

    yield events
    monkeypatch.delenv(tracing.ENV_TRACE)
    collector.reinit_after_fork()


def named(events, name):
    return [e for e in events if e["name"] == name]


def assert_inside(events, inner: str, *outers: str) -> None:
    """Every span called ``inner`` lies within a span called one of
    ``outers`` (containment in time, one process)."""
    found = named(events, inner)
    assert found, f"no {inner} span; have {sorted({e['name'] for e in events})}"
    hosts = [e for o in outers for e in named(events, o)]
    for e in found:
        assert any(
            h["ts"] <= e["ts"] and e["ts"] + e["dur"] <= h["ts"] + h["dur"]
            for h in hosts
        ), f"{inner} at {e['ts']} lies in no {outers} span"


def sharded(shape, seed: int):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    data = np.random.default_rng(seed).standard_normal(shape).astype(np.float32)
    return jax.device_put(data, NamedSharding(mesh, PartitionSpec("x")))


TREES = {
    "few_large": lambda: {f"w{i}": sharded((256, 1024), i) for i in range(2)},
    # Each under the arena threshold (256 KiB), together over the inline
    # one (64 KiB); sharded and single-device leaves mixed.
    "many_small": lambda: {
        f"w{i}": sharded((64, 128), i) if i % 2 else jax.numpy.full((64, 64), float(i))
        for i in range(40)
    },
}


@pytest.mark.parametrize("leaves", sorted(TREES))
async def test_channel_sync_emits_every_span(traced, leaves):
    import torchstore_tpu as ts

    store = f"spans_{leaves}"
    tree = TREES[leaves]()
    await ts.initialize(store_name=store)
    try:
        publisher = ts.WeightPublisher("policy", store_name=store)
        subscriber = ts.WeightSubscriber("policy", store_name=store)
        for _ in range(2):
            version = await publisher.publish({"params": tree})
            got, got_version = await subscriber.acquire(
                user_state_dict={"params": tree}, timeout=60
            )
            assert got_version == version
        for key, value in tree.items():
            np.testing.assert_array_equal(np.asarray(got["params"][key]), np.asarray(value))
        await publisher.close(delete=True)
    finally:
        await ts.shutdown(store)
    events = traced()
    assert_inside(events, "d2h.wait", "put.requests")
    assert_inside(events, "put.requests", "put_batch")
    assert_inside(events, "d2h.issue", "put_batch")
    assert_inside(events, "shm.attach", "transport.handshake")
    assert_inside(events, "shm.land", "transport.handshake")
    assert_inside(events, "transport.handshake", "transport.put")
    assert_inside(events, "transport.put_rpc", "transport.put")
    assert_inside(events, "h2d.dispatch", "weight_channel.acquire")
    assert_inside(events, "get.plan", "get_batch")
    # Around, not inside, the publish span: what used to be outside every span.
    publishes = named(events, "weight_channel.publish")
    assert len(publishes) == 2
    for name in ("weight_channel.resolve_version", "weight_channel.gc"):
        assert len(named(events, name)) == 2
        for e in named(events, name):
            assert not any(
                p["ts"] < e["ts"] + e["dur"] and e["ts"] < p["ts"] + p["dur"]
                for p in publishes
            )
    # Bytes ride the spans that move them (the collector derives GB/s), and
    # the attach span says whether the publish went cold.
    published = 2 * sum(np.asarray(v).nbytes for v in tree.values())
    for name in ("d2h.wait", "shm.land", "h2d.dispatch"):
        assert sum(e["args"]["bytes"] for e in named(events, name)) == published, name
    attach = named(events, "shm.attach")[0]["args"]
    assert {"offer_hit", "cold_create", "created_bytes"} <= set(attach)
    assert attach["offer_hit"] + attach["cold_create"] >= 1


async def test_direct_sync_emits_every_span(traced, monkeypatch):
    """What the chip does: the host-staged rung (`device_transfer.serves`
    says no for a TPU), register first, then a refresh."""
    import torchstore_tpu as ts
    from torchstore_tpu.transport import device_transfer

    monkeypatch.setattr(device_transfer, "SERVED_PLATFORMS", frozenset())
    tree = TREES["few_large"]()
    await ts.initialize(store_name="spans_direct")
    try:
        for _ in range(2):
            await ts.put_state_dict(
                "policy/direct", {"params": tree}, direct=True, store_name="spans_direct"
            )
        got = await ts.get_state_dict(
            "policy/direct",
            user_state_dict={"params": tree},
            direct=True,
            store_name="spans_direct",
        )
        for key, value in tree.items():
            np.testing.assert_array_equal(np.asarray(got["params"][key]), np.asarray(value))
    finally:
        await ts.shutdown("spans_direct")
    events = traced()
    assert len(named(events, "direct.register")) == 1
    assert len(named(events, "direct.refresh")) == 1
    assert_inside(events, "direct.stage_copy", "direct.register", "direct.refresh")
    # A traced run of the direct cell must print `direct_stage_copy_s`: the
    # register AND the refresh each hold a staging copy that took time.
    for outer in named(events, "direct.register") + named(events, "direct.refresh"):
        assert any(
            e["dur"] > 0
            and outer["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"]
            for e in named(events, "direct.stage_copy")
        ), f"no direct.stage_copy inside {outer['name']}"
    assert_inside(events, "d2h.issue", "direct.register", "direct.refresh")
    assert_inside(events, "d2h.wait", "direct.register", "direct.refresh")
    assert_inside(events, "direct.read", "direct.pull")
    assert_inside(events, "direct.land", "direct.pull")
    assert_inside(events, "h2d.dispatch", "direct.pull")
    assert [e["args"]["attempt"] for e in named(events, "direct.pull")] == [0]
    staged = sum(e["args"]["bytes"] for e in named(events, "direct.stage_copy"))
    assert staged == 2 * sum(np.asarray(v).nbytes for v in tree.values())


async def test_spans_sit_on_the_profilers_host_plane(traced, tmp_path):
    """One clock with the device trace: under a profiler session a store
    span of a process that has imported jax is a `ts/...` annotation."""
    import torchstore_tpu as ts
    from jax.profiler import ProfileData

    await ts.initialize(store_name="spans_profiled")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path / "profile"), profiler_options=options)
        try:
            await ts.put("k", sharded((64, 64), 0), store_name="spans_profiled")
        finally:
            jax.profiler.stop_trace()
    finally:
        await ts.shutdown("spans_profiled")
    (path,) = glob.glob(str(tmp_path / "profile/plugins/profile/*/*.xplane.pb"))
    on_host = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith(tracing.ANNOTATION_PREFIX)
    ]
    puts = [e for e in on_host if e[0] == "ts/put_batch"]
    waits = [e for e in on_host if e[0] == "ts/d2h.wait"]
    assert len(puts) == 1 and len(waits) == 4  # one wait per shard
    assert all(puts[0][1] <= s and e <= puts[0][2] for _, s, e in waits)


def test_a_process_without_jax_gets_no_annotation_and_no_jax(tmp_path):
    """Volume and controller actors never import jax (a chip belongs to one
    process): tracing must not change that."""
    code = (
        "import sys\n"
        "from torchstore_tpu.observability import tracing\n"
        "assert tracing.trace_enabled()\n"
        "with tracing.span('put_batch', nbytes=1) as sp:\n"
        "    assert sp._annotation is None\n"
        "tracing.flush_trace()\n"
        "assert 'jax' not in sys.modules, 'tracing imported jax'\n"
        "print(len(tracing.load_trace_events(sys.argv[1])))\n"
    )
    base = str(tmp_path / "trace.json")
    out = subprocess.run(
        [sys.executable, "-c", code, base],
        env={**os.environ, tracing.ENV_TRACE: base, "PYTHONPATH": REPO_ROOT},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert int(out.stdout.strip()) == 2  # the process_name header and the span


def test_a_disabled_span_costs_next_to_nothing():
    collector = tracing.collector()
    assert not collector.enabled
    before = len(collector.events)
    array = np.zeros(4)
    start = time.perf_counter()
    for _ in range(10_000):
        with tracing.span("d2h.wait", nbytes=array.nbytes) as sp:
            pass
    elapsed = time.perf_counter() - start
    assert elapsed < 0.05, f"10 000 disabled spans took {elapsed * 1e3:.1f} ms"
    assert len(collector.events) == before and sp._annotation is None
    assert sp._span_id is None and sp.elapsed >= 0.0


def _stage_seconds(op: str, stage: str) -> tuple[float, float]:
    series = obs_metrics.metrics_snapshot()["ts_op_stage_seconds"]["series"]
    for s in series:
        if s["labels"] == {"op": op, "stage": stage}:
            return s["value"]["sum"], s["value"]["count"]
    return 0.0, 0.0


def test_stage_seconds_never_decay_and_the_catalog_still_holds():
    total, count = _stage_seconds("put", "d2h")
    timeline.observe_stage("put", "d2h", 0.25)
    timeline.observe_stage("put", "d2h", 0.5)
    after, n = _stage_seconds("put", "d2h")
    assert after - total == pytest.approx(0.75) and n - count == 2
    assert {"d2h", "h2d"} <= timeline.STAGE_CATALOG
    with pytest.raises(ValueError, match="unregistered stage"):
        timeline.observe_stage("put", "device_to_host", 0.1)
    assert _stage_seconds("put", "device_to_host") == (0.0, 0.0)


async def test_a_put_of_a_device_array_books_d2h_apart_from_plan(monkeypatch):
    """What `ts.slo_report()` shows an operator: the D2H wait of a put is
    its own stage, once per batch, not part of "plan"."""
    import torchstore_tpu as ts

    monkeypatch.setenv(timeline.SLO_PUT_P99_MS, "0.000001")  # every put breaches
    await ts.initialize(store_name="spans_stage")
    try:
        d2h_before = _stage_seconds("put", "d2h")
        plan_before = _stage_seconds("put", "plan")
        h2d_before = _stage_seconds("get", "h2d")
        tree = {f"w{i}": sharded((64, 64), i) for i in range(6)}
        await ts.put_batch(tree, store_name="spans_stage")
        await ts.get_batch(tree, store_name="spans_stage")
        report = await ts.slo_report(store_name="spans_stage")
    finally:
        await ts.shutdown("spans_stage")
    d2h_after, plan_after = _stage_seconds("put", "d2h"), _stage_seconds("put", "plan")
    # One observation for the batch of six leaves, on both stages.
    assert d2h_after[1] - d2h_before[1] == 1 and plan_after[1] - plan_before[1] == 1
    assert d2h_after[0] > d2h_before[0]
    assert _stage_seconds("get", "h2d")[1] - h2d_before[1] == 1
    stages = report["slos"]["put_p99_ms"]["stages"]
    assert {"d2h", "plan", "transport", "notify"} <= set(stages)
