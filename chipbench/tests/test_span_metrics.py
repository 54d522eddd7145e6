"""The readers of the store's own spans (PR 23) on a hand-made run: each
gives the value computed by hand below, a program without the spans gives
None and raises nothing, and every new entry of `BENCHMARK.json` has its
reader. No store, no device: nothing here is a measurement."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO_ROOT)

from chipbench import run, span_sums  # noqa: E402

BENCH = run.load_json(os.path.join(REPO_ROOT, "BENCHMARK.json"))
CHANNEL_CELLS = ["mixtral-l1.buffered", "olmoe-l4.buffered", "mixtral-l2.reshard4"]
ALL = None  # no `workloads` key: every cell
NEW = {
    "d2h_s": ("client device edge", "publish_s", ALL),
    "shm_attach_s": ("transports", "publish_s", CHANNEL_CELLS),
    "shm_copy_s": ("transports", "publish_s", CHANNEL_CELLS),
    "put_protocol_s": ("host actors", "publish_s", CHANNEL_CELLS),
    "channel_gc_s": ("entry", "publish_s", CHANNEL_CELLS),
    "h2d_dispatch_s": ("client device edge", "sync_s", ALL),
    "direct_stage_copy_s": ("direct sync", "publish_s", ["mixtral-l1.direct"]),
    "direct_read_s": ("direct sync", "sync_s", ["mixtral-l1.direct"]),
    "direct_land_s": ("direct sync", "sync_s", ["mixtral-l1.direct"]),
    "unspanned_s": ("entry", "sync_s", ALL),
    "idle_unnamed_s": ("device", "sync_s", ALL),
}


def span(name, start, end):
    return {"name": name, "start": start, "end": end}


def phase(name, cycle, start, end):
    return {"name": name, "cycle": cycle, "start": start, "end": end}


# Two cycles on the host's clock. Cycle 0 publishes over [10, 20] and
# acquires over [20, 22] (its h2d_tail over [21.5, 22]); cycle 1 is the
# same 100 s later with a publish that goes cold (attach 2.0 s, not 0.5).
def cycle_spans(t, attach):
    return [
        span("weight_channel.resolve_version", t + 10.0, t + 10.2),  # 0.2
        span("weight_channel.publish", t + 10.2, t + 19.6),
        span("put_batch", t + 10.3, t + 19.5),
        span("d2h.issue", t + 10.3, t + 10.4),  # 0.1
        span("put.requests", t + 10.4, t + 14.5),
        span("d2h.wait", t + 10.4, t + 12.4),  # 2.0
        span("d2h.wait", t + 12.5, t + 14.5),  # 2.0; 0.1 of request building between
        span("transport.put", t + 14.6, t + 19.2),
        span("transport.handshake", t + 14.6, t + 18.6),  # 4.0
        span("shm.attach", t + 14.9, t + 14.9 + attach),
        span("shm.land", t + 17.0, t + 18.5),  # 1.5
        span("transport.put_rpc", t + 18.7, t + 19.1),  # 0.4
        span("put_batch/data_plane", t + 14.6, t + 19.2),
        span("put_batch/notify", t + 19.2, t + 19.5),  # 0.3
        span("weight_channel.gc", t + 19.6, t + 19.9),  # 0.3
        span("weight_channel.acquire", t + 20.0, t + 21.5),
        span("get.plan", t + 20.0, t + 20.2),  # 0.2
        span("transport.get", t + 20.2, t + 20.5),  # 0.3
        span("reshard", t + 20.5, t + 20.6),  # 0.1
        span("h2d.dispatch", t + 20.7, t + 20.9),  # 0.2
        span("h2d.dispatch", t + 21.0, t + 21.3),  # 0.3
    ]


def cycle_phases(cycle, t):
    return [
        phase("train", cycle, t + 9.0, t + 10.0),
        phase("publish", cycle, t + 10.0, t + 20.0),
        phase("acquire", cycle, t + 20.0, t + 22.0),
        phase("h2d_tail", cycle, t + 21.5, t + 22.0),
        phase("check", cycle, t + 22.0, t + 23.0),
    ]


def channel_run(**over):
    fields = dict(
        phases=cycle_phases(0, 0.0) + cycle_phases(1, 100.0),
        spans=cycle_spans(0.0, 0.5) + cycle_spans(100.0, 2.0),
        counters={"ts_landing_copy_seconds_sum{stage=put}": 3.0},
        cycles=2,
        planes={},
        device={},
        step_program="step",
    )
    fields.update(over)
    return run.TracedRun(**fields)


def direct_run():
    spans, phases = [], []
    for cycle, t in ((0, 0.0), (1, 50.0)):
        phases += [
            phase("publish", cycle, t + 1.0, t + 6.0),
            phase("acquire", cycle, t + 6.0, t + 8.0),
            phase("h2d_tail", cycle, t + 7.5, t + 8.0),
        ]
        spans += [
            span("direct.refresh", t + 1.1, t + 5.9),
            span("d2h.issue", t + 1.1, t + 1.2),  # 0.1
            span("d2h.wait", t + 1.2, t + 3.2),  # 2.0
            span("direct.stage_copy", t + 3.3, t + 4.3),  # 1.0
            span("d2h.issue", t + 4.4, t + 4.5),  # 0.1
            span("d2h.wait", t + 4.5, t + 5.0),  # 0.5
            span("direct.stage_copy", t + 5.1, t + 5.8),  # 0.7
            span("direct.pull", t + 6.1, t + 7.4),
            span("direct.read", t + 6.2, t + 6.3),  # 0.1
            span("direct.land", t + 6.3, t + 6.9),  # 0.6
            span("h2d.dispatch", t + 7.0, t + 7.1),  # 0.1
            span("h2d.dispatch", t + 7.2, t + 7.4),  # 0.2
        ]
    return run.TracedRun(
        phases=phases, spans=spans, counters={}, cycles=2, planes={}, device={},
        step_program="step",
    )


def read(name, traced):
    traced.last_readings = []
    return run.load_plugin("layer_metrics", name).read(traced)


CHANNEL_EXPECTED = {
    "d2h_s": 0.1 + 2.0 + 2.0,
    "shm_attach_s": (0.5 + 2.0) / 2,
    "shm_copy_s": 1.5,
    # Handshake 4.0 less attach and copy, then the put RPC and the notify.
    "put_protocol_s": ((4.0 - 0.5 - 1.5) + (4.0 - 2.0 - 1.5)) / 2 + 0.4 + 0.3,
    "channel_gc_s": 0.2 + 0.3,
    "h2d_dispatch_s": 0.2 + 0.3,
    # Publish: 10 s less resolve 0.2, issue 0.1, waits 4.0, handshake 4.0,
    # put RPC 0.4, notify 0.3, gc 0.3 = 0.7. Acquire up to the tail: 1.5 s
    # less plan 0.2, get 0.3, reshard 0.1, dispatch 0.5 = 0.4.
    "unspanned_s": 0.7 + 0.4,
}


@pytest.mark.parametrize("name", sorted(CHANNEL_EXPECTED))
def test_channel_readers_give_the_hand_computed_value(name):
    traced = channel_run()
    assert read(name, traced) == pytest.approx(CHANNEL_EXPECTED[name])
    if name == "shm_attach_s":
        # The alternation shows in the info line's per_phase.
        assert traced.last_readings == pytest.approx([0.5, 2.0])
    if name == "unspanned_s":
        assert traced.last_readings == pytest.approx([1.1, 1.1])


DIRECT_EXPECTED = {
    "d2h_s": 0.1 + 2.0 + 0.1 + 0.5,
    "direct_stage_copy_s": 1.0 + 0.7,
    "direct_read_s": 0.1,
    "direct_land_s": 0.6,
    "h2d_dispatch_s": 0.1 + 0.2,
    # Publish 5.0 less 2.7 of D2H and 1.7 of copies; acquire 1.5 less 1.0.
    "unspanned_s": (5.0 - 2.7 - 1.7) + (1.5 - 0.1 - 0.6 - 0.3),
}


@pytest.mark.parametrize("name", sorted(DIRECT_EXPECTED))
def test_direct_readers_give_the_hand_computed_value(name):
    assert read(name, direct_run()) == pytest.approx(DIRECT_EXPECTED[name])


def test_shm_copy_is_cross_checked_against_the_stores_counter(capsys):
    read("shm_copy_s", channel_run())  # spans 3.0 s, counter 3.0 s
    assert capsys.readouterr().err == ""
    read("shm_copy_s", channel_run(counters={"ts_landing_copy_seconds_sum{stage=put}": 2.0}))
    assert "ts_landing_copy_seconds 2.000 s" in capsys.readouterr().err


# The profiler's trace of one cycle, on its own clock. Device 0 is busy over
# [0.2, 0.3] (the step) and [6.0, 6.5] (inside the publish).
PLANES = {
    "/device:TPU:0": {
        "XLA Ops": [("%fusion = f32[8]", 0.2, 0.1), ("%copy = f32[8]", 6.0, 0.5)],
        "XLA Modules": [("jit_step(1)", 0.2, 0.1)],
    },
    "/host:CPU": {
        "main": [
            ("chipbench/train", 0.0, 1.0),
            ("chipbench/publish", 1.0, 9.0),  # [1, 10]
            ("ts/put_batch", 1.5, 8.0),  # not a leaf: names nothing
            ("ts/d2h.wait", 2.0, 3.0),  # [2, 5]
            ("ts/transport.handshake", 5.5, 2.5),  # [5.5, 8]; busy [6, 6.5] inside
            ("chipbench/acquire", 10.0, 2.0),  # [10, 12]
            ("ts/h2d.dispatch", 10.5, 0.5),  # [10.5, 11]
            ("chipbench/h2d_tail", 11.5, 0.5),
            ("chipbench/check", 12.0, 1.0),
            ("ts/transport.get", 12.2, 0.5),  # outside the windows
        ],
        "pool": [("ts/shm.land", 7.0, 0.5)],  # inside the handshake: counts once
    },
}


def test_idle_unnamed_is_idle_time_no_store_leaf_covers():
    # Windows [1, 12]: idle 11.0 - 0.5 busy = 10.5. Named and idle: the wait
    # 3.0, the handshake 2.5 - 0.5 busy = 2.0, the dispatch 0.5.
    traced = channel_run(planes=PLANES)
    assert read("idle_unnamed_s", traced) == pytest.approx(10.5 - 3.0 - 2.0 - 0.5)
    two = {
        "/device:TPU:0": PLANES["/device:TPU:0"],
        "/host:CPU": {
            "main": PLANES["/host:CPU"]["main"]
            + [(n, s + 20.0, d) for n, s, d in PLANES["/host:CPU"]["main"]],
            "pool": PLANES["/host:CPU"]["pool"],
        },
    }
    # A second cycle with an idle device: 11.0 - 3.0 - 2.5 - 0.5; per cycle.
    assert read("idle_unnamed_s", channel_run(planes=two)) == pytest.approx((5.0 + 5.0) / 2)


def test_a_trace_without_store_annotations_is_an_error_not_zero(capsys):
    bare = {
        "/device:TPU:0": PLANES["/device:TPU:0"],
        "/host:CPU": {
            "main": [e for e in PLANES["/host:CPU"]["main"] if e[0].startswith("chipbench/")]
        },
    }
    assert read("idle_unnamed_s", channel_run(planes=bare)) is None
    assert "no ts/ annotation" in capsys.readouterr().err


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_spans_gives_none(name):
    """The parent commit: the spans it had, none of the new ones, and a
    profiler trace with the benchmark's annotations only."""
    old = {"weight_channel.publish", "put_batch", "transport.put", "put_batch/data_plane",
           "put_batch/notify", "weight_channel.acquire", "transport.get", "reshard"}
    bare = {
        "/device:TPU:0": PLANES["/device:TPU:0"],
        "/host:CPU": {
            "main": [e for e in PLANES["/host:CPU"]["main"] if e[0].startswith("chipbench/")]
        },
    }
    traced = channel_run(
        spans=[s for s in cycle_spans(0.0, 0.5) if s["name"] in old], planes=bare
    )
    assert read(name, traced) is None


def test_every_new_entry_has_its_reader():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert list(entries)[-len(NEW):] == list(NEW)  # appended, in the issue's order
    for name, (layer, moves, workloads) in NEW.items():
        entry = entries[name]
        assert entry.get("workloads") == workloads
        assert (entry["unit"], entry["better"]) == ("s", "lower")
        reader = run.load_plugin("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"]
        ) == (layer, "s", entry["source"], moves)
        assert reader.read.__module__ and reader.__doc__
    assert set(span_sums.NEW_LEAF_SPANS) < set(span_sums.LEAF_SPANS)
