"""What the measurement still cannot see: the seconds of a cycle's publish
and acquire calls that no leaf span of the store covers
(`span_sums.LEAF_SPANS`: D2H, shm attach and copy, the RPCs the client
waits for, version resolve and GC, get planning, H2D dispatch, the direct
path's copies and reads, `transport.get`, `reshard`). The acquire counts
up to its return: the wait for the device that follows is the benchmark's
own `h2d_tail` phase, which `h2d_tail_s` names. Mean over the window's
cycles."""

from chipbench import span_sums, trace_reduce

LAYER = "entry"
UNIT = "s"
SOURCE = "program_span"
MOVES = "sync_s"

LEAVES = frozenset(span_sums.LEAF_SPANS)


def read(run):
    if not any(s["name"] in span_sums.NEW_LEAF_SPANS for s in run.spans):
        return None  # a program without the spans: nothing to hold it to
    tails = {p["cycle"]: p for p in run.phases_named("h2d_tail")}
    by_cycle: dict[int, float] = {}
    for phase in run.phases_named("publish") + run.phases_named("acquire"):
        lo, hi = phase["start"], phase["end"]
        if phase["name"] == "acquire" and phase["cycle"] in tails:
            hi = tails[phase["cycle"]]["start"]
        seen = trace_reduce.total(span_sums.cover(run.spans, LEAVES, lo, hi))
        by_cycle[phase["cycle"]] = by_cycle.get(phase["cycle"], 0.0) + (hi - lo) - seen
    run.last_readings = [by_cycle[c] for c in sorted(by_cycle)]
    if not by_cycle:
        return None
    return sum(by_cycle.values()) / len(by_cycle)
