"""Device idle time that no store span names, on the device trace's clock:
the idle time of the first device (no event on its `XLA Ops` line) inside
the benchmark's `chipbench/publish`, `chipbench/acquire` and
`chipbench/h2d_tail` annotations that no `ts/<leaf span>` annotation covers
(`span_sums.LEAF_SPANS`; the store writes every span of a process that has
imported jax as a `TraceAnnotation`), per traced cycle. A trace without a
single `ts/` annotation is an error, not 0: None, and a line on stderr."""

import sys

from chipbench import span_sums, trace_reduce

LAYER = "device"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "sync_s"

WINDOWS = ("publish", "acquire", "h2d_tail")
LEAVES = frozenset(span_sums.TS_PREFIX + name for name in span_sums.LEAF_SPANS)


def read(run):
    planes = run.planes
    device_planes = sorted(n for n in planes if n.startswith(trace_reduce.DEVICE_PLANE_PREFIX))
    notes = trace_reduce.annotations(planes)
    cycles = sum(name == "publish" for name, _, _ in notes)
    if not device_planes or not cycles:
        return None
    store = [
        (name, start, start + dur)
        for plane, lines in planes.items()
        if plane.startswith("/host:")
        for events in lines.values()
        for name, start, dur in events
        if name.startswith(span_sums.TS_PREFIX)
    ]
    if not store:
        print(
            "idle_unnamed_s: the profiler's trace holds no ts/ annotation "
            "(store tracing off, or a program that writes none)",
            file=sys.stderr,
        )
        return None
    lines = planes[device_planes[0]]
    events = lines.get(trace_reduce.OPS_LINE) or lines.get(trace_reduce.MODULES_LINE) or []
    busy = trace_reduce.union((s, s + d) for _, s, d in events)
    named = trace_reduce.union((s, e) for name, s, e in store if name in LEAVES)
    unnamed = 0.0
    for lo, hi in trace_reduce.union((s, e) for name, s, e in notes if name in WINDOWS):
        for a, b in trace_reduce.gaps(busy, lo, hi):
            unnamed += (b - a) - trace_reduce.total(trace_reduce.clip(named, a, b))
    return unnamed / cycles
