"""From traces to numbers. Two sources, both reduced here so that every PR
computes the same number in the same way:

- the profiler's trace (``.xplane.pb``), read into plain planes
  ``{plane name: {line name: [(event name, start_s, duration_s), ...]}}`` and
  reduced to the device's busy time, its longest-running operations, its
  idle gaps named by the benchmark's own annotations, and the time of one
  named program;
- the store's own spans (`TORCHSTORE_TPU_TRACE`, Chrome-trace events of this
  process), reduced to a span's time outside the spans it contains.

Knows no cell, configuration or metric by name."""

import bisect
import glob
import os

ANNOTATION_PREFIX = "chipbench/"
DEVICE_PLANE_PREFIX = "/device:TPU:"
# The lines of a device plane, as the TPU profiler names them.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


# --------------------------------------------------------------------------
# intervals
# --------------------------------------------------------------------------


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points as ``intervals``
    (pairs of start and end)."""
    out: list[list[float]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that the disjoint, sorted ``busy`` leaves
    uncovered."""
    out = []
    at = lo
    for a, b in clip(busy, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


# --------------------------------------------------------------------------
# the profiler's trace
# --------------------------------------------------------------------------


def find_xplane(profile_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    """The planes of one ``.xplane.pb``, times in seconds from the start of
    the trace."""
    from jax.profiler import ProfileData

    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events
            )
    return planes


def annotations(planes: dict) -> list[tuple[str, float, float]]:
    """The benchmark's own host annotations ``(name, start, end)``, without
    their prefix, in order of start. They sit on the device trace's clock."""
    out = []
    for plane_name, lines in planes.items():
        if plane_name.startswith("/host:"):
            for events in lines.values():
                out.extend(
                    (name[len(ANNOTATION_PREFIX):], start, start + dur)
                    for name, start, dur in events
                    if name.startswith(ANNOTATION_PREFIX)
                )
    return sorted(out, key=lambda a: a[1])


def annotated_window(planes: dict):
    """``(annotations, start, end)``: the window runs from the first
    annotation's start to the last one's end."""
    notes = annotations(planes)
    if not notes:
        raise ValueError("the trace holds none of the benchmark's annotations")
    return notes, min(s for _, s, _ in notes), max(e for _, _, e in notes)


def reduce_device(planes: dict, n_devices: int) -> dict:
    """Busy and idle of the device over the annotated window.

    A device is busy while an event of its ``XLA Ops`` line runs (the union
    of their intervals; ``XLA Modules`` where a trace has no ops line).
    ``busy_s`` is the average over the ``n_devices`` device planes.
    ``device_ops`` are the ten operations with the most time (summed over
    occurrences, averaged over devices) and ``idle_gaps`` the idle time of
    the first device by the annotation the host was in (innermost), most
    first. Raises where the trace has no annotation or no device plane."""
    notes, lo, hi = annotated_window(planes)
    device_planes = sorted(n for n in planes if n.startswith(DEVICE_PLANE_PREFIX))
    if len(device_planes) < n_devices:
        raise ValueError(
            f"the trace has device planes {device_planes}, {n_devices} chips were used"
        )
    busy_each = []
    op_seconds: dict[str, float] = {}
    for name in device_planes:
        lines = planes[name]
        events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        inside = [(n, s, s + d) for n, s, d in events if s + d > lo and s < hi]
        busy_each.append(clip(union((s, e) for _, s, e in inside), lo, hi))
        modules = sorted((s, s + d, n) for n, s, d in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in modules]
        for op, s, e in inside:
            # "program/op": the op's own name is an HLO line ("%fusion.1 =
            # bf16[...] fusion(...)") and repeats from program to program.
            at = bisect.bisect_right(starts, s) - 1
            program = (
                modules[at][2].split("(")[0] + "/"
                if at >= 0 and s < modules[at][1]
                else ""
            )
            label = program + op.split(" = ")[0].lstrip("%")[:80]
            op_seconds[label] = op_seconds.get(label, 0.0) + (min(e, hi) - max(s, lo))
    # A chip that ran nothing in the window still counts in the average.
    used = sorted(busy_each, key=total, reverse=True)[:n_devices]
    busy_s = sum(total(b) for b in used) / n_devices
    top = sorted(op_seconds.items(), key=lambda kv: kv[1], reverse=True)[:10]

    idle_by: dict[str, float] = {}
    for a, b in gaps(busy_each[0], lo, hi):
        # Cut the gap at every annotation boundary, and give each piece to
        # the innermost (latest started) annotation that covers it.
        cuts = sorted({a, b, *(t for _, s, e in notes for t in (s, e) if a < t < b)})
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            covering = [n for n, s, e in notes if s <= mid < e]
            label = covering[-1] if covering else "between phases"
            idle_by[label] = idle_by.get(label, 0.0) + (y - x)
    idle = sorted(idle_by.items(), key=lambda kv: kv[1], reverse=True)[:10]
    return {
        "window_s": hi - lo,
        "busy_s": busy_s,
        "device_ops": [[n, s / len(device_planes)] for n, s in top],
        "idle_gaps": [[n, s] for n, s in idle],
    }


def program_seconds(planes: dict, program: str) -> list[float]:
    """Device time of each run of the program whose module name contains
    ``program``, on the first device, within the annotated window."""
    device_planes = sorted(n for n in planes if n.startswith(DEVICE_PLANE_PREFIX))
    if not device_planes:
        return []
    _, lo, hi = annotated_window(planes)
    return [
        d
        for name, s, d in planes[device_planes[0]].get(MODULES_LINE, [])
        if program in name and s >= lo and s + d <= hi
    ]


# --------------------------------------------------------------------------
# the store's spans
# --------------------------------------------------------------------------


def load_spans(path: str) -> list[dict]:
    """This process's store spans ``{"name", "start", "end"}`` in seconds on
    the host's ``perf_counter`` clock (the clock the spans are stamped on)."""
    from torchstore_tpu.observability import tracing

    tracing.flush_trace()
    out = []
    for file in tracing.trace_files(path):
        for event in tracing.load_trace_events(file):
            if event.get("ph") == "X" and event.get("pid") == os.getpid():
                start = event["ts"] * 1e-6
                out.append(
                    {
                        "name": event["name"],
                        "start": start,
                        "end": start + event["dur"] * 1e-6,
                    }
                )
    return sorted(out, key=lambda s: s["start"])


def spans_within(spans, name: str, lo: float, hi: float) -> list[dict]:
    """The spans called ``name`` that lie inside ``[lo, hi]``."""
    return [s for s in spans if s["name"] == name and s["start"] >= lo and s["end"] <= hi]


def seconds_in(spans, name: str, lo: float, hi: float) -> float | None:
    """Sum of the durations of the spans called ``name`` inside ``[lo, hi]``;
    None where there is none."""
    found = spans_within(spans, name, lo, hi)
    return sum(s["end"] - s["start"] for s in found) if found else None


def time_outside(span: dict, spans, inner: str) -> float:
    """``span``'s duration minus the part of it that spans called ``inner``
    cover (their union, clipped to ``span``): its own time, by containment
    in time, which in one process driving one operation at a time is exact."""
    covered = clip(
        union((s["start"], s["end"]) for s in spans if s["name"] == inner),
        span["start"],
        span["end"],
    )
    return (span["end"] - span["start"]) - total(covered)
