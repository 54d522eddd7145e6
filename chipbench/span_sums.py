"""What the readers of the store's own spans share (`layer_metrics/d2h_s`,
`put_protocol_s`, `unspanned_s`, `idle_unnamed_s`, ...): seconds of an
interval that spans of given names cover, and the list of LEAF spans, the
ones that bracket one piece of work and nothing else of the list.

A program without these spans (a parent commit) gives None everywhere: the
reader then leaves its metric out."""

from chipbench import trace_reduce

# Where a sync's seconds are spent, span by span (torchstore_tpu: sharding.py,
# client.py, transport/buffers.py, transport/shared_memory.py,
# weight_channel.py, direct_weight_sync.py). `transport.handshake` counts
# whole: what `shm.attach` and `shm.land` leave of it is the handshake RPC.
NEW_LEAF_SPANS = (
    "d2h.issue",
    "d2h.wait",
    "shm.attach",
    "shm.land",
    "transport.handshake",
    "transport.put_rpc",
    "weight_channel.resolve_version",
    "weight_channel.gc",
    "get.plan",
    "h2d.dispatch",
    "direct.stage_copy",
    "direct.read",
    "direct.land",
)
# ... and the leaves the program had before them.
LEAF_SPANS = NEW_LEAF_SPANS + ("put_batch/notify", "transport.get", "reshard")
# The store's spans as the profiler's host plane names them.
TS_PREFIX = "ts/"


def cover(spans, names, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` inside a span called one of ``names``:
    sorted, disjoint."""
    return trace_reduce.clip(
        trace_reduce.union(
            (s["start"], s["end"]) for s in spans if s["name"] in names
        ),
        lo,
        hi,
    )


def covered_s(spans, names, lo: float, hi: float) -> float | None:
    """Seconds of ``[lo, hi]`` inside a span called one of ``names``; None
    where no such span touches it."""
    found = cover(spans, names, lo, hi)
    return trace_reduce.total(found) if found else None


def per_phase(run, phase: str, names) -> float | None:
    """Mean over the window's ``phase``s of the seconds spans called one of
    ``names`` cover in it."""
    names = frozenset(names)
    return run.mean_per_phase(
        phase, lambda p: covered_s(run.spans, names, p["start"], p["end"])
    )
