"""Time in the transport on an acquire: the sum of the `transport.get`
spans inside it. Mean over the window's acquires. A part of the
acquire, which every cell reports inside `sync_s` (and the cells whose runs
repeat it closely enough also as `acquire_s`)."""

from chipbench import trace_reduce

LAYER = "transports"
UNIT = "s"
SOURCE = "program_span"
MOVES = "sync_s"


def read(run):
    return run.mean_per_phase(
        "acquire",
        lambda p: trace_reduce.seconds_in(run.spans, "transport.get", p["start"], p["end"]),
    )
