"""Host-side reshard on an acquire: the sum of the `reshard` spans inside it
(the client's assembly of target shards from stored ones). An acquire whose
shards arrive as stored has none, and the metric is left out. Mean over
the window's acquires. A part of the
acquire, which every cell reports inside `sync_s` (and the cells whose runs
repeat it closely enough also as `acquire_s`)."""

from chipbench import trace_reduce

LAYER = "client reshard"
UNIT = "s"
SOURCE = "program_span"
MOVES = "sync_s"


def read(run):
    return run.mean_per_phase(
        "acquire",
        lambda p: trace_reduce.seconds_in(run.spans, "reshard", p["start"], p["end"]),
    )
