"""The channel's own time in a publish: the `weight_channel.publish` span
minus the `put_batch` spans inside it (version resolve, flatten, pointer
commit). Mean over the window's publishes."""

from chipbench import trace_reduce

LAYER = "entry"
UNIT = "s"
SOURCE = "program_span"
MOVES = "publish_s"


def read(run):
    def one(phase):
        outer = trace_reduce.spans_within(
            run.spans, "weight_channel.publish", phase["start"], phase["end"]
        )
        if not outer:
            return None
        return sum(trace_reduce.time_outside(s, run.spans, "put_batch") for s in outer)

    return run.mean_per_phase("publish", one)
