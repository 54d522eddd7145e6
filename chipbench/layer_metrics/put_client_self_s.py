"""The client's own time in a publish: the `put_batch` spans minus the
`transport.put` spans inside them. Until D2H has a span of its own this is
the D2H wait (`copy_to_host_async` + `np.asarray`), request building and the
notify together. Mean over the window's publishes."""

from chipbench import trace_reduce

LAYER = "client device edge"
UNIT = "s"
SOURCE = "program_span"
MOVES = "publish_s"


def read(run):
    def one(phase):
        puts = trace_reduce.spans_within(
            run.spans, "put_batch", phase["start"], phase["end"]
        )
        if not puts:
            return None
        return sum(trace_reduce.time_outside(s, run.spans, "transport.put") for s in puts)

    return run.mean_per_phase("publish", one)
