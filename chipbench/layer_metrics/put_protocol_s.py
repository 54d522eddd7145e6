"""The seconds of a publish in which the client waits on actors: the
`transport.handshake` spans' own time (outside the `shm.attach` and
`shm.land` inside them: the handshake RPC), the `transport.put_rpc` spans
(the volume's put RPC) and the `put_batch/notify` events (the controller's
notify RPC). Mean over the window's publishes."""

from chipbench import span_sums, trace_reduce

LAYER = "host actors"
UNIT = "s"
SOURCE = "program_span"
MOVES = "publish_s"

INSIDE_HANDSHAKE = frozenset(("shm.attach", "shm.land"))
RPCS = frozenset(("transport.put_rpc", "put_batch/notify"))


def read(run):
    def one(phase):
        lo, hi = phase["start"], phase["end"]
        handshakes = trace_reduce.spans_within(run.spans, "transport.handshake", lo, hi)
        rpcs = span_sums.covered_s(run.spans, RPCS, lo, hi)
        if not handshakes and rpcs is None:
            return None
        own = sum(
            (s["end"] - s["start"])
            - (span_sums.covered_s(run.spans, INSIDE_HANDSHAKE, s["start"], s["end"]) or 0.0)
            for s in handshakes
        )
        return own + (rpcs or 0.0)

    # On a program without these spans `put_batch/notify` alone would pass
    # for the whole protocol.
    if not any(s["name"] == "transport.put_rpc" for s in run.spans):
        return None
    return run.mean_per_phase("publish", one)
