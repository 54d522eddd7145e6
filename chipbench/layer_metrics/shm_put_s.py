"""Time in the transport on a publish: the sum of the `transport.put` spans
inside it (handshake, copy into the shm segment, the volume's put RPC).
Mean over the window's publishes. Cross-checked against the store's own
`ts_transport_op_seconds{op=put}` over the window: a disagreement of more
than a tenth goes to stderr."""

import sys

from chipbench import trace_reduce

LAYER = "transports"
UNIT = "s"
SOURCE = "program_span"
MOVES = "publish_s"


def read(run):
    value = run.mean_per_phase(
        "publish",
        lambda p: trace_reduce.seconds_in(run.spans, "transport.put", p["start"], p["end"]),
    )
    in_spans = sum(run.last_readings)
    counted = sum(
        v
        for k, v in run.counters.items()
        if k.startswith("ts_transport_op_seconds_sum{") and "op=put" in k
    )
    if in_spans and counted and abs(in_spans - counted) > 0.1 * counted:
        print(
            f"shm_put_s: spans give {in_spans:.3f} s over the window, "
            f"ts_transport_op_seconds {counted:.3f} s",
            file=sys.stderr,
        )
    return value
