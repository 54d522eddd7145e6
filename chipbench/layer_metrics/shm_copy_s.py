"""THE host copy of a publish, client arrays into shm segments: the
`shm.land` spans (`landing.land_async(..., stage="put")` in
`shared_memory._post_handshake`; the first-touch page faults of a fresh
segment land here). Mean over the window's publishes. Cross-checked
against the store's own `ts_landing_copy_seconds{stage=put}` over the
window: a disagreement of more than a tenth goes to stderr."""

import sys

from chipbench import span_sums

LAYER = "transports"
UNIT = "s"
SOURCE = "program_span"
MOVES = "publish_s"


def read(run):
    value = span_sums.per_phase(run, "publish", ("shm.land",))
    in_spans = sum(run.last_readings)
    counted = sum(
        v
        for k, v in run.counters.items()
        if k.startswith("ts_landing_copy_seconds_sum{") and "stage=put" in k
    )
    if in_spans and counted and abs(in_spans - counted) > 0.1 * counted:
        print(
            f"shm_copy_s: spans give {in_spans:.3f} s over the window, "
            f"ts_landing_copy_seconds {counted:.3f} s",
            file=sys.stderr,
        )
    return value
