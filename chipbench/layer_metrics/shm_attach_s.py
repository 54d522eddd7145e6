"""Attaching or creating the shm segments of a publish: the `shm.attach`
spans (`shared_memory._post_handshake`: the arena and the per-request
attach-or-`ShmSegment.create` loop). Mean over the window's publishes; the
info line's `per_phase` shows the publishes that went cold."""

from chipbench import span_sums

LAYER = "transports"
UNIT = "s"
SOURCE = "program_span"
MOVES = "publish_s"


def read(run):
    return span_sums.per_phase(run, "publish", ("shm.attach",))
