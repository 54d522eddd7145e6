"""A channel publish outside `weight_channel.publish`: resolving the next
version before it (`weight_channel.resolve_version`) and the GC of expired
versions after it (`weight_channel.gc`). Mean over the window's publishes."""

from chipbench import span_sums

LAYER = "entry"
UNIT = "s"
SOURCE = "program_span"
MOVES = "publish_s"


def read(run):
    return span_sums.per_phase(
        run, "publish", ("weight_channel.resolve_version", "weight_channel.gc")
    )
