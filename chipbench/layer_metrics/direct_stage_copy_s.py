"""Host copies into the direct path's published staging buffers on a
publish: the `direct.stage_copy` spans (`direct_weight_sync._refresh_host`,
`register`, `_materialize_host_handles`). Mean over the window's
publishes."""

from chipbench import span_sums

LAYER = "direct sync"
UNIT = "s"
SOURCE = "program_span"
MOVES = "publish_s"


def read(run):
    return span_sums.per_phase(run, "publish", ("direct.stage_copy",))
