"""Host copies from the read shards into the landing buffers on a direct
acquire: the `direct.land` spans (the `_apply_op` loops of
`DirectWeightSyncDest._pull_once`). Mean over the window's acquires."""

from chipbench import span_sums

LAYER = "direct sync"
UNIT = "s"
SOURCE = "program_span"
MOVES = "sync_s"


def read(run):
    return span_sums.per_phase(run, "acquire", ("direct.land",))
