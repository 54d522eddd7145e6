"""Reading the source's shards on a direct acquire (shm view or peer
socket): the `direct.read` spans of `DirectWeightSyncDest._pull_once`. Mean
over the window's acquires."""

from chipbench import span_sums

LAYER = "direct sync"
UNIT = "s"
SOURCE = "program_span"
MOVES = "sync_s"


def read(run):
    return span_sums.per_phase(run, "acquire", ("direct.read",))
