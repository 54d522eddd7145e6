"""Controller metadata RPCs this client issued per cycle: the difference of
`ts_meta_rpcs_total` (all ops) over the window, over the cycles in it. A
count: it should repeat exactly from run to run."""

LAYER = "host actors"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "publish_s"


def read(run):
    if not run.cycles:
        return None
    return run.counter("ts_meta_rpcs_total{") / run.cycles
