"""From the acquire call's return to `block_until_ready` on every leaf: the
part of the host-to-device copies that outlives the call. The benchmark's
own phase. Mean over the window's acquires. A part of the
acquire, which every cell reports inside `sync_s` (and the cells whose runs
repeat it closely enough also as `acquire_s`)."""

LAYER = "client device edge"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "sync_s"


def read(run):
    return run.mean_per_phase("h2d_tail", lambda p: p["end"] - p["start"])
