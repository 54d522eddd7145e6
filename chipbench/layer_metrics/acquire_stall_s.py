"""The generator's stall: the acquire call until `block_until_ready` on
every leaf, the benchmark's own phase. Mean over the window's acquires: the
total that `h2d_tail_s`, `shm_get_s` and `reshard_s` are parts of. Where a
cell's runs repeat it closely enough it is also the end-to-end `acquire_s`
(a median of rounds); at hundreds of leaves it is host Python on shared
cores and swings too far from run to run for any bound the contract allows
(PERF.md, PR 22), so that cell holds it here, with no bound, and end to end
inside `sync_s`."""

LAYER = "entry"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "sync_s"


def read(run):
    return run.mean_per_phase("acquire", lambda p: p["end"] - p["start"])
