"""Device to host on a publish: the seconds inside a `d2h.issue` span (the
`copy_to_host_async` loops of `client._put_batch` and
`sharding.put_requests`) or a `d2h.wait` span (each `np.asarray` of a shard,
and the direct path's host fallback) - their union, so that a wait that
overlaps an issue counts once. Mean over the window's publishes."""

from chipbench import span_sums

LAYER = "client device edge"
UNIT = "s"
SOURCE = "program_span"
MOVES = "publish_s"


def read(run):
    return span_sums.per_phase(run, "publish", ("d2h.issue", "d2h.wait"))
