"""Host to device on an acquire, as the host sees it: the `h2d.dispatch`
spans, one per leaf (`sharding.build_array`'s `device_put`s, the plain-spec
`jnp.asarray`, `device_transfer.upload_stamped`). Dispatch only: the
transfers themselves end in the benchmark's `h2d_tail`. Mean over the
window's acquires."""

from chipbench import span_sums

LAYER = "client device edge"
UNIT = "s"
SOURCE = "program_span"
MOVES = "sync_s"


def read(run):
    return span_sums.per_phase(run, "acquire", ("h2d.dispatch",))
