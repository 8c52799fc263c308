"""Share of the rebuilds' wall time inside ``replay.shard``: the deal of the
packed wire's lanes to the mesh's devices and their tile plans (host), from the
program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.shard")
