"""Share of the rebuilds' wall time inside ``replay.encode.guard`` (host): the
guard-row padding copies and the lane starts of ``pack_resident``, from the
program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.encode.guard", own=True)
