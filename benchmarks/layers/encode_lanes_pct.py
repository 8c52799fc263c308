"""Share of the rebuilds' wall time inside ``replay.encode.lanes`` (host): the
per-aggregate length count, the length sort and the grouped check of
``pack_resident``, from the program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.encode.lanes", own=True)
