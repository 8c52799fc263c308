"""Share of the rebuilds' wall time inside ``replay.encode.bytes`` (host): the
split of the packed words into wire bytes and side columns, from the program's
own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.encode.bytes", own=True)
