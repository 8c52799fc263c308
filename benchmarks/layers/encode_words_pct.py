"""Share of the rebuilds' wall time inside ``replay.encode.words`` (host): the
word build of ``pack_resident`` (``WireFormat._pack_words``), from the program's
own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.encode.words", own=True)
