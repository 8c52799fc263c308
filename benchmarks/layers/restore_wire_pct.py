"""Share of the restores' wall time inside ``replay.restore.wire`` (host):
``store/restore.py:_chunk_wire``, the chunk's packed wire loaded (mmapped)
from the cache beside the segment or, on a miss, packed (``replay.encode``
inside it) and saved, from the program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.restore.wire")
