"""Share of the restores' wall time inside ``replay.restore.read`` (host): the
reader's steps, a chunk's payloads read from the segment file and decoded
(``log/columnar.py:read_segment``) and the last step that finds the file's
end, from the program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.restore.read")
