"""Share of the projection rebuilds' wall time inside ``replay.scan.read``
(host): the reader's steps, a chunk's projected column payloads read from the
segment file and decoded (``log/columnar.py:read_segment(columns=...)``) and
the last step that finds the file's end, from the program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.scan.read")
