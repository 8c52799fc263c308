"""Share of the restores' wall time inside ``replay.restore.decode`` (host):
``codec/tensor.py:decode_states``, one state object a row of the pulled
columns, from the program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.restore.decode")
