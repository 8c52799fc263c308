"""Share of the rebuilds' wall time inside ``replay.fetch.wait``: from the
finalize dispatch to the bytes on the host, which is where the host waits for
the chip to densify and fold, from the program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.fetch.wait")
