"""Share of the projection rebuilds' wall time inside ``replay.scan.h2d``
(host and link): a chunk's columns padded to the event bucket on the host and
put on the device, through ``block_until_ready`` of every buffer, from the
program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.scan.h2d")
