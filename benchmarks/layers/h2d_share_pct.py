"""Share of the rebuilds' wall time inside ``replay.h2d``: the whole of
``upload_resident``, the host copy that buckets the buffers and the put
through ``block_until_ready``, from the program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.h2d")
