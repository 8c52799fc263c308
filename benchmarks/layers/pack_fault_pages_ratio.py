"""Bytes of the pages ``pack_resident`` touched for the first time over the
bytes of the wire it made: ``minflt`` of the ``replay.encode`` spans times the
page size, over their ``wire_bytes``. 1.0: every page of the wire is fresh each
rebuild and nothing else is; above: temporaries too; near 0 once a buffer is
reused."""

from benchmarks import stage_usage


def read(run):
    return stage_usage.ratio(run, ("replay.encode",),
                             stage_usage.usage("minflt"),
                             stage_usage.attribute("wire_bytes"),
                             scale=stage_usage.PAGE_BYTES)
