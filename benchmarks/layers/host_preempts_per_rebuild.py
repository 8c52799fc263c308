"""Times a rebuild's thread was taken off its core against its will:
``nivcsw`` of the rebuilds' root spans (``replay.encode``, ``replay.shard``,
``replay.h2d``, ``replay.resident``) over the rebuilds counted, the window's
first left out (counted by the operating system). The shared host's doing."""

from benchmarks import stage_usage


def read(run):
    return stage_usage.preempts_per_rebuild(run)
