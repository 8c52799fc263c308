"""The window's slowest rebuild over its median rebuild, by trace id: a
rebuild's seconds are its ``replay.encode`` + ``replay.shard`` + ``replay.h2d``
+ ``replay.resident``; the window's first rebuild (the traced one) is left
out. Near 1 in a quiet window; a stall of one rebuild stands out here where
every summed share dilutes it."""

from benchmarks import stage_usage


def read(run):
    return stage_usage.slowest_ratio(run)
