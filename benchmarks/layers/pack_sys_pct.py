"""Share of ``pack_resident``'s seconds that its thread spent in the kernel:
``sys_s`` of the ``replay.encode`` spans over their seconds (counted by the
operating system). A first-touch page fault, an ``mmap`` and a ``munmap`` are
kernel time; the word build itself is not."""

from benchmarks import stage_usage


def read(run):
    return stage_usage.ratio(run, ("replay.encode",),
                             stage_usage.usage("sys_s"), stage_usage.seconds,
                             scale=100.0)
