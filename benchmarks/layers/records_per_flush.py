"""Records per group-commit flush over the window: ``producer_stats()`` deltas."""


def read(run):
    c = run.counters
    if not c.get("flushes"):
        return None
    return c["records_published"] / c["flushes"]
