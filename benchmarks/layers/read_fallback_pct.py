"""Share of the rows asked for in the window that the host store answered
instead of the slab: ``plane.stats`` fallbacks delta over rows asked."""


def read(run):
    c = run.counters
    if not c.get("rows_asked"):
        return None
    return 100.0 * c["fallbacks"] / c["rows_asked"]
