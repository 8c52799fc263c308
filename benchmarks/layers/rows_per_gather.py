"""Rows per device gather over the window: ``plane.stats`` deltas."""


def read(run):
    c = run.counters
    if not c.get("gathers"):
        return None
    return c["gathered_rows"] / c["gathers"]
