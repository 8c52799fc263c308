"""Events folded per refresh round over the window: ``plane.stats`` deltas."""


def read(run):
    c = run.counters
    if not c.get("rounds"):
        return None
    return c["folded_events"] / c["rounds"]
