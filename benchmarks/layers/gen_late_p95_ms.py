"""How late the open-loop generator sent: send time less due time, 95th
percentile over every operation of the window."""


def read(run):
    return run.facts.get("gen_late_p95_ms")
