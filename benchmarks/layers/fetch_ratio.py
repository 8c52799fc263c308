"""Event slots the fold's fetches asked of one array over events in the log:
``fetched_slots`` over ``events`` of the ``replay.resident`` spans (counted by
the program). Beside ``pad_ratio``, the slots folded an event."""

from benchmarks import stage_usage


def read(run):
    return stage_usage.ratio(run, ("replay.resident",),
                             stage_usage.attribute("fetched_slots"),
                             stage_usage.attribute("events"))
