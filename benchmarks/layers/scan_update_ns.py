"""Device nanoseconds an update: the scan programs' device time within the
traced window (one whole rebuild, the window's first) over the ``updates`` its
``replay.scan.reduce`` spans carry (a chunk: events x reduces, counted by the
program, whatever implements them: one sort a chunk and the reduces over its
sorted runs, or the scatters on a view's small rounds). What the device costs
an event and reduce, whatever the host does."""

from benchmarks import spans

LAYER = "Scan programs"


def read(run):
    t = run.traced
    found = spans.program_spans(run)
    intervals = spans.window_intervals(run)
    if t is None or found is None or not intervals:
        return None
    lo, hi = intervals[0]  # the traced rebuild
    updates = sum(r["attributes"].get("updates", 0) for r in found[0]
                  if r["name"] == "replay.scan.reduce"
                  and lo <= r["start"] and r["end"] <= hi)
    device_s = t["layer_s"].get(LAYER, 0.0)
    if not updates or device_s <= 0:
        return None
    return 1e9 * device_s / updates
