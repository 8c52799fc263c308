"""Device microseconds a sequential step of the fold: ``jit_fold``'s device
time within the traced window (one whole rebuild) over the ``scan_steps`` its
``replay.resident`` span carries, the steps a sequential tile backend takes one
after the other (tiles x tile width; 0 under the ``assoc`` tree, which takes
none: then there is nothing to divide by, and no number)."""

from benchmarks import spans

PROGRAM = "jit_fold"


def read(run):
    t = run.traced
    found = spans.program_spans(run)
    if t is None or found is None:
        return None
    # the traced rebuild is the window's first: its fold is the first span
    steps = [r["attributes"]["scan_steps"] for r in found[0]
             if r["name"] == "replay.resident"
             and r["attributes"].get("scan_steps")]
    device_s = t["program_s"].get(PROGRAM, 0.0)
    if not steps or device_s <= 0:
        return None
    return 1e6 * device_s / steps[0]
