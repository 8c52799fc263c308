"""Event slots the fold scanned over events in the log (counted by the program)."""


def read(run):
    f = run.facts
    if not f.get("events"):
        return None
    return f["padded_events"] / f["events"]
