"""Share of the wall time of the window's whole rebuilds that passed inside
``pack_resident`` (host), from the harness's own spans. In a traced run the
time the profiler takes to stop lies between rebuilds and is left out."""


def read(run):
    f = run.facts
    if not f.get("rebuild_s"):
        return None
    return 100.0 * f["pack_s"] / f["rebuild_s"]
