"""The busiest device's events over an even share, from the counts
``replay.shard`` carries: ``events_max`` over ``events / devices``. 1.0 is an
even deal."""

from benchmarks import spans


def read(run):
    found = spans.program_spans(run)
    if found is None:
        return None
    deals = [r["attributes"] for r in found[0] if r["name"] == "replay.shard"
             and r["attributes"].get("events") and r["attributes"].get("devices")]
    if not deals:
        return None
    return (sum(a["events_max"] * a["devices"] for a in deals)
            / sum(a["events"] for a in deals))
