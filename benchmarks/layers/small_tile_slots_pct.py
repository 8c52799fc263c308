"""Share of the padded event slots a rebuild folds that the narrow tile
granularity carries, from the counts ``replay.resident`` carries
(``slots_small`` over ``padded_slots``): how much of the padding lies in the
shrinking prefix's remainder."""

from benchmarks import spans


def read(run):
    found = spans.program_spans(run)
    if found is None:
        return None
    folds = [r["attributes"] for r in found[0] if r["name"] == "replay.resident"
             and "slots_small" in r["attributes"]
             and r["attributes"].get("padded_slots")]
    if not folds:
        return None
    return (100.0 * sum(a["slots_small"] for a in folds)
            / sum(a["padded_slots"] for a in folds))
