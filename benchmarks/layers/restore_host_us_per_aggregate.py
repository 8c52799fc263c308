"""Host microseconds a restored aggregate: the seconds of the
``replay.restore.decode`` and ``replay.restore.writeback`` spans over the
``aggregates`` the write-backs counted (by the program). What the per-aggregate
Python costs whatever the chip does."""

from benchmarks import spans


def read(run):
    found = spans.program_spans(run)
    if found is None:
        return None
    seconds = sum(r["end"] - r["start"] for r in found[0] if r["name"] in (
        "replay.restore.decode", "replay.restore.writeback"))
    restored = sum(r["attributes"].get("aggregates", 0) for r in found[0]
                   if r["name"] == "replay.restore.writeback")
    return 1e6 * seconds / restored if restored else None
