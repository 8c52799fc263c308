"""Share of the restores' wall time inside ``replay.restore.writeback``
(host), the per-aggregate write-back: the id put back (``_with_aggregate_id``,
the model's ``decode_state``), ``serialize_state`` and ``store.put``, from the
program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.restore.writeback")
