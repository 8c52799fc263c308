"""Share of the projection rebuilds' wall time inside ``replay.scan.group``
(host): a chunk's group column factorised, its distinct values and every
event's group index (``replay/query.py:_factorize_group``; the span's ``how``
says ``table`` or ``sort``), from the program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.scan.group")
