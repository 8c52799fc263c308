"""Share of the projection rebuilds' wall time inside ``replay.scan.merge``
(host): the chunks' partial rows combined into one row a group key, once a
scan (``replay/query.py:_merge_scan_outputs``), from the program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.scan.merge")
