"""Share of the projection rebuilds' wall time inside ``replay.scan.group``
(host): what the host does to group a chunk (``replay/query.py``; the span's
``how`` says which): ``device``, where the program keys its sort by the group
column on the device and the host reads only the column's least and greatest
values; else ``table`` or ``sort``, the column factorised on the host into its
distinct values and every event's group index. From the program's own spans."""

from benchmarks import spans


def read(run):
    return spans.share_pct(run, "replay.scan.group")
