"""Share of the rebuilds' wall time inside ``replay.plan``, ``replay.compile``
/ ``replay.dispatch`` and ``replay.fetch.decode``: host time inside the replay
that no device program covers (lane order and work lists, the dispatch calls,
the unpack of the pulled buffer), from the program's own spans."""

from benchmarks import stage_usage


def read(run):
    return stage_usage.share_pct(run, ("replay.plan", "replay.compile",
                                       "replay.dispatch",
                                       "replay.fetch.decode"))
