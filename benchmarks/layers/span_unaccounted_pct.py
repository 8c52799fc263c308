"""Share of the rebuilds' wall time that no leaf span of the program covers:
the umbrellas' self time and what passes between ``pack_resident``,
``upload_resident`` and ``replay_resident``. What the spans cannot see."""

from benchmarks import spans


def read(run):
    return spans.unaccounted_pct(run)
