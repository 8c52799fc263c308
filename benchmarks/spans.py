"""The program's own spans, for the per-layer readers that split a rebuild.

``surge_tpu.tracing.default_tracer()`` keeps the spans the cold path records
(``replay.encode`` and its children, ``replay.h2d``, ``replay.resident``, ...)
in a bounded ring, on ``time.monotonic``. The harness's spans (``run.spans``)
are on ``time.perf_counter``: on Linux both read CLOCK_MONOTONIC, so intervals
of the two compare as they are. A reader takes the program's spans that lie
inside the harness's spans of the window's rebuilds and divides by
``run.facts["rebuild_s"]``, the wall time of those rebuilds: the denominator of
``pack_share_pct``, so the shares add up against it.

A program that has no such ring (an older commit) gives ``None`` everywhere,
and the result line leaves the metric out.

The arithmetic works on plain records, ``{"name", "id", "parent", "start",
"end", "attributes"}``, so that a test can hand it a span list written by hand.
"""

from __future__ import annotations


def records(spans) -> list:
    """Finished ``surge_tpu.tracing.Span`` objects as plain records."""
    return [{"name": s.name, "id": s.context.span_id, "parent": s.parent_id,
             "start": s.start_mono, "end": s.end_mono,
             "attributes": dict(s.attributes)}
            for s in spans if s.end_mono is not None]


def inside(recs: list, intervals: list) -> list:
    """The records that lie wholly inside one of ``intervals`` [(start, end)]."""
    return [r for r in recs
            if any(lo <= r["start"] and r["end"] <= hi for lo, hi in intervals)]


def children(recs: list) -> dict:
    """{id: [records that name it as parent and lie inside its interval]}. A
    span that only continues another's trace (an upload after the pack it
    follows) names a parent that had ended: that is no child."""
    by_id = {r["id"]: r for r in recs}
    out: dict = {r["id"]: [] for r in recs}
    for r in recs:
        parent = by_id.get(r["parent"])
        if (parent is not None and parent["start"] <= r["start"]
                and r["end"] <= parent["end"]):
            out[parent["id"]].append(r)
    return out


def self_seconds(recs: list) -> dict:
    """{id: the span's duration less what its children cover}. Children of one
    span run one after the other here, so what they cover is their sum."""
    kids = children(recs)
    return {r["id"]: (r["end"] - r["start"])
            - sum(k["end"] - k["start"] for k in kids[r["id"]])
            for r in recs}


def leaf_seconds(recs: list) -> float:
    """Summed duration of the spans that have no child among ``recs``."""
    kids = children(recs)
    return sum(r["end"] - r["start"] for r in recs if not kids[r["id"]])


def window_intervals(run) -> list | None:
    """The harness's spans of the window's whole rebuilds, [(start, end)]:
    of each name the last ``facts["rebuilds"]``, which leaves the set-up's
    warm-up rebuild out."""
    n = run.facts.get("rebuilds")
    if not n:
        return None
    by_name: dict = {}
    for name, start, end in run.spans:
        by_name.setdefault(name, []).append((start, end))
    return sorted(iv for ivs in by_name.values() for iv in ivs[-n:])


def program_spans(run) -> tuple | None:
    """``(records, seconds)``: the program's spans inside the window's
    rebuilds, and those rebuilds' wall time, ``facts["rebuild_s"]``. None where
    the program keeps no ring, or the driver timed no rebuild.

    The ring is bounded. Where a window made more spans than it holds, the
    oldest are gone: then only the harness's spans the ring still covers whole
    count, and the wall time is cut by the share of harness-span seconds they
    hold (a factor of exactly 1 when nothing was dropped)."""
    try:
        from surge_tpu.tracing import default_tracer
    except ImportError:
        return None
    intervals = window_intervals(run)
    if not intervals or not run.facts.get("rebuild_s"):
        return None
    ring = default_tracer()
    held = ring.spans()
    if not held:
        return None
    kept = intervals
    if ring.capacity is not None and len(held) >= ring.capacity:
        kept = [iv for iv in intervals if iv[0] >= held[0].start_mono]
    recs = inside(records(held), kept)
    if not recs:
        return None
    covered = (sum(hi - lo for lo, hi in kept)
               / sum(hi - lo for lo, hi in intervals))
    return recs, run.facts["rebuild_s"] * covered


def share_pct(run, name: str, own: bool = False):
    """The spans called ``name`` as a share of the rebuilds' wall time, in per
    cent: their whole durations, or with ``own`` their self time."""
    found = program_spans(run)
    if found is None:
        return None
    recs, total = found
    seconds = (self_seconds(recs) if own else
               {r["id"]: r["end"] - r["start"] for r in recs})
    named = [seconds[r["id"]] for r in recs if r["name"] == name]
    if not named:
        return None
    return 100.0 * sum(named) / total


def unaccounted_pct(run):
    """Wall time of the rebuilds that no leaf span covers: the umbrellas' self
    time and what passes between the program's calls."""
    found = program_spans(run)
    if found is None:
        return None
    recs, total = found
    return 100.0 * (total - leaf_seconds(recs)) / total
