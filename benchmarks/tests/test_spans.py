"""The span arithmetic behind the readers that split a rebuild, on a span list
written by hand: selection by the harness's spans, self time, the share no
leaf span covers, and a ring that has dropped the window's oldest spans."""

import types

import pytest

from benchmarks import spans


def rec(name, sid, parent, start, end, **attributes):
    return {"name": name, "id": sid, "parent": parent, "start": start,
            "end": end, "attributes": attributes}


# one rebuild of 10 s: pack [100, 105], upload [105.5, 106.5], replay [107, 110]
REBUILD = [
    rec("replay.encode", "e", None, 100.0, 105.0),
    rec("replay.encode.lanes", "e1", "e", 100.0, 101.0),
    rec("replay.encode.words", "e2", "e", 101.0, 103.5),
    rec("replay.encode.bytes", "e3", "e", 103.5, 104.25),
    rec("replay.encode.guard", "e4", "e", 104.5, 105.0),
    # continues the pack's trace: names it as parent, runs after it ended
    rec("replay.h2d", "h", "e", 105.5, 106.5, wire_bytes=100, put_bytes=128),
    rec("replay.h2d.bucket", "h1", "h", 105.5, 105.75),
    rec("replay.h2d.put", "h2", "h", 105.75, 106.5),
    rec("replay.resident", "r", "h", 107.0, 110.0),
    rec("replay.plan", "r1", "r", 107.0, 107.25),
    rec("replay.dispatch", "r2", "r", 107.25, 107.5),
    rec("replay.fetch", "f", "r", 107.5, 110.0),
    rec("replay.fetch.wait", "f1", "f", 107.5, 109.5),
    rec("replay.fetch.decode", "f2", "f", 109.5, 110.0),
]
HARNESS = [("pack", 100.0, 105.0), ("upload", 105.5, 106.5),
           ("replay", 107.0, 110.0)]


def test_a_span_that_follows_an_ended_parent_is_no_child():
    kids = spans.children(REBUILD)
    assert [k["name"] for k in kids["e"]] == [
        "replay.encode.lanes", "replay.encode.words", "replay.encode.bytes",
        "replay.encode.guard"]
    assert [k["id"] for k in kids["h"]] == ["h1", "h2"]  # not the fold
    assert [k["id"] for k in kids["r"]] == ["r1", "r2", "f"]


def test_self_time_is_the_duration_less_what_the_children_cover():
    own = spans.self_seconds(REBUILD)
    assert own["e"] == pytest.approx(0.25)  # the gap before the guard stage
    assert own["e2"] == pytest.approx(2.5)  # a leaf keeps its duration
    assert own["h"] == pytest.approx(0.0)
    assert own["r"] == pytest.approx(0.0)
    assert own["f"] == pytest.approx(0.0)
    assert spans.leaf_seconds(REBUILD) == pytest.approx(
        1.0 + 2.5 + 0.75 + 0.5 + 0.25 + 0.75 + 0.25 + 0.25 + 2.0 + 0.5)


def test_only_spans_inside_the_harness_spans_are_taken():
    warmup = [rec("replay.encode", "w", None, 50.0, 55.0),
              rec("replay.encode.words", "w1", "w", 51.0, 53.0)]
    straddling = [rec("replay.encode", "s", None, 99.0, 101.0)]
    taken = spans.inside(warmup + straddling + REBUILD,
                         [(s, e) for _n, s, e in HARNESS])
    assert [r["id"] for r in taken] == [r["id"] for r in REBUILD]


class FakeSpan:
    def __init__(self, r):
        self.name, self.parent_id = r["name"], r["parent"]
        self.context = types.SimpleNamespace(span_id=r["id"])
        self.start_mono, self.end_mono = r["start"], r["end"]
        self.attributes = r["attributes"]


class FakeRing:
    def __init__(self, recs, capacity):
        self._spans, self.capacity = [FakeSpan(r) for r in recs], capacity

    def spans(self, since_mono=None):
        return list(self._spans)


def run_of(harness, rebuilds, rebuild_s):
    return types.SimpleNamespace(
        spans=harness, facts={"rebuilds": rebuilds, "rebuild_s": rebuild_s})


def shifted(recs, by, tag):
    return [dict(r, id=r["id"] + tag, start=r["start"] + by, end=r["end"] + by,
                 parent=None if r["parent"] is None else r["parent"] + tag)
            for r in recs]


def test_shares_add_up_against_the_rebuilds_wall_time(monkeypatch):
    import surge_tpu.tracing as tracing

    warmup = [("pack", 50.0, 55.0), ("upload", 55.5, 56.5), ("replay", 57.0, 60.0)]
    ring = FakeRing(shifted(REBUILD, -50.0, "w") + REBUILD, capacity=4096)
    monkeypatch.setattr(tracing, "default_tracer", lambda: ring, raising=False)
    run = run_of(warmup + HARNESS, rebuilds=1, rebuild_s=10.0)
    assert spans.window_intervals(run) == [(100.0, 105.0), (105.5, 106.5),
                                           (107.0, 110.0)]
    assert spans.share_pct(run, "replay.encode.words", own=True) == \
        pytest.approx(25.0)
    assert spans.share_pct(run, "replay.h2d") == pytest.approx(10.0)
    assert spans.share_pct(run, "replay.fetch.wait") == pytest.approx(20.0)
    assert spans.share_pct(run, "replay.no-such-span") is None
    # 10 s of rebuild, 8.75 s under leaves: the encode umbrella's own 0.25 s,
    # and 1 s between the three calls
    assert spans.unaccounted_pct(run) == pytest.approx(12.5)
    leaves = sum(spans.share_pct(run, r["name"]) for r in REBUILD
                 if r["id"] not in ("e", "h", "r", "f"))
    assert leaves + spans.unaccounted_pct(run) == pytest.approx(100.0)


def test_a_ring_that_dropped_the_oldest_spans_counts_whole_rebuilds_only(
        monkeypatch):
    import surge_tpu.tracing as tracing

    first = shifted(REBUILD, -20.0, "a")
    harness = [(n, s - 20.0, e - 20.0) for n, s, e in HARNESS] + HARNESS
    # at capacity, the first rebuild's pack already gone from the ring
    held = first[5:] + REBUILD
    ring = FakeRing(held, capacity=len(held))
    monkeypatch.setattr(tracing, "default_tracer", lambda: ring, raising=False)
    run = run_of(harness, rebuilds=2, rebuild_s=20.0)
    recs, seconds = spans.program_spans(run)
    # the first rebuild's pack span starts before the oldest span held: out,
    # with its 5 of the 18 harness-span seconds
    assert min(r["start"] for r in recs) == 85.5
    assert seconds == pytest.approx(20.0 * 13.0 / 18.0)
    # nothing dropped: the whole window, the whole wall time
    whole = FakeRing(first + REBUILD, capacity=4096)
    monkeypatch.setattr(tracing, "default_tracer", lambda: whole, raising=False)
    recs, seconds = spans.program_spans(run)
    assert len(recs) == 2 * len(REBUILD) and seconds == 20.0


def test_a_program_without_a_ring_gives_nothing(monkeypatch):
    import surge_tpu.tracing as tracing

    monkeypatch.delattr(tracing, "default_tracer", raising=False)
    run = run_of(HARNESS, rebuilds=1, rebuild_s=10.0)
    assert spans.program_spans(run) is None
    assert spans.share_pct(run, "replay.h2d") is None
    assert spans.unaccounted_pct(run) is None
