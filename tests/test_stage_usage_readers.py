"""The eight per-layer readers over ``Span.usage`` and the rebuilds' trace ids
(``benchmarks/stage_usage.py``, ``benchmarks/layers/<metric>.py``), each on
span records written by hand: a window with a stalled rebuild, a ring that
dropped the oldest spans, a window too short to compare rebuilds, and a
parent commit whose spans carry no usage."""

import types

import pytest

from benchmarks import stage_usage
from benchmarks.run import load_reader

PAGE = stage_usage.PAGE_BYTES
READERS = ["pack_sys_pct", "pack_fault_pages_ratio", "h2d_put_gbps",
           "h2d_put_cores", "replay_host_pct", "fetch_ratio",
           "rebuild_slowest_ratio", "host_preempts_per_rebuild"]


def used(nivcsw=0, **kw):
    return {"user_s": 0.0, "sys_s": 0.0, "minflt": 0, "majflt": 0,
            "nivcsw": nivcsw, "proc_cpu_s": 0.0, "proc_minflt": 0, **kw}


def nested(nivcsw=0, **kw):
    """A stage inside another: the thread's figures alone."""
    usage = used(nivcsw, **kw)
    del usage["proc_cpu_s"], usage["proc_minflt"]
    return usage


def rebuild(tag, at, stall=0.0, preempted=0):
    """One rebuild's spans and the harness's three around them: a pack of 4 s
    (a quarter of it in the kernel, six fresh pages for a wire of four), an
    upload of 1 s (3e9 bytes in a put of 0.75 s, plus ``stall`` seconds the
    put waited, ``preempted`` times off the core; the process 0.5 s on its
    cores, 0.125 s of that the bucket's own copy), a replay of 3 s (1 s of
    it plan, dispatch and decode). ``(spans, harness)``."""
    u, r = at + 4.5, at + 6.0 + stall
    put_end = at + 5.5 + stall
    rows = [
        ("replay.encode", "e", None, at, at + 4.0,
         {"events": 1000, "wire_bytes": 4 * PAGE},
         used(1, sys_s=1.0, minflt=6)),
        ("replay.encode.words", "e1", "e", at + 1.0, at + 3.5, {},
         nested(1, sys_s=0.9, minflt=5)),
        ("replay.h2d", "h", "e", u, put_end, {"put_bytes": 3_000_000_000},
         used(preempted, user_s=0.25, proc_cpu_s=0.5)),
        ("replay.h2d.bucket", "h1", "h", u, u + 0.25, {},
         nested(user_s=0.125)),
        ("replay.h2d.put", "h2", "h", u + 0.25, put_end,
         {"put_bytes": 3_000_000_000}, nested(preempted, user_s=0.125)),
        ("replay.resident", "r", "h", r, r + 3.0,
         {"events": 1000, "padded_slots": 1280, "fetched_slots": 2560},
         used(2)),
        ("replay.plan", "r1", "r", r, r + 0.25, {}, nested()),
        ("replay.dispatch", "r2", "r", r + 0.25, r + 0.5, {}, nested()),
        ("replay.fetch", "f", "r", r + 0.5, r + 3.0, {}, nested(2)),
        ("replay.fetch.wait", "f1", "f", r + 0.5, r + 2.5, {}, nested(2)),
        ("replay.fetch.decode", "f2", "f", r + 2.5, r + 3.0, {}, nested()),
    ]
    spans = [{"name": n, "id": i + tag, "trace": "t" + tag,
              "parent": None if p is None else p + tag, "start": s, "end": e,
              "attributes": a, "usage": g} for n, i, p, s, e, a, g in rows]
    harness = [("pack", at, at + 4.0), ("upload", u, put_end),
               ("replay", r, r + 3.0)]
    return spans, harness


class FakeSpan:
    def __init__(self, rec, with_usage):
        self.name, self.parent_id = rec["name"], rec["parent"]
        self.context = types.SimpleNamespace(span_id=rec["id"],
                                             trace_id=rec["trace"])
        self.start_mono, self.end_mono = rec["start"], rec["end"]
        self.attributes = dict(rec["attributes"])
        if with_usage:
            self.usage = rec["usage"]
        else:  # a commit before Span.usage and fetched_slots
            self.attributes.pop("fetched_slots", None)


class FakeRing:
    def __init__(self, recs, capacity=4096, with_usage=True):
        self._spans = [FakeSpan(r, with_usage) for r in recs]
        self.capacity = capacity

    def spans(self, since_mono=None):
        return list(self._spans)


def window(rebuilds):
    """A warm-up rebuild before the window, then ``rebuilds``: the harness's
    spans, the ring's records, and the run the readers are handed. A
    rebuild's wall time is its harness spans' and 2 s between them."""
    recs, harness = rebuild("w", 50.0)
    wall = 0.0
    for tag, kw in rebuilds:
        spans, three = rebuild(tag, **kw)
        recs, harness = recs + spans, harness + three
        wall += three[-1][2] - three[0][1] + 1.0
    run = types.SimpleNamespace(
        spans=harness, facts={"rebuilds": len(rebuilds), "rebuild_s": wall})
    return run, recs


# the traced rebuild (100 preemptions: it must not count), two quiet ones and
# a third whose put stalled for 8 s and lost the core five times
STALLED = [("0", {"at": 100.0, "preempted": 100}), ("1", {"at": 120.0}),
           ("2", {"at": 140.0}),
           ("3", {"at": 160.0, "stall": 8.0, "preempted": 5})]
SHORT = STALLED[:2]

# {case: (rebuilds, how the ring holds them, {reader: value})}
CASES = {
    "a-stalled-third": (STALLED, {}, {
        "pack_sys_pct": 25.0, "pack_fault_pages_ratio": 1.5,
        "h2d_put_gbps": 12.0 / 11.0, "h2d_put_cores": 1.5 / 11.0,
        # 1 s a rebuild of 3 x 10 s and one of 18 s
        "replay_host_pct": 100.0 * 4.0 / 48.0, "fetch_ratio": 2.56,
        # roots 4 + 1 + 3 s, the stalled one's 4 + 9 + 3
        "rebuild_slowest_ratio": 2.0,
        "host_preempts_per_rebuild": (3 + 3 + 8) / 3}),
    # at capacity, the traced rebuild's pack already gone: its upload and
    # replay still count in the sums, the rebuild itself is not whole
    "the-ring-dropped-the-oldest": (STALLED, {"drop": 13}, {
        "pack_sys_pct": 25.0, "pack_fault_pages_ratio": 1.5,
        "h2d_put_gbps": 12.0 / 11.0, "h2d_put_cores": 1.5 / 11.0,
        # the wall time cut to the harness spans still covered: 36 s of 40
        "replay_host_pct": 100.0 * 4.0 / (48.0 * 36.0 / 40.0),
        "fetch_ratio": 2.56, "rebuild_slowest_ratio": 2.0,
        "host_preempts_per_rebuild": (3 + 3 + 8) / 3}),
    # one rebuild after the traced one: nothing to compare it with
    "a-window-of-two": (SHORT, {}, {
        "pack_sys_pct": 25.0, "pack_fault_pages_ratio": 1.5,
        "h2d_put_gbps": 4.0, "h2d_put_cores": 0.5,
        "replay_host_pct": 10.0, "fetch_ratio": 2.56,
        "rebuild_slowest_ratio": None, "host_preempts_per_rebuild": None}),
    "a-parent-without-usage": (STALLED, {"with_usage": False},
                               dict.fromkeys(READERS)),
}


def ring_for(recs, drop=0, with_usage=True):
    held = recs[drop:]
    return FakeRing(held, capacity=len(held) if drop else 4096,
                    with_usage=with_usage)


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_a_reader_on_spans_written_by_hand(monkeypatch, case, reader):
    import surge_tpu.tracing as tracing

    rebuilds, held, want = CASES[case]
    run, recs = window(rebuilds)
    ring = ring_for(recs, **held)
    monkeypatch.setattr(tracing, "default_tracer", lambda: ring)
    got = load_reader(reader)(run)
    if want[reader] is None:
        assert got is None
    else:
        assert got == pytest.approx(want[reader])


def test_rebuilds_are_grouped_by_trace_and_the_stalled_one_names_its_stage(
        monkeypatch):
    """What an operator reads from the ring after a slow window: which
    rebuild, and which of its stages carried the excess."""
    import surge_tpu.tracing as tracing

    run, recs = window(STALLED)
    monkeypatch.setattr(tracing, "default_tracer", lambda: ring_for(recs))
    counted = stage_usage.rebuilds(run)
    assert [b["trace"] for b in counted] == ["t1", "t2", "t3"]
    assert [b["seconds"] for b in counted] == [8.0, 8.0, 16.0]
    assert [b["nivcsw"] for b in counted] == [3, 3, 8]
    quiet, stalled = counted[0]["stages"], counted[2]["stages"]
    excess = {name: stalled[name] - quiet[name] for name in stalled}
    assert excess == {"replay.encode": 0.0, "replay.h2d": 8.0,
                      "replay.resident": 0.0}


def test_a_mesh_rebuild_counts_its_deal(monkeypatch):
    """``replay.shard`` follows the pack and the upload follows it: its
    seconds are the rebuild's too."""
    import surge_tpu.tracing as tracing

    run, recs = window(STALLED)
    for tag in "0123":
        (h2d,) = [r for r in recs if r["id"] == "h" + tag]
        recs.append({"name": "replay.shard", "id": "s" + tag,
                     "trace": "t" + tag, "parent": "e" + tag,
                     "start": h2d["start"] - 0.5, "end": h2d["start"],
                     "attributes": {}, "usage": used(1)})
    # the deal lies inside the harness's upload span
    run.spans = [(n, s - 0.5 if n == "upload" else s, e)
                 for n, s, e in run.spans]
    monkeypatch.setattr(tracing, "default_tracer", lambda: ring_for(recs))
    counted = stage_usage.rebuilds(run)
    assert [b["seconds"] for b in counted] == [8.5, 8.5, 16.5]
    assert [b["nivcsw"] for b in counted] == [4, 4, 9]
    assert load_reader("rebuild_slowest_ratio")(run) == pytest.approx(
        16.5 / 8.5)


def test_every_reader_has_its_entry_in_the_manifest():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        man = json.load(f)
    # the cold fold's rebuild cells; the projection rebuild scans, and opens
    # none of the fold's stages (PERF.md section 4)
    rebuilds = [w["name"] for w in man["workloads"]
                if w["name"].startswith("rebuild-")
                and w["config"] != "cart-projection-rebuild"]
    # a restore's chunks are loaded packed and fold under one root stage, so
    # the readers of the pack, of the upload's process figures and of the
    # rebuilds' trace ids find nothing to read in the restore cell
    in_a_restore = {"h2d_put_gbps", "replay_host_pct", "fetch_ratio"}
    entries = {m["name"]: m for m in man["per_layer"]}
    for name in READERS:
        assert entries[name]["workloads"] == rebuilds + (
            ["restore-cart-segment"] if name in in_a_restore else []), name
        assert entries[name]["layer"] == "Cold fold programs"
        assert entries[name]["moves"] == "rebuild_events_per_s"
