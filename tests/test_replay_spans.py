"""The cold rebuild's spans (pack -> upload -> fold -> pull): real intervals from
``ReplayProfiler.stage``, one trace a rebuild, on the profiler's clock as
``TraceAnnotation``s of the same names, kept in the bounded default ring."""

import ast
import glob
import inspect
import json
import os
import time

import numpy as np
import pytest

from surge_tpu.codec import wire as wire_module
from surge_tpu.codec.tensor import ColumnarEvents
from surge_tpu.config import default_config
from surge_tpu.models.counter import make_replay_spec
from surge_tpu.replay import engine as engine_module
from surge_tpu.replay.engine import (COLD_PATH_JIT_NAMES, ReplayEngine,
                                     ResidentWire)
from surge_tpu.replay.profiler import ReplayProfiler
from surge_tpu.tracing import (DEFAULT_RING_CAPACITY, InMemoryTracer,
                               JsonlSpanExporter, Tracer, active_span,
                               default_tracer)
# where the word is built -> a column dtype that sends it there
from tests.test_pack_blocked import WORDS_FROM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENCODE_CHILDREN = ["replay.encode.lanes", "replay.encode.words",
                   "replay.encode.bytes", "replay.encode.guard"]
H2D_CHILDREN = ["replay.h2d.bucket", "replay.h2d.put"]
FETCH_CHILDREN = ["replay.fetch.wait", "replay.fetch.decode"]
UMBRELLAS = ["replay.encode", "replay.h2d", "replay.resident"]


def make_events(n_agg=48, n_per=20, words_from="host"):
    n = n_agg * n_per
    dtype = WORDS_FROM[words_from]
    return ColumnarEvents(
        num_aggregates=n_agg,
        agg_idx=np.repeat(np.arange(n_agg, dtype=np.int32), n_per),
        type_ids=np.zeros(n, dtype=np.int32),
        cols={"increment_by": np.ones(n, dtype=dtype),
              "decrement_by": np.zeros(n, dtype=dtype)},
        derived_cols={"sequence_number": "ordinal"})


def make_engine(**kw):
    cfg = default_config().with_overrides({
        "surge.replay.batch-size": 64, "surge.replay.time-chunk": 16})
    return ReplayEngine(make_replay_spec(), config=cfg, **kw)


def rebuild(engine, events):
    wire = engine.pack_resident(events)
    resident = engine.upload_resident(wire)
    res = engine.replay_resident(resident)
    assert (res.states["count"] == 20).all()
    return wire, resident, res


def ring_since(since):
    return default_tracer().spans(since_mono=since)


def one(spans, name):
    found = [s for s in spans if s.name == name]
    assert len(found) == 1, (name, [s.name for s in spans])
    return found[0]


def assert_children(spans, parent, names):
    for name in names:
        for child in (s for s in spans if s.name == name):
            assert child.parent_id == parent.context.span_id, name
            assert child.context.trace_id == parent.context.trace_id
            # open while the parent was: inside it on the monotonic clock
            assert parent.start_mono <= child.start_mono, name
            assert child.end_mono <= parent.end_mono, name
        assert any(s.name == name for s in spans), name


@pytest.mark.parametrize("words_from", sorted(WORDS_FROM))
@pytest.mark.parametrize("fold", ["first", "again"])
def test_one_rebuild_is_one_trace_with_the_whole_tree(fold, words_from):
    """``again``: a second fold of the uploaded corpus is the same subtree in
    the same trace, with a steady dispatch where the first compiled.
    ``words_from``: the same tree whether the host packed the word or the
    upload put its three int32 sources and built it on the device."""
    engine = make_engine()  # no profiler, tracer or config key passed
    since = time.monotonic()
    wire, resident, res = rebuild(engine, make_events(words_from=words_from))
    spans = ring_since(since)
    assert len({s.context.trace_id for s in spans}) == 1
    encode, h2d, resident_span = (one(spans, n) for n in UMBRELLAS)
    # the trace replay.encode opened: the upload continues the pack's context
    # and the fold the upload's, each after the one it follows
    assert encode.parent_id is None
    assert h2d.parent_id == encode.context.span_id
    assert encode.end_mono <= h2d.start_mono <= h2d.end_mono
    assert wire.trace_ctx == encode.context
    assert resident.trace_ctx == h2d.context
    assert_children(spans, encode, ENCODE_CHILDREN)
    assert_children(spans, h2d, H2D_CHILDREN)
    bucket, put = (one(spans, name) for name in H2D_CHILDREN)
    assert all(s.parent_id is not None for s in spans if s is not encode)
    dispatched = "replay.compile"
    if fold == "again":
        since = time.monotonic()
        res = engine.replay_resident(resident)
        spans = ring_since(since)
        assert {s.context.trace_id for s in spans} == {encode.context.trace_id}
        first, resident_span = resident_span, one(spans, "replay.resident")
        assert resident_span.attributes == first.attributes
        dispatched = "replay.dispatch"
    fetch = one(spans, "replay.fetch")
    assert resident_span.parent_id == h2d.context.span_id
    assert h2d.end_mono <= resident_span.start_mono
    assert_children(spans, resident_span,
                    ["replay.plan", dispatched, "replay.fetch"])
    assert_children(spans, fetch, FETCH_CHILDREN)
    # one layout: plan, one dispatch a granularity, the pull, nothing else
    assert sorted({s.name for s in spans
                   if s.parent_id == resident_span.context.span_id}) == sorted(
        ["replay.plan", dispatched, "replay.fetch"])
    assert all(s.status == "ok" and s.end_mono is not None for s in spans)
    # the counts ride as attributes
    n = make_events().num_events
    assert encode.attributes["events"] == n
    assert encode.attributes["aggregates"] == 48
    on_device = words_from == "device"
    assert wire.host_packed != on_device  # the upload built none on the host
    guard_rows = wire.packed_shape[0]
    assert encode.attributes["wire_bytes"] == guard_rows == n + wire.guard
    # how the pack went: 960 grouped events are one block of the host's word
    # pass, and none where the sources were handed over
    assert encode.attributes["words_from"] == words_from
    assert encode.attributes["blocks"] == (0 if on_device else 1)
    assert encode.attributes["grouped"] is True
    assert encode.attributes["lanes_from"] == "boundaries"
    assert h2d.attributes["wire_bytes"] == encode.attributes["wire_bytes"]
    # a wire of under one piece: one put an array, padded on the host to its
    # bucket (the one-byte word alone, or its three int32 sources: the type
    # ids and the two packed columns), with the two int32 lane vectors
    arrays, put_bytes = (3, 3 * 4 << 16) if on_device else (1, 1 << 16)
    assert h2d.attributes["put_bytes"] == resident.wire_bytes == put_bytes
    assert h2d.attributes["pieces"] == arrays
    assert h2d.attributes["word_source_bytes"] == on_device * put_bytes
    assert h2d.attributes["copied_bytes"] == (
        put_bytes + 2 * 4 * resident.b_pad)
    assert bucket.attributes == {
        "copied_bytes": h2d.attributes["copied_bytes"]}
    assert put.attributes == {"put_bytes": put_bytes, "pieces": arrays}
    assert sorted(resident_span.attributes) == [
        "aggregates", "events", "fetched_slots", "gather", "padded_slots",
        "rounds", "rows_fetched", "scan_steps", "slots_small", "tiles",
        "tiles_small", "width", "width_cap"]
    assert resident_span.attributes["aggregates"] == 48
    assert resident_span.attributes["events"] == n
    assert resident_span.attributes["padded_slots"] == res.padded_events
    # logs of 20 under a cap of 16, one slice a lane: three tiles of 8 (24
    # slots a lane) beat two of 16
    assert resident_span.attributes["tiles"] == 3
    assert resident_span.attributes["width"] == 8
    assert resident_span.attributes["width_cap"] == 16
    # engine.stats keeps its keys, fed by the same intervals
    assert sorted(engine.stats) == ["h2d_s", "pack_s", "rows_fetched",
                                    "windows"]
    assert engine.stats["pack_s"] == encode.seconds
    assert engine.stats["h2d_s"] == h2d.seconds


@pytest.mark.parametrize("words_from", sorted(WORDS_FROM))
@pytest.mark.parametrize("fold", ["first", "again"])
def test_a_sharded_rebuild_is_one_trace_with_the_whole_tree(fold, words_from):
    """The mesh form (``replay/resident_mesh.py``): pack, deal, upload, fold
    and pull of one rebuild over four devices, the one-chip names where the
    work is the same, ``replay.shard`` for the deal, ``devices`` on all
    three of its umbrellas; the word packed on the host or built on each
    device from its slice of the sources."""
    import jax

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
    engine = make_engine(mesh=mesh)
    since = time.monotonic()
    wire = engine.pack_resident(make_events(words_from=words_from))
    sharded = engine.prepare_resident_sharded(wire)
    res = engine.replay_resident_sharded(sharded)
    assert (res.states["count"] == 20).all()
    spans = ring_since(since)
    assert len({s.context.trace_id for s in spans}) == 1
    encode, shard, h2d, resident_span = (one(spans, n) for n in (
        "replay.encode", "replay.shard", "replay.h2d", "replay.resident"))
    # each continues the one before it, after it
    assert encode.parent_id is None
    assert shard.parent_id == encode.context.span_id
    assert h2d.parent_id == shard.context.span_id
    assert encode.end_mono <= shard.start_mono <= shard.end_mono
    assert shard.end_mono <= h2d.start_mono
    assert sharded.trace_ctx == h2d.context
    assert_children(spans, h2d, H2D_CHILDREN)
    bucket, put = (one(spans, name) for name in H2D_CHILDREN)
    dispatched = "replay.compile"
    if fold == "again":
        since = time.monotonic()
        res = engine.replay_resident_sharded(sharded)
        spans = ring_since(since)
        assert {s.context.trace_id for s in spans} == {encode.context.trace_id}
        first, resident_span = resident_span, one(spans, "replay.resident")
        assert resident_span.attributes == first.attributes
        dispatched = "replay.dispatch"
    fetch = one(spans, "replay.fetch")
    assert resident_span.parent_id == h2d.context.span_id
    assert h2d.end_mono <= resident_span.start_mono
    assert_children(spans, resident_span,
                    ["replay.plan", dispatched, "replay.fetch"])
    assert_children(spans, fetch, FETCH_CHILDREN)
    assert sorted({s.name for s in spans
                   if s.parent_id == resident_span.context.span_id}) == sorted(
        ["replay.plan", dispatched, "replay.fetch"])
    assert all(s.status == "ok" and s.end_mono is not None for s in spans)
    # the deal: 48 logs of 20 events, 12 lanes and 240 events a device, one
    # tile a round each; equal logs tile the buffer in lane order, nothing is
    # copied
    n = make_events().num_events
    assert shard.attributes == {
        "aggregates": 48, "events": n, "devices": 4, "lanes_min": 12,
        "lanes_max": 12, "events_min": 240, "events_max": 240,
        "tiles_min": 3, "tiles_max": 3, "copied_bytes": 0}
    # the upload: one piece (the bucket) a device for the one-byte word, or
    # for each of its three int32 sources, and the two int32 lane vectors of
    # every device
    on_device = words_from == "device"
    assert wire.host_packed != on_device
    assert encode.attributes["words_from"] == words_from
    arrays, put_bytes = (3, 4 * 3 * 4 << 16) if on_device else (1, 4 << 16)
    assert h2d.attributes == {
        "wire_bytes": encode.attributes["wire_bytes"], "side_bytes": 0,
        "devices": 4, "put_bytes": put_bytes, "pieces": 4 * arrays,
        "word_source_bytes": on_device * put_bytes,
        "copied_bytes": put_bytes + 2 * 4 * 4 * sharded.b_pad}
    assert bucket.attributes == {
        "copied_bytes": h2d.attributes["copied_bytes"]}
    assert put.attributes == {"put_bytes": put_bytes, "pieces": 4 * arrays}
    assert sorted(resident_span.attributes) == [
        "aggregates", "devices", "events", "fetched_slots", "gather",
        "padded_slots", "rounds", "rows_fetched", "scan_steps", "slots_small",
        "tiles", "tiles_small", "width", "width_cap"]
    a = resident_span.attributes
    assert (a["aggregates"], a["events"], a["devices"]) == (48, n, 4)
    assert (a["width"], a["width_cap"]) == (sharded.width, 16) == (8, 16)
    assert a["padded_slots"] == res.padded_events
    # three tiles of 8 events a device; the steps ONE device takes in sequence
    assert (a["tiles"], a["rounds"], a["scan_steps"]) == (12, 3, 3 * 8)
    assert fetch.attributes == {"aggregates": 48}
    wait = one(spans, "replay.fetch.wait")
    assert wait.attributes == {"wire": "narrow", "bytes": 2 * (2 * 48 + 2)}
    assert sorted(engine.stats) == ["h2d_s", "pack_s", "rows_fetched",
                                    "windows"]
    assert engine.stats["h2d_s"] == h2d.seconds


@pytest.mark.parametrize("gather, per_lane", [("slices", 1), ("rows", 2)])
def test_the_fold_spans_say_how_the_lane_rows_were_fetched(monkeypatch, gather,
                                                           per_lane):
    """``gather``, ``rows_fetched`` and ``fetched_slots`` on
    ``replay.resident``: a window of up to 16 events starting anywhere needs
    two aligned rows of 128 slots, or one slice of its own width; the
    counter's wire is one array. Every fold of a corpus fetches the same."""
    monkeypatch.setattr(engine_module, "_lane_gather", lambda: gather)
    engine = make_engine()
    since = time.monotonic()
    _, resident, _ = rebuild(engine, make_events())
    spans = ring_since(since)
    plan = engine._plan_for(resident)
    want = sum(len(i0) * bs * per_lane
               for i0, bs in ((plan.big_i0, plan.bs_big),
                              (plan.small_i0, plan.bs_small)))
    fold = one(spans, "replay.resident")
    assert fold.attributes["gather"] == gather
    assert fold.attributes["rows_fetched"] == want > 0
    assert fold.attributes["fetched_slots"] == want * (
        128 if gather == "rows" else plan.width)
    assert engine.stats["rows_fetched"] == want
    since = time.monotonic()
    engine.replay_resident(resident)
    again = one(ring_since(since), "replay.resident")
    assert again.attributes["rows_fetched"] == want
    assert again.attributes["fetched_slots"] == (
        fold.attributes["fetched_slots"])
    assert engine.stats["rows_fetched"] == 2 * want


@pytest.mark.parametrize("gather", ["slices", "rows"])
@pytest.mark.parametrize("sharded", [False, True])
def test_the_fold_span_says_which_width_the_plan_chose(monkeypatch, gather,
                                                       sharded):
    """``width`` and ``width_cap`` on ``replay.resident``, on one device or
    four: under the default cap of 512, logs of 100 events fold at 128 in one
    round where the fetch reads aligned rows (the chip's), and at 8 in 13
    where it reads a slice a lane; ``scan_steps`` is the tiles of one device
    times that width, and the padded slots stay under two an event."""
    import jax

    monkeypatch.setattr(engine_module, "_lane_gather", lambda: gather)
    mesh = (jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
            if sharded else None)
    engine = ReplayEngine(make_replay_spec(), mesh=mesh,
                          config=default_config().with_overrides(
                              {"surge.replay.batch-size": 64}))
    events = make_events(n_agg=256, n_per=100)
    since = time.monotonic()
    wire = engine.pack_resident(events)
    if sharded:
        res = engine.replay_resident_sharded(
            engine.prepare_resident_sharded(wire))
    else:
        res = engine.replay_resident(engine.upload_resident(wire))
    assert (res.states["count"] == 100).all()
    a = one(ring_since(since), "replay.resident").attributes
    width, rounds = (128, 1) if gather == "rows" else (8, 13)
    assert (a["width"], a["width_cap"]) == (width, 512)
    assert engine.resident_tile_width() == 512 <= wire.guard
    assert a["rounds"] == rounds and a["tiles"] == 4 * rounds
    assert a["scan_steps"] == (1 if sharded else 4) * rounds * width
    assert a["padded_slots"] == res.padded_events == 256 * rounds * width
    assert a["padded_slots"] / a["events"] < 2
    assert a["rows_fetched"] == 256 * (2 if gather == "rows" else 13)
    # one array: two rows of 128 slots a lane, or 13 slices of 8
    assert a["fetched_slots"] == 256 * (2 * 128 if gather == "rows"
                                        else 13 * 8)


@pytest.mark.parametrize("words_from", sorted(WORDS_FROM))
@pytest.mark.parametrize("grouped, block, blocks, lanes_from", [
    (True, 1 << 18, 1, "boundaries"), (True, 100, 10, "boundaries"),
    (True, 7, 138, "boundaries"), (False, 100, 10, "bincount")])
def test_the_encode_span_says_how_the_pack_went(monkeypatch, grouped, block,
                                                blocks, lanes_from,
                                                words_from):
    """``blocks``, ``words_from``, ``grouped`` and ``lanes_from`` ride on
    ``replay.encode``; its four children keep their names and their parent
    whichever way the pack went, and ``stats["pack_s"]`` is the umbrella's
    seconds. Where the sources are handed over (``device``) the host runs no
    block, until someone reads ``packed``: that build is its own
    ``replay.encode.words`` span, in the pack's trace."""
    monkeypatch.setattr(wire_module, "FLAT_PACK_BLOCK", block)
    events = make_events(words_from=words_from)
    if not grouped:
        order = np.random.default_rng(0).permutation(events.num_events)
        events.agg_idx = events.agg_idx[order]
    engine = make_engine()
    since = time.monotonic()
    wire = engine.pack_resident(events)
    spans = ring_since(since)
    encode = one(spans, "replay.encode")
    assert sorted(s.name for s in spans if s is not encode) == sorted(
        ENCODE_CHILDREN)
    assert_children(spans, encode, ENCODE_CHILDREN)
    assert encode.attributes["words_from"] == words_from
    assert encode.attributes["blocks"] == (
        blocks if words_from == "host" else 0)
    assert encode.attributes["grouped"] is grouped
    assert encode.attributes["lanes_from"] == lanes_from
    assert encode.attributes["events"] == 960
    assert encode.attributes["wire_bytes"] == 960 + wire.guard
    assert engine.stats["pack_s"] == encode.seconds
    assert sum(s.seconds for s in spans if s is not encode) <= encode.seconds
    since = time.monotonic()
    res = engine.replay_resident(engine.upload_resident(wire))
    assert (res.states["count"] == 20).all()
    assert not any(s.name == "replay.encode.words" for s in ring_since(since))
    assert wire.host_packed == (words_from == "host")
    since = time.monotonic()
    assert wire.packed.shape == (960 + wire.guard, 1)  # the first reader
    late = [s for s in ring_since(since) if s.name == "replay.encode.words"]
    assert len(late) == (words_from == "device")
    for span in late:  # the host's build, in the trace the pack opened
        assert span.context.trace_id == encode.context.trace_id
        assert span.parent_id == encode.context.span_id


@pytest.mark.parametrize("derived, dtypes, aliased, copied_columns", [
    ({"sequence_number": "ordinal"}, {}, 0, 0),  # the counter cell: no side
    ({}, {"sequence_number": np.int32}, 1, 0),
    ({}, {"sequence_number": np.int64}, 0, 1),
    ({}, {"sequence_number": "strided"}, 0, 1),
    ({}, {"sequence_number": "ungrouped"}, 0, 1)])
def test_the_encode_span_says_whose_the_side_columns_are(
        derived, dtypes, aliased, copied_columns):
    """``side_aliased`` and ``side_copied_bytes`` on ``replay.encode``: a side
    column already in its wire dtype and contiguous is handed over as the
    caller's array; an int64 or strided one is cast into a fresh ``[N]``
    buffer, an ungrouped stream is sorted into one, and its bytes are
    counted. The stage keeps its name either way."""
    events = make_events()
    events.derived_cols = dict(derived)
    n = events.num_events
    for name, dtype in dtypes.items():
        seq = np.tile(np.arange(1, 21), 48)
        events.cols[name] = (
            np.repeat(seq, 2).astype(np.int32)[::2] if dtype == "strided"
            else seq.astype(np.int32 if dtype == "ungrouped" else dtype))
        if dtype == "ungrouped":  # aggregates interleaved, each log in order
            order = np.argsort(np.tile(np.arange(20), 48), kind="stable")
            events.agg_idx = events.agg_idx[order]
            events.cols[name] = events.cols[name][order]
    engine = make_engine()
    since = time.monotonic()
    wire = engine.pack_resident(events)
    spans = ring_since(since)
    encode = one(spans, "replay.encode")
    assert_children(spans, encode, ENCODE_CHILDREN)
    assert sorted(wire.side) == sorted(dtypes)
    assert encode.attributes["side_aliased"] == aliased
    assert encode.attributes["side_copied_bytes"] == 4 * n * copied_columns
    assert encode.attributes["side_bytes"] == 4 * n * len(dtypes)
    assert encode.attributes["wire_bytes"] == (
        n + wire.guard + 4 * n * len(dtypes))
    for name, col in wire.side.items():
        assert col.shape == (n,) and col.dtype == np.int32
        assert np.shares_memory(col, events.cols[name]) == bool(aliased)
    res = engine.replay_resident(engine.upload_resident(wire))
    assert (res.states["count"] == 20).all()
    assert (res.states["version"] == 20).all()


def test_a_callers_open_span_stays_the_parent_of_all_three():
    engine = make_engine()
    caller = InMemoryTracer(service="caller")  # whoever the caller is
    since = time.monotonic()
    with caller.start_span("engine.rebuild-from-events") as root:
        rebuild(engine, make_events())
        slab, padded = engine.fold_resident_slab(
            engine.upload_resident(engine.pack_resident(make_events())))
    assert active_span() is None
    spans = ring_since(since)
    assert {s.context.trace_id for s in spans} == {root.context.trace_id}
    for name in UMBRELLAS:
        for s in (s for s in spans if s.name == name):
            assert s.parent_id == root.context.span_id, name
            assert root.start_mono <= s.start_mono
            assert s.end_mono <= root.end_mono
            # no stage encloses it: the process's figures are on it
            assert "proc_cpu_s" in s.usage, name
    # fold_resident_slab is the same umbrella, without the pull
    folds = [s for s in spans if s.name == "replay.resident"]
    assert len(folds) == 2 and padded == folds[1].attributes["padded_slots"]
    fetches = [s for s in spans if s.name == "replay.fetch"]
    assert [f.parent_id for f in fetches] == [folds[0].context.span_id]


def test_a_loaded_wire_starts_a_new_trace(tmp_path):
    engine = make_engine()
    since = time.monotonic()
    wire = engine.pack_resident(make_events())
    wire.save(str(tmp_path / "wire"))
    with open(tmp_path / "wire" / "wire.json", encoding="utf-8") as f:
        assert "trace_ctx" not in json.load(f)
    loaded = ResidentWire.load(str(tmp_path / "wire"))
    assert loaded.trace_ctx is None
    engine.replay_resident(engine.upload_resident(loaded))
    spans = ring_since(since)
    encode, h2d = one(spans, "replay.encode"), one(spans, "replay.h2d")
    assert h2d.parent_id is None
    assert h2d.context.trace_id != encode.context.trace_id
    assert one(spans, "replay.resident").parent_id == h2d.context.span_id


@pytest.mark.filterwarnings("ignore:builtin type:DeprecationWarning")
def test_stages_are_trace_annotations_of_the_same_names(tmp_path):
    """A captured profile holds the stages as host events under their spans'
    names, nested in their umbrellas, with the ring's durations."""
    import jax
    from jax.profiler import ProfileData

    engine = make_engine()
    events = make_events(n_agg=4096, n_per=20)
    wire = engine.pack_resident(events)  # compiles outside the capture
    engine.replay_resident(engine.upload_resident(wire))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    since = time.monotonic()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        wire = engine.pack_resident(events)
        engine.replay_resident(engine.upload_resident(wire))
    finally:
        jax.profiler.stop_trace()
    ring = {s.name: s for s in ring_since(since)}
    pb, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                 "*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("replay."):
                        host[e.name] = (e.start_ns, e.start_ns + e.duration_ns,
                                        dict(e.stats))
    assert set(host) == set(ring)  # every stage, under its span's name
    for child, umbrella in (("replay.encode.words", "replay.encode"),
                            ("replay.h2d.put", "replay.h2d"),
                            ("replay.fetch.wait", "replay.fetch"),
                            ("replay.fetch", "replay.resident")):
        c_lo, c_hi, _ = host[child]
        u_lo, u_hi, _ = host[umbrella]
        assert u_lo <= c_lo and c_hi <= u_hi, (child, umbrella)
    for name in ("replay.encode.words", "replay.h2d.put", "replay.fetch.wait"):
        lo, hi, _ = host[name]
        traced_s, ring_s = (hi - lo) / 1e9, ring[name].seconds
        # within a fifth; a stage of well under a millisecond, as the put of
        # this small wire is on the CPU, within the entry and exit costs
        assert abs(traced_s - ring_s) <= max(0.2 * ring_s, 2e-4), (
            name, traced_s, ring_s)
    # the counts known when a stage opens are the annotation's metadata
    assert host["replay.encode"][2]["events"] == events.num_events
    assert host["replay.h2d.put"][2]["put_bytes"] == 1 << 17
    assert host["replay.h2d.put"][2]["pieces"] == 1


def test_the_ring_is_bounded_and_keeps_the_newest():
    ring = InMemoryTracer(capacity=8)
    for i in range(9):
        ring.start_span(f"s{i}").finish()
    assert [s.name for s in ring.spans()] == [f"s{i}" for i in range(1, 9)]
    assert len(ring.finished) == 8
    cut = ring.spans()[4].start_mono
    assert [s.name for s in ring.spans(since_mono=cut)][-1] == "s8"
    assert all(s.start_mono >= cut for s in ring.spans(since_mono=cut))
    # unbounded as before without a capacity
    plain = InMemoryTracer()
    for i in range(9):
        plain.start_span(f"s{i}").finish()
    assert len(plain.finished) == 9
    # the process-wide default: one ring, every trace kept, no exporter beyond it
    default = default_tracer()
    assert default is default_tracer()
    assert default.capacity == DEFAULT_RING_CAPACITY == 4096
    assert default.sample_rate == 1.0
    assert default.finished.maxlen == 4096


def test_dump_to_writes_the_jsonl_exporters_record_shape(tmp_path):
    streamed = tmp_path / "streamed.jsonl"
    with JsonlSpanExporter(str(streamed)) as exporter:
        ring = InMemoryTracer(capacity=4)
        with ring.start_span("replay.encode") as span:
            span.set_attribute("events", 7)
            span.add_event("note", {"k": 1})
        exporter(span)
    dumped = tmp_path / "dumped.jsonl"
    assert ring.dump_to(str(dumped)) == 1
    assert (json.loads(dumped.read_text())
            == json.loads(streamed.read_text()))
    assert json.loads(dumped.read_text())["attributes"] == {"events": 7}


def test_a_stage_whose_body_raises_still_finishes_its_span():
    tracer = InMemoryTracer()
    prof = ReplayProfiler.counters(tracer=tracer)
    with pytest.raises(RuntimeError):
        with prof.stage("fetch", aggregates=3):
            with prof.stage("fetch.wait"):
                raise RuntimeError("device lost")
    wait, fetch = tracer.finished
    assert (wait.name, fetch.name) == ("replay.fetch.wait", "replay.fetch")
    assert wait.parent_id == fetch.context.span_id
    for span in (wait, fetch):
        assert span.end_mono is not None and span.status == "error"
    assert active_span() is None  # nothing left open in this context
    assert prof.stage_n["fetch"] == prof.stage_n["fetch.wait"] == 1
    assert prof.stage_s["fetch"] >= prof.stage_s["fetch.wait"] > 0
    # a profiler with no tracer times its stages all the same, exporting none
    bare = ReplayProfiler()
    with bare.stage("encode") as span:
        pass
    assert bare.stage_n["encode"] == 1 and span.end_mono is not None


THREAD_KEYS = ["majflt", "minflt", "nivcsw", "sys_s", "user_s"]
USAGE_KEYS = sorted([*THREAD_KEYS, "proc_cpu_s", "proc_minflt"])


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one-chip", "sharded"])
def test_every_stage_span_says_what_it_cost_the_host(sharded):
    """``Span.usage``: the operating system's counters over the span's
    interval, on every ``replay.*`` span of a rebuild: the calling thread's
    on every stage, the whole process's too on a stage no other encloses.
    Measurements: none of them is an attribute, and an umbrella's include
    its children's."""
    import jax

    mesh = (jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
            if sharded else None)
    engine = make_engine(mesh=mesh)
    since = time.monotonic()
    wire = engine.pack_resident(make_events())
    if sharded:
        engine.replay_resident_sharded(engine.prepare_resident_sharded(wire))
    else:
        engine.replay_resident(engine.upload_resident(wire))
    spans = ring_since(since)
    names = {s.name for s in spans}
    assert names >= {*UMBRELLAS, *ENCODE_CHILDREN, *H2D_CHILDREN,
                     *FETCH_CHILDREN, "replay.plan", "replay.fetch"}
    assert ("replay.shard" in names) == sharded
    outermost = {"replay.encode", "replay.shard", "replay.h2d",
                 "replay.resident"}
    for span in spans:
        assert span.name.startswith("replay.")
        assert sorted(span.usage) == (
            USAGE_KEYS if span.name in outermost else THREAD_KEYS), span.name
        assert all(v >= 0 for v in span.usage.values()), (span.name,
                                                          span.usage)
        assert not set(span.attributes) & set(USAGE_KEYS), span.name
    for umbrella in (s for s in spans if s.name in (*UMBRELLAS,
                                                    "replay.fetch")):
        kids = [s for s in spans if s.parent_id == umbrella.context.span_id
                and s.end_mono <= umbrella.end_mono]  # not what follows it
        assert kids, umbrella.name
        for key in ("minflt", "nivcsw"):
            assert umbrella.usage[key] >= sum(k.usage[key] for k in kids)
        for key in ("user_s", "sys_s"):
            assert umbrella.usage[key] >= sum(
                k.usage[key] for k in kids) - 1e-6, (umbrella.name, key)
        if umbrella.name in outermost:  # the process holds the thread
            u = umbrella.usage
            assert u["proc_minflt"] >= u["minflt"]
            assert u["proc_cpu_s"] >= u["user_s"] + u["sys_s"] - 0.005


def test_a_stage_that_sleeps_costs_no_cpu_and_one_that_spins_costs_its_seconds():
    prof = ReplayProfiler.counters(tracer=InMemoryTracer())
    with prof.stage("fetch.wait") as asleep:
        time.sleep(0.05)
    cpu = asleep.usage["user_s"] + asleep.usage["sys_s"]
    assert asleep.seconds >= 0.05 and cpu < 0.02
    with prof.stage("encode.words") as spinning:
        until = time.thread_time() + 0.05  # this thread's own CPU clock
        while time.thread_time() < until:
            pass
    cpu = spinning.usage["user_s"] + spinning.usage["sys_s"]
    assert 0.04 <= cpu <= spinning.seconds + 0.005
    assert spinning.usage["proc_cpu_s"] >= cpu - 0.005


def test_a_stage_that_touches_fresh_pages_reads_their_faults():
    prof = ReplayProfiler.counters(tracer=InMemoryTracer())
    with prof.stage("encode.words") as fresh:
        buf = np.empty(32 << 20, dtype=np.uint8)  # mapped, not yet touched
        buf[::512] = 1
    with prof.stage("encode.words") as again:
        buf[::512] = 2
    assert fresh.usage["minflt"] > 0
    assert fresh.usage["proc_minflt"] >= fresh.usage["minflt"]
    assert again.usage["minflt"] < fresh.usage["minflt"]
    assert fresh.attributes == again.attributes == {}


@pytest.mark.parametrize("missing", ["resource", "RUSAGE_THREAD"])
def test_a_host_without_the_counters_leaves_usage_empty(monkeypatch, missing):
    import resource
    import sys

    if missing == "resource":
        monkeypatch.setitem(sys.modules, "resource", None)  # unimportable
    else:
        monkeypatch.delattr(resource, "RUSAGE_THREAD")
    tracer = InMemoryTracer()
    prof = ReplayProfiler.counters(tracer=tracer)
    with prof.stage("encode", events=3) as outer:
        with prof.stage("encode.words"):
            pass
    assert [s.usage for s in tracer.finished] == [{}, {}]
    assert outer.attributes == {"events": 3} and outer.end_mono is not None
    assert prof.stage_n["encode"] == prof.stage_n["encode.words"] == 1


def test_a_stage_whose_body_raises_keeps_its_usage():
    tracer = InMemoryTracer()
    prof = ReplayProfiler.counters(tracer=tracer)
    with pytest.raises(RuntimeError):
        with prof.stage("fetch", aggregates=3):
            with prof.stage("fetch.wait"):
                np.empty(8 << 20, dtype=np.uint8)[::512] = 1
                raise RuntimeError("device lost")
    wait, fetch = tracer.finished
    assert wait.status == fetch.status == "error"
    assert sorted(fetch.usage) == USAGE_KEYS
    assert sorted(wait.usage) == THREAD_KEYS  # inside fetch
    assert fetch.usage["minflt"] >= wait.usage["minflt"] > 0


def test_usage_is_written_out_only_where_a_span_has_any(tmp_path):
    """``dump_to`` and the JSONL exporter write ``usage`` beside
    ``attributes`` for a stage's span (the exporter as the stage closes) and
    no such key for a span that has none."""
    streamed = tmp_path / "streamed.jsonl"
    ring = InMemoryTracer(capacity=8)
    with JsonlSpanExporter(str(streamed)) as exporter:
        streaming = Tracer(exporter=exporter)
        with ReplayProfiler.counters(tracer=streaming).stage("encode",
                                                             events=7):
            pass
    with ReplayProfiler.counters(tracer=ring).stage("encode", events=7):
        pass
    ring.start_span("caller.span").finish()  # no stage: no usage
    dumped = tmp_path / "dumped.jsonl"
    assert ring.dump_to(str(dumped)) == 2
    stage, plain = (json.loads(line)
                    for line in dumped.read_text().splitlines())
    assert stage["name"] == "replay.encode"
    assert stage["attributes"] == {"events": 7}
    assert sorted(stage["usage"]) == USAGE_KEYS
    assert plain["name"] == "caller.span" and "usage" not in plain
    (line,) = streamed.read_text().splitlines()
    assert sorted(json.loads(line)["usage"]) == USAGE_KEYS
    assert json.loads(line)["attributes"] == {"events": 7}


def test_cold_path_jit_names_are_pinned():
    """Every ``jax.jit`` of replay/engine.py is made from a function whose
    name the pinned tuple holds, and the benchmark's prefix file maps each
    ``jit_<name>``: a rename would unmap a program from its layer."""
    tree = ast.parse(inspect.getsource(engine_module))
    jitted = []
    for call in ast.walk(tree):
        if (isinstance(call, ast.Call) and isinstance(call.func, ast.Attribute)
                and call.func.attr == "jit"
                and isinstance(call.func.value, ast.Name)
                and call.func.value.id == "jax"):
            made_from = call.args[0]  # a function the engine defines, by name
            assert isinstance(made_from, ast.Name), ast.dump(made_from)
            jitted.append(made_from.id)
    assert len(jitted) >= 5
    assert set(jitted) == set(COLD_PATH_JIT_NAMES)
    with open(os.path.join(ROOT, "benchmarks", "programs", "cold-fold.json"),
              encoding="utf-8") as f:
        prefixes = json.load(f)["prefixes"]
    for name in COLD_PATH_JIT_NAMES:
        assert any(f"jit_{name}".startswith(p) for p in prefixes), name
    # and the programs a driven engine holds carry those names
    engine = ReplayEngine(make_replay_spec())
    rebuild(engine, make_events(words_from="device"))
    held = [*engine._resident_folds.values(),
            *engine._slab_programs.values(),
            *engine._finalize_programs.values(),
            *engine._word_programs.values(),
            engine_module._zero_bucket, engine_module._place_piece]
    assert {p.__name__ for p in held} == set(COLD_PATH_JIT_NAMES)


def test_the_sharded_programs_carry_the_cold_path_names():
    """The mesh form's programs (``replay/resident_mesh.py``: the ``shard_map``
    fold, the slab on its devices, the one-chip finalize) are made from
    functions of the pinned names, so XLA calls them ``jit_fold``, ``jit_mk``
    and ``jit_finalize`` and some ``programs/*.json`` prefix maps each."""
    import jax

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
    engine = make_engine(mesh=mesh)
    res = engine.replay_resident_sharded(
        engine.prepare_resident_sharded(make_events()))
    assert (res.states["count"] == 20).all()
    held = [*engine._resident_folds.values(),
            *engine._slab_programs.values(),
            *engine._finalize_programs.values()]
    assert {p.__name__ for p in held} == {"fold", "mk", "finalize"}
    assert {p.__name__ for p in held} <= set(COLD_PATH_JIT_NAMES)
    prefixes = []
    for path in glob.glob(os.path.join(ROOT, "benchmarks", "programs",
                                       "*.json")):
        with open(path, encoding="utf-8") as f:
            prefixes += json.load(f)["prefixes"]
    for program in held:
        assert any(f"jit_{program.__name__}".startswith(p)
                   for p in prefixes), program.__name__
