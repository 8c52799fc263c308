"""The cart's cold start from a committed columnar segment
(``store/restore.py:restore_from_segment``), held to the scalar fold's bytes:
a restored cart carries its id, whichever restore chain rebuilt it; a second
restore hits the wire cache and, through the caller's engine, compiles
nothing; one restore is one trace with the whole ``replay.restore`` tree and
the counts the corpus implies."""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from benchmarks import gen_cart, reference_cart_restore
from benchmarks.drivers import cart_restore as driver
from surge_tpu import create_engine
from surge_tpu.config import default_config
from surge_tpu.engine.business_logic import SurgeCommandBusinessLogic
from surge_tpu.log import InMemoryLog, LogRecord, TopicSpec
from surge_tpu.log.columnar import (build_segment_from_topic, read_segment,
                                    segment_info)
from surge_tpu.models import shopping_cart
from surge_tpu.replay import ReplayEngine
from surge_tpu.replay.engine import _WIRE_GUARD_MIN
from surge_tpu.replay.resident_state import ResidentStatePlane
from surge_tpu.serialization import SerializedMessage
from surge_tpu.store import (InMemoryKeyValueStore, restore_from_events,
                             restore_from_segment)
from surge_tpu.tracing import default_tracer
from tests.test_cart_rebuild import LAW

CARTS, EVENTS, CHUNK = 300, 9_000, 128  # chunks of 128, 128 and 44 carts
CHUNKS = 3
TOPIC = "cart-events"
CFG = default_config().with_overrides({
    "surge.replay.batch-size": 64, "surge.replay.time-chunk": 32})
EVT = shopping_cart.event_formatting()


def make_logic():
    return SurgeCommandBusinessLogic(
        aggregate_name="cart", model=shopping_cart.CartModel(),
        state_format=shopping_cart.state_formatting(),
        event_format=shopping_cart.event_formatting())


def hooks(logic):
    """The three arguments ``engine/pipeline.py:_rebuild_from_segment`` takes
    from the business logic."""
    fmt = logic.state_format
    return dict(replay_spec=logic.replay_spec(),
                serialize_state=lambda agg_id, st: fmt.write_state(st).value,
                decode_state=getattr(logic, "decode_state", None))


@pytest.fixture(scope="module")
def corpus():
    corpus = gen_cart.cart_corpus(CARTS, EVENTS, 2**31 + 37, LAW)
    assert corpus.lengths.min() >= 1 and corpus.lengths.max() > 60  # ragged
    ids = reference_cart_restore.cart_ids(CARTS)
    want = reference_cart_restore.scalar_fold_bytes(corpus, ids, range(CARTS))
    return corpus, ids, want


@pytest.fixture
def segment(corpus, tmp_path):
    path = str(tmp_path / "cart.scol")
    info = driver.write_segment(path, corpus[0], corpus[1], CHUNK)
    assert info["num_chunks"] == CHUNKS and info["num_events"] == EVENTS
    return path


def topic_of(corpus, ids):
    """The corpus as a one-partition events topic of JSON records."""
    log = InMemoryLog()
    log.create_topic(TopicSpec(TOPIC, 1))
    producer = log.transactional_producer("seed")
    producer.begin()
    for b in range(corpus.num_aggregates):
        for event in reference_cart_restore.cart_events(corpus, ids, b):
            msg = EVT.write_event(event)
            producer.send(LogRecord(topic=TOPIC, partition=0, key=msg.key,
                                    value=msg.value))
    producer.commit()
    return log


def read_event(raw):
    return EVT.read_event(SerializedMessage(key="", value=raw))


# --- a restored cart is the scalar fold's cart, id included -----------------------

def case_segment(corpus, ids, tmp_path, **overrides):
    path = str(tmp_path / "cart.scol")
    driver.write_segment(path, corpus, ids, CHUNK)
    store = InMemoryKeyValueStore()
    res = restore_from_segment(path, store, config=CFG.with_overrides(overrides),
                               **hooks(make_logic()))
    assert (res.num_aggregates, res.num_events) == (CARTS, EVENTS)
    return dict(store.all_items())


def case_segment_without_the_wire_cache(corpus, ids, tmp_path):
    return case_segment(corpus, ids, tmp_path,
                        **{"surge.replay.segment-wire-cache": False})


def case_segment_streamed(corpus, ids, tmp_path):
    """``segment-backend = streaming``: the chunks' columns through the
    windowed fold, under the same root and the same write-back."""
    since = time.monotonic()
    got = case_segment(corpus, ids, tmp_path,
                       **{"surge.replay.segment-backend": "streaming"})
    spans = default_tracer().spans(since_mono=since)
    (root,) = [s for s in spans if s.name == "replay.restore"]
    assert root.attributes["backend"] == "streaming"
    assert (root.attributes["wire_hits"], root.attributes["wire_misses"]) == (0, 0)
    assert not [s for s in spans if s.name in ("replay.restore.wire",
                                               "replay.resident")]
    assert len([s for s in spans
                if s.name == "replay.restore.writeback"]) == CHUNKS
    return got


def case_events_topic(corpus, ids, tmp_path):
    logic = make_logic()
    store = InMemoryKeyValueStore()
    res = restore_from_events(
        topic_of(corpus, ids), TOPIC, store, deserialize_event=read_event,
        model=logic.model, config=CFG, **hooks(logic))
    assert (res.backend, res.num_events) == ("tpu", EVENTS)
    return dict(store.all_items())


def case_resident_plane(corpus, ids, tmp_path):
    """The plane's read path: the spilled carts through ``_state_of``, the
    resident ones through the precompiled materializer of the gather lane."""
    logic = make_logic()
    serialize = hooks(logic)["serialize_state"]

    async def scenario():
        plane = ResidentStatePlane(
            topic_of(corpus, ids), TOPIC, logic.replay_spec(),
            config=CFG.with_overrides({"surge.replay.resident.capacity": 200}),
            deserialize_event=read_event, serialize_state=serialize,
            decode_state=getattr(logic, "decode_state", None))
        await plane.start()
        try:
            assert plane.occupancy() == 200  # a hundred carts spilled
            out = {}
            for cart_id in ids:
                hit, state = await plane.read_state(cart_id)
                assert hit and state.cart_id == cart_id
                out[cart_id] = serialize(cart_id, state)
            snapshot = plane.snapshot_states()
            assert {a: serialize(a, s) for a, s in snapshot.items()} == out
            return out
        finally:
            await plane.stop()

    return asyncio.run(scenario())


def case_engine_cold_start(corpus, ids, tmp_path):
    """The whole way: a node with ``surge.replay.segment-path`` set builds the
    segment from its topic and restores through it on start."""
    cfg = CFG.with_overrides({
        "surge.engine.num-partitions": 1,
        "surge.replay.segment-path": str(tmp_path / "engine.scol"),
        "surge.replay.restore-on-start": True})

    async def scenario():
        engine = create_engine(make_logic(), log=topic_of(corpus, ids),
                               config=cfg)
        await engine.start()
        try:
            state = await engine.aggregate_for(ids[7]).get_state()
            assert state.cart_id == ids[7]
            return dict(engine.indexer.store.all_items())
        finally:
            await engine.stop()

    return asyncio.run(scenario())


@pytest.mark.parametrize("case", [
    case_segment, case_segment_without_the_wire_cache, case_segment_streamed,
    case_events_topic,
    case_resident_plane, case_engine_cold_start], ids=lambda c: c.__name__)
def test_a_restored_cart_is_the_scalar_folds_cart(case, corpus, tmp_path):
    corpus, ids, want = corpus
    got = case(corpus, ids, tmp_path)
    assert sorted(got) == ids  # every cart, and nothing else
    wrong = [a for a in ids if got[a] != want[a]]
    assert not wrong, (wrong[:3], got[wrong[0]], want[wrong[0]])
    assert json.loads(got[ids[-1]])["cart_id"] == ids[-1]


def test_a_cart_without_the_hook_loses_its_id():
    """What the hook is for: the tensor schema carries no string."""
    logic = make_logic()
    bare = logic.replay_spec().registry.state.from_record(
        {"item_count": 2, "total_cents": 198, "checked_out": False,
         "version": 2})
    assert bare.cart_id == ""
    assert logic.decode_state("cart-9", bare) == shopping_cart.Cart(
        "cart-9", 2, 198, False, 2)
    assert logic.decode_state == logic.model.decode_state
    counter_logic = SurgeCommandBusinessLogic(
        aggregate_name="x", model=object(), state_format=None,
        event_format=None)
    assert counter_logic.decode_state is None  # a model without the hook


# --- the driver's segment is the topic builder's ----------------------------------

def test_the_drivers_segment_is_the_topic_builders(corpus, segment, tmp_path):
    corpus, ids, _ = corpus
    built = str(tmp_path / "built.scol")
    build_segment_from_topic(
        topic_of(corpus, ids), TOPIC, shopping_cart.make_registry(),
        EVT.read_event, built, derived_cols={"sequence_number": "ordinal"},
        chunk_aggregates=CHUNK)
    ours, theirs = list(read_segment(segment)), list(read_segment(built))
    assert len(ours) == len(theirs) == CHUNKS
    for a, b in zip(ours, theirs):
        assert a.aggregate_ids == b.aggregate_ids
        assert a.num_aggregates == b.num_aggregates
        assert a.derived_cols == b.derived_cols == {"sequence_number": "ordinal"}
        assert a.source_ordinal == b.source_ordinal
        for x, y in [(a.agg_idx, b.agg_idx), (a.type_ids, b.type_ids)] + [
                (a.cols[k], b.cols[k]) for k in sorted(b.cols)]:
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(x, y)
        assert sorted(a.cols) == sorted(b.cols)
    a, b = segment_info(segment), segment_info(built)
    assert a["schema"]["columns"] == b["schema"]["columns"]
    assert not a["num_snapshots"] and not b["num_snapshots"]


# --- the spans: one restore, one trace ----------------------------------------------

def restore(path, **kw):
    since = time.monotonic()
    store = InMemoryKeyValueStore()
    res = restore_from_segment(path, store, config=CFG, **hooks(make_logic()),
                               **kw)
    return res, store, default_tracer().spans(since_mono=since)


def named(spans, name):
    return [s for s in spans if s.name == name]


PER_CHUNK = ["replay.restore.wire", "replay.h2d", "replay.resident",
             "replay.restore.decode", "replay.restore.writeback"]


@pytest.mark.parametrize("wire", ["miss", "hit"])
def test_one_restore_is_one_trace_with_the_whole_tree(wire, corpus, segment):
    corpus, ids, want = corpus
    if wire == "hit":
        restore(segment)  # fills the cache beside the segment
    res, store, spans = restore(segment)
    assert len({s.context.trace_id for s in spans}) == 1
    (root,) = named(spans, "replay.restore")
    assert root.parent_id is None
    hit = wire == "hit"
    assert root.attributes == {
        "backend": "resident", "segment_bytes": os.path.getsize(segment),
        "chunks": CHUNKS, "aggregates": CARTS, "events": EVENTS,
        "wire_hits": CHUNKS if hit else 0, "wire_misses": 0 if hit else CHUNKS}
    assert root.usage.get("proc_cpu_s", 1) > 0  # the outermost stage's
    # a chunk: read, wire, upload, fold, decode, write-back, in that order,
    # each a child of the root and inside it; the reader's last step finds
    # the end, and the snapshot pass follows
    children = [s for s in spans if s.parent_id == root.context.span_id]
    assert [s.name for s in sorted(children, key=lambda s: s.start_mono)] == (
        ["replay.restore.read"] + PER_CHUNK) * CHUNKS + [
        "replay.restore.read", "replay.restore.snapshots"]
    for earlier, later in zip(children, children[1:]):
        assert root.start_mono <= earlier.start_mono
        assert earlier.end_mono <= later.start_mono <= root.end_mono
    assert all(s.status == "ok" for s in spans)
    # the counts the corpus implies
    lengths = corpus.lengths
    carts = [CHUNK, CHUNK, CARTS - 2 * CHUNK]
    events = [int(lengths[i * CHUNK:(i + 1) * CHUNK].sum()) for i in range(CHUNKS)]
    reads = named(spans, "replay.restore.read")
    assert reads[-1].attributes == {}  # no chunk in the last step
    raw = [20 * n + len("\n".join(ids[i * CHUNK:(i + 1) * CHUNK]))
           for i, n in enumerate(events)]  # five int32 columns, and the ids
    assert [s.attributes["raw_bytes"] for s in reads[:-1]] == raw
    assert sum(s.attributes["stored_bytes"] for s in reads[:-1]) < os.path.getsize(
        segment)
    assert {s.attributes["codec"] for s in reads[:-1]} <= {"slz", "raw", "mixed"}
    assert all((s.attributes["codec"] == "raw")
               == (s.attributes["stored_bytes"] == s.attributes["raw_bytes"])
               for s in reads[:-1])
    wires = named(spans, "replay.restore.wire")
    assert [s.attributes["hit"] for s in wires] == [hit] * CHUNKS
    guard = max(_WIRE_GUARD_MIN, ReplayEngine(
        shopping_cart.make_replay_spec(), config=CFG).resident_tile_width())
    assert [s.attributes["bytes"] for s in wires] == [
        (n + guard) + 12 * n for n in events]  # the word's byte, three int32
    encodes = named(spans, "replay.encode")
    assert len(encodes) == (0 if hit else CHUNKS)  # the pack, on a miss only
    assert all(e.parent_id == w.context.span_id
               for e, w in zip(encodes, wires))
    # a cached wire carries the host-packed word: nothing for mk_word to build
    assert {s.attributes["word_source_bytes"] for s in named(spans, "replay.h2d")
            } == {0}
    assert [s.attributes["events"] for s in named(spans, "replay.resident")
            ] == events
    assert [s.attributes["aggregates"]
            for s in named(spans, "replay.restore.decode")] == carts
    backs = named(spans, "replay.restore.writeback")
    assert [s.attributes["aggregates"] for s in backs] == carts
    assert [s.attributes["bytes"] for s in backs] == [
        sum(len(want[a]) for a in ids[i * CHUNK:(i + 1) * CHUNK])
        for i in range(CHUNKS)]
    (snaps,) = named(spans, "replay.restore.snapshots")
    assert snaps.attributes == {"snapshots": 0}
    assert dict(store.all_items()) == want
    assert (res.num_aggregates, res.num_events) == (CARTS, EVENTS)


def test_a_second_restore_through_the_callers_engine_compiles_nothing(segment):
    """The pipeline keeps one engine a process: the programs live on it."""
    engine = ReplayEngine(shopping_cart.make_replay_spec(), config=CFG)
    _, first, spans = restore(segment, engine=engine)
    assert named(spans, "replay.compile")
    compiled = engine.num_compiles
    _, second, spans = restore(segment, engine=engine)
    assert not named(spans, "replay.compile")
    assert len(named(spans, "replay.dispatch")) >= CHUNKS
    assert engine.num_compiles == compiled
    assert dict(second.all_items()) == dict(first.all_items())
