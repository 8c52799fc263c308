"""Columnar event-log segments: round-trip, compression, topic conversion, and
chunked replay (SURVEY.md §7 hard-part 3 — bulk replay without per-event objects)."""

import numpy as np
import pytest

from surge_tpu.codec.tensor import ColumnarEvents
from surge_tpu.log import InMemoryLog, LogRecord, TopicSpec
from surge_tpu.log import segment as seg
from surge_tpu.log.columnar import (
    ColumnarSegmentWriter,
    build_segment_from_topic,
    read_segment,
    segment_info,
)
from surge_tpu.models import counter
from surge_tpu.replay.corpus import synth_counter_corpus
from surge_tpu.replay.engine import ReplayEngine


def _chunks_of(corpus, n_chunks):
    ev = corpus.events.sorted_by_aggregate()
    b = corpus.num_aggregates
    per = (b + n_chunks - 1) // n_chunks
    out = []
    for start in range(0, b, per):
        out.append(ev.slice_aggregates(start, min(start + per, b)))
    return out


def test_segment_round_trip_and_replay(tmp_path):
    corpus = synth_counter_corpus(500, 20_000, seed=13)
    path = str(tmp_path / "events.scol")
    with ColumnarSegmentWriter(path) as w:
        for chunk in _chunks_of(corpus, 4):
            w.append(chunk)

    info = segment_info(path)
    assert info["num_aggregates"] == 500
    assert info["num_events"] == corpus.num_events
    assert info["num_chunks"] == 4
    assert info["schema"]["derived"] == {"sequence_number": "ordinal"}

    # chunk round-trip is exact
    back = list(read_segment(path))
    ev = corpus.events.sorted_by_aggregate()
    merged_types = np.concatenate([c.type_ids for c in back])
    np.testing.assert_array_equal(merged_types, ev.type_ids)

    # replay straight off the file: identical to the in-memory corpus fold
    eng = ReplayEngine(counter.make_replay_spec())
    res = eng.replay_columnar_chunks(read_segment(path))
    np.testing.assert_array_equal(res.states["count"], corpus.expected_count)
    np.testing.assert_array_equal(res.states["version"], corpus.expected_version)
    assert res.num_events == corpus.num_events


def test_segment_compresses_event_columns(tmp_path):
    if not seg.native_codec_available():
        pytest.skip("native segment codec not built")
    corpus = synth_counter_corpus(2000, 200_000, seed=3)
    path = str(tmp_path / "events.scol")
    with ColumnarSegmentWriter(path) as w:
        w.append(corpus.events)
    import os

    raw_bytes = corpus.events.nbytes()
    assert os.path.getsize(path) < raw_bytes / 2  # narrow int columns compress well


def test_divergent_chunk_schema_round_trips_via_meta_overrides(tmp_path):
    """A chunk whose schema differs from the header (the delta-chunk case:
    stored vs derived columns) persists per-chunk overrides and reads back with
    its own dtypes, not the header's."""
    corpus = synth_counter_corpus(10, 100, seed=1)
    path = str(tmp_path / "mixed.scol")
    w = ColumnarSegmentWriter(path)
    w.append(corpus.events)
    other = ColumnarEvents(num_aggregates=1, agg_idx=np.zeros(1, np.int32),
                           type_ids=np.zeros(1, np.int32),
                           cols={"weird": np.full(1, 2.5, np.float32)})
    w.append(other)
    w.close()
    chunks = list(read_segment(path))
    assert set(chunks[0].cols) == set(corpus.events.cols)
    assert set(chunks[1].cols) == {"weird"}
    assert chunks[1].cols["weird"].dtype == np.float32
    assert float(chunks[1].cols["weird"][0]) == 2.5


def test_build_segment_from_topic(tmp_path):
    """The offline conversion job: a real events topic (JSON records written by the
    command path's formats) becomes a columnar segment, and replaying it matches
    the scalar fold of the same records."""
    from surge_tpu.engine.model import fold_events

    log = InMemoryLog()
    log.create_topic(TopicSpec("counter-events", 2))
    fmt = counter.event_formatting()
    model = counter.CounterModel()
    rng = np.random.default_rng(5)
    expected = {}
    prod = log.transactional_producer("seg-test")
    for i in range(60):
        agg = f"agg-{i}"
        n = int(rng.integers(1, 12))
        events = [counter.CountIncremented(agg, int(rng.integers(1, 4)), k + 1)
                  for k in range(n)]
        expected[agg] = fold_events(model, None, events)
        prod.begin()
        for e in events:
            m = fmt.write_event(e)
            prod.send(LogRecord(topic="counter-events", key=agg, value=m.value,
                                partition=i % 2))
        prod.commit()

    path = str(tmp_path / "converted.scol")
    info = build_segment_from_topic(
        log, "counter-events", counter.make_registry(), fmt.read_event, path,
        derived_cols={"sequence_number": "ordinal"}, chunk_aggregates=16)
    assert info["num_aggregates"] == 60
    order = info["aggregate_order"]

    eng = ReplayEngine(counter.make_replay_spec())
    res = eng.replay_columnar_chunks(read_segment(path))
    for i, agg in enumerate(order):
        st = expected[agg]
        assert int(res.states["count"][i]) == st.count, agg
        assert int(res.states["version"][i]) == st.version, agg


def test_extend_segment_appends_delta_and_restores_without_rebuild(tmp_path):
    """VERDICT r3 next #8: after post-build traffic, extend appends delta
    chunks (schema-overridden: ordinals stored, not derived), state-only delta
    snapshots, and a watermark override; a restore folds base chunks then
    CONTINUES each touched aggregate's fold through init_carry — states match
    the scalar ground truth exactly, and a second extend with no new data is a
    no-op."""
    import numpy as np

    from surge_tpu.engine.model import fold_events
    from surge_tpu.log.columnar import extend_segment_from_topic, segment_info
    from surge_tpu.store.kv import InMemoryKeyValueStore
    from surge_tpu.store.restore import restore_from_segment

    log = InMemoryLog()
    log.create_topic(TopicSpec("counter-events", 2))
    log.create_topic(TopicSpec("counter-state", 2, compacted=True))
    model = counter.CounterModel()
    fmt = counter.event_formatting()
    sfmt = counter.state_formatting()
    rng = np.random.default_rng(9)
    prod = log.transactional_producer("seg")
    logs: dict = {}

    def send_events(agg, events, partition):
        prod.begin()
        for e in events:
            prod.send(LogRecord(topic="counter-events", key=agg,
                                value=fmt.write_event(e).value,
                                partition=partition))
        st = fold_events(model, None, logs.get(agg, []) + list(events))
        prod.send(LogRecord(topic="counter-state", key=agg,
                            value=sfmt.write_state(st).value,
                            partition=partition))
        prod.commit()
        logs.setdefault(agg, []).extend(events)

    # base: 20 aggregates
    for i in range(20):
        agg = f"agg-{i}"
        n = int(rng.integers(1, 9))
        send_events(agg, [counter.CountIncremented(agg, int(rng.integers(1, 4)),
                                                   k + 1) for k in range(n)],
                    i % 2)
    # a state-only key at build time
    prod.begin()
    prod.send(LogRecord(topic="counter-state", key="lonely", value=b"OLD",
                        partition=0))
    prod.commit()

    path = str(tmp_path / "inc.scol")
    build_segment_from_topic(
        log, "counter-events", counter.make_registry(), fmt.read_event, path,
        derived_cols={"sequence_number": "ordinal"}, chunk_aggregates=8,
        state_topic="counter-state")
    base_chunks = segment_info(path)["num_chunks"]

    # post-build traffic: continuations, brand-new aggregates, a state-only
    # update, and an update to the snapshot-only key (demoted path)
    for i in range(0, 20, 3):
        agg = f"agg-{i}"
        start = len(logs[agg])
        send_events(agg, [counter.CountIncremented(agg, 2, start + k + 1)
                          for k in range(3)], i % 2)
    for i in range(20, 24):
        agg = f"agg-{i}"
        send_events(agg, [counter.CountIncremented(agg, 1, k + 1)
                          for k in range(2)], i % 2)
    prod.begin()
    prod.send(LogRecord(topic="counter-state", key="lonely", value=b"NEW",
                        partition=0))
    prod.commit()

    info = extend_segment_from_topic(
        log, "counter-events", counter.make_registry(), fmt.read_event, path,
        state_topic="counter-state")
    assert info["num_chunks"] > base_chunks  # delta chunks landed
    wm = info["schema"]["extra"]["watermarks"]
    assert all(int(wm[str(p)]) == log.end_offset("counter-events", p)
               for p in range(2))

    store = InMemoryKeyValueStore()
    res = restore_from_segment(
        path, store, replay_spec=counter.make_replay_spec(),
        serialize_state=lambda a, s: sfmt.write_state(s).value)
    for agg, events in logs.items():
        truth = fold_events(model, None, events)
        got = sfmt.read_state(store.get(agg))
        assert (got.count, got.version) == (truth.count, truth.version), agg
    assert store.get("lonely") == b"NEW"  # demoted snapshot superseded OLD
    assert res.watermarks == {p: log.end_offset("counter-state", p)
                              for p in range(2)}

    # nothing new: extend is a no-op (same chunk count, same watermarks)
    info2 = extend_segment_from_topic(
        log, "counter-events", counter.make_registry(), fmt.read_event, path,
        state_topic="counter-state")
    assert info2["num_chunks"] == info["num_chunks"]


def test_build_segment_refuses_false_ordinal_claim(tmp_path):
    """A noop-bearing log (seq != position) must be rejected when declared ordinal,
    not silently corrupted."""
    log = InMemoryLog()
    log.create_topic(TopicSpec("ev", 1))
    fmt = counter.event_formatting()
    prod = log.transactional_producer("t")
    prod.begin()
    # NoOp doesn't bump version, so the next event's seq != its position
    for e in [counter.CountIncremented("a", 1, 1), counter.NoOpEvent("a", 2),
              counter.CountIncremented("a", 1, 2)]:
        m = fmt.write_event(e)
        prod.send(LogRecord(topic="ev", key="a", value=m.value))
    prod.commit()
    with pytest.raises(ValueError, match="not positional"):
        build_segment_from_topic(
            log, "ev", counter.make_registry(), fmt.read_event,
            str(tmp_path / "x.scol"), derived_cols={"sequence_number": "ordinal"})


def test_segment_carries_ids_snapshots_and_watermarks(tmp_path):
    """Chunks persist aggregate ids, the snapshot section carries state-only
    aggregates, and the header records build-time watermarks — together a complete
    cold-start image (restore_from_segment consumes all three)."""
    from surge_tpu.log.columnar import read_segment_snapshots, segment_info
    from surge_tpu.store import InMemoryKeyValueStore, restore_from_segment

    log = InMemoryLog()
    log.create_topic(TopicSpec("counter-events", 2))
    log.create_topic(TopicSpec("counter-state", 2, compacted=True))
    fmt = counter.event_formatting()
    prod = log.transactional_producer("seed")
    expected = {}
    from surge_tpu.engine.model import fold_events
    model = counter.CounterModel()
    for i in range(10):
        agg = f"agg-{i}"
        events = [counter.CountIncremented(agg, 2, k + 1) for k in range(i + 1)]
        expected[agg] = fold_events(model, None, events)
        prod.begin()
        for e in events:
            prod.send(LogRecord(topic="counter-events", key=agg,
                                value=fmt.write_event(e).value, partition=i % 2))
        prod.commit()
    # a state-only snapshot (no events for this key)
    prod.begin()
    prod.send(LogRecord(topic="counter-state", key="lonely", value=b"SNAP",
                        partition=0))
    prod.commit()

    path = str(tmp_path / "full.scol")
    info = build_segment_from_topic(
        log, "counter-events", counter.make_registry(), fmt.read_event, path,
        derived_cols={"sequence_number": "ordinal"}, chunk_aggregates=4,
        state_topic="counter-state")
    assert info["num_snapshots"] == 1
    extra = info["schema"]["extra"]
    assert extra["watermarks"] == {str(p): log.end_offset("counter-events", p)
                                   for p in range(2)}
    assert extra["state_watermarks"] == {str(p): log.end_offset("counter-state", p)
                                         for p in range(2)}

    chunks = list(read_segment(path))
    assert all(c.aggregate_ids is not None for c in chunks)
    # chunks are per source partition (sorted within each), enabling
    # partition-scoped restore; the union covers every aggregate exactly once
    ids = [i for c in chunks for i in c.aggregate_ids]
    assert sorted(ids) == sorted(expected) and ids == info["aggregate_order"]
    evens = [f"agg-{i}" for i in range(0, 10, 2)]
    odds = [f"agg-{i}" for i in range(1, 10, 2)]
    assert ids == evens + odds  # partition 0 chunks first, then partition 1
    p0_ids = [i for c in read_segment(path, partitions={0})
              for i in c.aggregate_ids]
    assert p0_ids == evens
    assert list(read_segment_snapshots(path)) == [("lonely", b"SNAP")]
    assert list(read_segment_snapshots(path, partitions={1})) == []
    assert list(read_segment_snapshots(path, partitions={0})) == [("lonely", b"SNAP")]

    # restore writes every folded state + snapshot into the store
    store = InMemoryKeyValueStore()
    sfmt = counter.state_formatting()
    res = restore_from_segment(
        path, store, replay_spec=counter.make_replay_spec(),
        serialize_state=lambda a, s: sfmt.write_state(s).value)
    assert res.backend == "segment"
    assert res.num_aggregates == 11
    assert res.watermarks == {p: log.end_offset("counter-state", p) for p in range(2)}
    assert store.get("lonely") == b"SNAP"
    for agg, st in expected.items():
        got = sfmt.read_state(store.get(agg))
        assert (got.count, got.version) == (st.count, st.version), agg

    # the first restore left per-chunk wire caches beside the segment; a
    # second cold start must consume them WITHOUT re-packing
    import os
    import unittest.mock as mock

    from surge_tpu.replay.engine import ReplayEngine

    assert os.path.isdir(path + ".wires") and os.listdir(path + ".wires")
    store2 = InMemoryKeyValueStore()
    with mock.patch.object(ReplayEngine, "pack_resident",
                           side_effect=AssertionError("must hit wire cache")):
        res2 = restore_from_segment(
            path, store2, replay_spec=counter.make_replay_spec(),
            serialize_state=lambda a, s: sfmt.write_state(s).value)
    assert res2.num_aggregates == 11
    for agg, st in expected.items():
        got = sfmt.read_state(store2.get(agg))
        assert (got.count, got.version) == (st.count, st.version), agg


def test_rebuilt_segment_never_serves_stale_wires(tmp_path):
    """A segment REBUILT at the same path — same chunk ordinals, same event
    counts, different content — must restore the NEW states, not the previous
    build's cached wires (ADVICE r4): every fresh segment stamps a new
    build_id into its header and creation drops the sidecar cache outright."""
    import os

    from surge_tpu.engine.model import fold_events
    from surge_tpu.log.columnar import build_segment_from_topic, segment_info
    from surge_tpu.store import InMemoryKeyValueStore, restore_from_segment

    model = counter.CounterModel()
    fmt = counter.event_formatting()
    sfmt = counter.state_formatting()
    path = str(tmp_path / "events.scol")

    def build_and_restore(increment_by: int):
        log = InMemoryLog()
        log.create_topic(TopicSpec("ev", 1))
        prod = log.transactional_producer("seed")
        expected = {}
        for i in range(6):
            agg = f"agg-{i}"
            events = [counter.CountIncremented(agg, increment_by, k + 1)
                      for k in range(3)]  # SAME count every build
            expected[agg] = fold_events(model, None, events)
            prod.begin()
            for e in events:
                prod.send(LogRecord(topic="ev", key=agg,
                                    value=fmt.write_event(e).value))
            prod.commit()
        build_segment_from_topic(
            log, "ev", counter.make_registry(), fmt.read_event, path,
            derived_cols={"sequence_number": "ordinal"}, chunk_aggregates=6)
        store = InMemoryKeyValueStore()
        restore_from_segment(
            path, store, replay_spec=counter.make_replay_spec(),
            serialize_state=lambda a, s: sfmt.write_state(s).value)
        return expected, store

    exp1, store1 = build_and_restore(increment_by=2)
    build1_id = segment_info(path)["schema"]["extra"]["build_id"]
    assert os.path.isdir(path + ".wires") and os.listdir(path + ".wires")
    for agg, st in exp1.items():
        assert sfmt.read_state(store1.get(agg)).count == st.count

    exp2, store2 = build_and_restore(increment_by=3)  # rebuild, new content
    assert segment_info(path)["schema"]["extra"]["build_id"] != build1_id
    for agg, st in exp2.items():
        got = sfmt.read_state(store2.get(agg))
        assert (got.count, got.version) == (st.count, st.version), agg


@pytest.mark.parametrize("dtype", [np.int32, np.int8, np.float32, np.int64])
def test_a_compressed_column_is_decompressed_into_its_own_array(dtype):
    """``_decode_array`` hands a compressed payload's bytes to the codec with
    the array's own buffer as the destination: the same values
    ``slz_decompress`` gives, in a writable array that owns them; a
    destination of another size, or one that is not contiguous, is refused."""
    from surge_tpu.log import columnar

    if not seg.native_codec_available():
        pytest.skip("native segment codec not built")
    want = (np.arange(50_000) % 97).astype(dtype)
    stored = seg.slz_compress(want.tobytes())
    assert stored is not None and len(stored) < want.nbytes
    got = columnar._decode_array(stored, seg.CODEC_SLZ, want.nbytes,
                                 np.dtype(dtype))
    assert got.dtype == want.dtype and got.tolist() == want.tolist()
    assert got.flags.writeable and got.flags.owndata
    assert got.tobytes() == seg.slz_decompress(stored, want.nbytes)
    raw = columnar._decode_array(want.tobytes(), seg.CODEC_RAW, want.nbytes,
                                 np.dtype(dtype))
    assert raw.tolist() == want.tolist()
    with pytest.raises(ValueError, match="decompression failed"):
        seg.slz_decompress_into(stored, np.empty(want.size - 1, dtype=dtype))
    with pytest.raises(ValueError, match="contiguous"):
        seg.slz_decompress_into(stored, np.empty(2 * want.size, dtype=dtype)[::2])
    if np.dtype(dtype).itemsize > 1:
        with pytest.raises(ValueError, match="no whole"):
            columnar._decode_array(stored, seg.CODEC_SLZ, want.nbytes - 1,
                                   np.dtype(dtype))
