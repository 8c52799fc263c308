"""A tile's lane rows as aligned rows (``engine._make_lane_fetch``, ``rows``):
the chip's lowering, forced here on the CPU backend, against the one
``dynamic_slice`` a lane it replaced, kept below as the oracle. Element for
element at the helper, state for state through every tile backend, on the
first fold of a corpus and on the next."""

import time
from dataclasses import make_dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surge_tpu.codec.schema import FieldSpec, SchemaRegistry
from surge_tpu.codec.tensor import encode_events_columnar
from surge_tpu.codec.wire import WireFormat
from surge_tpu.config import default_config
from surge_tpu.engine.model import fold_events
from surge_tpu.log import InMemoryLog, LogRecord, TopicSpec
from surge_tpu.log.columnar import (build_segment_from_topic,
                                    extend_segment_from_topic)
from surge_tpu.models import counter, shopping_cart
from surge_tpu.replay import engine as engine_module
from surge_tpu.replay.engine import (_LANE_ROW, ReplayEngine, _bucket_len,
                                     _make_lane_fetch, _rows_per_lane)
from surge_tpu.replay.resident_state import ResidentStatePlane
from surge_tpu.serialization import SerializedMessage
from surge_tpu.store import InMemoryKeyValueStore, restore_from_segment
from surge_tpu.tracing import default_tracer

A = _LANE_ROW
ROWS = 40  # the buffer: 40 aligned rows
N = ROWS * A


@pytest.fixture
def rows_on_cpu(monkeypatch):
    """What the chip runs: tier-1 has no accelerator, so the backend's choice
    is made here, in the test."""
    monkeypatch.setattr(engine_module, "_lane_gather", lambda: "rows")


def make_wire(word_bits):
    """A one-type schema: ``a`` packed in ``word_bits`` (a 1- or 3-byte word),
    an int32 and a bool side column."""
    ev = make_dataclass("Ev", [("a", int), ("q", int), ("flag", bool)])
    st = make_dataclass("St", [("a", int)])
    reg = SchemaRegistry()
    reg.register_event(ev, fields=[FieldSpec("a", np.int32, bits=word_bits),
                                   FieldSpec("q", np.int32),
                                   FieldSpec("flag", np.bool_)])
    reg.register_state(st, fields=[FieldSpec("a", np.int32)])
    return WireFormat(reg)


def make_buffers(wire, n, seed=3):
    rng = np.random.default_rng(seed)
    flat_wire = rng.integers(0, 256, size=(n, wire.nbytes), dtype=np.uint8)
    side = {"q": rng.integers(-2**31, 2**31, size=n, dtype=np.int64
                              ).astype(np.int32),
            "flag": rng.integers(0, 2, size=n).astype(np.bool_)}
    return jnp.asarray(flat_wire), {k: jnp.asarray(v) for k, v in side.items()}


def oracle_fetch(wire, width, flat_wire, side_flat, p):
    """The parent's tile build: ``vmap(dynamic_slice)`` over the lane starts."""
    bs = p.shape[0]
    word = jax.vmap(lambda s0: jax.lax.dynamic_slice(
        flat_wire, (s0, 0), (width, wire.nbytes)))(p)
    word = wire.expand_flat(word.reshape(bs * width, wire.nbytes))
    sides = {n: jax.vmap(lambda s0: jax.lax.dynamic_slice(
        arr, (s0,), (width,)))(p).T for n, arr in side_flat.items()}
    return word.reshape(bs, width).T, sides


def lane_starts(width):
    """Named vectors of window starts ``start + t_base``, one a lane."""
    far = 1 << 29  # past any buffer, short of int32 overflow
    return {
        "offset_0": [0, A, 7 * A, (ROWS - 8) * A],
        "offset_1": [1, A + 1, 7 * A + 1, 33],
        "offset_last": [A - 1, 2 * A - 1, 9 * A - 1, 20 * A - 1],
        "crosses_rows": [A - 3, 3 * A - width // 2, 5 * A + 77, 2 * A - 1],
        "ends_on_last_row": [N - width, N - width - 1, N - width - A, 0],
        "ends_in_guard_rows": [N - width - 5, N - 2 * width, N - width - A + 1,
                               N - width - 63],
        "padding_lane": [0, 0, width, 3 * width],  # start 0, any t_base
        "far_past_the_end": [far, far + 100, far + N - 1, far + 4 * A],
        "past_the_end": [N - width + 1, N - 1, N, N + 5 * A],
        "every_offset": list(range(3 * A, 4 * A + 1)),
    }


@pytest.mark.parametrize("case", sorted(lane_starts(16)))
@pytest.mark.parametrize("width", [16, 256])
@pytest.mark.parametrize("word_bits", [5, 20], ids=["word1", "word3"])
def test_rows_fetch_is_the_slice_fetch(word_bits, width, case):
    wire = make_wire(word_bits)
    assert wire.nbytes == {5: 1, 20: 3}[word_bits]
    flat_wire, side_flat = make_buffers(wire, N)
    p = jnp.asarray(lane_starts(width)[case], dtype=jnp.int32)
    want_w, want_s = oracle_fetch(wire, width, flat_wire, side_flat, p)
    for gather in ("rows", "slices"):
        view, fetch = _make_lane_fetch(wire, width, gather)
        got_w, got_s = jax.jit(lambda fw, sf, p: fetch(view(fw, sf), p))(
            flat_wire, side_flat, p)
        assert got_w.shape == (width, p.shape[0]) and got_w.dtype == jnp.uint32
        np.testing.assert_array_equal(np.asarray(got_w), np.asarray(want_w))
        assert sorted(got_s) == ["flag", "q"]
        for name in got_s:
            assert got_s[name].dtype == side_flat[name].dtype, name
            np.testing.assert_array_equal(np.asarray(got_s[name]),
                                          np.asarray(want_s[name]), name)


def test_rows_per_lane_cover_any_offset():
    for width in (8, 16, 128, 256, 512):
        r = _rows_per_lane(width, "rows")
        assert (r - 1) * A < (A - 1) + width <= r * A  # no row too many
        assert _rows_per_lane(width, "slices") == 1
    assert _rows_per_lane(512, "rows") == 5


# -- through the engine -------------------------------------------------------

def counter_logs(n_agg=300, n_per=40, seed=5):
    """Equal logs: no ``perm``, one round of tiles a time chunk."""
    rng = np.random.default_rng(seed)
    logs = []
    for a in range(n_agg):
        log = []
        for k in range(n_per):
            if rng.random() < 0.6:
                log.append(counter.CountIncremented(f"c{a}", 1, k + 1))
            else:
                log.append(counter.CountDecremented(f"c{a}", 1, k + 1))
        logs.append(log)
    return logs


def cart_logs(n_agg=260, seed=9):
    """Lognormal logs around 40 events: a ``perm``, several rounds, both tile
    granularities, zero-length lanes."""
    rng = np.random.default_rng(seed)
    lengths = np.minimum(rng.lognormal(np.log(40), 0.6, size=n_agg), 400
                         ).astype(int)
    lengths[::37] = 0
    logs = []
    for a, n in enumerate(lengths):
        log = []
        for k in range(n):
            code, price = int(rng.integers(0, 65536)), int(rng.integers(99, 50000))
            if k == n - 1 and rng.random() < 0.3:
                log.append(shopping_cart.CheckedOut(f"k{a}", k + 1))
            elif rng.random() < 0.62:
                log.append(shopping_cart.ItemAdded(
                    f"k{a}", code, int(rng.integers(1, 6)), price, k + 1))
            else:
                log.append(shopping_cart.ItemRemoved(
                    f"k{a}", code, int(rng.integers(1, 3)), price, k + 1))
        logs.append(log)
    return logs


#: name -> (the model's module, its logs, its scalar model)
MODELS = {"counter": (counter, counter_logs, counter.CounterModel),
          "cart": (shopping_cart, cart_logs, shopping_cart.CartModel)}


def make_engine(model, tile, **overrides):
    cfg = default_config().with_overrides({
        "surge.replay.batch-size": 64, "surge.replay.time-chunk": 32,
        "surge.replay.tile-backend": tile, **overrides})
    return ReplayEngine(model.make_replay_spec(), config=cfg)


def resident_spans(since):
    return [s for s in default_tracer().spans(since_mono=since)
            if s.name == "replay.resident"]


@pytest.mark.parametrize("tile", ["xla", "assoc"])
@pytest.mark.parametrize("fold", ["first", "again"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_rebuild_on_rows_matches_replay_ragged(rows_on_cpu, name, fold, tile):
    """``again``: the same uploaded corpus folded a second time, the first
    fold's slab donated and gone: nothing of a fold outlives it but the plan,
    and the second asks the buffers for the same rows."""
    model, make_logs, _ = MODELS[name]
    logs = make_logs()
    engine = make_engine(model, tile)
    assert engine.lane_gather == "rows" and engine.donate_carry
    want = engine.replay_ragged(logs)
    wire = engine.pack_resident(
        encode_events_columnar(model.make_registry(), logs))
    assert (wire.perm is not None) == (name == "cart")
    resident = engine.upload_resident(wire)
    since = time.monotonic()
    got = engine.replay_resident(resident)
    if fold == "again":
        got = engine.replay_resident(resident)
        first, second = resident_spans(since)
        assert second.attributes["rows_fetched"] == (
            first.attributes["rows_fetched"]) > 0
        assert engine.stats["rows_fetched"] == (
            2 * first.attributes["rows_fetched"])
        assert [k if isinstance(k, str) else k[0]
                for k in resident.cache] == ["plan", "invperm"]
    assert got.num_events == sum(len(log) for log in logs)
    for field, col in want.states.items():
        assert got.states[field].dtype == col.dtype, field
        np.testing.assert_array_equal(got.states[field], col, field)


# -- the callers that fold an uploaded corpus once, as the chip runs them ------

def assert_columns_are(states, want):
    """Every state column ``{field: [B]}`` against scalar states (None: no
    event)."""
    for field, col in states.items():
        got = np.asarray(col)
        exp = np.asarray([getattr(st, field) if st is not None else 0
                          for st in want]).astype(got.dtype)
        np.testing.assert_array_equal(got, exp, field)


@pytest.mark.parametrize("tile", ["xla", "assoc"])
@pytest.mark.parametrize("start", ["fresh", "resumed"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_streamed_fold_on_rows_matches_the_scalar_fold(rows_on_cpu, name, start,
                                                       tile):
    """The counter's equal logs tile the buffer in lane order (pieces are lane
    ranges); the cart's perm over grouped input makes an indirect wire (pieces
    re-sorted by length). ``resumed``: every log's later half streamed onto
    the states and ordinals its first half left, each piece taking its own
    lanes' share."""
    module, make_logs, model = MODELS[name]
    logs = make_logs()
    engine = make_engine(module, tile)
    resume, tails = {}, logs
    if start == "resumed":
        heads = [log[:len(log) // 2] for log in logs]
        tails = [log[len(log) // 2:] for log in logs]
        resume = {
            "init_carry": engine.replay_resident(engine.prepare_resident(
                encode_events_columnar(module.make_registry(), heads))).states,
            "ordinal_base": np.asarray([len(h) for h in heads], np.int32)}
    wire = engine.pack_resident(
        encode_events_columnar(module.make_registry(), tails))
    rows_before = engine.stats["rows_fetched"]
    since = time.monotonic()
    got = engine.replay_resident_streamed(wire, segments=3, **resume)
    (umbrella,) = resident_spans(since)
    assert umbrella.attributes["segments"] == 3
    assert umbrella.attributes["gather"] == "rows"
    assert umbrella.attributes["rows_fetched"] == (
        engine.stats["rows_fetched"] - rows_before) > 0
    # of ONE array, in slots: the pieces' rows over the arrays fetched, a
    # row 128 slots
    assert umbrella.attributes["fetched_slots"] == (
        umbrella.attributes["rows_fetched"] * 128 // (1 + len(wire.side)))
    assert got.num_events == sum(len(log) for log in tails)
    assert len(got.states) == len(module.make_registry().state.field_names)
    assert_columns_are(got.states,
                       [fold_events(model(), None, log) for log in logs])


def counter_topic():
    """A two-partition events topic holding 40 ragged counter logs, as
    ``(log, {aggregate: events}, send)``; ``send(agg, n)`` appends ``n``."""
    log = InMemoryLog()
    log.create_topic(TopicSpec("counter-events", 2))
    fmt = counter.event_formatting()
    logs = {}

    def send(agg, n):
        prod = log.transactional_producer("lane-rows")
        prod.begin()
        for _ in range(n):
            ev = counter.CountIncremented(agg, 1 + len(agg) % 3,
                                          len(logs.setdefault(agg, [])) + 1)
            logs[agg].append(ev)
            prod.send(LogRecord(topic="counter-events", key=agg,
                                value=fmt.write_event(ev).value,
                                partition=int(agg.rsplit("-", 1)[1]) % 2))
        prod.commit()

    for i in range(40):
        send(f"agg-{i}", 1 + (7 * i) % 45)
    return log, logs, send


@pytest.mark.parametrize("segment", ["base", "extended"])
def test_segment_restore_on_rows_matches_the_scalar_fold(rows_on_cpu, tmp_path,
                                                         segment):
    """``restore_from_segment`` folds every chunk through the resident path,
    once; an extended segment's delta chunks resume from ``init_carry``."""
    log, logs, send = counter_topic()
    fmt, sfmt = counter.event_formatting(), counter.state_formatting()
    path = str(tmp_path / "seg.scol")
    build_segment_from_topic(
        log, "counter-events", counter.make_registry(), fmt.read_event, path,
        derived_cols={"sequence_number": "ordinal"}, chunk_aggregates=16)
    if segment == "extended":  # longer logs and new ones, after the build
        for i in range(0, 46, 3):
            send(f"agg-{i}", 5)
    info = extend_segment_from_topic(
        log, "counter-events", counter.make_registry(), fmt.read_event, path)
    assert (info.get("num_extends", 0) > 0) == (segment == "extended")
    store = InMemoryKeyValueStore()
    since = time.monotonic()
    res = restore_from_segment(
        path, store, replay_spec=counter.make_replay_spec(),
        serialize_state=lambda a, s: sfmt.write_state(s).value,
        config=default_config().with_overrides({
            "surge.replay.batch-size": 64, "surge.replay.time-chunk": 32,
            "surge.replay.segment-wire-cache": False}))
    folds = resident_spans(since)
    assert len(folds) == info["num_chunks"] >= 3
    assert {s.attributes["gather"] for s in folds} == {"rows"}
    assert res.num_events == sum(len(evs) for evs in logs.values())
    model = counter.CounterModel()
    for agg, events in logs.items():
        truth = fold_events(model, None, events)
        got = sfmt.read_state(store.get(agg))
        assert (got.count, got.version) == (truth.count, truth.version), agg


@pytest.mark.parametrize("caller", ["seed_from_log", "shadow_replay_rows"])
def test_the_planes_cold_folds_on_rows_match_the_scalar_fold(rows_on_cpu,
                                                             caller):
    """The resident plane's seed and the auditor's shadow replay: an uploaded
    corpus folded once through ``fold_resident_slab``, rows left on device."""
    log, logs, _ = counter_topic()
    fmt, sfmt = counter.event_formatting(), counter.state_formatting()
    plane = ResidentStatePlane(
        log, "counter-events", counter.make_replay_spec(),
        config=default_config().with_overrides({
            "surge.replay.resident.capacity": 64,
            "surge.replay.batch-size": 16, "surge.replay.time-chunk": 8}),
        deserialize_event=lambda raw: fmt.read_event(
            SerializedMessage(key="", value=raw)),
        serialize_state=lambda a, s: sfmt.write_state(s).value)
    model = counter.CounterModel()
    want = {agg: fold_events(model, None, evs) for agg, evs in logs.items()}
    since = time.monotonic()
    plane.seed_from_log()
    if caller == "seed_from_log":
        assert plane.snapshot_states() == want
    else:  # the auditor's, over a seeded plane
        since = time.monotonic()
        ids = sorted(logs)
        rows = plane.shadow_replay_rows([logs[a] for a in ids])
        assert_columns_are(rows, [want[a] for a in ids])
    (fold,) = resident_spans(since)
    assert fold.attributes["gather"] == "rows"
    assert fold.attributes["rows_fetched"] > 0


def test_a_cpu_host_keeps_the_slices():
    """No accelerator here: the backend's own choice is the old slice, and the
    buffers are the host's rows in their power-of-two bucket."""
    engine = make_engine(counter, "xla")
    assert engine.lane_gather == "slices"
    wire = engine.pack_resident(
        encode_events_columnar(counter.make_registry(), counter_logs(7, 9)))
    resident = engine.upload_resident(wire)
    assert resident.flat_wire.shape[0] == _bucket_len(wire.packed_shape[0])
    assert (engine.replay_resident(resident).states["version"] == 9).all()
