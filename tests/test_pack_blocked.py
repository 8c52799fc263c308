"""The blocked flat pack of ``ReplayEngine.pack_resident`` against the plain
whole-column one: ``WireFormat.pack_flat`` plus the lane arithmetic the pack
used before it ran in blocks (``bincount``, the length sort, ``np.diff``,
``np.pad``), byte for byte. The block is shrunk through the module constant so
that small inputs span many blocks."""

from dataclasses import make_dataclass

import numpy as np
import pytest

from surge_tpu.codec import wire as wire_module
from surge_tpu.codec.schema import FieldSpec, SchemaRegistry
from surge_tpu.codec.tensor import ColumnarEvents
from surge_tpu.codec.wire import WireFormat, grouped_lengths
from surge_tpu.config import default_config
from surge_tpu.engine.model import ReplayHandlers, ReplaySpec
from surge_tpu.models.counter import make_registry, make_replay_spec
from surge_tpu.replay.engine import _WIRE_GUARD_MIN, ReplayEngine, ResidentWire

BLOCK = 37  # no length below is a multiple of it


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(wire_module, "FLAT_PACK_BLOCK", BLOCK)


def two_field_registry(bits_a, bits_b):
    """Two event types, ``a`` and ``b`` packed at the given widths: 2 type
    bits + bits_a + bits_b decide the wire's bytes."""
    reg = SchemaRegistry()
    reg.register_event(make_dataclass("EvA", [("a", int)]),
                       fields=[FieldSpec("a", np.int32, bits=bits_a)])
    reg.register_event(make_dataclass("EvB", [("b", int)]),
                       fields=[FieldSpec("b", np.int32, bits=bits_b)])
    reg.register_state(make_dataclass("St", [("a", int)]),
                       fields=[FieldSpec("a", np.int32)])
    return reg


# name -> (registry, derived columns, wire bytes, side columns)
SCHEMAS = {
    "counter-1B": (make_registry, {"sequence_number": "ordinal"}, 1, []),
    "counter-1B-side": (make_registry, {}, 1, ["sequence_number"]),
    "fields-2B": (lambda: two_field_registry(5, 6), {}, 2, []),
    "fields-3B": (lambda: two_field_registry(10, 9), {}, 3, []),
    "fields-4B": (lambda: two_field_registry(14, 14), {}, 4, []),
}


def make_engine(schema, **overrides):
    registry, _derived, _nbytes, _side = SCHEMAS[schema]
    spec = (make_replay_spec() if schema.startswith("counter")
            else ReplaySpec(registry=registry(), handlers=ReplayHandlers({})))
    cfg = default_config().with_overrides({
        "surge.replay.batch-size": 64, "surge.replay.time-chunk": 16,
        **overrides})
    return ReplayEngine(spec, config=cfg)


def lengths_of(kind, rng):
    if kind == "equal":
        return np.full(23, 11)
    if kind == "ragged":  # the block edge falls inside most aggregates
        return rng.integers(1, 60, size=29)
    if kind == "gaps":  # empty aggregates: in front, inside, a run at the end
        lengths = rng.integers(1, 50, size=31)
        lengths[[0, 4, 5, 17, 28, 29, 30]] = 0
        return lengths
    if kind == "one-long":  # a single aggregate over many blocks
        return np.array([5 * BLOCK + 3])
    if kind == "sub-block":  # an input shorter than one block: one block
        return np.array([3, 0, 9, 2])
    assert kind == "empty"
    return np.zeros(7, dtype=np.int64)


def make_events(schema, kind, seed=0, dtype=np.int32, ungrouped=False,
                wild_types=False):
    registry, derived, _nbytes, _side = SCHEMAS[schema]
    wire = WireFormat(registry(), derived)
    rng = np.random.default_rng(seed)
    lengths = lengths_of(kind, rng)
    n = int(lengths.sum())
    agg = np.repeat(np.arange(len(lengths), dtype=np.int32), lengths)
    type_ids = rng.integers(0, wire.num_types, size=n).astype(np.int32)
    if wild_types:  # padding and corrupt ids: all must pack as the sentinel
        type_ids[rng.random(n) < 0.2] = -1
        type_ids[rng.random(n) < 0.2] = wire.num_types + 3
        type_ids[rng.random(n) < 0.1] = np.iinfo(np.int32).min
    cols = {pf.name: rng.integers(0, pf.mask + 1, size=n).astype(dtype)
            for pf in wire.packed_fields}
    for f in wire.side_fields:
        cols[f.name] = rng.integers(0, 1 << 20, size=n).astype(dtype)
    if ungrouped:
        order = rng.permutation(n)
        agg, type_ids = agg[order], type_ids[order]
        cols = {k: v[order] for k, v in cols.items()}
    return ColumnarEvents(num_aggregates=len(lengths), agg_idx=agg,
                          type_ids=type_ids, cols=cols,
                          derived_cols=dict(derived))


def plain_words(wire, type_ids, cols):
    """The word build as it stood before the blocks: whole-column masks."""
    wdtype = (np.uint8 if wire.nbytes == 1
              else np.uint16 if wire.nbytes == 2 else np.uint32)
    tid = np.asarray(type_ids)
    word = np.where((tid < 0) | (tid >= wire.num_types),
                    wire.pad_code, tid).astype(wdtype)
    for pf in wire.packed_fields:
        col = np.asarray(cols[pf.name])
        if col.size and ((col < 0) | (col > pf.mask)).any():
            raise ValueError(
                f"column {pf.name!r} overflows its declared {pf.bits}-bit "
                f"wire width (max value {int(col.max())}, "
                f"min {int(col.min())})")
        word |= col.astype(wdtype) << np.asarray(pf.shift, dtype=wdtype)
    return word


def plain_pack(engine, colev):
    """``pack_resident`` as it stood before the blocks, on ``pack_flat``."""
    b = colev.num_aggregates
    agg = np.asarray(colev.agg_idx)
    lengths = np.bincount(agg, minlength=b).astype(np.int64)
    perm = None
    if engine.sort_by_length and b > 1:
        perm = np.argsort(-lengths, kind="stable").astype(np.int32)
        if np.array_equal(perm, np.arange(b, dtype=np.int32)):
            perm = None
    grouped = bool((np.diff(agg) >= 0).all()) if agg.size > 1 else True
    if grouped:
        to_pack = colev
    else:
        if perm is not None:
            inv = np.empty_like(perm)
            inv[perm] = np.arange(b, dtype=np.int32)
            colev = ColumnarEvents(
                num_aggregates=b, agg_idx=inv[colev.agg_idx],
                type_ids=colev.type_ids, cols=colev.cols,
                derived_cols=dict(colev.derived_cols))
            lengths = lengths[perm]
        to_pack = colev.sorted_by_aggregate()
    wire = WireFormat(engine.spec.registry, dict(to_pack.derived_cols))
    packed, side = wire.pack_flat(to_pack.type_ids, to_pack.cols)
    assert np.array_equal(packed[:, 0].astype(np.uint32) | sum(
        packed[:, k].astype(np.uint32) << (8 * k)
        for k in range(1, wire.nbytes)),
        plain_words(wire, to_pack.type_ids, to_pack.cols))
    guard = max(engine.resident_tile_width(), _WIRE_GUARD_MIN)
    packed = np.pad(packed, ((0, guard), (0, 0)))  # the word buffer alone
    starts = np.zeros(b + 1, dtype=np.int64)
    np.cumsum(lengths, out=starts[1:])
    starts_lane, lens_lane = starts[:-1], lengths
    if grouped and perm is not None:
        starts_lane, lens_lane = starts_lane[perm], lengths[perm]
    return ResidentWire(
        derived_key=dict(to_pack.derived_cols), packed=packed, side=side,
        starts=starts_lane.astype(np.int32),
        lengths=lens_lane.astype(np.int32), perm=perm, guard=guard,
        num_events=to_pack.num_events, layout=wire.layout_fingerprint())


def assert_same_wire(got, want):
    for name in ("packed", "starts", "lengths"):
        g, w = getattr(got, name), getattr(want, name)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert g.tobytes() == w.tobytes(), name
    assert sorted(got.side) == sorted(want.side)
    for name, w in want.side.items():
        g = got.side[name]
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert g.tobytes() == w.tobytes(), name
    assert (got.perm is None) == (want.perm is None)
    if want.perm is not None:
        assert got.perm.dtype == want.perm.dtype
        assert np.array_equal(got.perm, want.perm)
    assert (got.guard, got.num_events, got.layout, got.derived_key) == (
        want.guard, want.num_events, want.layout, want.derived_key)


def packed_with_span(engine, events):
    """``pack_resident`` and the attributes of its ``replay.encode`` span."""
    wire = engine.pack_resident(events)
    span, = [s for s in engine.profiler.tracer.spans()
             if s.context == wire.trace_ctx]
    return wire, span.attributes


#: where the word is built -> a column dtype that sends it there: int32
#: columns go up as they lie and the device builds the word (the host's
#: build then runs when ``packed`` is first read: these comparisons); an
#: int64 column never takes that path and is packed in ``pack_resident``
WORDS_FROM = {"device": np.int32, "host": np.int64}


def assert_words_from(got, attrs, words_from, num_events):
    """The span's account of the word pass, before anyone reads ``packed``."""
    assert attrs["words_from"] == words_from
    assert got.host_packed == (words_from == "host")
    assert attrs["blocks"] == (-(-num_events // BLOCK)
                               if words_from == "host" else 0)


@pytest.mark.parametrize("words_from", sorted(WORDS_FROM))
@pytest.mark.parametrize("kind", ["equal", "ragged", "gaps", "one-long",
                                  "sub-block", "empty"])
@pytest.mark.parametrize("schema", sorted(SCHEMAS))
def test_grouped_input_packs_byte_for_byte(schema, kind, words_from):
    engine = make_engine(schema)
    events = make_events(schema, kind, seed=3, dtype=WORDS_FROM[words_from])
    _registry, _derived, nbytes, side = SCHEMAS[schema]
    got, attrs = packed_with_span(engine, events)
    assert_words_from(got, attrs, words_from, events.num_events)
    assert got.packed_shape == (events.num_events + got.guard, nbytes)
    assert_same_wire(got, plain_pack(engine, events))
    assert got.packed.shape == (events.num_events + got.guard, nbytes)
    assert sorted(got.side) == side
    assert attrs["grouped"] is True and attrs["lanes_from"] == "boundaries"
    # every log as long as the next: no sort, so no permutation
    if kind in ("equal", "empty", "one-long"):
        assert got.perm is None
    if kind in ("ragged", "gaps"):
        assert got.perm is not None
        assert events.num_events > 5 * BLOCK  # the host's pass: many blocks


@pytest.mark.parametrize("words_from", sorted(WORDS_FROM))
@pytest.mark.parametrize("kind", ["equal", "ragged", "gaps"])
@pytest.mark.parametrize("schema", ["counter-1B", "counter-1B-side",
                                    "fields-3B"])
def test_ungrouped_input_keeps_the_bincount_path(schema, kind, words_from):
    engine = make_engine(schema)
    events = make_events(schema, kind, seed=5, ungrouped=True,
                         dtype=WORDS_FROM[words_from])
    got, attrs = packed_with_span(engine, events)
    assert_words_from(got, attrs, words_from, events.num_events)
    assert_same_wire(got, plain_pack(engine, events))
    assert attrs["grouped"] is False and attrs["lanes_from"] == "bincount"


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.uint16])
@pytest.mark.parametrize("schema", ["counter-1B", "fields-2B", "fields-3B"])
def test_column_dtypes_and_wild_type_ids(schema, dtype):
    """int32 and int64 columns (and an unsigned one) pack alike; -1, too-large
    and most-negative type ids pack as the pad sentinel in every block."""
    engine = make_engine(schema)
    events = make_events(schema, "ragged", seed=7, dtype=dtype,
                         wild_types=True)
    got = engine.pack_resident(events)
    assert_same_wire(got, plain_pack(engine, events))
    wire = WireFormat(engine.spec.registry, dict(events.derived_cols))
    n = events.num_events
    word = sum(got.packed[:n, k].astype(np.uint32) << (8 * k)
               for k in range(wire.nbytes))
    wild = (events.type_ids < 0) | (events.type_ids >= wire.num_types)
    assert wild.sum() > n // 4
    assert ((word[wild] & ((1 << wire.type_bits) - 1)) == wire.pad_code).all()


@pytest.mark.parametrize("type_dtype", [np.int8, np.int64, np.uint8])
def test_type_id_dtypes(type_dtype):
    engine = make_engine("counter-1B")
    events = make_events("counter-1B", "ragged", seed=9)
    tid = events.type_ids.astype(type_dtype)
    if np.dtype(type_dtype).kind == "i":
        tid[::5] = -1
    tid[1::7] = 100
    events.type_ids = tid
    assert_same_wire(engine.pack_resident(events), plain_pack(engine, events))


@pytest.mark.parametrize("sort_by_length", [True, False])
def test_sort_by_length_off_never_permutes(sort_by_length):
    engine = make_engine("counter-1B",
                         **{"surge.replay.sort-by-length": sort_by_length})
    events = make_events("counter-1B", "ragged", seed=11)
    got = engine.pack_resident(events)
    assert_same_wire(got, plain_pack(engine, events))
    assert (got.perm is not None) == sort_by_length


@pytest.mark.parametrize("bad, where", [(4, "first"), (-1, "late"),
                                        (2 ** 32, "late"), (7, "last")])
@pytest.mark.parametrize("schema", ["counter-1B", "fields-3B"])
def test_an_overflowing_column_raises_the_same_error(schema, bad, where):
    """Whichever block holds the value, the error is the whole column's: the
    same text the whole-column build gives."""
    engine = make_engine(schema)
    events = make_events(schema, "ragged", seed=13, dtype=np.int64)
    wire = WireFormat(engine.spec.registry, dict(events.derived_cols))
    name, mask = wire.packed_fields[1].name, wire.packed_fields[1].mask
    n = events.num_events
    at = {"first": 0, "late": n - BLOCK - 2, "last": n - 1}[where]
    events.cols[name][at] = bad if bad < 0 or bad > mask else mask + 1
    events.cols[name][3] = mask  # the column's legal maximum, in block 0
    with pytest.raises(ValueError) as plain:
        plain_words(wire, events.type_ids, events.cols)
    with pytest.raises(ValueError) as blocked:
        engine.pack_resident(events)
    assert str(blocked.value) == str(plain.value)
    assert f"column {name!r} overflows its declared" in str(blocked.value)
    with pytest.raises(ValueError) as flat:
        wire.pack_flat(events.type_ids, events.cols)
    assert str(flat.value) == str(plain.value)


@pytest.mark.parametrize("ids, b", [([-1, 0, 1], 2), ([0, 1, 2], 2),
                                    ([0, 0, 5], 3)])
def test_grouped_ids_outside_the_aggregates_raise(ids, b):
    with pytest.raises(ValueError):
        grouped_lengths(np.asarray(ids, dtype=np.int32), b)
    with pytest.raises(ValueError):
        np.cumsum(np.bincount(np.asarray(ids), minlength=b),
                  out=np.zeros(b, dtype=np.int64))  # as it was: it raised too


@pytest.mark.parametrize("dtype", [np.int8, np.int32, np.int64, np.uint32])
def test_grouped_lengths_match_bincount(dtype):
    rng = np.random.default_rng(17)
    for b, n in ((1, 0), (1, 1), (5, 1), (200, 3 * BLOCK), (9, 2 * BLOCK + 1)):
        b = min(b, np.iinfo(dtype).max)
        agg = np.sort(rng.integers(0, b, size=n)).astype(dtype)
        got = grouped_lengths(agg, b)
        assert got.dtype == np.int64
        assert np.array_equal(got, np.bincount(agg, minlength=b))
    # more aggregates declared than the id dtype can name
    agg = np.array([0, 0, 126, 127], dtype=np.int8)
    assert np.array_equal(grouped_lengths(agg, 300),
                          np.bincount(agg, minlength=300))


@pytest.mark.parametrize("at", [1, BLOCK - 1, BLOCK, BLOCK + 1, 3 * BLOCK,
                                4 * BLOCK + 5])
def test_one_descent_anywhere_is_ungrouped(at):
    """The neighbour compare overlaps its blocks by one element: a descent
    across a block edge is seen like one inside a block."""
    agg = np.repeat(np.arange(10, dtype=np.int32), BLOCK // 2)[:4 * BLOCK + 6]
    assert grouped_lengths(agg, 10) is not None
    agg = agg.copy()
    agg[at - 1] = agg[at] + 1  # the only descent: from at - 1 to at
    assert (np.diff(agg) < 0).sum() == 1
    assert grouped_lengths(agg, 10) is None


@pytest.mark.parametrize("tile", ["xla", "assoc"])
@pytest.mark.parametrize("ungrouped", [False, True])
def test_a_wire_of_many_blocks_folds_to_the_right_states(tile, ungrouped):
    engine = make_engine("counter-1B",
                         **{"surge.replay.tile-backend": tile})
    events = make_events("counter-1B", "gaps", seed=19, ungrouped=ungrouped)
    res = engine.replay_resident(
        engine.upload_resident(engine.pack_resident(events)))
    inc = np.where(events.type_ids == 0, events.cols["increment_by"], 0)
    dec = np.where(events.type_ids == 1, events.cols["decrement_by"], 0)
    want = np.bincount(events.agg_idx, weights=inc - dec,
                       minlength=events.num_aggregates)
    assert np.array_equal(np.asarray(res.states["count"]), want)
    assert res.num_events == events.num_events


def test_a_saved_plain_wire_loads_and_folds(tmp_path):
    """A wire the whole-column pack wrote to disk folds under this engine as
    the blocked pack's own does."""
    engine = make_engine("counter-1B")
    events = make_events("counter-1B", "ragged", seed=23)
    plain_pack(engine, events).save(str(tmp_path / "w"))
    loaded = ResidentWire.load(str(tmp_path / "w"))
    assert_same_wire(engine.pack_resident(events), loaded)
    got = engine.replay_resident(engine.upload_resident(loaded))
    own = engine.replay_resident(
        engine.upload_resident(engine.pack_resident(events)))
    for name in ("count", "version"):
        assert np.array_equal(np.asarray(got.states[name]),
                              np.asarray(own.states[name]))


def test_nothing_is_kept_between_packs():
    """A second pack of other columns through the same engine is that input's
    wire: no lengths, groupedness or buffer survives a call."""
    engine = make_engine("counter-1B")
    first = make_events("counter-1B", "equal", seed=29)
    second = make_events("counter-1B", "ragged", seed=31, ungrouped=True)
    a = engine.pack_resident(first)
    b = engine.pack_resident(second)
    assert_same_wire(a, plain_pack(engine, first))
    assert_same_wire(b, plain_pack(engine, second))
    assert not np.shares_memory(a.packed, b.packed)
