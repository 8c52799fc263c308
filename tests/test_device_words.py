"""The packed word built on the device (``replay/engine.py:mk_word``) from the
caller's own columns, against the host's word pass: where ``pack_resident``
hands the word's sources over, the buffer ``upload_resident`` leaves on the
device is element for element ``np.pad`` of what the whole-column host pack
(``tests/test_pack_blocked.py:plain_pack``) builds, the states are the
host-packed wire's, and a column outside its declared width raises the host's
message before any corpus exists. Input that cannot go up as it lies (int64,
strided), a saved wire and the streamed fold keep the host's pass."""

import time

import jax
import numpy as np
import pytest

from surge_tpu.codec.tensor import ColumnarEvents
from surge_tpu.codec.wire import WireFormat
from surge_tpu.replay import engine as engine_module
from surge_tpu.replay.engine import ReplayEngine, ResidentWire, _bucket_len
from surge_tpu.replay.resident_mesh import _deal
from surge_tpu.tracing import default_tracer
from tests.test_pack_blocked import SCHEMAS, make_engine, plain_pack
from tests.test_upload_pieces import PIECE


@pytest.fixture
def small_pieces(monkeypatch):
    monkeypatch.setattr(engine_module, "_PIECE_ROWS", PIECE)


def lengths_of(kind, rng):
    if kind == "equal":
        return np.full(300, 40)
    if kind == "ragged":
        return rng.integers(0, 90, size=400)
    if kind == "sub-piece":  # a handful of events: one padded piece
        return np.array([3, 0, 9, 2])
    if kind == "several-pieces":  # 3 whole pieces of 2^16 and a partial one
        return rng.integers(100, 700, size=3 * PIECE // 400 + 40)
    assert kind == "empty"
    return np.zeros(7, dtype=np.int64)


def make_events(schema, kind, seed=0, dtype=np.int32, type_dtype=np.int32,
                wild_types=False):
    """Grouped events of ``schema`` with the type ids in ``type_dtype`` and
    every column in ``dtype``, all in range (the packed ones under their
    masks and under what ``dtype`` holds)."""
    registry, derived, _nbytes, _side = SCHEMAS[schema]
    wire = WireFormat(registry(), derived)
    rng = np.random.default_rng(seed)
    lengths = lengths_of(kind, rng)
    n = int(lengths.sum())
    type_ids = rng.integers(0, wire.num_types, size=n).astype(type_dtype)
    if wild_types:  # padding and corrupt ids: all must build the sentinel
        info = np.iinfo(type_dtype)
        if info.min < 0:
            type_ids[rng.random(n) < 0.2] = -1
            type_ids[rng.random(n) < 0.1] = info.min
        type_ids[rng.random(n) < 0.2] = wire.num_types + 3
        type_ids[rng.random(n) < 0.1] = info.max
    top = np.iinfo(dtype).max
    cols = {pf.name: rng.integers(0, min(pf.mask, top) + 1, size=n).astype(dtype)
            for pf in wire.packed_fields}
    for f in wire.side_fields:
        cols[f.name] = rng.integers(0, min(1 << 20, top), size=n).astype(dtype)
    return ColumnarEvents(
        num_aggregates=len(lengths), type_ids=type_ids, cols=cols,
        agg_idx=np.repeat(np.arange(len(lengths), dtype=np.int32), lengths),
        derived_cols=dict(derived))


def encode_span(wire):
    (span,) = [s for s in default_tracer().spans()
               if s.context == wire.trace_ctx]
    return span.attributes


def h2d_since(since):
    return [s.attributes for s in default_tracer().spans(since_mono=since)
            if s.name == "replay.h2d"]


def padded(packed, rows):
    return np.pad(packed, ((0, rows - packed.shape[0]), (0, 0)))


def assert_same_states(got, want):
    assert sorted(got.states) == sorted(want.states)
    for name, col in want.states.items():
        np.testing.assert_array_equal(got.states[name], col, name)
    assert got.num_events == want.num_events


KINDS = ["equal", "ragged", "sub-piece", "several-pieces", "empty"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("schema", sorted(SCHEMAS))
def test_the_device_builds_the_hosts_word(small_pieces, schema, kind):
    engine = make_engine(schema)
    events = make_events(schema, kind, seed=3)
    reference = plain_pack(engine, events)  # the whole-column host pack
    wire = engine.pack_resident(events)
    attrs = encode_span(wire)
    assert attrs["words_from"] == "device" and attrs["blocks"] == 0
    assert not wire.host_packed
    assert wire.words.type_ids is events.type_ids  # the caller's own arrays
    for name, col in wire.words.cols.items():
        assert col is events.cols[name], name
    since = time.monotonic()
    resident = engine.upload_resident(wire)
    assert not wire.host_packed  # the upload packed nothing on the host
    bucket = _bucket_len(events.num_events + wire.guard)
    assert resident.flat_wire.dtype == np.uint8
    np.testing.assert_array_equal(np.asarray(resident.flat_wire),
                                  padded(reference.packed, bucket))
    for name, col in reference.side.items():
        np.testing.assert_array_equal(
            np.asarray(resident.flat_side[name]),
            np.pad(col, (0, bucket - col.shape[0])), name)
    # the counts: each source goes up as a side column goes, in pieces of
    # 2^16 rows, the last padded in a buffer of its own
    (h2d,) = h2d_since(since)
    n, piece = events.num_events, min(PIECE, bucket)
    sources = 1 + len(wire.words.cols)
    pieces = max(-(-n // piece), 1)
    assert h2d["pieces"] == pieces * (sources + len(wire.side))
    source_bytes = sum(a.dtype.itemsize for a in wire.words.arrays())
    assert h2d["word_source_bytes"] == pieces * piece * source_bytes
    assert h2d["wire_bytes"] == attrs["wire_bytes"] == (
        (n + wire.guard) * wire.packed_shape[1]
        + sum(v.nbytes for v in wire.side.values()))
    assert_same_states(engine.replay_resident(resident),
                       engine.replay_resident(engine.upload_resident(reference)))
    if kind == "several-pieces":
        assert pieces == 4 and n % PIECE


@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.uint16, np.int32,
                                   np.uint32])
@pytest.mark.parametrize("schema", ["counter-1B", "fields-2B", "fields-4B"])
def test_narrow_sources_and_wild_type_ids_build_the_hosts_word(small_pieces,
                                                               schema, dtype):
    """Sources of any integer width up to four bytes, signed or not: -1, ids
    past the last type and the dtype's least and greatest all build the pad
    sentinel, as the host's clamp does."""
    engine = make_engine(schema)
    events = make_events(schema, "ragged", seed=7, dtype=dtype,
                         type_dtype=dtype, wild_types=True)
    reference = plain_pack(engine, events)
    wire = engine.pack_resident(events)
    assert encode_span(wire)["words_from"] == "device"
    resident = engine.upload_resident(wire)
    assert not wire.host_packed
    np.testing.assert_array_equal(
        np.asarray(resident.flat_wire),
        padded(reference.packed, resident.flat_wire.shape[0]))
    fmt = WireFormat(engine.spec.registry, dict(events.derived_cols))
    tids = events.type_ids.astype(np.int64)
    wild = (tids < 0) | (tids >= fmt.num_types)
    assert wild.sum() > events.num_events // 4
    word = sum(np.asarray(resident.flat_wire)[:events.num_events, k]
               .astype(np.uint32) << (8 * k) for k in range(fmt.nbytes))
    assert ((word[wild] & ((1 << fmt.type_bits) - 1)) == fmt.pad_code).all()


def bad_events(schema, where, bad, dtype=np.int32):
    """Events of several pieces with one value of the second packed column
    out of its range, in the first, a middle or the last piece."""
    events = make_events(schema, "several-pieces", seed=13, dtype=dtype)
    fmt = WireFormat(SCHEMAS[schema][0](), SCHEMAS[schema][1])
    pf = fmt.packed_fields[1]
    n = events.num_events
    at = {"first": 5, "middle": PIECE + PIECE // 2, "last": n - 1}[where]
    events.cols[pf.name][at] = pf.mask + 1 if bad == "past" else bad
    events.cols[pf.name][3] = pf.mask  # the legal maximum, in piece 0
    return events, fmt, pf


@pytest.mark.parametrize("bad", ["past", -1, np.iinfo(np.int32).min])
@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("schema", ["counter-1B", "fields-3B"])
def test_a_column_past_its_width_raises_the_hosts_error_from_the_upload(
        small_pieces, schema, where, bad):
    engine = make_engine(schema)
    events, fmt, pf = bad_events(schema, where, bad)
    with pytest.raises(ValueError) as host:
        fmt.pack_blocks(events.type_ids, events.cols)
    assert f"column {pf.name!r} overflows its declared" in str(host.value)
    wire = engine.pack_resident(events)  # hands the sources over: no error
    assert encode_span(wire)["words_from"] == "device"
    since = time.monotonic()
    with pytest.raises(ValueError) as device:
        engine.upload_resident(wire)
    assert str(device.value) == str(host.value)
    # the failed upload's span closed with the error; the engine is whole:
    # the mended column goes up and folds
    (h2d,) = [s for s in default_tracer().spans(since_mono=since)
              if s.name == "replay.h2d"]
    assert h2d.status != "ok"
    with pytest.raises(ValueError) as read:
        wire.packed  # whoever reads the host's buffer meets the same error
    assert str(read.value) == str(host.value)
    events.cols[pf.name][events.cols[pf.name] > pf.mask] = 0
    events.cols[pf.name][events.cols[pf.name] < 0] = 0
    mended = engine.pack_resident(events)
    assert_same_states(
        engine.replay_resident(engine.upload_resident(mended)),
        engine.replay_resident(engine.upload_resident(
            plain_pack(engine, events))))


@pytest.mark.parametrize("path", ["device", "host"])
def test_a_negative_in_a_column_narrower_than_its_field_raises(small_pieces,
                                                               path):
    """An int8 column in a 14-bit field: a -1, read as an unsigned byte,
    stays under the field's mask, and is out of range all the same."""
    engine = make_engine("fields-4B")
    events = make_events("fields-4B", "ragged", seed=5, dtype=np.int8)
    events.cols["a"][17] = -1
    if path == "host":  # a strided view never goes up as it lies
        events.cols["a"] = np.repeat(events.cols["a"], 2)[::2]
    with pytest.raises(ValueError, match="column 'a' overflows its declared "
                       r"14-bit wire width \(max value 127, min -1\)"):
        engine.upload_resident(engine.pack_resident(events))


@pytest.mark.parametrize("kind", ["int64", "strided", "int64-type-ids",
                                  "float"])
def test_other_input_is_packed_on_the_host_as_before(small_pieces, kind):
    """A column jax would narrow or reshape on its way up never takes the
    device path: it is packed in ``pack_resident``, which raises for a bad
    value as it always has."""
    engine = make_engine("counter-1B")
    events = make_events("counter-1B", "ragged", seed=9)
    name = "increment_by"
    if kind == "int64":
        events.cols[name] = events.cols[name].astype(np.int64)
    elif kind == "strided":
        events.cols[name] = np.repeat(events.cols[name], 2)[::2]
        assert not events.cols[name].flags.c_contiguous
    elif kind == "float":
        events.cols[name] = events.cols[name].astype(np.float32)
    else:
        events.type_ids = events.type_ids.astype(np.int64)
    wire = engine.pack_resident(events)
    attrs = encode_span(wire)
    assert attrs["words_from"] == "host" and attrs["blocks"] == 1
    assert wire.host_packed and wire.words is None
    reference = plain_pack(engine, events)
    assert wire.packed.tobytes() == reference.packed.tobytes()
    since = time.monotonic()
    resident = engine.upload_resident(wire)
    (h2d,) = h2d_since(since)
    assert h2d["word_source_bytes"] == 0 and h2d["pieces"] == 1
    assert_same_states(engine.replay_resident(resident),
                       engine.replay_resident(engine.upload_resident(reference)))
    events.cols[name][11] = 4  # two bits hold 0..3
    with pytest.raises(ValueError, match="overflows its declared 2-bit"):
        engine.pack_resident(events)


@pytest.mark.parametrize("schema", ["counter-1B", "counter-1B-side",
                                    "fields-3B"])
def test_a_saved_source_wire_is_the_host_packed_file(small_pieces, tmp_path,
                                                     schema):
    """``save`` reads ``packed``: the host's build, byte for byte the file a
    host-packed wire writes; the loaded wire carries no sources, goes up as it
    lies and folds to the device-built word's states."""
    engine = make_engine(schema)
    events = make_events(schema, "several-pieces", seed=21)
    wire = engine.pack_resident(events)
    device_built = engine.replay_resident(engine.upload_resident(wire))
    assert not wire.host_packed
    since = time.monotonic()
    wire.save(str(tmp_path / "device"))
    assert wire.host_packed
    (late,) = [s for s in default_tracer().spans(since_mono=since)
               if s.name == "replay.encode.words"]
    assert late.context.trace_id == wire.trace_ctx.trace_id
    plain_pack(engine, events).save(str(tmp_path / "host"))
    for name in ("packed.npy", "starts.npy", "lengths.npy", "wire.json",
                 *(f"side_{k}.npy" for k in wire.side)):
        assert ((tmp_path / "device" / name).read_bytes()
                == (tmp_path / "host" / name).read_bytes()), name
    loaded = ResidentWire.load(str(tmp_path / "device"))
    assert loaded.words is None and isinstance(loaded.packed, np.memmap)
    since = time.monotonic()
    assert_same_states(engine.replay_resident(engine.upload_resident(loaded)),
                       device_built)
    # and the wire that was saved now holds its packed buffer: it goes up as
    # it lies too
    assert_same_states(engine.replay_resident(engine.upload_resident(wire)),
                       device_built)
    assert [a["word_source_bytes"] for a in h2d_since(since)] == [0, 0]


@pytest.mark.parametrize("schema", ["counter-1B", "counter-1B-side"])
def test_the_streamed_fold_reads_the_hosts_buffer(small_pieces, schema):
    engine = make_engine(schema)
    events = make_events(schema, "several-pieces", seed=25)
    want = engine.replay_resident(engine.upload_resident(
        plain_pack(engine, events)))
    wire = engine.pack_resident(events)
    assert not wire.host_packed
    since = time.monotonic()
    got = engine.replay_resident_streamed(wire, segments=3)
    assert wire.host_packed
    assert all(a["word_source_bytes"] == 0 for a in h2d_since(since))
    assert len(h2d_since(since)) == 3
    assert_same_states(got, want)


def test_the_callers_columns_are_theirs_again_after_the_upload(small_pieces):
    """Every source has landed in a device buffer of its own when the upload
    returns: writing the columns after it changes nothing on the device."""
    engine = make_engine("counter-1B")
    events = make_events("counter-1B", "several-pieces", seed=27)
    reference = plain_pack(engine, events)
    want = engine.replay_resident(engine.upload_resident(reference))
    resident = engine.upload_resident(engine.pack_resident(events))
    events.type_ids[:] = 1
    for col in events.cols.values():
        col[:] = 3
    np.testing.assert_array_equal(
        np.asarray(resident.flat_wire),
        padded(reference.packed, resident.flat_wire.shape[0]))
    assert_same_states(engine.replay_resident(resident), want)


def test_chunks_of_one_layout_share_the_word_program(small_pieces):
    """One ``mk_word`` an engine and layout, one compile a bucket: a restore's
    chunks of differing lengths in one bucket compile it once."""
    engine = make_engine("counter-1B")
    for seed in (1, 2, 3):
        events = make_events("counter-1B", "several-pieces", seed=seed)
        engine.upload_resident(engine.pack_resident(events))
    (program,) = engine._word_programs.values()
    assert program.__name__ == "mk_word" and program._cache_size() == 1
    other = make_events("counter-1B-side", "ragged", seed=4)  # another layout
    engine.upload_resident(engine.pack_resident(other))
    assert len(engine._word_programs) == 2


# -- on a mesh -----------------------------------------------------------------

def mesh_engine(schema, devices):
    assert len(jax.devices()) >= devices, "tier-1 forces 8 host devices"
    plain = make_engine(schema)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:devices]), ("data",))
    return ReplayEngine(plain.spec, config=plain.config, mesh=mesh)


@pytest.mark.parametrize("kind", ["ragged", "several-pieces", "empty"])
@pytest.mark.parametrize("devices", [2, 4])
@pytest.mark.parametrize("schema", ["counter-1B", "counter-1B-side",
                                    "fields-3B"])
def test_a_sharded_upload_of_sources_equals_the_host_packed_ones(
        small_pieces, schema, devices, kind):
    """Each device builds its shard's word from its slice of the sources: the
    joined buffers and the states are those of the same wire packed on the
    host before the deal."""
    engine = mesh_engine(schema, devices)
    events = make_events(schema, kind, seed=31)
    device_wire = engine.pack_resident(events)
    host_wire = engine.pack_resident(events)
    assert host_wire.packed.tobytes() == plain_pack(
        make_engine(schema), events).packed.tobytes()
    _, shards, _, copied = _deal(device_wire, devices)
    assert copied == 0 and not device_wire.host_packed
    for word, _side, _starts in shards:  # views of the caller's own columns
        assert len(word) == 1 + len(device_wire.words.cols)
        assert np.shares_memory(word[0], events.type_ids) or not word[0].size
    since = time.monotonic()
    on_device = engine.prepare_resident_sharded(device_wire)
    on_host = engine.prepare_resident_sharded(host_wire)
    assert not device_wire.host_packed
    a, b = h2d_since(since)
    assert a["word_source_bytes"] > 0 and b["word_source_bytes"] == 0
    assert a["wire_bytes"] == b["wire_bytes"]
    assert on_device.flat_wire.shape == on_host.flat_wire.shape
    np.testing.assert_array_equal(np.asarray(on_device.flat_wire),
                                  np.asarray(on_host.flat_wire))
    for name in on_host.flat_side:
        np.testing.assert_array_equal(np.asarray(on_device.flat_side[name]),
                                      np.asarray(on_host.flat_side[name]))
    assert_same_states(engine.replay_resident_sharded(on_device),
                       engine.replay_resident_sharded(on_host))


@pytest.mark.parametrize("where", ["first", "middle", "last"])
def test_a_sharded_upload_raises_the_hosts_error_too(small_pieces, where):
    engine = mesh_engine("counter-1B", 4)
    events, fmt, _pf = bad_events("counter-1B", where, "past")
    with pytest.raises(ValueError) as host:
        fmt.pack_blocks(events.type_ids, events.cols)
    wire = engine.pack_resident(events)
    with pytest.raises(ValueError) as device:
        engine.prepare_resident_sharded(wire)
    assert str(device.value) == str(host.value)
