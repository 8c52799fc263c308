"""A side column goes up from the caller's own array (``WireFormat.side_columns``
-> ``ReplayEngine.pack_resident`` -> ``upload_resident``): a column already in
its wire dtype and contiguous is not copied on the host, the wire holds it
``[N]`` rows long beside the packed buffer's ``[N + guard]``, and the upload
gives both one bucket, whose device zeros are the rows past ``N``. Held to
``pack_flat``, to ``np.pad`` of the column and to the scalar fold
(``fold_events``); the piece is 2^16 rows here (2^22 on the chip)."""

import time

import numpy as np
import pytest

from surge_tpu.codec.tensor import ColumnarEvents
from surge_tpu.codec.wire import WireFormat
from surge_tpu.config import default_config
from surge_tpu.engine.model import fold_events
from surge_tpu.models import shopping_cart as sc
from surge_tpu.replay import engine as engine_module
from surge_tpu.replay.engine import ReplayEngine, ResidentWire, _bucket_len
from tests.test_upload_pieces import PIECE, h2d_spans

SIDES = ("item_code", "quantity", "unit_price_cents")
FIELDS = ("item_count", "total_cents", "checked_out", "version")

#: events of the corpus, against pieces of 2^16 rows and a guard of 8192:
#: name -> (N, pieces of a side column, pieces of the packed buffer, bucket)
SIZES = {
    "one_piece": (40_000, 1, 1, PIECE),
    "packed_alone_passes_the_piece": (60_000, 1, 2, 2 * PIECE),
    "a_power_of_two": (2 * PIECE, 2, 3, 4 * PIECE),
    "whole_pieces": (3 * PIECE, 3, 4, 4 * PIECE),
    "the_guard_passes_the_bucket": (4 * PIECE - 100, 4, 5, 8 * PIECE),
}


@pytest.fixture
def small_pieces(monkeypatch):
    monkeypatch.setattr(engine_module, "_PIECE_ROWS", PIECE)


def make_engine(mesh=None, **overrides):
    cfg = default_config().with_overrides({
        "surge.replay.batch-size": 256, "surge.replay.time-chunk": 64,
        **overrides})
    return ReplayEngine(sc.make_replay_spec(), config=cfg, mesh=mesh)


def cart_events(n, seed=0, carts=300, dtype=np.int32):
    """``n`` grouped cart events over ``carts`` carts of unequal lengths; the
    last cart's log is an eighth of them or more, so the stream's last rows
    are the last events of a lane that reads past them. Columns contiguous,
    in ``dtype``."""
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.choice(np.arange(1, n - n // 8), carts - 1,
                              replace=False))
    lengths = np.diff(np.concatenate([[0], cuts, [n]]))
    type_ids = rng.choice([sc.ADDED, sc.REMOVED, sc.CHECKED_OUT], size=n,
                          p=[0.6, 0.35, 0.05]).astype(np.int32)
    cols = {"item_code": rng.integers(0, 1 << 16, size=n),
            "quantity": rng.integers(1, 6, size=n),
            "unit_price_cents": rng.integers(1, 5000, size=n)}
    return ColumnarEvents(
        num_aggregates=carts,
        agg_idx=np.repeat(np.arange(carts, dtype=np.int32), lengths),
        type_ids=type_ids, cols={k: v.astype(dtype) for k, v in cols.items()},
        derived_cols={"sequence_number": "ordinal"})


def scalar_states(events):
    """Every cart's state by ``fold_events`` over the model's own events."""
    model = sc.CartModel()
    item, qty, price = (events.cols[k].tolist() for k in SIDES)
    tids, aggs = events.type_ids.tolist(), events.agg_idx.tolist()
    logs = [[] for _ in range(events.num_aggregates)]
    for i, (a, t) in enumerate(zip(aggs, tids)):
        seq = len(logs[a]) + 1
        logs[a].append(
            sc.CheckedOut("c", seq) if t == sc.CHECKED_OUT else
            (sc.ItemAdded if t == sc.ADDED else sc.ItemRemoved)(
                "c", item[i], qty[i], price[i], seq))
    states = [fold_events(model, None, log) for log in logs]
    return {f: np.array([getattr(s, f) for s in states]) for f in FIELDS}


def assert_states(res, want):
    for f in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(res.states[f]).astype(want[f].dtype), want[f], f)


def assert_one_bucket(resident, wire, rows):
    """Every device buffer ``rows`` long: the packed rows, then zeros."""
    assert resident.flat_wire.shape == (rows, wire.packed.shape[1])
    np.testing.assert_array_equal(
        np.asarray(resident.flat_wire),
        np.pad(wire.packed, ((0, rows - wire.packed.shape[0]), (0, 0))))
    assert sorted(resident.flat_side) == sorted(SIDES)
    for name, host in wire.side.items():
        dev = resident.flat_side[name]
        assert dev.shape == (rows,) and dev.dtype == host.dtype
        np.testing.assert_array_equal(
            np.asarray(dev), np.pad(host, (0, rows - host.shape[0])), name)


# -- (a) the side half of the pack -------------------------------------------

@pytest.mark.parametrize("kind", ["wire_dtype", "memmapped", "int64",
                                  "strided", "mixed"])
def test_a_wire_dtype_column_is_the_callers_memory(kind, tmp_path):
    events = cart_events(5_000, seed=1)
    cols = dict(events.cols)
    if kind == "memmapped":
        for k, v in cols.items():
            np.save(tmp_path / f"{k}.npy", v)
            cols[k] = np.load(tmp_path / f"{k}.npy", mmap_mode="r")
    if kind in ("int64", "mixed"):
        cols["quantity"] = cols["quantity"].astype(np.int64)
    if kind == "int64":
        cols = {k: v.astype(np.int64) for k, v in cols.items()}
    if kind in ("strided", "mixed"):
        wide = np.repeat(cols["item_code"], 2)
        cols["item_code"] = wide[::2]
        assert not cols["item_code"].flags.c_contiguous
    fresh = {"wire_dtype": (), "memmapped": (), "int64": SIDES,
             "strided": ("item_code",),
             "mixed": ("item_code", "quantity")}[kind]
    wire = WireFormat(sc.make_registry(), {"sequence_number": "ordinal"})
    side = wire.side_columns(cols)
    _, flat_side = wire.pack_flat(events.type_ids, cols)
    assert sorted(side) == sorted(flat_side) == sorted(SIDES)
    for name in SIDES:
        got = side[name]
        assert got.shape == (5_000,) and got.dtype == np.int32
        assert got.flags.c_contiguous
        assert np.shares_memory(got, cols[name]) == (name not in fresh)
        assert got.tobytes() == flat_side[name].tobytes()
        assert got.tobytes() == events.cols[name].astype(np.int32).tobytes()


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_pack_resident_hands_the_columns_over_with_no_guard_rows(dtype):
    engine = make_engine()
    events = cart_events(20_000, seed=2, dtype=dtype)
    wire = engine.pack_resident(events)
    assert wire.guard == 8192
    assert wire.packed.shape == (20_000 + wire.guard, 1)
    assert not wire.packed[20_000:].any()
    for name in SIDES:
        assert wire.side[name].shape == (20_000,)
        assert wire.side[name].dtype == np.int32
        assert np.shares_memory(wire.side[name], events.cols[name]) == (
            dtype is np.int32)
        np.testing.assert_array_equal(wire.side[name], events.cols[name])


# -- (b) one bucket a wire, the packed buffer's ------------------------------

@pytest.mark.parametrize("words_from", ["device", "host"])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_side_columns_take_the_packed_buffers_bucket(small_pieces, size,
                                                     words_from):
    """``device``: the type ids (the cart's whole word) go up as a fourth
    ``[N]`` int32 column and ``mk_word`` builds the packed buffer from their
    pieces; ``host``: the packed buffer exists before the upload (someone
    read it) and goes up as it lies. The same device buffers either way."""
    n, side_pieces, packed_pieces, bucket = SIZES[size]
    engine = make_engine()
    events = cart_events(n, seed=n)
    wire = engine.pack_resident(events)
    if words_from == "host":
        assert wire.packed.shape == (n + wire.guard, 1)
    assert bucket == _bucket_len(n + wire.guard)
    since = time.monotonic()
    resident = engine.upload_resident(wire)
    (h2d,) = (s.attributes for s in h2d_spans(since))
    assert_one_bucket(resident, wire, bucket)
    whole = min(PIECE, bucket)
    # the host copies an array's last piece where it is partial, no other
    # row of a column, and none at all of whole pieces
    lanes = 2 * 4 * resident.b_pad
    partial = 0 if n % whole == 0 else 4 * whole
    if words_from == "host":
        assert h2d["pieces"] == packed_pieces + 3 * side_pieces
        put_bytes = whole * (packed_pieces + 4 * 3 * side_pieces)
        assert h2d["copied_bytes"] == lanes + whole + 3 * partial
        assert h2d["word_source_bytes"] == 0
    else:
        assert h2d["pieces"] == 4 * side_pieces
        put_bytes = whole * 4 * 4 * side_pieces
        assert h2d["copied_bytes"] == lanes + 4 * partial
        assert h2d["word_source_bytes"] == whole * 4 * side_pieces
    assert h2d["put_bytes"] == resident.wire_bytes == put_bytes
    assert h2d["wire_bytes"] == (n + wire.guard) + 3 * 4 * n
    assert_states(engine.replay_resident(resident), scalar_states(events))


def test_a_second_length_shares_the_bucket_and_its_programs(small_pieces):
    """Lengths that differ in their own buckets and their piece counts, one
    packed bucket: the second upload and fold compile nothing."""
    engine = make_engine()
    first = cart_events(2 * PIECE, seed=5)  # its own bucket would be 2^17
    resident = engine.upload_resident(engine.pack_resident(first))
    assert_states(engine.replay_resident(resident), scalar_states(first))
    placements = engine_module._place_piece._cache_size()
    buckets = engine_module._zero_bucket._cache_size()
    folds = engine.num_compiles()
    second = cart_events(3 * PIECE + 17, seed=6)
    again = engine.upload_resident(engine.pack_resident(second))
    assert again.flat_wire.shape == resident.flat_wire.shape
    assert_states(engine.replay_resident(again), scalar_states(second))
    assert engine_module._place_piece._cache_size() == placements
    assert engine_module._zero_bucket._cache_size() == buckets
    assert engine.num_compiles() == folds


# -- (c) saved wires, old and new; the one rule of check_wire ----------------

def old_style(wire):
    """The wire as a build before this rule packed and saved it: every side
    column with the packed buffer's guard rows."""
    return ResidentWire(
        derived_key=dict(wire.derived_key), packed=wire.packed,
        side={k: np.pad(v, (0, wire.guard)) for k, v in wire.side.items()},
        starts=wire.starts, lengths=wire.lengths, perm=wire.perm,
        guard=wire.guard, num_events=wire.num_events, layout=wire.layout)


@pytest.mark.parametrize("style", ["new", "old"])
def test_a_saved_wire_loads_and_folds(small_pieces, tmp_path, style):
    engine = make_engine()
    events = cart_events(2 * PIECE, seed=8)
    wire = engine.pack_resident(events)
    fresh = engine.upload_resident(wire)
    rows = wire.num_events + (wire.guard if style == "old" else 0)
    (old_style(wire) if style == "old" else wire).save(str(tmp_path / "w"))
    loaded = ResidentWire.load(str(tmp_path / "w"))
    assert all(isinstance(v, np.memmap) and v.shape == (rows,)
               for v in loaded.side.values())
    engine.check_wire(loaded)
    resident = engine.upload_resident(loaded)
    # the same device buffers either way: the rows past N are zeros, whoever
    # supplied them
    assert_one_bucket(resident, wire, fresh.flat_wire.shape[0])
    assert_states(engine.replay_resident(resident), scalar_states(events))


@pytest.mark.parametrize("rows, ok", [
    ("one_short", False), ("num_events", True), ("inside_the_guard", True),
    ("the_packed_rows", True), ("one_over", False)])
def test_check_wire_holds_a_side_column_between_the_events_and_the_packed_rows(
        rows, ok):
    engine = make_engine()
    wire = engine.pack_resident(cart_events(9_000, seed=9))
    n = {"one_short": 8_999, "num_events": 9_000, "inside_the_guard": 9_100,
         "the_packed_rows": 9_000 + wire.guard,
         "one_over": 9_001 + wire.guard}[rows]
    wire.side["quantity"] = np.resize(wire.side["quantity"], n)
    if ok:
        engine.check_wire(wire)
        return
    with pytest.raises(ValueError, match="side column 'quantity' holds"):
        engine.check_wire(wire)
    with pytest.raises(ValueError, match="side column 'quantity' holds"):
        engine.upload_resident(wire)


def test_check_wire_refuses_a_packed_buffer_short_of_its_guard():
    engine = make_engine()
    wire = engine.pack_resident(cart_events(9_000, seed=9))
    wire.packed = wire.packed[:-1]
    with pytest.raises(ValueError, match="fewer than its 9000 events"):
        engine.check_wire(wire)


# -- (d) the streamed and the sharded fold -----------------------------------

@pytest.mark.parametrize("segments", [2, 3])
def test_the_streamed_fold_slices_short_side_columns(small_pieces, segments):
    """The last segment's slice of a side column ends with the events, short
    of the guard rows its packed slice has; every segment one bucket."""
    engine = make_engine()
    events = cart_events(3 * PIECE, seed=10)
    wire = engine.pack_resident(events)
    since = time.monotonic()
    got = engine.replay_resident_streamed(wire, segments=segments)
    assert len(h2d_spans(since)) == segments
    assert got.num_events == 3 * PIECE
    assert_states(got, scalar_states(events))


@pytest.mark.parametrize("source", ["columns", "wire", "old_wire"])
def test_the_sharded_fold_reads_the_callers_columns(mesh8, source):
    engine = make_engine(mesh=mesh8)
    events = cart_events(30_000, seed=11, carts=203)
    given = events
    if source != "columns":
        given = engine.pack_resident(events)
        assert all(v.shape == (30_000,) for v in given.side.values())
        if source == "old_wire":
            given = old_style(given)
    res = engine.replay_resident_sharded(
        engine.prepare_resident_sharded(given))
    assert_states(res, scalar_states(events))


# -- (e) the columns are the caller's again once the upload has returned -----

def aligned(col, to=64):
    """``col`` at an address the CPU backend's ``device_put`` takes without a
    copy (a ``jax.Array`` over the host's own memory): the case in which a
    device buffer could be the caller's column."""
    raw = np.empty(col.nbytes + to, dtype=np.uint8)
    at = -raw.ctypes.data % to
    out = raw[at: at + col.nbytes].view(col.dtype)
    out[:] = col
    return out


@pytest.mark.parametrize("mode", ["one_piece", "pieces", "whole_pieces",
                                  "streamed"])
def test_writing_the_columns_after_the_upload_changes_nothing(monkeypatch,
                                                              mode):
    """The wire's side columns are the caller's arrays; the device buffers
    are not: scribbling over the columns once ``upload_resident`` has
    returned leaves the buffers and the fold's states as they were."""
    monkeypatch.setattr(engine_module, "_PIECE_ROWS", PIECE)
    n = {"one_piece": 40_000, "pieces": 2 * PIECE + 5,
         "whole_pieces": 2 * PIECE, "streamed": 70_000}[mode]
    engine = make_engine()
    events = cart_events(n, seed=12)
    events.cols = {k: aligned(v) for k, v in events.cols.items()}
    assert all(v.ctypes.data % 64 == 0 for v in events.cols.values())
    want = scalar_states(events)
    kept = {k: v.copy() for k, v in events.cols.items()}
    wire = engine.pack_resident(events)
    assert all(np.shares_memory(wire.side[k], events.cols[k]) for k in SIDES)
    if mode == "streamed":
        got = engine.replay_resident_streamed(wire, segments=2)
        for col in events.cols.values():
            col[:] = -7
        assert_states(got, want)
        return
    resident = engine.upload_resident(wire)
    for col in events.cols.values():
        col[:] = -7
    for name in SIDES:
        np.testing.assert_array_equal(
            np.asarray(resident.flat_side[name][:n]), kept[name], name)
        assert not np.asarray(resident.flat_side[name][n:]).any()
    assert_states(engine.replay_resident(resident), want)
