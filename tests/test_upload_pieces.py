"""The bucketed upload in pieces (``engine._bucket_pieces`` / ``_put_pieces``,
``ReplayEngine.upload_resident``): the power-of-two bucket is the device
buffer's, the host hands over the wire's own rows and copies one piece an
array at most. Held to ``np.pad`` of the host buffer, which is what the upload
did before, element for element; the piece is 2^16 rows here (2^22 on the
chip) so that tier-1 sizes make many."""

import time

import numpy as np
import pytest

from surge_tpu.config import default_config
from surge_tpu.models import counter, shopping_cart
from surge_tpu.replay import engine as engine_module
from surge_tpu.replay.corpus import synth_counter_corpus
from surge_tpu.replay.engine import (ReplayEngine, ResidentWire, _bucket_len,
                                     _bucket_pieces, _place_piece, _put_pieces)
from surge_tpu.tracing import default_tracer
from tests.test_cart_rebuild import make_corpus as make_cart_corpus

PIECE = 1 << 16  # the least bucket: the smallest piece that divides them all

#: rows of the host buffer, against pieces of 2^16 rows
ROWS = {"under_a_piece": 40_000, "three_pieces": 3 * PIECE,
        "three_pieces_and_a_row": 3 * PIECE + 1,
        "a_row_short_of_the_bucket": 4 * PIECE - 1}
#: what a wire holds: the packed word (1 and 3 bytes), int32 and bool columns
KINDS = {"word1": ((1,), np.uint8), "word3": ((3,), np.uint8),
         "int32": ((), np.int32), "bool": ((), np.bool_)}


def host_buffer(rows, kind, source, tmp_path):
    tail, dtype = KINDS[kind]
    rng = np.random.default_rng(rows)
    high = 2 if dtype is np.bool_ else 250
    extra = 777 if source == "sliced" else 0  # a view into a longer buffer
    arr = rng.integers(1, high, size=(rows + extra, *tail)).astype(dtype)
    if source == "mmapped":
        np.save(tmp_path / "arr.npy", arr)
        arr = np.load(tmp_path / "arr.npy", mmap_mode="r")
    return arr[extra:]


@pytest.mark.parametrize("source", ["fresh", "mmapped", "sliced"])
@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("rows", sorted(ROWS))
def test_the_pieces_make_the_padded_buffer(rows, kind, source, tmp_path):
    n = ROWS[rows]
    arr = host_buffer(n, kind, source, tmp_path)
    pieces, copied = _bucket_pieces(arr, PIECE)
    assert len(pieces) == -(-n // PIECE)
    # whole pieces are the caller's own rows; the host copies one at most
    for piece in pieces[:-1]:
        assert piece.shape[0] == PIECE and np.shares_memory(piece, arr)
    row_bytes = arr.dtype.itemsize * int(np.prod(arr.shape[1:], dtype=int))
    assert copied <= PIECE * row_bytes
    assert copied == (0 if np.shares_memory(pieces[-1], arr)
                      else pieces[-1].nbytes)
    dev = _put_pieces(pieces, _bucket_len(n))
    want = np.pad(arr, [(0, _bucket_len(n) - n)] + [(0, 0)] * (arr.ndim - 1))
    assert dev.shape == want.shape and dev.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(dev), want)
    assert sum(p.nbytes for p in pieces) <= want.nbytes


# -- through the engine -------------------------------------------------------

def counter_corpus(events, seed=14):
    corpus = synth_counter_corpus(2000, events, seed=seed)
    return corpus.events


def cart_corpus(events, seed=11):
    return make_cart_corpus(3000, events, seed)[1]


#: name -> (the model's module, its corpus, the arrays of its wire: the cart
#: has three int32 side columns beside the one-byte word)
MODELS = {"counter": (counter, counter_corpus, 1),
          "cart": (shopping_cart, cart_corpus, 4)}
#: the arrays a wire fresh from ``pack_resident`` goes up as, the word's
#: sources in place of the word: the counter's type ids and its two packed
#: columns; the cart's type ids beside its three side columns
SOURCE_ARRAYS = {"counter": 3, "cart": 4}


def make_engine(model, **keys):
    cfg = default_config().with_overrides({
        "surge.replay.batch-size": 256, "surge.replay.time-chunk": 64, **keys})
    return ReplayEngine(model.make_replay_spec(), config=cfg)


def h2d_spans(since):
    return [s for s in default_tracer().spans(since_mono=since)
            if s.name == "replay.h2d"]


def assert_device_buffers_are_the_padded_wire(resident, wire):
    """One bucket a wire, the packed buffer's: a side column's fewer rows
    (``[N]`` beside ``[N + guard]``) are padded to the same device shape."""
    bucket = _bucket_len(wire.packed.shape[0])
    for dev, host in ((resident.flat_wire, wire.packed),
                      *((resident.flat_side[k], v)
                        for k, v in wire.side.items())):
        want = np.pad(host, [(0, bucket - host.shape[0])]
                      + [(0, 0)] * (host.ndim - 1))
        assert dev.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(dev), want)


@pytest.mark.parametrize("source", ["fresh", "loaded"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_an_upload_in_pieces_folds_the_one_piece_uploads_states(
        monkeypatch, tmp_path, name, source):
    """The same wire uploaded whole (the piece of the chip: one piece an
    array at these sizes) and in pieces of 2^16 rows: the same device
    buffers, the same states; the spans say which ran."""
    model, make_corpus, arrays = MODELS[name]
    engine = make_engine(model)
    wire = engine.pack_resident(make_corpus(200_000))
    if source == "loaded":
        wire.save(str(tmp_path / "wire"))
        wire = ResidentWire.load(str(tmp_path / "wire"))
        assert isinstance(wire.packed, np.memmap)
    else:  # the device builds the word from its sources' pieces
        arrays = SOURCE_ARRAYS[name]
    since = time.monotonic()
    whole = engine.upload_resident(wire)
    monkeypatch.setattr(engine_module, "_PIECE_ROWS", PIECE)
    pieced = engine.upload_resident(wire)
    assert wire.host_packed == (source == "loaded")
    one, many = (s.attributes for s in h2d_spans(since))
    assert one["pieces"] == arrays and many["pieces"] == 4 * arrays
    assert (many["word_source_bytes"] > 0) == (source == "fresh")
    assert one["wire_bytes"] == many["wire_bytes"] <= many["put_bytes"]
    assert many["put_bytes"] == whole.wire_bytes == pieced.wire_bytes
    lanes = 2 * 4 * whole.b_pad  # starts and lens
    assert many["copied_bytes"] - lanes <= many["put_bytes"] // 4
    assert one["copied_bytes"] - lanes == one["put_bytes"]
    assert_device_buffers_are_the_padded_wire(pieced, wire)
    assert_device_buffers_are_the_padded_wire(whole, wire)
    want, got = engine.replay_resident(whole), engine.replay_resident(pieced)
    assert got.num_events == want.num_events == wire.num_events
    for field, col in want.states.items():
        np.testing.assert_array_equal(got.states[field], col, field)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_streamed_folds_sub_wires_go_up_in_pieces(monkeypatch, name):
    """``replay_resident_streamed`` uploads slices of the wire's buffers: each
    a view, each in pieces, and the states are the plain fold's."""
    model, make_corpus, arrays = MODELS[name]
    engine = make_engine(model)
    wire = engine.pack_resident(make_corpus(400_000))
    want = engine.replay_resident(engine.upload_resident(wire))
    monkeypatch.setattr(engine_module, "_PIECE_ROWS", PIECE)
    since = time.monotonic()
    got = engine.replay_resident_streamed(wire, segments=2)
    uploads = [s.attributes for s in h2d_spans(since)]
    assert len(uploads) == 2
    row_bytes = 1 + 4 * (arrays - 1)
    for up in uploads:  # about 200,000 rows each: four pieces an array
        assert up["pieces"] == 4 * arrays
        assert up["put_bytes"] == 4 * PIECE * row_bytes
    for field, col in want.states.items():
        np.testing.assert_array_equal(got.states[field], col, field)


def test_a_second_length_in_the_bucket_compiles_no_placement(monkeypatch):
    """The placement's compile key is (bucket rows, piece rows, dtype,
    ``nbytes``): wires of other lengths in the same bucket reuse it, as a
    restore over many segment lengths must. The fold's key holds the tile
    width too, which a plan takes from its corpus's log lengths: pinned here
    (a ladder of one width), the fold compiles nothing either."""
    monkeypatch.setattr(engine_module, "_PIECE_ROWS", PIECE)
    engine = make_engine(counter, **{"surge.replay.min-time-window": 64})
    assert engine._tile_widths() == [64]
    first = engine.upload_resident(engine.pack_resident(counter_corpus(150_000)))
    engine.replay_resident(first)
    placements, folds = _place_piece._cache_size(), engine.num_compiles()
    (mk_word,) = engine._word_programs.values()  # one layout, one program
    words = mk_word._cache_size()
    for events, seed in ((180_000, 2), (240_000, 3)):
        corpus = synth_counter_corpus(2000, events, seed=seed)
        resident = engine.upload_resident(engine.pack_resident(corpus.events))
        assert resident.flat_wire.shape == first.flat_wire.shape
        got = engine.replay_resident(resident)
        np.testing.assert_array_equal(got.states["count"],
                                      corpus.expected_count)
    assert _place_piece._cache_size() == placements
    assert list(engine._word_programs.values()) == [mk_word]
    assert mk_word._cache_size() == words == 1
    assert engine.num_compiles() == folds
