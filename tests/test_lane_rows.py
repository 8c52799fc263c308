"""A tile's lane rows as aligned rows (``engine._make_lane_fetch``, ``rows``):
the chip's lowering, forced here on the CPU backend, against the one
``dynamic_slice`` a lane it replaced, kept below as the oracle. Element for
element at the helper, byte for byte in the dense buffers, state for state
through every layout and tile backend."""

from dataclasses import make_dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from surge_tpu.codec.schema import FieldSpec, SchemaRegistry
from surge_tpu.codec.tensor import encode_events_columnar
from surge_tpu.codec.wire import WireFormat
from surge_tpu.config import default_config
from surge_tpu.models import counter, shopping_cart
from surge_tpu.replay import engine as engine_module
from surge_tpu.replay.engine import (_LANE_ROW, _NOOP_TILE_T, ReplayEngine,
                                     _make_densify, _make_lane_fetch,
                                     _rows_per_lane)

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


def oracle_densify(wire, width, bs):
    """The parent's ``_make_densify``, verbatim."""
    nbytes = wire.nbytes

    def densify(flat_wire, side_flat, starts_all, i0s, t_bases):
        def one(args):
            i0, tb = args
            starts = jax.lax.dynamic_slice(starts_all, (i0,), (bs,))
            rows = jax.vmap(lambda s0: jax.lax.dynamic_slice(
                flat_wire, (s0, 0), (width, nbytes)))(starts + tb)
            sides = {n: jax.vmap(lambda s0: jax.lax.dynamic_slice(
                arr, (s0,), (width,)))(starts + tb).T
                for n, arr in side_flat.items()}
            return jnp.transpose(rows, (1, 0, 2)), sides

        return jax.lax.map(one, (i0s, t_bases))

    return densify


def lane_starts(width):
    """Named vectors of window starts ``start + t_base``, one a lane."""
    noop = int(_NOOP_TILE_T)
    return {
        "offset_0": [0, A, 7 * A, (ROWS - 8) * A],
        "offset_1": [1, A + 1, 7 * A + 1, 33],
        "offset_last": [A - 1, 2 * A - 1, 9 * A - 1, 20 * A - 1],
        "crosses_rows": [A - 3, 3 * A - width // 2, 5 * A + 77, 2 * A - 1],
        "ends_on_last_row": [N - width, N - width - 1, N - width - A, 0],
        "ends_in_guard_rows": [N - width - 5, N - 2 * width, N - width - A + 1,
                               N - width - 63],
        "padding_lane": [0, 0, width, 3 * width],  # start 0, any t_base
        "noop_worklist_entry": [noop, noop + 100, noop + N - 1, noop + 4 * A],
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


@pytest.mark.parametrize("word_bits", [5, 20], ids=["word1", "word3"])
def test_densify_builds_the_oracles_buffers(word_bits):
    """Work lists with ``_NOOP_TILE_T`` padding entries and a padding lane."""
    wire = make_wire(word_bits)
    width, bs = 64, 8
    flat_wire, side_flat = make_buffers(wire, N)
    starts = np.zeros(32, dtype=np.int32)
    starts[:27] = np.sort(np.random.default_rng(1).integers(
        0, N - 4 * width, size=27))
    i0s = jnp.asarray([0, 8, 16, 24, 0, 8, 0, 0], dtype=jnp.int32)
    t_bases = np.full(8, _NOOP_TILE_T, dtype=np.int32)
    t_bases[:6] = [0, 0, 0, 0, width, width]
    args = (flat_wire, side_flat, jnp.asarray(starts), i0s,
            jnp.asarray(t_bases))
    want_w, want_s = jax.jit(oracle_densify(wire, width, bs))(*args)
    got_w, got_s = jax.jit(_make_densify(wire, width, bs, "rows"))(*args)
    assert got_w.dtype == jnp.uint8
    assert got_w.shape == (8, width, bs, wire.nbytes)
    np.testing.assert_array_equal(np.asarray(got_w), np.asarray(want_w))
    for name, want in want_s.items():
        assert got_s[name].dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got_s[name]),
                                      np.asarray(want), name)


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


MODELS = {"counter": (counter, counter_logs), "cart": (shopping_cart, cart_logs)}


def make_engine(model, layout, tile, **overrides):
    cfg = default_config().with_overrides({
        "surge.replay.batch-size": 64, "surge.replay.time-chunk": 32,
        "surge.replay.resident-layout": layout,
        "surge.replay.tile-backend": tile, **overrides})
    return ReplayEngine(model.make_replay_spec(), config=cfg)


@pytest.mark.parametrize("tile", ["xla", "assoc"])
@pytest.mark.parametrize("layout", ["flat", "dense"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_rebuild_on_rows_matches_replay_ragged(rows_on_cpu, name, layout, tile):
    model, make_logs = MODELS[name]
    logs = make_logs()
    engine = make_engine(model, layout, tile)
    assert engine.lane_gather == "rows"
    want = engine.replay_ragged(logs)
    wire = engine.pack_resident(
        encode_events_columnar(model.make_registry(), logs))
    assert (wire.perm is not None) == (name == "cart")
    resident = engine.upload_resident(wire)
    got = engine.replay_resident(resident)
    assert got.num_events == sum(len(log) for log in logs)
    for field, col in want.states.items():
        assert got.states[field].dtype == col.dtype, field
        np.testing.assert_array_equal(got.states[field], col, field)
    # a second fold of the same corpus (dense: from the cached tiles)
    again = engine.replay_resident(resident)
    for field, col in want.states.items():
        np.testing.assert_array_equal(again.states[field], col, field)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_dense_buffers_on_rows_are_the_slice_buffers(monkeypatch, name):
    """``_dense_tiles``' ``dw`` and ``ds`` of one corpus, fetched both ways."""
    model, make_logs = MODELS[name]
    events = encode_events_columnar(model.make_registry(), make_logs())
    built = {}
    for gather in ("slices", "rows"):
        monkeypatch.setattr(engine_module, "_lane_gather", lambda g=gather: g)
        engine = make_engine(model, "dense", "xla")
        resident = engine.upload_resident(engine.pack_resident(events))
        engine.replay_resident(resident)
        built[gather] = {k: v for k, v in resident.cache.items()
                         if k[0] == "dense"}
        assert built[gather]
    assert sorted(built["rows"]) == sorted(built["slices"])
    for key, (dw, ds, _, _) in built["rows"].items():
        want_dw, want_ds, _, _ = built["slices"][key]
        assert dw.dtype == jnp.uint8 and dw.shape == want_dw.shape
        np.testing.assert_array_equal(np.asarray(dw), np.asarray(want_dw))
        assert sorted(ds) == sorted(want_ds)
        for col in ds:
            assert ds[col].dtype == want_ds[col].dtype
            np.testing.assert_array_equal(np.asarray(ds[col]),
                                          np.asarray(want_ds[col]), col)


@pytest.mark.parametrize("layout", ["flat", "dense"])
def test_exact_bucket_rounds_the_device_buffers_up(rows_on_cpu, layout):
    """``resident-len-bucket = exact`` with a length ``A`` does not divide:
    the host puts the wire as it is and the device pads it to whole rows."""
    logs = cart_logs(n_agg=90, seed=2)
    engine = make_engine(shopping_cart, layout, "assoc", **{
        "surge.replay.resident-len-bucket": "exact"})
    wire = engine.pack_resident(
        encode_events_columnar(shopping_cart.make_registry(), logs))
    n = wire.packed.shape[0]
    assert n % A, "pick a corpus the row does not divide"
    resident = engine.upload_resident(wire)
    assert resident.wire_bytes == wire.packed.nbytes + sum(
        v.nbytes for v in wire.side.values())  # nothing more crossed the link
    want_rows = -(-n // A) * A
    assert resident.flat_wire.shape == (want_rows, wire.packed.shape[1])
    assert all(v.shape == (want_rows,) for v in resident.flat_side.values())
    assert not np.asarray(resident.flat_wire[n:]).any()
    got = engine.replay_resident(resident)
    want = engine.replay_ragged(logs)
    for field, col in want.states.items():
        np.testing.assert_array_equal(got.states[field], col, field)


def test_a_cpu_host_keeps_the_slices():
    """No accelerator here: the backend's own choice is the old slice, and the
    buffers stay as the host put them."""
    engine = make_engine(counter, "flat", "xla", **{
        "surge.replay.resident-len-bucket": "exact"})
    assert engine.lane_gather == "slices"
    wire = engine.pack_resident(
        encode_events_columnar(counter.make_registry(), counter_logs(7, 9)))
    resident = engine.upload_resident(wire)
    assert resident.flat_wire.shape[0] == wire.packed.shape[0]
    assert (engine.replay_resident(resident).states["version"] == 9).all()
