"""The sharded resident rebuild (``replay/resident_mesh.py``): the packed wire
dealt over a ``data`` mesh as contiguous slices of its own buffers, each shard
up through the one-chip upload's pieces, one ``shard_map`` fold, the one-chip
pull. Held to the scalar fold (``fold_events``; for the mixed corpus the
reference handlers of ``benchmarks/reference_mixed.py``) on the CPU backend's
forced host devices; the piece is 2^16 rows where a test wants several."""

import time

import jax
import numpy as np
import pytest

from benchmarks import gen_mixed, reference_mixed
from surge_tpu.codec.tensor import ColumnarEvents, encode_events_columnar
from surge_tpu.config import default_config
from surge_tpu.engine.model import fold_events
from surge_tpu.models import counter
from surge_tpu.replay import engine as engine_module
from surge_tpu.replay.engine import ReplayEngine, ResidentWire
from surge_tpu.replay.resident_mesh import _deal
from surge_tpu.replay.resident_state import ResidentStatePlane
from surge_tpu.serialization import SerializedMessage
from surge_tpu.tracing import default_tracer
from tests.test_lane_rows import (MODELS, assert_columns_are, cart_logs,
                                  counter_topic)
from tests.test_mixed_rebuild import (LAW, hold_to_reference, make_mixed,
                                      parts_of)
from tests.test_side_columns import assert_states, cart_events, scalar_states
from tests.test_upload_pieces import PIECE


def mesh_of(n):
    devices = jax.devices()
    assert len(devices) >= n, "tier-1 forces 8 host devices (root conftest)"
    return jax.sharding.Mesh(np.array(devices[:n]), ("data",))


def make_engine(spec, devices, batch=64, chunk=16):
    cfg = default_config().with_overrides({
        "surge.replay.batch-size": batch, "surge.replay.time-chunk": chunk})
    return ReplayEngine(spec, config=cfg, mesh=mesh_of(devices))


def spans_since(since, name):
    return [s for s in default_tracer().spans(since_mono=since)
            if s.name == name]


def bits(col):
    col = np.asarray(col)
    return col.view(np.uint32) if col.dtype == np.float32 else col


def corpus_of(name, seed=3):
    """``(spec, grouped ColumnarEvents, want {field: [B]} by the scalar
    fold, hold)``: ``hold(res)`` for what a whole-corpus answer is held to
    besides."""
    if name == "mixed":
        mixed = make_mixed()
        corpus = gen_mixed.mixed_corpus(900, 36_000, seed, LAW)
        events = mixed.merge_columnar(parts_of(corpus), corpus.family)
        return (mixed.spec, events, reference_mixed.closed_form(corpus),
                lambda res: hold_to_reference(corpus, res))
    module, make_logs, model = MODELS[name]
    logs = (make_logs(n_agg=150, n_per=24, seed=seed) if name == "counter"
            else cart_logs(n_agg=230, seed=seed))
    if name == "few":  # fewer lanes than devices
        logs = logs[1:4]
    states = [fold_events(model(), None, log) for log in logs]
    fields = module.make_registry().state.field_names
    want = {f: np.asarray([getattr(st, f) if st is not None else 0
                           for st in states]) for f in fields}
    events = encode_events_columnar(module.make_registry(), logs)
    return module.make_replay_spec(), events, want, lambda res: None


MODELS = dict(MODELS, few=MODELS["cart"])


def interleaved(events):
    """The same logs, event ``k`` of every aggregate before event ``k + 1`` of
    any: ungrouped input, which ``pack_resident`` re-sorts into a contiguous,
    length-sorted wire."""
    starts = np.searchsorted(events.agg_idx, np.arange(events.num_aggregates))
    at = np.arange(events.num_events) - starts[events.agg_idx]
    order = np.lexsort((events.agg_idx, at))
    return ColumnarEvents(
        num_aggregates=events.num_aggregates, agg_idx=events.agg_idx[order],
        type_ids=events.type_ids[order],
        cols={k: v[order] for k, v in events.cols.items()},
        derived_cols=dict(events.derived_cols))


def every_other_lane(w):
    """A hand-built wire whose slabs do not tile its buffer: every other lane
    of ``w``, the rest's rows left lying between them. Its lanes are the
    aggregates ``kept`` of ``w``'s corpus, in that order."""
    keep = np.arange(0, w.lengths.shape[0], 2)
    kept = keep if w.perm is None else w.perm[keep]
    return ResidentWire(
        derived_key=dict(w.derived_key), packed=w.packed, side=w.side,
        starts=w.starts[keep], lengths=w.lengths[keep], perm=None,
        guard=w.guard, num_events=int(w.lengths[keep].sum()),
        layout=w.layout), kept


CASES = [  # corpus, wire, devices, gather
    ("cart", "grouped", 1, "slices"), ("cart", "grouped", 2, "slices"),
    ("cart", "grouped", 4, "slices"), ("cart", "grouped", 8, "slices"),
    ("cart", "grouped", 4, "rows"), ("cart", "contiguous", 2, "slices"),
    ("cart", "contiguous", 4, "rows"), ("cart", "untiled", 4, "slices"),
    ("cart", "untiled", 8, "rows"), ("few", "grouped", 8, "slices"),
    ("few", "contiguous", 4, "slices"), ("counter", "grouped", 4, "slices"),
    ("counter", "grouped", 8, "rows"), ("counter", "contiguous", 2, "slices"),
    ("counter", "untiled", 4, "slices"), ("mixed", "grouped", 4, "slices"),
    ("mixed", "grouped", 4, "rows"), ("mixed", "contiguous", 4, "slices"),
    ("mixed", "untiled", 4, "slices"), ("mixed", "grouped", 8, "slices")]


@pytest.mark.parametrize("name, wire_kind, devices, gather", CASES,
                         ids=["-".join(map(str, c)) for c in CASES])
def test_the_sharded_rebuild_equals_the_scalar_fold(monkeypatch, name,
                                                    wire_kind, devices, gather):
    monkeypatch.setattr(engine_module, "_lane_gather", lambda: gather)
    spec, events, want, hold = corpus_of(name)
    engine = make_engine(spec, devices)
    given = interleaved(events) if wire_kind == "contiguous" else events
    wire = engine.pack_resident(given)
    kept = np.arange(events.num_aggregates)
    if wire_kind == "untiled":
        wire, kept = every_other_lane(wire)
    since = time.monotonic()
    sharded = engine.prepare_resident_sharded(wire)
    res = engine.replay_resident_sharded(sharded)
    assert res.num_aggregates == len(kept)
    assert res.num_events == wire.num_events
    for field, col in want.items():
        got = np.asarray(res.states[field])
        assert np.array_equal(bits(got), bits(col[kept].astype(got.dtype))), field
    if wire_kind != "untiled":
        hold(res)  # the scalar sample, foreign columns, dtypes (mixed)
    # what the spans say of the deal: it copied events of the untiled wire
    # alone, and every lane and event went to one device
    (deal,) = spans_since(since, "replay.shard")
    (fold,) = spans_since(since, "replay.resident")
    a = deal.attributes
    assert a["devices"] == fold.attributes["devices"] == devices
    assert (a["copied_bytes"] > 0) == (wire_kind == "untiled")
    if wire_kind == "untiled":
        assert a["copied_bytes"] == wire.num_events * (
            wire.packed.shape[1] + sum(v.dtype.itemsize
                                       for v in wire.side.values()))
    assert sum(len(lanes) for lanes in sharded.deals) == len(kept)
    assert a["lanes_min"] == min(len(lanes) for lanes in sharded.deals)
    assert a["events_min"] <= wire.num_events // devices <= a["events_max"]
    longest = int(wire.lengths.max(initial=0))
    assert a["events_max"] - a["events_min"] <= 2 * longest
    assert fold.attributes["gather"] == gather
    assert fold.attributes["padded_slots"] == res.padded_events
    # the contract of fold_resident_sharded's callers: row [d, j] of the slab
    # holds sorted-rank lane deals[d][j]
    for lanes in sharded.deals:
        assert (np.diff(lanes) > 0).all()
        assert (np.diff(wire.lengths[lanes]) <= 0).all() or wire.perm is None


@pytest.mark.parametrize("wire_kind", ["grouped", "contiguous"])
@pytest.mark.parametrize("name", ["cart", "counter"])
def test_a_resumed_sharded_fold_equals_the_whole(name, wire_kind):
    """``init_carry`` + ``ordinal_base`` across two sharded folds: every
    log's later half folded onto the states and ordinals its first half left,
    each device taking its own lanes' share of both."""
    module, make_logs, model = MODELS[name]
    logs = make_logs(seed=8)
    engine = make_engine(module.make_replay_spec(), 4)
    shape = interleaved if wire_kind == "contiguous" else (lambda ev: ev)
    halves = [[log[:len(log) // 2] for log in logs],
              [log[len(log) // 2:] for log in logs]]
    first, second = (engine.prepare_resident_sharded(shape(
        encode_events_columnar(module.make_registry(), half)))
        for half in halves)
    head = engine.replay_resident_sharded(first)
    got = engine.replay_resident_sharded(
        second, init_carry=head.states,
        ordinal_base=np.asarray([len(h) for h in halves[0]], np.int32))
    assert_columns_are(got.states,
                       [fold_events(model(), None, log) for log in logs])


@pytest.mark.parametrize("gather", ["slices", "rows"])
@pytest.mark.parametrize("devices", [2, 4])
def test_every_shard_folds_at_the_one_width_of_the_whole_corpus(monkeypatch,
                                                                devices, gather):
    """One ``shard_map`` program folds every device's tiles, so the width is
    chosen once, from the whole corpus's lengths before the deal. A
    length-sorted contiguous wire deals the few long logs to the first device
    and short ones to the rest: left to itself each device would choose its
    own."""
    monkeypatch.setattr(engine_module, "_lane_gather", lambda: gather)
    rng = np.random.default_rng(devices)
    lengths = np.concatenate([np.full(40, 160), rng.integers(1, 7, size=1200)])
    logs = [[counter.CountIncremented(f"c{a}", 1, k + 1) for k in range(n)]
            for a, n in enumerate(rng.permutation(lengths).tolist())]
    engine = make_engine(counter.make_replay_spec(), devices, chunk=64)
    wire = engine.pack_resident(interleaved(
        encode_events_columnar(counter.make_registry(), logs)))
    since = time.monotonic()
    sharded = engine.prepare_resident_sharded(wire)
    res = engine.replay_resident_sharded(sharded)
    assert_columns_are(res.states, [fold_events(counter.CounterModel(), None,
                                                log) for log in logs])
    whole = engine._chosen_width(wire.lengths)
    assert sharded.width == whole in engine._tile_widths()
    assert [p.width for p in sharded.plans] == [whole] * devices
    own = [engine._chosen_width(wire.lengths[lanes])
           for lanes in sharded.deals]
    assert len(set(own)) > 1, own
    (fold,) = spans_since(since, "replay.resident")
    a = fold.attributes
    assert (a["width"], a["width_cap"]) == (whole, 64)
    assert a["scan_steps"] == max(p.tiles for p in sharded.plans) * whole
    assert a["padded_slots"] == sum(p.padded_slots for p in sharded.plans)
    # a second corpus of other lengths under the same engine: its own width
    other = engine.prepare_resident_sharded(engine.pack_resident(
        encode_events_columnar(counter.make_registry(),
                               [log[:16] for log in logs if len(log) > 64])))
    assert other.width == 16 != whole
    assert (engine.replay_resident_sharded(other).states["count"] == 16).all()


@pytest.mark.parametrize("words_from", ["device", "host"])
@pytest.mark.parametrize("devices", [2, 4])
def test_a_tiling_wire_goes_up_as_it_lies(monkeypatch, devices, words_from):
    """Several pieces a shard: every shard is a slice of the wire's own arrays
    (a side column still the caller's), widened to whole pieces, the last
    backwards, so the host copies no event and pads no piece. The word's
    rows are the packed buffer's where the host has built one (``host``:
    someone read ``packed`` before the deal), else the caller's type ids,
    the cart's whole word, which each device builds it from."""
    monkeypatch.setattr(engine_module, "_PIECE_ROWS", PIECE)
    n = 75_000 * devices
    events = cart_events(n, seed=devices, carts=500)
    engine = make_engine(MODELS["cart"][0].make_replay_spec(), devices,
                         batch=256, chunk=64)
    wire = engine.pack_resident(events)
    on_device = words_from == "device"
    lies_in, row_bytes = ((events.type_ids, 4) if on_device
                          else (wire.packed, 1))
    deals, shards, rows, copied = _deal(wire, devices)
    assert copied == 0 and rows == 2 * PIECE + wire.guard
    assert wire.host_packed != on_device
    for (word,), side, starts in shards:
        assert word.shape[0] == 2 * PIECE
        assert np.shares_memory(word, lies_in)
        for k, col in side.items():
            assert col.shape == (2 * PIECE,)
            assert np.shares_memory(col, events.cols[k]), k
    assert np.shares_memory(shards[-1][0][0], lies_in[n - 1:])  # backwards
    since = time.monotonic()
    res = engine.replay_resident_sharded(
        engine.prepare_resident_sharded(wire))
    assert_states(res, scalar_states(events))
    (deal,) = spans_since(since, "replay.shard")
    (h2d,) = spans_since(since, "replay.h2d")
    assert deal.attributes["copied_bytes"] == 0
    b_pad = h2d.attributes["copied_bytes"] // (2 * 4 * devices)
    assert h2d.attributes["copied_bytes"] == 2 * 4 * devices * b_pad  # lanes
    assert h2d.attributes["pieces"] == devices * 4 * 2
    assert h2d.attributes["put_bytes"] == devices * 2 * PIECE * (
        row_bytes + 3 * 4)
    assert h2d.attributes["word_source_bytes"] == (
        devices * 2 * PIECE * 4 * on_device)
    assert wire.host_packed != on_device  # the upload read no ``packed``
    assert h2d.attributes["wire_bytes"] == wire.packed.nbytes + 3 * 4 * n


@pytest.mark.parametrize("gather", ["slices", "rows"])
@pytest.mark.parametrize("caller", ["seed_from_log", "shadow_replay_rows"])
def test_the_planes_cold_folds_under_a_mesh_match_the_scalar_fold(
        monkeypatch, caller, gather):
    """The resident plane's seed and the auditor's shadow replay under a
    mesh: ``fold_resident_sharded`` and ``sharded.deals``, rows left on the
    devices, give the rows they gave."""
    monkeypatch.setattr(engine_module, "_lane_gather", lambda: gather)
    log, logs, _ = counter_topic()
    fmt, sfmt = counter.event_formatting(), counter.state_formatting()
    plane = ResidentStatePlane(
        log, "counter-events", counter.make_replay_spec(), mesh=mesh_of(4),
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
    (fold,) = spans_since(since, "replay.resident")
    assert fold.attributes["devices"] == 4
    assert fold.attributes["gather"] == gather
    assert spans_since(since, "replay.shard")[0].attributes["copied_bytes"] == 0
