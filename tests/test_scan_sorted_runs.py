"""The scan's reduce over sorted runs (``replay/query.py``, ``partials``): a
chunk's events are sorted by group once, ``count`` is the distance between two
runs' starts, an integer ``sum`` the difference of a wrapping prefix sum at a
run's two ends, ``min`` / ``max`` the run's running extreme at its last row.
Every case is held to ``scan_reference`` (numpy's ``add.at`` / ``minimum.at``
/ ``maximum.at``, the same dtypes), exact, at the corners the mechanism has:
empty runs, the sentinel run, wrapping prefixes, garbage past a chunk's
events in a reused buffer."""

import dataclasses
import time
import types

import numpy as np
import pytest

from surge_tpu.codec.schema import FieldSpec, SchemaRegistry
from surge_tpu.codec.tensor import ColumnarEvents
from surge_tpu.config import Config
from surge_tpu.replay import query as query_module
from surge_tpu.replay.query import (Aggregate, Predicate, QueryEngine,
                                    ScanQuery, scan_reference)
from surge_tpu.tracing import default_tracer

BUCKET = 1024  # surge.query.chunk-events: the least event bucket the engine takes
RUNS_FROM_UPDATES = query_module._RUNS_FROM_UPDATES  # as the module has it
I32 = np.iinfo(np.int32)


@dataclasses.dataclass
class Kept:
    pass


@dataclasses.dataclass
class Dropped:
    pass


FIELDS = (FieldSpec("a", np.int32), FieldSpec("b", np.int32),
          FieldSpec("code", np.int32), FieldSpec("narrow", np.int16),
          FieldSpec("tiny", np.int8), FieldSpec("ratio", np.float32),
          FieldSpec("serial", np.uint32), FieldSpec("flag", np.bool_))


def make_spec():
    registry = SchemaRegistry()
    registry.register_event(Kept, type_id=0, fields=FIELDS)
    registry.register_event(Dropped, type_id=1, fields=FIELDS)
    return types.SimpleNamespace(registry=registry)


SPEC = make_spec()


@pytest.fixture(autouse=True)
def runs_at_every_size(monkeypatch):
    """The sorted reduce at the test's sizes: a chunk whose event rows times
    its reduces are under ``_RUNS_FROM_UPDATES`` takes the scatters."""
    monkeypatch.setattr(query_module, "_RUNS_FROM_UPDATES", 1)


def make_engine(mesh=None):
    return QueryEngine(SPEC, config=Config(
        {"surge.query.chunk-events": BUCKET}), mesh=mesh)


def chunk(n, groups, *, agg_idx=None, type_ids=None, seed=0, **cols):
    """``n`` events over ``groups`` aggregates; every column not given is
    drawn from the seed."""
    rng = np.random.default_rng(seed)
    given = {
        "a": rng.integers(-1000, 1000, n), "b": rng.integers(-1000, 1000, n),
        "code": rng.integers(0, 7, n), "narrow": rng.integers(-300, 300, n),
        "tiny": rng.integers(-100, 100, n),
        "ratio": rng.integers(-8, 8, n) / 4,
        # near the top of its range: a least value past int32's
        "serial": rng.integers(2**32 - 3 * n, 2**32, n),
        "flag": rng.integers(0, 2, n)}
    given.update(cols)
    return ColumnarEvents(
        num_aggregates=groups,
        agg_idx=(rng.integers(0, groups, n) if agg_idx is None
                 else np.asarray(agg_idx)).astype(np.int32),
        type_ids=(np.zeros(n) if type_ids is None
                  else np.asarray(type_ids)).astype(np.int32),
        cols={f.name: np.asarray(given[f.name]).astype(f.dtype)
              for f in FIELDS},
        aggregate_ids=[f"g{i}" for i in range(groups)])


EVERY_OP = (Aggregate("count"), Aggregate("sum", "a"), Aggregate("min", "a"),
            Aggregate("max", "b"))


def case_every_event_is_masked_out():
    # every key is the sentinel: one run, past every group
    return [chunk(700, 9, type_ids=np.ones(700))], ScanQuery(
        aggregates=EVERY_OP, event_types=("Kept",))


def case_one_group_holds_every_event():
    return [chunk(900, 1)], ScanQuery(aggregates=EVERY_OP)


def case_groups_the_chunk_does_not_show():
    # the bucket's first and last row among them: empty runs at both ends
    # (16 aggregates fill their row bucket) and in the middle
    n = 500
    idx = np.random.default_rng(3).choice([1, 2, 5, 11, 14], n)
    return [chunk(n, 16, agg_idx=idx)], ScanQuery(aggregates=EVERY_OP)


def case_no_spare_row_and_no_padding_row():
    # b == b_bucket, n == n_bucket
    return [chunk(BUCKET, 64, seed=4)], ScanQuery(
        aggregates=EVERY_OP, predicates=(Predicate("code", "<", 5),))


def case_a_prefix_that_wraps_while_every_group_fits():
    # 1000 events of about 2^30: the running prefix passes 2^31 hundreds of
    # times; a group of four or five events sums to under 2^31 only because
    # half of them are negative
    n = 1000
    a = np.where(np.arange(n) % 2 == 0, 2**30 - 7, -(2**30) + 11)
    return [chunk(n, 200, agg_idx=np.arange(n) // 5, a=a)], ScanQuery(
        aggregates=(Aggregate("count"), Aggregate("sum", "a")))


def case_a_group_sum_that_itself_wraps():
    # equal to the scatter's (and numpy's) wrapped int32 value
    n = 600
    return [chunk(n, 3, agg_idx=np.arange(n) % 3,
                  a=np.full(n, 2**29 + 12345))], ScanQuery(
        aggregates=(Aggregate("sum", "a"), Aggregate("min", "a")))


def case_the_dtypes_own_extremes_under_min_and_max():
    # values equal to the sentinels an empty run carries
    n = 300
    a = np.random.default_rng(5).choice([I32.min, I32.max, 0, -1], n)
    return [chunk(n, 40, a=a, b=a[::-1])], ScanQuery(
        aggregates=(Aggregate("max", "a"), Aggregate("min", "a"),
                    Aggregate("min", "b"), Aggregate("max", "b")))


def case_narrow_columns_summed_in_their_own_dtype():
    # int16 and int8 sums wrap in int16 and int8
    return [chunk(1000, 6, seed=6)], ScanQuery(
        aggregates=(Aggregate("sum", "narrow"), Aggregate("sum", "tiny"),
                    Aggregate("max", "narrow"), Aggregate("min", "tiny")))


def case_two_max_outputs_over_two_columns():
    return [chunk(800, 33, seed=7)], ScanQuery(
        aggregates=(Aggregate("max", "a"), Aggregate("max", "b")),
        or_groups=((Predicate("code", "==", 1), Predicate("a", ">", 0)),))


def case_group_by_an_event_column():
    return [chunk(900, 12, seed=8), chunk(400, 5, seed=9)], ScanQuery(
        aggregates=EVERY_OP, event_types=("Kept",), group_by="code")


def case_per_aggregate_with_agg_idx_unsorted():
    # two chunks that repeat their aggregates: the merge adds the partials,
    # the second chunk's empty runs carry the sentinels into it
    first = chunk(1000, 50, seed=10, type_ids=np.arange(1000) % 2)
    second = chunk(30, 50, seed=11)
    return [first, second], ScanQuery(aggregates=EVERY_OP,
                                      event_types=("Kept",))


def case_a_float_column():
    # a float sum takes the whole query to the scatters, min / max with it
    return [chunk(700, 20, seed=12)], ScanQuery(
        aggregates=(Aggregate("count"), Aggregate("sum", "ratio"),
                    Aggregate("min", "ratio"), Aggregate("max", "ratio"),
                    Aggregate("sum", "a")))


def case_a_float_columns_extremes_over_the_runs():
    return [chunk(700, 20, seed=14)], ScanQuery(
        aggregates=(Aggregate("min", "ratio"), Aggregate("max", "ratio"),
                    Aggregate("sum", "narrow")),
        predicates=(Predicate("ratio", ">", -1.5),))


def case_the_type_id_pseudo_column():
    return [chunk(500, 10, seed=13, type_ids=np.arange(500) % 2)], ScanQuery(
        aggregates=(Aggregate("sum", "type_id"), Aggregate("max", "type_id")))


def case_keyed_by_a_narrow_column_of_negative_values():
    # int16 over -300..299: the least value is negative, the key's offsets
    # taken in uint16; the second chunk's least value is another one
    return [chunk(900, 12, seed=15), chunk(400, 5, seed=16,
                                           narrow=np.arange(400) - 700)
            ], ScanQuery(aggregates=EVERY_OP, group_by="narrow")


def case_keyed_by_an_int8_astride_zero():
    return [chunk(800, 9, seed=17)], ScanQuery(
        aggregates=(Aggregate("sum", "narrow"), Aggregate("max", "tiny"),
                    Aggregate("min", "b")),
        predicates=(Predicate("a", "<", 500),), group_by="tiny")


def case_keyed_by_an_unsigned_column_near_its_top():
    return [chunk(700, 7, seed=18)], ScanQuery(
        aggregates=EVERY_OP, group_by="serial")


def case_keyed_by_a_bool():
    return [chunk(600, 7, seed=19), chunk(50, 3, seed=20,
                                          flag=np.ones(50))], ScanQuery(
        aggregates=(Aggregate("count"), Aggregate("sum", "tiny"),
                    Aggregate("min", "tiny"), Aggregate("max", "a")),
        group_by="flag")


def case_keyed_by_type_id_where_the_filter_rejects_a_type():
    # every event of type 1 is rejected: its group is there, count 0
    return [chunk(500, 10, seed=21, type_ids=np.arange(500) % 2)], ScanQuery(
        aggregates=EVERY_OP, event_types=("Kept",), group_by="type_id")


def case_a_value_only_rejected_events_show():
    # code 3's events all fail the predicate, and code 6 is shown by no
    # event at all: 3 is a group with zeros (sentinels before the
    # normalisation), 6 no group
    n = 900
    rng = np.random.default_rng(22)
    code = rng.integers(0, 6, n)
    a = np.where(code == 3, -rng.integers(0, 900, n),
                 rng.integers(-900, 900, n))
    return [chunk(n, 20, seed=22, code=code, a=a)], ScanQuery(
        aggregates=EVERY_OP, predicates=(Predicate("a", ">", 0),),
        group_by="code")


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]
#: the cases that group by an integer event column of a narrow range: their
#: chunks' runs are keyed by the column itself, on the device
KEYED = [case_group_by_an_event_column,
         case_keyed_by_a_narrow_column_of_negative_values,
         case_keyed_by_an_int8_astride_zero,
         case_keyed_by_an_unsigned_column_near_its_top, case_keyed_by_a_bool,
         case_keyed_by_type_id_where_the_filter_rejects_a_type,
         case_a_value_only_rejected_events_show]


def assert_equal(got, want):
    assert got.aggregate_ids == want.aggregate_ids
    assert set(got.columns) == set(want.columns)
    for name, column in want.columns.items():
        assert got.columns[name].dtype == column.dtype, name
        assert np.array_equal(got.columns[name], column), name
    assert (got.scanned_events, got.matched_events) == (
        want.scanned_events, want.matched_events)


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
def test_the_sorted_reduce_is_the_plain_references(case):
    chunks, query = case()
    assert_equal(make_engine().scan_chunks(chunks, query),
                 scan_reference(chunks, query, SPEC.registry))


@pytest.mark.parametrize("case", [case_groups_the_chunk_does_not_show,
                                  case_the_dtypes_own_extremes_under_min_and_max],
                         ids=lambda c: c.__name__[5:])
def test_an_empty_run_carries_the_sentinel_until_the_merge(case):
    """Before normalisation a group the chunk does not show reads ``count``
    0, ``sum`` 0 and the dtype's own extreme under ``min`` / ``max``: what
    the merge across chunks and ``_normalize_zero_match`` depend on."""
    (only,), query = case()
    _ids, raw = make_engine()._raw_scan(only, query)
    shown = np.bincount(only.agg_idx, minlength=only.num_aggregates) > 0
    assert np.array_equal(raw["count"] > 0, shown)
    for a in query.aggregates:
        if a.op == "count":
            continue
        empty = raw[a.name][~shown]
        want = {"sum": 0, "min": I32.max, "max": I32.min}[a.op]
        assert (empty == want).all(), a.name


def group_spans(since):
    return sorted((s for s in default_tracer().spans(since_mono=since)
                   if s.name == "replay.scan.group"),
                  key=lambda s: s.start_mono)


def assert_raw_equal(got, want):
    (got_ids, got_out), (want_ids, want_out) = got, want
    assert got_ids == want_ids
    assert set(got_out) == set(want_out)
    for name, column in want_out.items():
        assert got_out[name].dtype == column.dtype, name
        assert np.array_equal(got_out[name], column), name


@pytest.mark.parametrize("case", KEYED, ids=lambda c: c.__name__[5:])
def test_the_device_key_gives_the_factorisations_rows(case, monkeypatch):
    """Where the chunk is reduced over sorted runs and its group column is an
    integer's of a narrow range, the program keys the runs by the column
    itself (``how`` = ``device``): every chunk's raw outputs, the sentinels
    of a group no event matched among them, and its keys are the factorising
    path's bit for bit, and normalised they are ``scan_reference``'s."""
    chunks, query = case()
    engine = make_engine()
    since = time.monotonic()
    raws = [engine._raw_scan(c, query) for c in chunks]
    assert [s.attributes["how"] for s in group_spans(since)] == [
        "device"] * len(chunks)
    # no range is narrow enough for a table: the factorising path, np.unique
    monkeypatch.setattr(query_module, "_TABLE_SPAN_PER_EVENT", 0)
    factorising = make_engine()
    since = time.monotonic()
    for c, raw in zip(chunks, raws):
        assert_raw_equal(raw, factorising._raw_scan(c, query))
        want = scan_reference([c], query, SPEC.registry)
        ids, out = raw
        assert ids == want.aggregate_ids
        for name, column in want.columns.items():
            assert np.array_equal(np.where(out["count"] > 0, out[name], 0),
                                  column), name
    assert {s.attributes["how"] for s in group_spans(since)} == {"sort"}


def test_a_group_only_rejected_events_show_carries_the_sentinels():
    (only,), query = case_a_value_only_rejected_events_show()
    ids, raw = make_engine()._raw_scan(only, query)
    assert ids == ["0", "1", "2", "3", "4", "5"]
    assert (raw["count"][3], raw["sum_a"][3], raw["min_a"][3],
            raw["max_b"][3]) == (0, 0, I32.max, I32.min)
    assert (raw["count"][[0, 1, 2, 4, 5]] > 0).all()


@pytest.mark.parametrize("query", [
    ScanQuery(aggregates=EVERY_OP),
    ScanQuery(aggregates=EVERY_OP, group_by="narrow")],
    ids=["per_aggregate", "keyed_on_the_device"])
def test_rows_past_the_events_of_a_reused_buffer_are_garbage(query):
    """A scan's host buffers are never cleared: the second chunk, shorter,
    leaves the first chunk's rows past its own events, and a poisoned buffer
    leaves out-of-range garbage there (under ``group_by`` in the group
    column the program keys by). The sentinel key takes them all."""
    engine = make_engine()
    long, short = chunk(1000, 30, seed=20), chunk(10, 4, seed=21)
    buffers: dict = {}
    engine._collect_scan(engine._dispatch_scan(long, query, buffers))
    for buf in buffers.values():
        # the group index far out of range on both sides, the columns extreme
        buf[:] = np.where(np.arange(buf.shape[0]) % 2, I32.min, I32.max
                          ).astype(buf.dtype)
    ids, raw = engine._collect_scan(
        engine._dispatch_scan(short, query, buffers))
    want = scan_reference([short], query, SPEC.registry)
    assert ids == want.aggregate_ids
    for name, column in want.columns.items():
        assert np.array_equal(
            np.where(raw["count"] > 0, raw[name], 0), column), name


@pytest.mark.parametrize("case", [
    case_groups_the_chunk_does_not_show,
    case_per_aggregate_with_agg_idx_unsorted, case_a_float_column,
    # keyed on the device, a shard at a time: presence adds across as count
    case_keyed_by_a_narrow_column_of_negative_values,
    case_keyed_by_type_id_where_the_filter_rejects_a_type,
    case_a_value_only_rejected_events_show],
    ids=lambda c: c.__name__[5:])
def test_the_mesh_twin_sorts_a_shard_and_reduces_across(case):
    import jax

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
    chunks, query = case()
    assert_equal(make_engine(mesh).scan_chunks(chunks, query),
                 scan_reference(chunks, query, SPEC.registry))


@pytest.mark.parametrize("case,runs_from,how", [
    (case_groups_the_chunk_does_not_show, 1, "runs"),
    (case_narrow_columns_summed_in_their_own_dtype, 1, "runs"),
    (case_a_float_columns_extremes_over_the_runs, 1, "runs"),
    (case_a_float_column, 1, "scatter"),
    # the bucket's rows x the query's four reduces: at the line, one short
    (case_per_aggregate_with_agg_idx_unsorted, 4 * BUCKET, "runs"),
    (case_per_aggregate_with_agg_idx_unsorted, 4 * BUCKET + 1, "scatter")],
    ids=lambda c: getattr(c, "__name__", c))
def test_the_reduce_span_says_how_the_program_reduced(case, runs_from, how,
                                                      monkeypatch):
    """``how`` is ``runs`` where every output is read from the sorted runs,
    ``scatter`` where the program takes the scatters: a float ``sum`` (the
    order of a float's additions is its answer), or an event bucket whose
    rows times the reduces are under ``_RUNS_FROM_UPDATES``; ``updates`` is
    the work the query asked for either way, events x reduces."""
    monkeypatch.setattr(query_module, "_RUNS_FROM_UPDATES", runs_from)
    chunks, query = case()
    since = time.monotonic()
    make_engine().scan_chunks(chunks, query)
    spans = sorted((s for s in default_tracer().spans(since_mono=since)
                    if s.name == "replay.scan.reduce"),
                   key=lambda s: s.start_mono)
    assert [s.attributes for s in spans] == [
        {"bucket": BUCKET, "group_bucket": max(8, 1 << (
            c.num_aggregates - 1).bit_length()), "how": how,
         "updates": query.reduces * c.num_events} for c in chunks]


@pytest.mark.parametrize("rows,reduces,shards,how", [
    (1 << 23, 3, 1, "runs"), (1 << 21, 3, 1, "runs"),  # the cell's chunks
    (1 << 20, 3, 1, "runs"), (1 << 19, 3, 1, "scatter"),
    (1 << 21, 1, 1, "runs"), (1 << 20, 1, 1, "scatter"),
    (1 << 16, 5, 1, "scatter"),  # a view's round, whatever it asks for
    (1 << 21, 3, 4, "scatter"), (1 << 23, 3, 4, "runs")])  # a shard's rows
def test_the_regime_is_chosen_by_a_shards_rows_times_the_reduces(
        monkeypatch, rows, reduces, shards, how):
    """The constant as it stands decides each measured crossing the way the
    chip read it (PERF.md section 6, PR 40); nothing compiles here."""
    import jax

    monkeypatch.setattr(query_module, "_RUNS_FROM_UPDATES", RUNS_FROM_UPDATES)
    mesh = None if shards == 1 else jax.sharding.Mesh(
        np.array(jax.devices()[:shards]), ("data",))
    columns = ("a", "b", "code", "narrow")[:reduces - 1]
    query = ScanQuery(aggregates=(Aggregate("count"),) + tuple(
        Aggregate("max", c) for c in columns))
    _prog, said = make_engine(mesh)._program(
        query, rows, 65536, tuple((c, np.dtype(np.int32)) for c in columns))
    assert said == how


@pytest.mark.parametrize("runs_from,values,aggregates,group,reduce", [
    # the chunk over sorted runs, the range at most four times its events
    (1, [0, 399], EVERY_OP, {"how": "device", "span": 400}, "runs"),
    (1, [-1, 398], EVERY_OP, {"how": "device", "span": 400}, "runs"),
    # one value wider: the factorisation, whose table takes the same rule
    (1, [0, 400], EVERY_OP, {"distinct": 2, "how": "sort"}, "runs"),
    # a float group column
    (1, "ratio", EVERY_OP, {"distinct": 16, "how": "sort"}, "runs"),
    # the scatters (a view's round: rows x reduces under the line; a float
    # sum) keep the host's factorisation, and the programs they had
    (1 << 40, [0, 399], EVERY_OP, {"distinct": 2, "how": "table"},
     "scatter"),
    (1, [0, 399], (Aggregate("sum", "ratio"),),
     {"distinct": 2, "how": "table"}, "scatter")],
    ids=["device", "device_below_zero", "one_past_the_range", "a_float",
         "scatter_regime", "a_float_sum"])
def test_the_group_span_says_how_the_chunk_was_grouped(
        monkeypatch, runs_from, values, aggregates, group, reduce):
    """``replay.scan.group`` says ``device`` where the program keys the runs
    by the column (with the range, ``span``; ``distinct`` is then counted by
    ``replay.scan.reduce``, where the keys are read off), else ``table`` /
    ``sort`` with ``distinct``; the result is the reference's either way."""
    monkeypatch.setattr(query_module, "_RUNS_FROM_UPDATES", runs_from)
    n = 100
    if values == "ratio":
        c, column = chunk(n, 5, seed=23), "ratio"
    else:
        c = chunk(n, 5, seed=23, a=np.resize(values, n))
        column = "a"
    query = ScanQuery(aggregates=aggregates, group_by=column)
    since = time.monotonic()
    got = make_engine().scan_chunks([c], query)
    spans = default_tracer().spans(since_mono=since)
    assert [s.attributes for s in spans if s.name == "replay.scan.group"] == [
        group]
    (reduced,) = [s.attributes for s in spans
                  if s.name == "replay.scan.reduce"]
    assert reduced["how"] == reduce
    assert reduced.get("distinct") == (
        got.num_aggregates if group["how"] == "device" else None)
    assert_equal(got, scan_reference([c], query, SPEC.registry))
