"""The cart's read-side projection rebuild (``QueryEngine.scan_segment`` with
``group_by`` an event column), held to the benchmark's plain reference: every
output of every group over a multi-chunk segment whose chunks repeat every
group; the table factorisation is ``np.unique``'s, value for value and index
for index; one scan is one trace with the whole ``replay.scan`` tree; the
scan's jitted programs carry the pinned names; the cell's driver at its
rehearse size ends correct and its control does not."""

import ast
import glob
import json
import os
import time

import numpy as np
import pytest

from benchmarks import control as control_module
from benchmarks import gen_cart, reference_cart_projection
from benchmarks import run as harness
from benchmarks.drivers import cart_projection as driver
from benchmarks.drivers import cart_restore
from surge_tpu.codec.tensor import ColumnarEvents
from surge_tpu.config import default_config
from surge_tpu.engine.business_logic import SurgeCommandBusinessLogic
from surge_tpu.log.columnar import read_segment
from surge_tpu.models import bank_account, shopping_cart
from surge_tpu.replay import query as query_module
from surge_tpu.replay.query import (SCAN_JIT_NAMES, Aggregate, QueryEngine,
                                    ScanQuery, _factorize_group,
                                    scan_reference)
from surge_tpu.tracing import default_tracer
from tests.test_cart_rebuild import LAW

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "rebuild-cart-item-rollup"
CARTS, EVENTS, CHUNK = 300, 9_000, 128  # chunks of 128, 128 and 44 carts
CHUNKS = 3
CODES = 97  # so few codes that every chunk repeats every group
PER_CHUNK = ["replay.scan.read", "replay.scan.group", "replay.scan.h2d",
             "replay.scan.dispatch"]
QUERY = ScanQuery(aggregates=(Aggregate("count"), Aggregate("sum", "quantity"),
                              Aggregate("max", "unit_price_cents")),
                  event_types=("ItemAdded",), group_by="item_code")


def make_engine():
    """The engine as ``engine/pipeline.py:query_engine`` builds it for the
    cart's business logic, with no mesh."""
    logic = SurgeCommandBusinessLogic(
        aggregate_name="cart", model=shopping_cart.CartModel(),
        state_format=shopping_cart.state_formatting(),
        event_format=shopping_cart.event_formatting())
    return QueryEngine(logic.replay_spec(), config=default_config(), mesh=None)


def make_segment(tmp_path, seed, codes=CODES):
    corpus = gen_cart.cart_corpus(CARTS, EVENTS, seed,
                                  dict(LAW, item_codes=codes))
    path = str(tmp_path / "cart.scol")
    info = cart_restore.write_segment(
        path, corpus, [f"cart-{i:07d}" for i in range(CARTS)], CHUNK)
    assert info["num_chunks"] == CHUNKS and info["num_events"] == EVENTS
    return corpus, path


def named(spans, name):
    return sorted((s for s in spans if s.name == name),
                  key=lambda s: s.start_mono)


# --- the scan is the plain reference's rollup, and scan_reference's -----------------

@pytest.mark.parametrize("seed", [5, 2**31 + 37, 3_000_000_019])
def test_the_scan_of_a_segment_is_the_plain_references_rollup(tmp_path, seed):
    corpus, path = make_segment(tmp_path, seed)
    engine = make_engine()
    result = engine.scan_segment(path, QUERY)
    want = reference_cart_projection.expected_rows(corpus, CODES)
    assert len(want) == CODES  # every code is some event's
    assert driver.rows_of(result) == want
    assert result.aggregate_ids == [str(c) for c in range(CODES)]
    assert result.scanned_events == EVENTS
    assert result.matched_events == reference_cart_projection.matched_events(
        corpus)
    assert (result.chunks, result.num_aggregates) == (CHUNKS, CODES)
    # every chunk repeated every group: the merge had two rows a key to add
    (merge,) = named(default_tracer().spans(), "replay.scan.merge")[-1:]
    assert merge.attributes == {"groups": CODES, "repeated": 2 * CODES}
    # and the numpy mirror of the device program agrees, column for column
    mirror = scan_reference(read_segment(path), QUERY, engine.registry)
    assert mirror.aggregate_ids == result.aggregate_ids
    assert set(mirror.columns) == set(result.columns) == {
        "count", "sum_quantity", "max_unit_price_cents"}
    for name, column in mirror.columns.items():
        assert column.dtype == result.columns[name].dtype == np.int32
        assert column.tolist() == result.columns[name].tolist()
    # the scalar loop over a sample of codes, the benchmark's third form
    sample = driver.sample_codes(CODES, 20, seed)
    for key, row in reference_cart_projection.scalar_rows(corpus,
                                                          sample).items():
        assert want[key] == row


def test_a_group_no_added_event_carries_reports_zero(tmp_path):
    """A group forms over every event's code; one whose events are all
    removals is there, with 0 everywhere (``scan_reference``'s rule)."""
    corpus, path = make_segment(tmp_path, 11, codes=4001)
    want = reference_cart_projection.expected_rows(corpus, 4001)
    silent = [key for key, row in want.items() if row == (0, 0, 0)]
    assert silent and len(want) < 4001  # some codes removed only, some absent
    rows = driver.rows_of(make_engine().scan_segment(path, QUERY))
    assert rows == want
    compared = driver.judge(corpus, 4001, [(rows, EVENTS,
                            reference_cart_projection.matched_events(corpus))],
                            200, 11)
    assert [(name, value) for name, value, _limit in compared] == [
        ("rows_wrong", 0), ("groups_missing", 0), ("groups_extra", 0),
        ("events_unaccounted", 0), ("scalar_sample_wrong", 0)]


# --- the factorisation: np.unique's result, by a table where the column allows ------

def seeded(kind, seed):
    rng = np.random.default_rng(seed)
    n = 5000
    return {
        "dense": lambda: rng.integers(0, 97, n, dtype=np.int32),
        "sparse": lambda: rng.choice(
            rng.integers(0, 4 * n, 40), n).astype(np.int32),
        "negative": lambda: rng.integers(-700, -200, n, dtype=np.int32),
        "astride_zero": lambda: rng.integers(-128, 128, n).astype(np.int8),
        "int32_floor": lambda: (np.iinfo(np.int32).min + rng.integers(
            0, 50, n)).astype(np.int32),
        "unsigned": lambda: rng.integers(60_000, 65_536, n).astype(np.uint16),
        "bools": lambda: rng.integers(0, 2, n).astype(bool),
        "one_value": lambda: np.full(n, 7, dtype=np.int32),
        "too_wide": lambda: rng.integers(0, 2**30, n, dtype=np.int32),
        "floats": lambda: rng.integers(0, 50, n).astype(np.float32) / 4,
        "empty": lambda: np.zeros(0, dtype=np.int32),
    }[kind]()


SORTED = ("too_wide", "floats", "empty")


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
@pytest.mark.parametrize("kind", [
    "dense", "sparse", "negative", "astride_zero", "int32_floor", "unsigned",
    "bools", "one_value", "too_wide", "floats", "empty"])
def test_the_factorisation_is_np_uniques(kind, seed):
    col = seeded(kind, seed)
    keys, index, how = _factorize_group(col)
    values, inverse = np.unique(col, return_inverse=True)
    assert how == ("sort" if kind in SORTED else "table")
    assert index.dtype == np.int32 and index.shape == col.shape
    assert index.tolist() == inverse.reshape(-1).tolist()
    if col.dtype.kind == "f":
        assert keys == [repr(float(v)) for v in values]
    else:
        assert keys == [str(int(v)) for v in values]
    # a key and an index give the event's own value back
    if col.size:
        assert [keys[i] for i in index[:50]] == [
            keys[list(values).index(v)] for v in col[:50]]


def test_the_table_is_taken_by_the_columns_range_not_by_a_key():
    """The rule reads the column: the same values take the table while their
    range is at most four times their count, the sort beyond it."""
    few = np.array([0, 39], dtype=np.int32)
    assert _factorize_group(np.tile(few, 5))[2] == "table"  # span 40 <= 4 x 10
    assert _factorize_group(np.tile(few, 4))[2] == "sort"  # span 40 > 4 x 8
    assert query_module._TABLE_SPAN_PER_EVENT == 4


# --- one scan is one trace ------------------------------------------------------------

def test_one_scan_is_one_trace_with_the_whole_tree(tmp_path, monkeypatch):
    # the cell's reduce (its chunks are past ``_RUNS_FROM_UPDATES``), at this
    # size: a bucket of 65,536 rows x three reduces
    monkeypatch.setattr(query_module, "_RUNS_FROM_UPDATES", 3 * 65536)
    corpus, path = make_segment(tmp_path, 2**31 + 37)
    engine = make_engine()
    engine.scan_segment(path, QUERY)  # compiles
    since = time.monotonic()
    result = engine.scan_segment(path, QUERY)
    spans = default_tracer().spans(since_mono=since)
    assert {s.context.trace_id for s in spans} == {spans[0].context.trace_id}
    (root,) = named(spans, "replay.scan")
    assert root.parent_id is None
    children = sorted((s for s in spans
                       if s.parent_id == root.context.span_id),
                      key=lambda s: s.start_mono)
    assert len(children) == len(spans) - 1  # a flat tree under the root
    # a chunk's outputs are awaited once the next chunk's program is under
    # way (the last one's once the reader has found the end)
    assert [s.name for s in children] == PER_CHUNK + (
        PER_CHUNK + ["replay.scan.reduce"]) * (CHUNKS - 1) + [
        "replay.scan.read", "replay.scan.reduce", "replay.scan.merge"]
    for earlier, later in zip(children, children[1:]):
        assert root.start_mono <= earlier.start_mono
        assert earlier.end_mono <= later.start_mono <= root.end_mono
    assert all(s.status == "ok" for s in spans)
    # the counts the corpus implies, as exact dictionaries
    matched = reference_cart_projection.matched_events(corpus)
    assert root.attributes == {
        "group_by": "item_code", "columns": 3, "chunks": CHUNKS,
        "events": EVENTS, "matched": matched, "groups": CODES}
    assert result.matched_events == matched
    events = [int(corpus.lengths[i * CHUNK:(i + 1) * CHUNK].sum())
              for i in range(CHUNKS)]
    ids = [len("\n".join(f"cart-{i:07d}" for i in range(
        c * CHUNK, min((c + 1) * CHUNK, CARTS)))) for c in range(CHUNKS)]
    reads = named(spans, "replay.scan.read")
    assert reads[-1].attributes == {}  # no chunk in the last step
    for read, n, id_bytes in zip(reads, events, ids):
        # five int32 payloads (agg_idx and type_ids among them) and the ids:
        # the cart stores no column this projection does not read
        assert set(read.attributes) == {"stored_bytes", "raw_bytes",
                                        "columns_read", "columns_skipped"}
        assert read.attributes["raw_bytes"] == 20 * n + id_bytes
        assert 0 < read.attributes["stored_bytes"] <= 20 * n + id_bytes
        assert (read.attributes["columns_read"],
                read.attributes["columns_skipped"]) == (5, 0)
    # the runs are keyed by ``item_code`` itself on the device: the host
    # reads its least value and range (0 to 96), and no group index
    assert [s.attributes for s in named(spans, "replay.scan.group")] == [
        {"how": "device", "span": CODES}] * CHUNKS
    bucket = 65536  # surge.query.chunk-events, the least event bucket
    # four int32 buffers of a bucket go up (type ids and the three columns),
    # the chunk's own rows copied into them; beside them the event count,
    # the one allowed type id and the key's least value
    assert [s.attributes for s in named(spans, "replay.scan.h2d")] == [
        {"padded_events": bucket, "copied_bytes": 16 * n,
         "put_bytes": 16 * bucket + 12} for n in events]
    # every output read from the chunk's sorted runs; ``updates`` is still
    # the work the query asked for: events x its three reduces; the groups
    # are counted where the keys are read off the program's outputs
    assert [s.attributes for s in named(spans, "replay.scan.reduce")] == [
        {"bucket": bucket, "group_bucket": 128, "how": "runs",
         "updates": 3 * n, "distinct": CODES} for n in events]
    assert [s.attributes for s in named(spans, "replay.scan.dispatch")] == [
        {}] * CHUNKS
    (merge,) = named(spans, "replay.scan.merge")
    assert merge.attributes == {"groups": CODES, "repeated": 2 * CODES}
    # every stage says what it cost the host; the root alone the process's
    assert {"user_s", "sys_s", "nivcsw", "proc_cpu_s"} <= set(root.usage)
    assert all("user_s" in s.usage and "proc_cpu_s" not in s.usage
               for s in children)
    assert engine.stats == {"scans": 2, "chunks": 2 * CHUNKS,
                            "scanned_events": 2 * EVENTS,
                            "matched_events": 2 * matched}


def test_a_projection_is_pushed_down_and_a_float_column_is_sorted(tmp_path):
    """Grouped by aggregate id there is no group stage and the reader skips
    the columns the query does not name; a float group column takes the
    sort."""
    _corpus, path = make_segment(tmp_path, 5)
    since = time.monotonic()
    by_cart = make_engine().scan_segment(path, ScanQuery(
        aggregates=(Aggregate("sum", "quantity"),)))
    spans = default_tracer().spans(since_mono=since)
    assert by_cart.num_aggregates == CARTS
    assert not named(spans, "replay.scan.group")
    assert [(s.attributes["columns_read"], s.attributes["columns_skipped"])
            for s in named(spans, "replay.scan.read")[:-1]] == [(3, 2)] * CHUNKS
    (root,) = named(spans, "replay.scan")
    assert (root.attributes["group_by"], root.attributes["columns"]) == ("", 1)
    assert named(spans, "replay.scan.merge")[0].attributes == {
        "groups": CARTS, "repeated": 0}

    spec = bank_account.make_replay_spec()
    n = 64
    amounts = (np.arange(n) % 5).astype(np.float32) / 4
    cols = {f.name: np.zeros(n, dtype=f.dtype)
            for f in spec.registry.union_columns()}
    cols["amount"] = amounts
    chunk = ColumnarEvents(
        num_aggregates=4, agg_idx=np.repeat(np.arange(4, dtype=np.int32), 16),
        type_ids=np.ones(n, dtype=np.int32), cols=cols,
        aggregate_ids=[f"acct-{i}" for i in range(4)])
    since = time.monotonic()
    rolled = QueryEngine(spec).scan_chunks(
        [chunk], ScanQuery(aggregates=(Aggregate("count"),),
                           group_by="amount"))
    (group,) = named(default_tracer().spans(since_mono=since),
                     "replay.scan.group")
    assert group.attributes == {"distinct": 5, "how": "sort"}
    assert rolled.aggregate_ids == ["0.0", "0.25", "0.5", "0.75", "1.0"]
    assert rolled.columns["count"].tolist() == [13, 13, 13, 13, 12]


# --- the programs' names ------------------------------------------------------------

def test_the_scan_programs_carry_the_pinned_names(tmp_path):
    """Every ``jax.jit`` of ``replay/query.py`` is made from a function whose
    name is pinned in ``SCAN_JIT_NAMES``; ``benchmarks/programs/scan.json``
    maps each ``jit_<name>`` to the layer and no other file claims it; the
    programs a driven engine holds, single-device and sharded, carry them."""
    import jax

    with open(query_module.__file__, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    jitted = []
    for call in ast.walk(tree):
        if (isinstance(call, ast.Call) and isinstance(call.func, ast.Attribute)
                and call.func.attr == "jit"
                and isinstance(call.func.value, ast.Name)
                and call.func.value.id == "jax"):
            made_from = call.args[0]  # a function the module defines, by name
            assert isinstance(made_from, ast.Name), ast.dump(made_from)
            jitted.append(made_from.id)
    assert len(jitted) == 2 and set(jitted) == set(SCAN_JIT_NAMES)
    with open(os.path.join(ROOT, "benchmarks", "programs", "scan.json"),
              encoding="utf-8") as f:
        scan_layer = json.load(f)
    assert scan_layer["layer"] == "Scan programs"
    assert scan_layer["prefixes"] == [f"jit_{n}" for n in SCAN_JIT_NAMES]
    for path in glob.glob(os.path.join(ROOT, "benchmarks", "programs",
                                       "*.json")):
        with open(path, encoding="utf-8") as f:
            other = json.load(f)
        if other["layer"] != "Scan programs":
            assert not any(f"jit_{n}".startswith(p) or p.startswith(f"jit_{n}")
                           for p in other["prefixes"] for n in SCAN_JIT_NAMES)
    _corpus, path = make_segment(tmp_path, 5)
    single = make_engine()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
    sharded = QueryEngine(single.spec, mesh=mesh)
    rows = [driver.rows_of(e.scan_segment(path, QUERY))
            for e in (single, sharded)]
    assert rows[0] == rows[1]
    for engine in (single, sharded):
        held = list(engine._programs.values())
        assert held and {p.__name__ for p in held} == set(SCAN_JIT_NAMES)
    bucket = 65536
    lowered = next(iter(single._programs.values())).lower(
        *(jax.ShapeDtypeStruct((bucket,), dt) for dt in (np.int32, np.int32,
                                                         np.bool_)),
        jax.ShapeDtypeStruct((0,), np.float32),
        jax.ShapeDtypeStruct((1,), np.int32),
        {name: jax.ShapeDtypeStruct((bucket,), np.int32)
         for name in ("quantity", "unit_price_cents", "item_code")})
    assert "jit_scan" in lowered.as_text()[:200]


# --- the cell: its driver at the rehearse size, its control, its readers ----------

def rehearsal(seed, seconds=0.5):
    _man, cell, config, traffic = harness.load_cell(CELL)
    run = harness.Run(cell, config, traffic, seed, seconds, False, True)
    run.meter = harness.CompileMeter()
    return run


def failed_numbers(compared):
    return {name for name, value, limit in compared if value > limit}


@pytest.fixture(scope="module")
def sound_run():
    run = rehearsal(2**31 + 21)
    return run, driver.run(run)


def test_the_driver_at_its_rehearse_size_ends_correct(sound_run):
    run, outcome = sound_run
    assert run.sizes == {"aggregates": 2000, "events": 200_000,
                         "chunk_aggregates": 131}
    assert [name for name, _v, _l in outcome["compared"]] == [
        "rows_wrong", "groups_missing", "groups_extra", "events_unaccounted",
        "scalar_sample_wrong"]
    assert all(limit == 0 for _n, _v, limit in outcome["compared"])
    assert not failed_numbers(outcome["compared"])
    assert outcome["attempted"] >= 1 and outcome["failed"] == 0
    assert run.window_compilations == 0
    facts = run.facts
    assert facts["rebuilds"] == outcome["attempted"]
    assert (facts["aggregates"], facts["events"]) == (2000, 200_000)
    assert facts["chunks"] == 16 and 50_000 < facts["groups"] <= 65_536
    assert facts["padded_events"] == 16 * 65536  # counted by the h2d spans
    assert outcome["metrics"]["rebuild_events_per_s"] == pytest.approx(
        facts["rebuilds"] * 200_000 / facts["window_s"])


def test_the_cells_readers_read_the_run(sound_run):
    run, _outcome = sound_run
    shares = {name: harness.load_reader(name)(run) for name in (
        "scan_read_pct", "scan_group_pct", "scan_h2d_pct", "scan_merge_pct")}
    assert all(value is not None and value > 0 for value in shares.values())
    unaccounted = harness.load_reader("span_unaccounted_pct")(run)
    assert 0 <= unaccounted < 100
    assert harness.load_reader("pad_ratio")(run) == pytest.approx(
        16 * 65536 / 200_000)
    # the two device readers find no trace in an untraced run, and say nothing
    assert harness.load_reader("scan_roofline")(run) is None
    assert harness.load_reader("scan_update_ns")(run) is None
    # on a trace: config.work's bytes over the peak, the spans' updates
    run.traced = {"layer_s": {"Scan programs": 0.004}}
    run.device = {"kind": "TPU v5 lite"}
    groups = run.facts["groups"]
    least_s = (200_000 * 16 + groups * 16 * 12) / 819e9
    assert harness.load_reader("scan_roofline")(run) == pytest.approx(
        100 * least_s / 0.004)
    assert harness.load_reader("scan_update_ns")(run) == pytest.approx(
        1e9 * 0.004 / (3 * 200_000))
    run.traced = {"layer_s": {}}  # a trace in which no scan program ran
    assert harness.load_reader("scan_roofline")(run) is None
    assert harness.load_reader("scan_update_ns")(run) is None
    run.traced = None


def test_the_driver_leaves_a_program_without_the_scans_account(monkeypatch):
    """A program that pins no scan program and opens no ``replay.scan`` span
    cannot run the configuration: the driver says so and leaves before any
    set-up, with an exit code that is not 0."""
    monkeypatch.delattr(query_module, "SCAN_JIT_NAMES")
    with pytest.raises(SystemExit) as left:
        driver.run(None)  # no run is touched: nothing was set up
    assert left.value.code not in (0, None)
    assert "SCAN_JIT_NAMES" in str(left.value.code)


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 12345])
def test_the_cells_control_is_not_correct(seed):
    compared = control_module.control(rehearsal(seed))
    assert {"rows_wrong", "groups_missing", "groups_extra"} <= failed_numbers(
        compared)
    values = {name: value for name, value, _limit in compared}
    # one code in a thousand lost an event (66 codes, those the log carries)
    assert 0 < values["events_unaccounted"] <= 2 * 66


def test_the_manifest_names_the_cell_and_its_readers():
    assert harness.main(["--check"]) == 0
    man, cell, config, traffic = harness.load_cell(CELL)
    assert (cell["chips"], config["chips"], traffic["name"]) == (
        1, 1, "rebuild-loop")
    restore = harness.load_cell("restore-cart-segment")[2]
    assert config["corpus"] == restore["corpus"]
    assert config["sizes"] == restore["sizes"]
    assert config["reduced"] == ["chips"] and len(config["source"]) <= 200
    assert ScanQuery.from_json(config["projection"]) == QUERY
    assert QUERY.as_json() == config["projection"]
    layers = {m["name"]: m for m in man["per_layer"]}
    for name in ("scan_roofline", "scan_update_ns", "scan_read_pct",
                 "scan_group_pct", "scan_h2d_pct", "scan_merge_pct"):
        m = layers[name]
        assert m["workloads"] == [CELL] and m["layer"] == "Scan programs"
        assert m["moves"] == "rebuild_events_per_s"
    reported = {m["name"] for m in man["per_layer"]
                if harness.reports(m, CELL, man)}
    assert reported == {"device_idle_pct.rebuild", "span_unaccounted_pct",
                        "pad_ratio", "scan_roofline", "scan_update_ns",
                        "scan_read_pct", "scan_group_pct", "scan_h2d_pct",
                        "scan_merge_pct"}
