"""TPU scan engine over committed columnar segments — the analytics plane.

PAPER.md's KTable analogy has two halves: Surge materializes per-aggregate
STATE (the resident plane serves that), but the reference never had the other
half — analytical reads over the event log itself. ``log/columnar.py`` already
stores committed events as struct-of-arrays chunks, which is exactly the
layout a vectorized scan wants: this module runs projection / filter /
grouped-aggregation queries over those chunks as batched device programs,
turning the event store into a real-time analytics plane no JVM Surge
deployment could offer (ROADMAP item 4).

Design:

- **Predicate pushdown on typed columns.** A :class:`ScanQuery` carries
  conjunctive predicates over the union event columns (plus ``type_id`` and an
  event-type name filter); the segment reader is told exactly which columns
  the query touches, so untouched column payloads are *seeked past, never
  decompressed* (``read_segment(columns=...)``) — and inside the device
  program the predicate mask is fused into the segment reduce, so filtered
  events cost a compare, not a branch.
- **Grouped aggregates keyed by aggregate id.** ``count | sum | min | max``
  per aggregate via one segment-reduce over the flat event axis — no
  per-aggregate padding, no [B, T] batch materialization. A large chunk is
  reduced over SORTED RUNS (one device sort by group, then reads at the runs'
  boundaries: :func:`_reduce_runs`), a small one or a float ``sum`` by
  scatters (``.at[agg_idx].add/min/max``: :func:`_reduce_scatter`); the
  program chooses by its bucket and dtypes (:data:`_RUNS_FROM_UPDATES`).
  Chunks cover disjoint aggregate ranges (the columnar-segment contract), so
  chunk results concatenate.
- **Mesh-sharded scans.** With a mesh, the EVENT axis shards across devices
  (``shard_map``): each device reduces its slice into full per-aggregate
  partials, then ONE collective per output (psum / pmin / pmax) replicates the
  result — the scan scales with devices and only ``[B]``-sized partials cross
  the interconnect.
- **Bucketed shapes.** Event and aggregate axes pad to power-of-two buckets
  (events at least ``surge.query.chunk-events``), so a steady stream of
  different-sized chunks reuses a handful of compiled programs.
- **Chunks in a pipeline.** A segment's next chunk is read on a reader
  thread, and a chunk's outputs are awaited only once the next chunk's
  program is dispatched, so the host's read, grouping and upload of chunk
  ``k + 1`` run beside the device's reduce of chunk ``k``. Under
  ``group_by`` a chunk reduced over sorted runs is keyed by its group
  column itself, on the device, where the column is an integer's of a
  narrow range (:func:`_key_offsets`: the host reads its least and greatest
  values alone); any other chunk's group column is factorised on the host,
  without a sort where its range allows (:func:`_factorize_group`). Every
  stage is a ``replay.scan*`` span (docs/observability.md, "Replay
  profiler").
- **Exactness contract.** Arithmetic happens in the DEVICE dtype of each
  column (with x64 off an int64 column reduces in int32); the numpy host
  reference (:func:`scan_reference`) mirrors that bit for bit, and the
  query-engine tests hold device == reference on every op. Aggregates with
  zero matched events report 0 for every output (the ``count`` column, always
  present, is the tell).

Served through ``SurgeEngine.query()`` / ``query_states()`` and the admin
``ScanSegments`` / ``QueryStates`` RPCs (docs/replay.md "Query engine").
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import (Any, Dict, Iterable, Iterator, List, Mapping, Optional,
                    Sequence, Tuple)

import numpy as np

from surge_tpu.codec.tensor import ColumnarEvents
from surge_tpu.config import Config, default_config
from surge_tpu.replay.profiler import ReplayProfiler
from surge_tpu.tracing import default_tracer

__all__ = ["Predicate", "Aggregate", "ScanQuery", "StateQuery", "QueryResult",
           "QueryEngine", "scan_reference", "state_query_reference",
           "predicate_mask_np", "SCAN_JIT_NAMES"]

#: comparison ops a predicate may use (conjunctive; applied on device)
_OPS = ("==", "!=", "<", "<=", ">", ">=")

#: the functions the scan's jitted programs are made from, single-device and
#: sharded alike: XLA names a program ``jit_<function>``, and the benchmark's
#: trace reduction maps those names to a layer (benchmarks/programs/scan.json;
#: tests/test_cart_projection.py holds the two to each other, as
#: ``engine.COLD_PATH_JIT_NAMES`` is held to programs/cold-fold.json)
SCAN_JIT_NAMES = ("scan",)

#: a presence table over a group column's value range costs one pass over the
#: range beside two over the events: taken where the range is at most this many
#: times the events (:func:`_factorize_group`)
_TABLE_SPAN_PER_EVENT = 4
#: a chunk is reduced over sorted runs where its event bucket's rows (a shard's,
#: on a mesh) times the query's reduces reach this, else by scatters. The
#: scatters cost 7.3 ns a padded row and reduce; the sorted program one sort of
#: the rows and 8-10 ms for the searches and gathers at 65,536 groups' run
#: boundaries, whatever the reduces. On a v5e (PERF.md section 6, PR 40), ms by
#: scatters / over runs, 65,536 groups: three reduces 6.9 / 11.2 at 2^18 rows,
#: 12.6 / 12.8 at 2^19, 23.9 / 14.5 at 2^20, 46.7 / 16.8 at 2^21, 182.9 / 32.6
#: at 2^23; ``count`` alone 8.3 / 10.4 at 2^20, 15.9 / 11.3 at 2^21. With few
#: groups the sorted program is ahead from 65,536 rows (2.8 / 1.0 ms) but
#: compiles in 10-20 s where the scatters take 0.2-1.0, which a view's round
#: (65,536 rows) would pay on the served path on every new bucket pair
_RUNS_FROM_UPDATES = 1 << 21


@dataclass(frozen=True)
class Predicate:
    """One comparison over a typed event column (or ``type_id``)."""

    column: str
    op: str
    value: float

    def __post_init__(self) -> None:
        if self.op not in _OPS:
            raise ValueError(f"unknown predicate op {self.op!r} (one of {_OPS})")

    def as_json(self) -> dict:
        return {"column": self.column, "op": self.op, "value": self.value}


@dataclass(frozen=True)
class Aggregate:
    """One grouped aggregate: ``count`` (no column) or ``sum|min|max`` over a
    column. Output column name: ``count`` / ``<op>_<column>``."""

    op: str
    column: Optional[str] = None

    def __post_init__(self) -> None:
        if self.op not in ("count", "sum", "min", "max"):
            raise ValueError(f"unknown aggregate op {self.op!r}")
        if self.op != "count" and not self.column:
            raise ValueError(f"aggregate {self.op!r} needs a column")

    @property
    def name(self) -> str:
        return "count" if self.op == "count" else f"{self.op}_{self.column}"

    def as_json(self) -> dict:
        out: dict = {"op": self.op}
        if self.column:
            out["column"] = self.column
        return out


@dataclass(frozen=True)
class ScanQuery:
    """Filter + grouped-aggregate scan over event columns.

    Rows group by aggregate id, or — with ``group_by`` — by the distinct
    values of one event column (``type_id`` allowed), the classic
    group-by-dimension rollup. ``event_types`` filters by event CLASS name
    (resolved to type ids against the registry — the typed pushdown the wire
    format makes free); ``predicates`` are conjunctive, and each entry of
    ``or_groups`` is a disjunction (OR) of predicates whose groups AND with
    each other and with ``predicates`` — CNF, enough for the dashboard-filter
    shapes the reference's KTable reads cover. A ``count`` output is always
    computed even when not requested, so zero-match groups are
    distinguishable."""

    aggregates: Tuple[Aggregate, ...]
    predicates: Tuple[Predicate, ...] = ()
    event_types: Optional[Tuple[str, ...]] = None
    or_groups: Tuple[Tuple[Predicate, ...], ...] = ()
    group_by: Optional[str] = None

    def __post_init__(self) -> None:
        # normalize nested sequences so signature()/program keys hash
        object.__setattr__(self, "or_groups",
                           tuple(tuple(g) for g in self.or_groups))
        for g in self.or_groups:
            if not g:
                raise ValueError("empty OR-group (would match nothing)")

    def as_json(self) -> dict:
        out: dict = {"aggregates": [a.as_json() for a in self.aggregates],
                     "predicates": [p.as_json() for p in self.predicates]}
        if self.event_types is not None:
            out["event_types"] = list(self.event_types)
        if self.or_groups:
            out["or_groups"] = [[p.as_json() for p in g]
                                for g in self.or_groups]
        if self.group_by is not None:
            out["group_by"] = self.group_by
        return out

    @classmethod
    def from_json(cls, d: Mapping[str, Any]) -> "ScanQuery":
        return cls(
            aggregates=tuple(Aggregate(a["op"], a.get("column"))
                             for a in d.get("aggregates", ())),
            predicates=tuple(Predicate(p["column"], p["op"], p["value"])
                             for p in d.get("predicates", ())),
            event_types=(tuple(d["event_types"])
                         if d.get("event_types") is not None else None),
            or_groups=tuple(
                tuple(Predicate(p["column"], p["op"], p["value"]) for p in g)
                for g in d.get("or_groups", ())),
            group_by=d.get("group_by"))

    def all_predicates(self) -> Tuple[Predicate, ...]:
        """Flat predicate order the device program indexes by: conjunctive
        predicates first, then each OR-group's members in declaration
        order."""
        return self.predicates + tuple(p for g in self.or_groups for p in g)

    @property
    def reduces(self) -> int:
        """The reduces a chunk's program runs: ``count``, always, and one for
        each ``sum`` / ``min`` / ``max``."""
        return 1 + sum(1 for a in self.aggregates if a.op != "count")

    def columns_needed(self) -> List[str]:
        """Every stored union column this query touches — the projection the
        segment reader pushes down (``type_id`` / ``type_ids`` ride the chunk
        header columns and cost nothing extra, for predicates AND
        aggregates)."""
        cols: List[str] = []
        for p in self.all_predicates():
            if p.column not in cols and p.column != "type_id":
                cols.append(p.column)
        for a in self.aggregates:
            if a.column and a.column not in cols and a.column != "type_id":
                cols.append(a.column)
        if self.group_by and self.group_by != "type_id" \
                and self.group_by not in cols:
            cols.append(self.group_by)
        return cols

    def signature(self) -> tuple:
        """Hashable program-cache key: everything that changes the compiled
        scan (values are traced, so they are NOT part of the key — except
        each value's integrality, which picks the comparison dtype)."""
        return (tuple((p.column, p.op, _is_integral(p.value))
                      for p in self.predicates),
                tuple(tuple((p.column, p.op, _is_integral(p.value))
                            for p in g) for g in self.or_groups),
                tuple((a.op, a.column) for a in self.aggregates),
                self.event_types is not None)


@dataclass(frozen=True)
class StateQuery:
    """Projection + filter over FOLDED aggregate state columns: the segment's
    chunks fold through the (mesh-aware) replay engine, then predicates run
    over the resulting state columns and ``select`` projects the output."""

    select: Optional[Tuple[str, ...]] = None
    predicates: Tuple[Predicate, ...] = ()
    limit: Optional[int] = None

    def as_json(self) -> dict:
        out: dict = {"predicates": [p.as_json() for p in self.predicates]}
        if self.select is not None:
            out["select"] = list(self.select)
        if self.limit is not None:
            out["limit"] = self.limit
        return out

    @classmethod
    def from_json(cls, d: Mapping[str, Any]) -> "StateQuery":
        return cls(
            select=(tuple(d["select"]) if d.get("select") is not None
                    else None),
            predicates=tuple(Predicate(p["column"], p["op"], p["value"])
                             for p in d.get("predicates", ())),
            limit=d.get("limit"))


@dataclass
class QueryResult:
    """Grouped scan output: per-aggregate columns in chunk order."""

    aggregate_ids: Optional[List[str]]
    columns: Dict[str, np.ndarray]
    num_aggregates: int
    scanned_events: int
    matched_events: int
    chunks: int
    elapsed_s: float = 0.0

    def rows(self, limit: Optional[int] = None) -> List[dict]:
        """Row-oriented view (the RPC payload shape): one dict per aggregate."""
        names = list(self.columns)
        ids = (self.aggregate_ids if self.aggregate_ids is not None
               else [str(i) for i in range(self.num_aggregates)])
        n = self.num_aggregates if limit is None else min(limit,
                                                          self.num_aggregates)
        cols = [self.columns[k][:n].tolist() for k in names]
        return [{"aggregate_id": ids[j],
                 **{k: cols[i][j] for i, k in enumerate(names)}}
                for j in range(n)]


def _pow2(n: int, lo: int) -> int:
    cap = lo
    while cap < n:
        cap *= 2
    return cap


def _is_integral(v) -> bool:
    """Whether a predicate value is exactly an integer (picks the compare
    dtype: fractional values against integer columns compare in f32 —
    truncating them to the column dtype would corrupt <=/>=/==/!=)."""
    try:
        return float(v).is_integer()
    except (TypeError, ValueError):
        return True


def _apply_op_np(col, op: str, value):
    if op == "==":
        return col == value
    if op == "!=":
        return col != value
    if op == "<":
        return col < value
    if op == "<=":
        return col <= value
    if op == ">":
        return col > value
    return col >= value


def _pred_mask_one_np(col: np.ndarray, p: Predicate) -> np.ndarray:
    if not _is_integral(p.value) and col.dtype.kind != "f":
        # mirror the device program: fractional vs integer compares in f32,
        # not by truncating the value to the column dtype
        return _apply_op_np(col.astype(np.float32), p.op, np.float32(p.value))
    return _apply_op_np(col, p.op, np.asarray(p.value, dtype=col.dtype))


def predicate_mask_np(cols: Mapping[str, np.ndarray], type_ids: np.ndarray,
                      predicates: Sequence[Predicate],
                      or_groups: Sequence[Sequence[Predicate]] = ()
                      ) -> np.ndarray:
    """Host mirror of the device predicate mask, over DEVICE-dtype columns
    (cast them first — ``QueryEngine._device_dtype``). Conjunctive
    ``predicates`` AND together; each ``or_groups`` entry ORs internally then
    ANDs with the rest. Shared by :func:`scan_reference` and the
    materialized-view oracle so every predicate consumer filters
    identically."""
    n = len(type_ids)
    mask = np.ones((n,), dtype=bool)
    for p in predicates:
        col = type_ids if p.column == "type_id" else cols[p.column]
        mask &= _pred_mask_one_np(col, p)
    for g in or_groups:
        hit = np.zeros((n,), dtype=bool)
        for p in g:
            col = type_ids if p.column == "type_id" else cols[p.column]
            hit |= _pred_mask_one_np(col, p)
        mask &= hit
    return mask


def _group_keys(vals: np.ndarray) -> List[str]:
    """Stable string keys for a group-by column's values (views and
    changefeeds key rows by these across processes, so the format is part of
    the wire contract): an integer or a bool as ``str(int(v))``, a float as
    ``repr(float(v))``."""
    if vals.dtype.kind == "b":
        vals = vals.view(np.uint8)
    return [repr(v) for v in vals.tolist()]  # an int's repr is its str


def _value_range(col: np.ndarray) -> Optional[Tuple[int, int]]:
    """``(lo, span)`` of an integer (or bool) column whose values span at
    most ``_TABLE_SPAN_PER_EVENT`` times its length: its least value and
    ``max - lo + 1``, two linear passes. None for any other column (a float,
    a wider or sparser range, an empty chunk)."""
    if col.dtype.kind not in "iub" or not col.size:
        return None
    values = col.view(np.uint8) if col.dtype.kind == "b" else col
    lo = int(values.min())
    span = int(values.max()) - lo + 1
    return (lo, span) if span <= _TABLE_SPAN_PER_EVENT * col.size else None


def _unsigned_low(dtype: np.dtype, lo: int) -> np.ndarray:
    """``lo`` in the unsigned kin of ``dtype`` (a bool's: ``uint8``): modulo
    that kin's width, a value less it is the true difference, in ``[0,
    span)`` for every value of the range."""
    width = np.dtype(dtype).itemsize
    return np.asarray(lo % (1 << 8 * width), dtype=f"u{width}")


def _factorize_group(col: np.ndarray) -> Tuple[List[str], np.ndarray, str]:
    """Distinct values of a DEVICE-dtype group column → (string keys in
    ascending value order, int32 group index per event, how).

    The result is ``np.unique(col, return_inverse=True)``'s, value for value
    and index for index; ``how`` says which way it was reached, chosen from
    the column itself. ``"table"``: a column :func:`_value_range` finds
    narrow takes a presence table over ``[min, max]``, whose running count
    is every value's rank: two linear passes over the events and one over
    the range, no sort. ``"sort"``: any other column takes ``np.unique``'s
    argsort. (A chunk the scan reduces over sorted runs needs no rank: its
    program keys by the column itself, :func:`_key_offsets`.)"""
    found = _value_range(col)
    if found is not None:
        lo, span = found
        values = col.view(np.uint8) if col.dtype.kind == "b" else col
        if lo == 0:
            off = values
        else:
            off = (values.view(f"u{values.dtype.itemsize}")
                   - _unsigned_low(values.dtype, lo))
        present = np.zeros((span,), dtype=bool)
        present[off] = True
        rank = np.cumsum(present, dtype=np.int32)
        rank -= 1
        vals = (np.flatnonzero(present) + lo).astype(col.dtype)
        return _group_keys(vals), rank[off], "table"
    vals, inv = np.unique(col, return_inverse=True)
    return _group_keys(vals), inv.astype(np.int32).reshape(-1), "sort"


def _key_offsets(col, lo):
    """The device's group key: ``col - lo`` as int32, taken in the unsigned
    kin of the column's dtype (``lo`` is :func:`_unsigned_low`'s scalar,
    traced, so a new least value does not recompile); in ``[0, span)`` on
    every row of the chunk, garbage on the rows past it."""
    import jax
    import jax.numpy as jnp

    if col.dtype == jnp.bool_:
        col = col.astype(jnp.uint8)
    if col.dtype != lo.dtype:
        col = jax.lax.bitcast_convert_type(col, lo.dtype)
    return (col - lo).astype(jnp.int32)


def _sentinel(op: str, dt: np.dtype):
    """The identity element min/max partials carry until normalization."""
    if op == "min":
        return np.finfo(dt).max if dt.kind == "f" else np.iinfo(dt).max
    return np.finfo(dt).min if dt.kind == "f" else np.iinfo(dt).min


def _run_extreme(skey, col, op: str):
    """The running ``min`` / ``max`` of ``col`` within each run of equal,
    sorted ``skey``, by doubling: after the step of distance ``d`` a row holds
    the extreme of the (at most) ``2 d`` rows of its run that end at it, so
    after log2(rows) steps a run's LAST row holds the run's. Exact in any
    dtype; log2(rows) fused elementwise passes, no scan primitive
    (``lax.associative_scan`` over ``(key, value)`` at 2^23 rows did not
    finish compiling in 45 minutes: PERF.md section 6, PR 40)."""
    import jax.numpy as jnp

    pick = jnp.minimum if op == "min" else jnp.maximum
    d = 1
    while d < skey.shape[0]:
        # a row's predecessor at distance d lies in its run iff the keys are
        # equal (the run is contiguous); the first d rows have none
        same = jnp.concatenate([jnp.zeros((d,), bool), skey[d:] == skey[:-d]])
        before = jnp.concatenate([col[:d], col[:-d]])
        col = jnp.where(same, pick(col, before), col)
        d *= 2
    return col


def _reduce_runs(mask, group, reduced: dict, aggs, b_bucket: int,
                 valid=None) -> dict:
    """A chunk's outputs read from its SORTED RUNS. An event the mask rejects
    takes the sentinel key ``b_bucket`` and sorts past every group (the
    garbage rows past a chunk's events in a reused buffer among them: nothing
    else holds them); ONE sort a chunk carries the reduced columns; every
    output is then read at the ``b_bucket + 1`` run boundaries: ``count`` the
    distance between two runs' starts, an integer ``sum`` the difference of a
    prefix sum at a run's two ends, ``min`` / ``max`` the run's running
    extreme at its last row. A group the chunk does not show is an empty run:
    ``count`` 0, ``sum`` 0, ``min`` / ``max`` the sentinel.

    With ``valid`` (the rows of the chunk's events) ``group`` is the device's
    own key (:func:`_key_offsets`) and a group is every key a valid row
    shows, whether the mask takes the row or not, as ``scan_reference`` forms
    groups. A valid row's sort key is then ``2 x key``, plus one where the
    mask rejects it, and the rest take ``2 x b_bucket``: the runs' starts are
    searched at the even keys alone, so a key's run holds its taken rows and
    then its rejected ones, and the outputs read the taken rows by the sort
    key's low bit (a rejected row adds 0 and carries the sentinel). One more
    output, ``_shown``, is a key's valid rows: the chunk's groups are the
    keys where it is above 0."""
    import jax
    import jax.numpy as jnp

    names = sorted(reduced)
    if valid is None:
        key, halves = jnp.where(mask, group, b_bucket), 0
    else:
        key, halves = jnp.where(valid, 2 * group + (~mask).astype(jnp.int32),
                                2 * b_bucket), 1
    skey, *scols = jax.lax.sort(
        (key, *(reduced[c] for c in names)), num_keys=1, is_stable=False)
    scol = dict(zip(names, scols))
    starts = jnp.searchsorted(
        skey, jnp.arange(b_bucket + 1, dtype=jnp.int32) << halves
    ).astype(jnp.int32)
    lo, hi = starts[:-1], starts[1:]
    last = jnp.maximum(hi - 1, 0)

    def across(upto):
        # a prefix sum's difference at each run's two ends
        ends = jnp.where(starts > 0, upto[jnp.maximum(starts - 1, 0)],
                         jnp.zeros((), upto.dtype))
        return ends[1:] - ends[:-1]

    if valid is None:
        run, taken = skey, None
        out = {"count": hi - lo}
    else:
        # the sentinel's rows read as taken too, past every run read here
        run, taken = skey >> 1, (skey & 1) == 0
        out = {"count": across(jnp.cumsum(taken, dtype=jnp.int32)),
               "_shown": hi - lo}
    for op, cname, oname in aggs:
        if op == "count":
            continue
        col = scol[cname]
        dt = col.dtype
        if op == "sum":
            # the prefix sum is taken in the output's own dtype and wraps as
            # the scatter's adds wrap: modulo 2^w its difference at a run's
            # two ends is the run's sum in the same ring, bit for bit
            if taken is not None:
                col = jnp.where(taken, col, jnp.zeros((), dt))
            out[oname] = across(jnp.cumsum(col, dtype=dt))
        else:
            idle = jnp.array(_sentinel(op, np.dtype(dt)), dt)
            if taken is not None:
                col = jnp.where(taken, col, idle)
            out[oname] = jnp.where(hi > lo, _run_extreme(run, col, op)[last],
                                   idle)
    return out


def _reduce_scatter(mask, group, reduced: dict, aggs, b_bucket: int) -> dict:
    """A chunk's outputs by one scatter reduce an output, the mask fused in
    (``group`` in range on every row). XLA's TPU scatter takes unsorted
    updates one after the other, about 9 ns each (PERF.md section 6, PR 39):
    the small chunk's program (:data:`_RUNS_FROM_UPDATES`), and any float
    ``sum``'s, whose answer is the order of its additions."""
    import jax.numpy as jnp

    out = {"count": jnp.zeros((b_bucket,), jnp.int32).at[group].add(
        mask.astype(jnp.int32))}
    for op, cname, oname in aggs:
        if op == "count":
            continue
        col = reduced[cname]
        dt = col.dtype
        if op == "sum":
            out[oname] = jnp.zeros((b_bucket,), dt).at[group].add(
                jnp.where(mask, col, jnp.zeros((), dt)))
            continue
        idle = jnp.array(_sentinel(op, np.dtype(dt)), dt)
        rows = jnp.full((b_bucket,), idle, dt).at[group]
        masked = jnp.where(mask, col, idle)
        out[oname] = rows.min(masked) if op == "min" else rows.max(masked)
    return out


def _normalize_zero_match(out: Dict[str, np.ndarray], query: ScanQuery
                          ) -> Dict[str, np.ndarray]:
    """Zero-match aggregates report 0 everywhere: min/max sentinels flip to 0
    (the always-present ``count`` column is the tell; sum/count are already
    0). Runs ONCE, after any cross-chunk merge."""
    count = out["count"]
    for a in query.aggregates:
        if a.op in ("min", "max"):
            col = out[a.name]
            out[a.name] = np.where(count > 0, col, np.zeros((), col.dtype))
    return out


def _merge_scan_outputs(collected, query: ScanQuery):
    """Combine per-chunk RAW scan outputs ``[(keys | None, outputs)]`` into
    the final grouped columns.

    Disjoint chunks (the common case) concatenate; chunks repeating a key —
    auto-extended segments append delta chunks continuing base-chunk
    aggregates, and under ``group_by`` every chunk repeats the group values —
    MERGE into one row per key (count/sum add, min/max combine over the
    sentinel-carrying partials). Chunks without keys cannot be matched across
    chunks and keep the disjointness contract. Returns ``(keys | None,
    columns, repeated)`` post-normalization; ``repeated`` counts the chunk
    rows whose key an earlier chunk had shown."""
    saw_ids = all(ids_c is not None for ids_c, _out in collected)
    seen: Dict[str, int] = {}
    repeated = 0
    if saw_ids:
        for ids_c, _out in collected:
            before = len(seen)
            for a in ids_c:
                seen.setdefault(a, len(seen))
            repeated += len(ids_c) - (len(seen) - before)
    has_dup = repeated > 0
    agg_specs = [(a.op, a.name) for a in query.aggregates if a.op != "count"]
    if not (saw_ids and has_dup):
        parts: Dict[str, List[np.ndarray]] = {}
        ids: List[str] = []
        for ids_c, out in collected:
            for name, col in out.items():
                parts.setdefault(name, []).append(col)
            if saw_ids:
                ids.extend(ids_c)
        columns = {name: (np.concatenate(arrs) if arrs
                          else np.zeros((0,), np.int32))
                   for name, arrs in parts.items()}
        if not columns:
            columns = {"count": np.zeros((0,), np.int32)}
        return (ids if saw_ids else None,
                _normalize_zero_match(columns, query), 0)
    b = len(seen)
    columns = {"count": np.zeros((b,), np.int32)}
    for ids_c, out in collected:
        if not ids_c:
            continue
        idxs = np.fromiter((seen[a] for a in ids_c), dtype=np.int64,
                           count=len(ids_c))
        np.add.at(columns["count"], idxs, out["count"])
        for op, name in agg_specs:
            col = out[name]
            if name not in columns:
                init = (0 if op == "sum"
                        else _sentinel(op, np.dtype(col.dtype)))
                columns[name] = np.full((b,), init, dtype=col.dtype)
            if op == "sum":
                np.add.at(columns[name], idxs, col)
            elif op == "min":
                np.minimum.at(columns[name], idxs, col)
            else:
                np.maximum.at(columns[name], idxs, col)
    return list(seen), _normalize_zero_match(columns, query), repeated


def _read_ahead(chunks: Iterable[ColumnarEvents]) -> Iterator[ColumnarEvents]:
    """``chunks``, each step taken one ahead of the consumer on a thread of
    its own: a segment's next chunk is read and decompressed (native code,
    which releases the interpreter lock) while the scan groups, puts and
    reduces this one, so the scan waits for the device and not for the disk.
    One step is under way at a time, in order; what it raises is raised to
    the consumer at that chunk; at most one chunk waits beside the one being
    scanned."""
    stream = iter(chunks)
    with ThreadPoolExecutor(max_workers=1,
                            thread_name_prefix="surge-scan-read") as reader:
        ahead = reader.submit(next, stream, None)
        while (chunk := ahead.result()) is not None:
            ahead = reader.submit(next, stream, None)
            yield chunk


class QueryEngine:
    """Batched (optionally mesh-sharded) scan executor for one model family.

    One engine caches compiled scan programs per (query signature, shape
    bucket); chunks stream through :meth:`scan_chunks` /
    :meth:`scan_segment`. ``mesh`` shards the event axis; without one the
    same program runs single-device."""

    def __init__(self, spec, config: Config | None = None, mesh=None,
                 mesh_axis: Optional[str] = None) -> None:
        self.spec = spec
        self.registry = spec.registry
        self.config = config or default_config()
        self.mesh = mesh if self.config.get_bool("surge.query.mesh", True) \
            else None
        if mesh_axis is None:
            mesh_axis = (self.config.get_str("surge.replay.mesh-axes", "data")
                         .split(",")[0].strip() or "data")
        self.mesh_axis = mesh_axis
        # normalized to a power of two: the raw knob value seeds the bucket
        # ladder, and a non-pow2 floor would produce buckets no device count
        # divides (shard_map rejects the event-axis sharding outright)
        self._event_bucket = _pow2(max(
            self.config.get_int("surge.query.chunk-events", 65536), 1), 1024)
        self._programs: dict = {}
        self._col_dtypes = {f.name: np.dtype(f.dtype)
                            for f in self.registry.union_columns()}
        self._type_ids = {s.cls.__name__: s.type_id
                          for s in self.registry.event_schemas}
        self.stats = {"scans": 0, "chunks": 0, "scanned_events": 0,
                      "matched_events": 0}
        #: every stage of a scan is a span of this profiler, in the
        #: process-wide ring as the replay engine's are (one scan, one trace)
        self.profiler = ReplayProfiler.counters(tracer=default_tracer())

    # -- helpers ------------------------------------------------------------------------

    def _n_dev(self) -> int:
        return 1 if self.mesh is None else int(np.prod(self.mesh.devices.shape))

    def resolve_type_ids(self, names: Sequence[str]) -> np.ndarray:
        try:
            return np.asarray(sorted(self._type_ids[n] for n in names),
                              dtype=np.int32)
        except KeyError as exc:
            raise ValueError(
                f"unknown event type {exc.args[0]!r} (registry has "
                f"{sorted(self._type_ids)})") from None

    def _device_dtype(self, dt: np.dtype):
        """The dtype a column actually reduces in on device: with
        jax_enable_x64 off (the default) 64-bit columns canonicalize to their
        32-bit kin — the host reference mirrors this exactly."""
        import jax

        if not jax.config.read("jax_enable_x64") and dt.itemsize == 8:
            return np.dtype(np.int32 if dt.kind in "iu" else np.float32)
        return dt

    def _materialize_columns(self, colev: ColumnarEvents,
                             needed: Sequence[str]) -> Dict[str, np.ndarray]:
        """The query's columns from a chunk, deriving declared-derived ones
        (an ``ordinal`` column is positional — synthesized from agg_idx, the
        exact inverse of ``columnar._drop_derived``'s verification)."""
        out: Dict[str, np.ndarray] = {}
        for name in needed:
            col = colev.cols.get(name)
            if col is not None:
                out[name] = col
                continue
            kind = colev.derived_cols.get(name)
            if kind != "ordinal":
                raise ValueError(
                    f"query references column {name!r} which the chunk "
                    f"neither stores nor derives (has "
                    f"{sorted(colev.cols) + sorted(colev.derived_cols)})")
            n = colev.num_events
            starts = np.zeros(colev.num_aggregates + 1, dtype=np.int64)
            np.cumsum(np.bincount(colev.agg_idx,
                                  minlength=colev.num_aggregates),
                      out=starts[1:])
            dt = self._col_dtypes.get(name, np.dtype(np.int32))
            out[name] = (np.arange(n, dtype=np.int64)
                         - starts[colev.agg_idx] + 1).astype(dt)
        return out

    # -- the device program -------------------------------------------------------------

    def _regime(self, query: ScanQuery, n_bucket: int,
                col_dts: Tuple[Tuple[str, np.dtype], ...]) -> str:
        """How a chunk's program reduces, by what it sees: ``"runs"``
        (:func:`_reduce_runs`) where the event bucket's rows, a shard's on a
        mesh, times the reduces reach :data:`_RUNS_FROM_UPDATES` and every
        ``sum`` is an integer's, else ``"scatter"`` (:func:`_reduce_scatter`:
        a float's sum IS the order of its additions, and a prefix difference
        would cancel)."""
        dts = dict(col_dts, type_id=np.dtype(np.int32))
        return "runs" if (
            n_bucket // self._n_dev() * query.reduces >= _RUNS_FROM_UPDATES
            and all(dts[a.column].kind in "iu"
                    for a in query.aggregates if a.op == "sum")) else "scatter"

    def _program(self, query: ScanQuery, n_bucket: int, b_bucket: int,
                 col_dts: Tuple[Tuple[str, np.dtype], ...],
                 keyed_by: Optional[str] = None) -> tuple:
        """The chunk's jitted program for the query at these buckets and the
        put columns' ``(name, dtype)``, and how it reduces
        (:meth:`_regime`). ``keyed_by`` names the group column the program
        keys its sorted runs by itself (its first argument is then the
        chunk's least value, :func:`_key_offsets`); without it the first
        argument is every event's group index, put."""
        aggs = tuple((a.op, a.column, a.name) for a in query.aggregates)
        how = self._regime(query, n_bucket, col_dts)
        key = (query.signature(), n_bucket, b_bucket, col_dts, keyed_by)
        hit = self._programs.get(key)
        if hit is not None:
            return hit, how
        import jax
        import jax.numpy as jnp

        col_names = tuple(name for name, _ in col_dts)
        preds = tuple((p.column, p.op, _is_integral(p.value))
                      for p in query.predicates)
        groups = tuple(tuple((p.column, p.op, _is_integral(p.value))
                             for p in g) for g in query.or_groups)
        has_types = query.event_types is not None

        def partials(group, type_ids, first, n, pred_vals, type_allow, cols):
            # rows past the chunk's ``n`` events hold whatever the host's
            # buffer held: masked here, by position (``first`` is this
            # shard's offset on the event axis)
            valid = first + jnp.arange(type_ids.shape[0], dtype=jnp.int32) < n

            def compare(cname, op, integral, j):
                # one predicate leg, indexed into the FLAT pred_vals vector
                # (conjunctive predicates first, then OR-group members)
                col = type_ids if cname == "type_id" else cols[cname]
                if not integral and not jnp.issubdtype(col.dtype,
                                                       jnp.floating):
                    # fractional value vs integer column: compare in f32
                    # (exact for |values| < 2^24) — truncating the value to
                    # the column dtype would corrupt <=/>=/==/!=
                    col = col.astype(jnp.float32)
                    v = pred_vals[j].astype(jnp.float32)
                else:
                    v = pred_vals[j].astype(col.dtype)
                if op == "==":
                    return col == v
                if op == "!=":
                    return col != v
                if op == "<":
                    return col < v
                if op == "<=":
                    return col <= v
                if op == ">":
                    return col > v
                return col >= v

            mask = valid
            if has_types:
                # few allowed ids: an OR of compares beats a gather-based
                # isin and fuses into the same elementwise pass
                hit_t = jnp.zeros_like(mask)
                for j in range(type_allow.shape[0]):
                    hit_t = hit_t | (type_ids == type_allow[j])
                mask = mask & hit_t
            j = 0
            for cname, op, integral in preds:
                mask = mask & compare(cname, op, integral, j)
                j += 1
            for g in groups:
                hit = None
                for cname, op, integral in g:
                    leg = compare(cname, op, integral, j)
                    hit = leg if hit is None else hit | leg
                    j += 1
                mask = mask & hit
            reduced = {cname: type_ids if cname == "type_id" else cols[cname]
                       for op, cname, _ in aggs if op != "count"}
            if keyed_by is not None:
                gcol = type_ids if keyed_by == "type_id" else cols[keyed_by]
                return _reduce_runs(mask, _key_offsets(gcol, group), reduced,
                                    aggs, b_bucket, valid=valid)
            if how == "runs":
                return _reduce_runs(mask, group, reduced, aggs, b_bucket)
            return _reduce_scatter(mask, jnp.where(valid, group, 0),
                                   reduced, aggs, b_bucket)

        if self.mesh is None or self._n_dev() <= 1:
            def scan(group, type_ids, n, pred_vals, type_allow, cols):
                return partials(group, type_ids, 0, n, pred_vals,
                                type_allow, cols)

            prog = jax.jit(scan)
        else:
            from jax.sharding import PartitionSpec as P

            axis = self.mesh_axis
            pe = P(axis)  # event axis, sharded
            pr = P()      # replicated (predicate values, type filter, output)

            def scan(group, type_ids, n, pred_vals, type_allow, cols):
                first = jax.lax.axis_index(axis) * type_ids.shape[0]
                part = partials(group, type_ids, first, n, pred_vals,
                                type_allow, cols)
                # ONE collective per output column: partial per-aggregate
                # reduces combine across the event shards (``_shown`` as
                # ``count``)
                out: dict = {}
                for name, v in part.items():
                    op = next((a[0] for a in aggs if a[2] == name), "count")
                    if op == "min":
                        out[name] = jax.lax.pmin(v, axis)
                    elif op == "max":
                        out[name] = jax.lax.pmax(v, axis)
                    else:  # count / sum
                        out[name] = jax.lax.psum(v, axis)
                return out

            scan = jax.shard_map(
                scan, mesh=self.mesh,
                in_specs=(pe if keyed_by is None else pr, pe, pr, pr, pr,
                          {n: pe for n in col_names}),
                out_specs={name: pr for name in
                           ["count"] + [a[2] for a in aggs
                                        if a[0] != "count"]
                           + ([] if keyed_by is None else ["_shown"])},
                check_vma=False)
            prog = jax.jit(scan)
        self._programs[key] = prog
        return prog, how

    # -- chunk / segment scans ----------------------------------------------------------

    def scan_chunk(self, colev: ColumnarEvents, query: ScanQuery
                   ) -> Dict[str, np.ndarray]:
        """Scan one chunk; returns ``{output: np[num_groups]}`` (always
        including ``count``). Zero-match groups report 0 everywhere."""
        return _normalize_zero_match(self._raw_scan(colev, query)[1], query)

    def _raw_scan(self, colev: ColumnarEvents, query: ScanQuery
                  ) -> Tuple[Optional[List[str]], Dict[str, np.ndarray]]:
        """The device scan of one chunk WITHOUT zero-match normalization:
        min/max keep their dtype sentinels, so per-chunk partials of a
        repeated group (delta chunks, per-refresh-round view folds) stay
        combinable. Returns ``(group keys, raw outputs)`` — keys are the
        chunk's aggregate ids, or under ``group_by`` the distinct group-column
        values of THIS chunk as stable strings. The chunk's program is
        dispatched and its outputs awaited at once; :meth:`scan_chunks` takes
        the two halves apart."""
        return self._collect_scan(self._dispatch_scan(colev, query, {}))

    def _dispatch_scan(self, colev: ColumnarEvents, query: ScanQuery,
                       buffers: dict) -> tuple:
        """The first half of a chunk's scan, up to its program under way on
        the device: ``(group keys, groups, outputs on the device, the reduce
        stage's counts, the device key's least value and dtype)`` for
        :meth:`_collect_scan`. ``buffers`` holds the event-bucket host
        buffers the chunk's arrays are copied into, one an array and bucket,
        made on first use and never cleared (the program masks the rows past
        the chunk's events): the caller may hand the same dict to a later
        chunk once this one's outputs are collected.

        Three stages, each a span (docs/observability.md, "Replay profiler"):
        ``replay.scan.group`` (under ``group_by``: where the chunk is reduced
        over sorted runs and the group column is an integer's of a narrow
        range (:func:`_value_range`), its least value and range alone, the
        program keying its runs by the column itself; else the column's
        distinct values and every event's group index,
        :func:`_factorize_group`),
        ``replay.scan.h2d`` (the arrays copied into the buffers and put on
        the device) and ``replay.scan.dispatch`` (the program's asynchronous
        dispatch, with its compilation on a first signature)."""
        import jax

        stage = self.profiler.stage
        n = colev.num_events
        needed = tuple(query.columns_needed())
        cols_np = self._materialize_columns(colev, needed)
        col_dts = tuple((name, self._device_dtype(cols_np[name].dtype))
                        for name in needed)
        n_dev = self._n_dev()
        n_bucket = _pow2(max(n, 1), max(self._event_bucket, n_dev))
        keyed = None  # the device key's (least value, dtype)
        if query.group_by is not None:
            with stage("scan.group") as grouped:
                gcol = (colev.type_ids if query.group_by == "type_id"
                        else cols_np[query.group_by])
                gcol = gcol.astype(self._device_dtype(np.dtype(gcol.dtype)),
                                   copy=False)
                found = (_value_range(gcol) if self._regime(
                    query, n_bucket, col_dts) == "runs" else None)
                # the sort key 2 x key + 1 and its sentinel fit an int32
                if found is not None and _pow2(found[1], 8) <= 1 << 30:
                    lo, b = found
                    keyed, ids = (lo, gcol.dtype), None
                    grouped.attributes.update(how="device", span=b)
                else:
                    ids, grp_idx, how = _factorize_group(gcol)
                    grouped.attributes.update(distinct=len(ids), how=how)
                    b = len(ids)
        else:
            ids, grp_idx = colev.aggregate_ids, colev.agg_idx
            b = colev.num_aggregates
        b_bucket = _pow2(max(b, 1), 8)

        with stage("scan.h2d", padded_events=n_bucket) as h2d:
            def padded(name: str, col: np.ndarray, dtype) -> np.ndarray:
                # rows past ``n`` are never cleared: the program masks them
                key = (name, n_bucket, np.dtype(dtype))
                buf = buffers.get(key)
                if buf is None:
                    buf = buffers[key] = np.zeros((n_bucket,), dtype=dtype)
                buf[:n] = col
                return buf

            # the program's first argument: every event's group index, or
            # the device key's least value
            events = (None if keyed else padded("", grp_idx, np.int32),
                      padded("type_id", colev.type_ids, np.int32),
                      {name: padded(name, cols_np[name], dt)
                       for name, dt in col_dts})
            pred_vals = np.asarray([p.value for p in query.all_predicates()],
                                   dtype=np.float64)
            type_allow = (self.resolve_type_ids(query.event_types)
                          if query.event_types is not None
                          else np.zeros((0,), dtype=np.int32))
            scalars = (np.int32(n), pred_vals, type_allow,
                       _unsigned_low(keyed[1], keyed[0]) if keyed else None)

            if self.mesh is not None and n_dev > 1:
                from jax.sharding import NamedSharding, PartitionSpec as P

                on_events = NamedSharding(self.mesh, P(self.mesh_axis))
                replicated = NamedSharding(self.mesh, P())
            else:
                on_events = replicated = None  # the default device
            (index_d, type_d, cols_d, n_d, pred_d, allow_d,
             low_d) = jax.block_until_ready(
                (*jax.device_put(events, on_events),
                 *jax.device_put(scalars, replicated)))
            leaves = jax.tree_util.tree_leaves(events)
            h2d.attributes.update(
                copied_bytes=n * sum(a.itemsize for a in leaves),
                put_bytes=sum(a.nbytes for a in leaves) + sum(
                    a.nbytes for a in jax.tree_util.tree_leaves(scalars)))
        with stage("scan.dispatch"):
            prog, how = self._program(query, n_bucket, b_bucket, col_dts,
                                      query.group_by if keyed else None)
            out_dev = prog(low_d if keyed else index_d, type_d, n_d, pred_d,
                           allow_d, cols_d)
        return ids, b, out_dev, dict(bucket=n_bucket, group_bucket=b_bucket,
                                     how=how, updates=n * query.reduces), keyed

    def _collect_scan(self, dispatched: tuple
                      ) -> Tuple[Optional[List[str]], Dict[str, np.ndarray]]:
        """The second half: ``replay.scan.reduce``, the wait for the program
        and its outputs' way to the host, cut to the chunk's groups. Where
        the program keyed its runs by the group column, the groups are the
        keys its valid rows showed (``_shown``): their values, the least one
        added back in the column's dtype, make the row keys, ascending, as
        :func:`_factorize_group` makes them, and ``distinct`` is counted
        here."""
        ids, b, out_dev, counts, keyed = dispatched
        with self.profiler.stage("scan.reduce", **counts) as reduce:
            out = {k: np.asarray(v)[:b] for k, v in out_dev.items()}
            if keyed:
                lo, dtype = keyed
                slots = np.flatnonzero(out.pop("_shown"))
                ids = _group_keys((slots + lo).astype(dtype))
                out = {k: v[slots] for k, v in out.items()}
                reduce.attributes.update(distinct=len(ids))
        return ids, out

    def scan_chunks(self, chunks: Iterable[ColumnarEvents], query: ScanQuery
                    ) -> QueryResult:
        """Scan a stream of chunks. Disjoint-aggregate chunks (the base
        columnar-segment layout) concatenate in chunk order; chunks REPEATING
        an aggregate id (auto-extended segments append delta chunks whose
        aggregates continue base chunks) MERGE into one row per id —
        count/sum add, min/max combine, zero-match normalization runs after
        the merge. Chunks without aggregate ids cannot be matched across
        chunks and keep the disjointness contract. Under ``group_by`` rows
        key by group value (the same value recurring across chunks merges
        exactly like a repeated aggregate id).

        One scan is one trace: the root span ``replay.scan``, a chunk
        ``replay.scan.read`` (one step of ``chunks``: of a segment, the wait
        for its reader, which reads and decodes a chunk ahead; the last step
        finds the end), :meth:`_dispatch_scan`'s stages and the
        ``replay.scan.reduce`` of the chunk before (:meth:`_collect_scan`),
        then ``replay.scan.merge``."""
        t0 = time.perf_counter()
        stage = self.profiler.stage
        collected: List[Tuple[Optional[List[str]], Dict[str, np.ndarray]]] = []
        scanned = n_chunks = 0
        # a chunk's program runs while the next chunk is read, grouped and
        # put: its outputs are collected once the next one is under way, so
        # the device always has a program waiting. Two sets of host buffers
        # take turns, since a backend may read a put array where it lies
        # until the program that takes it has ended
        buffers: Tuple[dict, dict] = ({}, {})
        under_way = None
        with stage("scan", group_by=query.group_by or "",
                   columns=len(query.columns_needed())) as root:
            stream = iter(chunks)
            while True:
                with stage("scan.read") as read:
                    colev = next(stream, None)
                    if colev is not None and colev.source_stored is not None:
                        read.attributes.update(
                            {k: colev.source_stored[k] for k in (
                                "stored_bytes", "raw_bytes", "columns_read",
                                "columns_skipped")})
                following = None
                if colev is not None:
                    following = self._dispatch_scan(colev, query,
                                                    buffers[n_chunks % 2])
                    n_chunks += 1
                    scanned += colev.num_events
                if under_way is not None:
                    collected.append(self._collect_scan(under_way))
                under_way = following
                if colev is None:
                    break
            matched = sum(int(out["count"].sum()) for _ids, out in collected)
            with stage("scan.merge") as merge:
                ids, columns, repeated = _merge_scan_outputs(collected, query)
                groups = len(next(iter(columns.values())))
                merge.attributes.update(groups=groups, repeated=repeated)
            root.attributes.update(chunks=len(collected), events=scanned,
                                   matched=matched, groups=groups)
        self.stats["scans"] += 1
        self.stats["chunks"] += len(collected)
        self.stats["scanned_events"] += scanned
        self.stats["matched_events"] += matched
        return QueryResult(
            aggregate_ids=ids, columns=columns, num_aggregates=groups,
            scanned_events=scanned, matched_events=matched,
            chunks=len(collected), elapsed_s=time.perf_counter() - t0)

    def scan_segment(self, path: str, query: ScanQuery,
                     partitions: Optional[set] = None) -> QueryResult:
        """Scan a committed columnar segment file. Only the columns the query
        touches are decompressed (projection pushdown into the reader), a
        chunk ahead of the scan (:func:`_read_ahead`)."""
        from surge_tpu.log.columnar import read_segment

        return self.scan_chunks(
            _read_ahead(read_segment(path, partitions=partitions,
                                     columns=query.columns_needed())),
            query)

    # -- state queries (fold + filter + project) ----------------------------------------

    def query_states(self, chunks: Iterable[ColumnarEvents],
                     query: StateQuery, replay_engine) -> QueryResult:
        """Fold the chunks' events to per-aggregate STATE through the
        (mesh-aware) replay engine, then filter on state columns and project
        ``select`` — the "current state of every matching aggregate" read.

        Chunks REPEATING an aggregate id (auto-extended segments append delta
        chunks continuing base chunks) fold as CONTINUATIONS: the repeated
        rows' carries and already-folded event counts seed the delta fold,
        and the final row is the complete state — one row per id, same as
        the segment restore. (Snapshot-only aggregates — state publishes with
        no events at all — live in snapshot sections the tensor fold cannot
        see; they are a restore concern, not a state-query one.)"""
        t0 = time.perf_counter()
        chunk_list = list(chunks)
        state_names = [f.name for f in self.spec.registry.state.fields]
        dtypes = {f.name: np.dtype(f.dtype)
                  for f in self.spec.registry.state.fields}
        if any(c.aggregate_ids is None for c in chunk_list):
            # id-less chunks cannot be matched across chunks: keep the
            # disjoint-aggregate contract verbatim
            res = replay_engine.replay_columnar_chunks(chunk_list)
            states, ids_order = res.states, res.aggregate_ids
            num_events = res.num_events
        else:
            init_tree = self.spec.init_state_tree()
            index: Dict[str, int] = {}
            ids_order = []
            states = {n: np.zeros((0,), dtype=dtypes[n])
                      for n in state_names}
            folded = np.zeros((0,), dtype=np.int32)  # events per id so far
            num_events = 0
            for colev in chunk_list:
                b_c = colev.num_aggregates
                ids_c = colev.aggregate_ids
                rep = [(j, index[a]) for j, a in enumerate(ids_c)
                       if a in index]
                init_carry = None
                ord_base = None
                if rep:
                    # continuation: repeated rows resume from their folded
                    # carry + event count (delta chunks store positional
                    # columns explicitly, but a derived declaration still
                    # continues correctly through ordinal_base)
                    init_carry = {n: np.full((b_c,), init_tree[n],
                                             dtype=dtypes[n])
                                  for n in state_names}
                    ord_base = np.zeros((b_c,), dtype=np.int32)
                    js = np.asarray([j for j, _ in rep], dtype=np.int64)
                    ks = np.asarray([k for _, k in rep], dtype=np.int64)
                    for n in state_names:
                        init_carry[n][js] = states[n][ks]
                    ord_base[js] = folded[ks]
                res = replay_engine.replay_columnar(
                    colev, init_carry=init_carry, ordinal_base=ord_base)
                counts_c = np.bincount(colev.agg_idx,
                                       minlength=b_c).astype(np.int32)
                num_events += res.num_events
                new = [j for j, a in enumerate(ids_c) if a not in index]
                if rep:
                    for n in state_names:
                        states[n][ks] = res.states[n][js]
                    folded[ks] += counts_c[js]
                if new:
                    nj = np.asarray(new, dtype=np.int64)
                    for n in state_names:
                        states[n] = np.concatenate(
                            [states[n], res.states[n][nj]])
                    folded = np.concatenate([folded, counts_c[nj]])
                    for j in new:
                        index[ids_c[j]] = len(ids_order)
                        ids_order.append(ids_c[j])
        n_rows = len(next(iter(states.values()))) if states else 0
        mask = np.ones((n_rows,), dtype=bool)
        for p in query.predicates:
            if p.column not in states:
                raise ValueError(
                    f"state query references unknown state column "
                    f"{p.column!r} (has {state_names})")
            mask &= _apply_op_np(states[p.column], p.op, p.value)
        select = list(query.select) if query.select is not None else state_names
        for name in select:
            if name not in states:
                raise ValueError(f"unknown state column {name!r} in select "
                                 f"(has {state_names})")
        idx = np.nonzero(mask)[0]
        if query.limit is not None:
            idx = idx[: query.limit]
        columns = {name: states[name][idx] for name in select}
        ids = ([ids_order[i] for i in idx]
               if ids_order is not None else None)
        self.stats["scans"] += 1
        self.stats["scanned_events"] += num_events
        return QueryResult(
            aggregate_ids=ids, columns=columns, num_aggregates=len(idx),
            scanned_events=num_events, matched_events=len(idx),
            chunks=len(chunk_list), elapsed_s=time.perf_counter() - t0)

    def query_states_segment(self, path: str, query: StateQuery,
                             replay_engine,
                             partitions: Optional[set] = None) -> QueryResult:
        from surge_tpu.log.columnar import read_segment

        return self.query_states(read_segment(path, partitions=partitions),
                                 query, replay_engine)


# -- numpy host references (the golden the device scans must equal) ------------------


def scan_reference(chunks: Iterable[ColumnarEvents], query: ScanQuery,
                   registry) -> QueryResult:
    """Pure-numpy oracle for :meth:`QueryEngine.scan_chunks` — identical
    dtype discipline (device-canonicalized reduce dtypes), identical
    zero-match normalization. The query-engine tests hold device == this."""
    import jax

    def dev_dt(dt: np.dtype) -> np.dtype:
        if not jax.config.read("jax_enable_x64") and dt.itemsize == 8:
            return np.dtype(np.int32 if dt.kind in "iu" else np.float32)
        return dt

    type_ids_of = {s.cls.__name__: s.type_id for s in registry.event_schemas}
    union_dts = {f.name: np.dtype(f.dtype) for f in registry.union_columns()}
    collected: List[Tuple[Optional[List[str]], Dict[str, np.ndarray]]] = []
    scanned = matched = 0
    for colev in chunks:
        n = colev.num_events
        cols: Dict[str, np.ndarray] = {}
        for name in query.columns_needed():
            col = colev.cols.get(name)
            if col is None and colev.derived_cols.get(name) == "ordinal":
                starts = np.zeros(colev.num_aggregates + 1, dtype=np.int64)
                np.cumsum(np.bincount(colev.agg_idx,
                                      minlength=colev.num_aggregates),
                          out=starts[1:])
                col = (np.arange(n, dtype=np.int64)
                       - starts[colev.agg_idx] + 1).astype(
                    union_dts.get(name, np.dtype(np.int32)))
            cols[name] = col.astype(dev_dt(col.dtype))
        if query.group_by is not None:
            gcol = (colev.type_ids if query.group_by == "type_id"
                    else cols[query.group_by])
            ids_c, grp_idx, _how = _factorize_group(gcol)
            b = len(ids_c)
        else:
            ids_c, grp_idx = colev.aggregate_ids, colev.agg_idx
            b = colev.num_aggregates
        mask = np.ones((n,), dtype=bool)
        if query.event_types is not None:
            allow = {type_ids_of[t] for t in query.event_types}
            mask &= np.isin(colev.type_ids, sorted(allow))
        mask &= predicate_mask_np(cols, colev.type_ids, query.predicates,
                                  query.or_groups)
        count = np.zeros((b,), dtype=np.int32)
        np.add.at(count, grp_idx, mask.astype(np.int32))
        out: Dict[str, np.ndarray] = {"count": count}
        for a in query.aggregates:
            if a.op == "count":
                continue
            col = (colev.type_ids.astype(np.int32) if a.column == "type_id"
                   else cols[a.column])
            dt = col.dtype
            if a.op == "sum":
                acc = np.zeros((b,), dtype=dt)
                np.add.at(acc, grp_idx, np.where(mask, col,
                                                 np.zeros((), dt)))
            elif a.op == "min":
                big = _sentinel("min", dt)
                acc = np.full((b,), big, dtype=dt)
                np.minimum.at(acc, grp_idx,
                              np.where(mask, col, np.asarray(big, dt)))
            else:
                small = _sentinel("max", dt)
                acc = np.full((b,), small, dtype=dt)
                np.maximum.at(acc, grp_idx,
                              np.where(mask, col, np.asarray(small, dt)))
            out[a.name] = acc  # raw: sentinels normalize after the merge
        collected.append((ids_c, out))
        scanned += n
        matched += int(count.sum())
    ids, columns, _repeated = _merge_scan_outputs(collected, query)
    return QueryResult(aggregate_ids=ids, columns=columns,
                       num_aggregates=len(next(iter(columns.values()))),
                       scanned_events=scanned, matched_events=matched,
                       chunks=len(collected))


def state_query_reference(states: Mapping[str, np.ndarray],
                          aggregate_ids: Optional[Sequence[str]],
                          query: StateQuery) -> QueryResult:
    """Numpy oracle for :meth:`QueryEngine.query_states`, given already-folded
    state columns (fold them with the scalar model in tests)."""
    n = len(next(iter(states.values()))) if states else 0
    mask = np.ones((n,), dtype=bool)
    for p in query.predicates:
        mask &= _apply_op_np(states[p.column], p.op, p.value)
    idx = np.nonzero(mask)[0]
    if query.limit is not None:
        idx = idx[: query.limit]
    select = list(query.select) if query.select is not None else list(states)
    return QueryResult(
        aggregate_ids=([aggregate_ids[i] for i in idx]
                       if aggregate_ids is not None else None),
        columns={name: np.asarray(states[name])[idx] for name in select},
        num_aggregates=len(idx), scanned_events=0, matched_events=len(idx),
        chunks=1)
