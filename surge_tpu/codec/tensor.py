"""Struct-of-arrays encoding of ragged per-aggregate event logs.

Layout (``EncodedEvents``), chosen for the TPU scan (SURVEY.md §7 "Event→tensor codec"):

- ``type_ids``: int32 ``[B, T]`` — tagged-union discriminant; ``PAD_TYPE_ID`` (-1) marks
  padding past each aggregate's log length.
- ``cols``: dict of ``[B, T]`` arrays, one per union column (see
  ``SchemaRegistry.union_columns``). Fields an event type lacks are zero-filled.
- ``lengths``: int32 ``[B]`` — true log lengths (mask = position < length).

B is the aggregate batch dimension (vmap/shard axis), T the time dimension (lax.scan
axis). Encoding is pure NumPy on the host; the replay engine moves arrays to device and
transposes to time-major itself.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Mapping, Sequence

import numpy as np

from surge_tpu.codec.schema import SchemaRegistry, StateSchema

PAD_TYPE_ID = -1


@dataclass
class EncodedEvents:
    type_ids: np.ndarray  # [B, T] int32
    cols: dict[str, np.ndarray]  # each [B, T]
    lengths: np.ndarray  # [B] int32
    # union columns the producer declares derivable on device instead of stored/
    # transferred ({name: surge_tpu.codec.wire.DERIVE_*}); e.g. positional sequence
    # numbers ({"sequence_number": "ordinal"})
    derived_cols: dict[str, str] = field(default_factory=dict)

    @property
    def batch_size(self) -> int:
        return int(self.type_ids.shape[0])

    @property
    def max_len(self) -> int:
        return int(self.type_ids.shape[1])

    def mask(self) -> np.ndarray:
        """bool [B, T]: True where a real event exists."""
        return self.type_ids != PAD_TYPE_ID

    def nbytes(self) -> int:
        return self.type_ids.nbytes + self.lengths.nbytes + sum(c.nbytes for c in self.cols.values())


@dataclass
class ColumnarEvents:
    """Flat struct-of-arrays event log: N events across B aggregates, time-ordered
    within each aggregate. This is the *storage* layout (log segments are columnar so
    bulk replay never touches Python objects — SURVEY.md §7 hard-part "host-side
    encode"); :func:`columnar_to_batch` scatters it into the padded ``[B, T]`` batch
    with pure vectorized NumPy.

    - ``agg_idx``: int32 ``[N]`` — which aggregate (dense 0..B-1) each event belongs to.
    - ``type_ids``: int32 ``[N]``.
    - ``cols``: dict of ``[N]`` arrays (union columns; zero where a type lacks a field).
    """

    num_aggregates: int
    agg_idx: np.ndarray
    type_ids: np.ndarray
    cols: dict[str, np.ndarray]
    # columns the device derives instead of reading (see EncodedEvents.derived_cols)
    derived_cols: dict[str, str] = field(default_factory=dict)
    # optional aggregate-id strings, indexed by aggregate index 0..B-1 — carried by
    # segment chunks so bulk replay can write folded states back to the keyed store
    aggregate_ids: list[str] | None = None
    # global chunk ordinal within the source segment file (set by read_segment;
    # chunks are immutable once written, so this is a stable O(1) identity for
    # caches keyed per chunk)
    source_ordinal: int | None = None
    # how the chunk lay in its segment file (set by read_segment):
    # ``stored_bytes`` read for it, the ``raw_bytes`` they decode to (columns
    # and ids), its ``codec``: "slz", "raw", or "mixed" where the writer
    # compressed some of its payloads only, and the column payloads decoded
    # and seeked past under a projection (``columns_read``, ``columns_skipped``)
    source_stored: dict | None = None

    @property
    def num_events(self) -> int:
        return int(self.type_ids.shape[0])

    def nbytes(self) -> int:
        return (self.agg_idx.nbytes + self.type_ids.nbytes
                + sum(c.nbytes for c in self.cols.values()))

    def sorted_by_aggregate(self) -> "ColumnarEvents":
        """Events grouped by aggregate (stable: per-aggregate time order preserved),
        which makes :meth:`slice_aggregates` a contiguous O(1)-index slice."""
        if self.agg_idx.size and np.all(np.diff(self.agg_idx) >= 0):
            return self
        order = np.argsort(self.agg_idx, kind="stable")
        return ColumnarEvents(
            num_aggregates=self.num_aggregates, agg_idx=self.agg_idx[order],
            type_ids=self.type_ids[order],
            cols={k: v[order] for k, v in self.cols.items()},
            derived_cols=dict(self.derived_cols),
            aggregate_ids=self.aggregate_ids)

    def slice_aggregates(self, start: int, stop: int) -> "ColumnarEvents":
        """Sub-log for aggregates [start, stop). Requires aggregate-sorted order
        (see :meth:`sorted_by_aggregate`); re-indexes agg_idx to 0..(stop-start)."""
        lo, hi = np.searchsorted(self.agg_idx, (start, stop))
        return ColumnarEvents(
            num_aggregates=stop - start,
            agg_idx=self.agg_idx[lo:hi] - np.int32(start),
            type_ids=self.type_ids[lo:hi],
            cols={k: v[lo:hi] for k, v in self.cols.items()},
            derived_cols=dict(self.derived_cols),
            aggregate_ids=(None if self.aggregate_ids is None
                           else self.aggregate_ids[start:stop]))


def columnar_to_batch(colev: ColumnarEvents, pad_to: int | None = None) -> EncodedEvents:
    """Scatter a flat columnar log into the padded ``[B, T]`` batch. Fully vectorized
    (one stable argsort + one fancy-index scatter per column); no per-event Python."""
    b = colev.num_aggregates
    n = colev.num_events
    lengths = np.bincount(colev.agg_idx, minlength=b).astype(np.int32)
    t = int(pad_to) if pad_to is not None else int(lengths.max(initial=0))
    if lengths.size and int(lengths.max(initial=0)) > t:
        raise ValueError(f"pad_to={t} < longest log {int(lengths.max())}")

    # stable sort groups events by aggregate while preserving per-aggregate time order;
    # sorted_by_aggregate is a no-op on the hot path (replay_columnar slices an
    # already-sorted log)
    srt = colev.sorted_by_aggregate()
    sorted_agg, src_tids, src_cols = srt.agg_idx, srt.type_ids, srt.cols
    starts = np.zeros(b + 1, dtype=np.int64)
    np.cumsum(lengths, out=starts[1:])
    slot = np.arange(n, dtype=np.int64) - starts[sorted_agg]

    type_ids = np.full((b, t), PAD_TYPE_ID, dtype=np.int32)
    type_ids[sorted_agg, slot] = src_tids
    cols = {}
    for name, col in src_cols.items():
        buf = np.zeros((b, t), dtype=col.dtype)
        buf[sorted_agg, slot] = col
        cols[name] = buf
    return EncodedEvents(type_ids=type_ids, cols=cols, lengths=lengths,
                         derived_cols=dict(colev.derived_cols))


def encode_events_columnar(registry: SchemaRegistry,
                           event_logs: Sequence[Sequence[Any]]) -> ColumnarEvents:
    """Flatten object logs into the columnar layout. Groups the per-event Python work
    by event type so each field extracts in one comprehension per (type, field) rather
    than a nested per-event/per-field loop."""
    union = registry.union_columns()
    flat: list[Any] = []
    agg_idx_parts: list[np.ndarray] = []
    for i, log in enumerate(event_logs):
        flat.extend(log)
        agg_idx_parts.append(np.full(len(log), i, dtype=np.int32))
    n = len(flat)
    agg_idx = (np.concatenate(agg_idx_parts) if agg_idx_parts
               else np.zeros(0, dtype=np.int32))

    type_ids = np.empty(n, dtype=np.int32)
    by_type: dict[type, list[int]] = {}
    for k, ev in enumerate(flat):
        by_type.setdefault(type(ev), []).append(k)
    cols = {f.name: np.zeros(n, dtype=f.dtype) for f in union}
    for cls, idxs in by_type.items():
        schema = registry.schema_for_cls(cls)
        ii = np.asarray(idxs, dtype=np.int64)
        type_ids[ii] = schema.type_id
        getter = schema.getter
        for f in schema.fields:
            name = f.name
            cols[name][ii] = [getter(flat[k], name) for k in idxs]
    return ColumnarEvents(num_aggregates=len(event_logs), agg_idx=agg_idx,
                          type_ids=type_ids, cols=cols)


def encode_events(registry: SchemaRegistry, event_logs: Sequence[Sequence[Any]],
                  pad_to: int | None = None) -> EncodedEvents:
    """Encode ragged per-aggregate event lists into a dense tagged-union batch."""
    colev = encode_events_columnar(registry, event_logs)
    enc = columnar_to_batch(colev, pad_to=pad_to)
    return enc


def decode_events(registry: SchemaRegistry, enc: EncodedEvents) -> list[list[Any]]:
    """Inverse of :func:`encode_events` — for golden round-trip tests."""
    out: list[list[Any]] = []
    for i in range(enc.batch_size):
        log: list[Any] = []
        for j in range(int(enc.lengths[i])):
            tid = int(enc.type_ids[i, j])
            schema = registry.schema_for_id(tid)
            kwargs = {}
            for f in schema.fields:
                v = enc.cols[f.name][i, j]
                if f.dtype.kind == "b":
                    kwargs[f.name] = bool(v)
                elif f.dtype.kind in "iu":
                    kwargs[f.name] = int(v)
                else:
                    kwargs[f.name] = float(v)
            log.append(_construct(schema.cls, kwargs))
        out.append(log)
    return out


_EXCLUDED_DEFAULTS = {str: "", int: 0, float: 0.0, bool: False}


def _excluded_defaults(cls: type, given) -> dict[str, Any]:
    """Neutral values for the dataclass fields of ``cls`` that ``given`` (the
    names the tensor schema carries) leaves out, e.g. aggregate-id strings. A
    field with a default or a factory of its own is left to the class."""
    out: dict[str, Any] = {}
    for f in dataclasses.fields(cls):
        if f.name in given:
            continue
        if f.default is not dataclasses.MISSING or f.default_factory is not dataclasses.MISSING:  # type: ignore[misc]
            continue
        ann = f.type if isinstance(f.type, type) else {"str": str, "int": int,
                                                       "float": float, "bool": bool}.get(str(f.type))
        out[f.name] = _EXCLUDED_DEFAULTS.get(ann, None)
    return out


def _construct(cls: type, kwargs: dict[str, Any]) -> Any:
    """Build a dataclass instance, filling fields excluded from the tensor schema
    (e.g. aggregate-id strings) with neutral defaults."""
    return cls(**kwargs, **_excluded_defaults(cls, kwargs))


def encode_states(schema: StateSchema, states: Sequence[Any]) -> dict[str, np.ndarray]:
    """Batch scalar states into the dict-of-arrays carry pytree ``{name: [B]}``."""
    out: dict[str, np.ndarray] = {}
    for f in schema.fields:
        out[f.name] = np.asarray([getattr(s, f.name) for s in states], dtype=f.dtype)
    return out


_KIND_TYPES = {"b": bool, "i": int, "u": int, "f": float}


def state_columns(schema: StateSchema, tree: Mapping[str, np.ndarray],
                  count: int | None = None) -> list[list]:
    """The first ``count`` rows (all by default) of a state tree as plain
    Python values, a list a schema field in field order: what
    :func:`state_materializer`'s constructor indexes. One C-speed ``tolist()``
    a column gives every cell the type ``StateSchema.from_record`` converts it
    to (bool / int / float by the field's dtype kind); only a column that
    arrives in another kind than its field's is converted a cell."""
    cols = []
    for f in schema.fields:
        a = np.asarray(tree[f.name])
        col = (a if count is None else a[:count]).tolist()
        want = _KIND_TYPES.get(f.dtype.kind)
        if want is not None and _KIND_TYPES.get(a.dtype.kind) is not want:
            col = list(map(want, col))
        cols.append(col)
    return cols


def state_materializer(schema: StateSchema, decode_state=None, *,
                       with_ids: bool = False):
    """``make(aggregate_id, cols, j)``: row ``j`` of :func:`state_columns`'
    ``cols`` as one state object of ``schema.cls``. The row-to-state step of
    every bulk path (:func:`decode_states` for the restores of
    ``store/restore.py``, the resident plane's gather lane), worked out once a
    schema and not once a row: the field names in order, the excluded fields'
    neutral values (:func:`_excluded_defaults`) and where the aggregate id
    goes are compiled into one keyword call of the class.

    Without ``with_ids`` the state is ``schema.from_record``'s of the row and
    ``aggregate_id`` is not read. With it, the state is what
    ``store.restore._with_aggregate_id`` makes of that: a dataclass field
    named ``aggregate_id`` which the class gives no default, or an empty one,
    takes the id at construction (a non-empty default or a factory is left
    to the class). ``decode_state(aggregate_id, state)``, the model's hook,
    is then called on every state when given."""
    cls = schema.cls
    names = schema.field_names
    fixed = _excluded_defaults(cls, names)
    parts = [f"{n}=c[{i}][j]" for i, n in enumerate(names)]
    if with_ids:
        own = next((f for f in dataclasses.fields(cls)
                    if f.name == "aggregate_id" and f.name not in names), None)
        if own is not None and own.default_factory is dataclasses.MISSING and (  # type: ignore[misc]
                own.default is dataclasses.MISSING or not own.default):
            fixed.pop("aggregate_id", None)
            parts.append("aggregate_id=a")
    # codegen the constructor call (field names are dataclass identifiers):
    # one keyword call a row indexing straight into the tolist'd columns, no
    # kwargs dict, no per-row tuple
    consts = {f"_fixed{i}": v for i, v in enumerate(fixed.values())}
    parts += [f"{n}={k}" for n, k in zip(fixed, consts)]
    base = eval(  # noqa: S307 — names come from dataclass fields
        f"lambda a, c, j: _cls({', '.join(parts)})", {"_cls": cls, **consts})
    if decode_state is None:
        return base
    return lambda agg_id, c, j: decode_state(agg_id, base(agg_id, c, j))


def decode_states(schema: StateSchema, tree: Mapping[str, np.ndarray]) -> list[Any]:
    """Inverse of :func:`encode_states`: one state object a row, equal to
    ``schema.from_record`` of that row (types included), built by
    :func:`state_materializer` from :func:`state_columns`."""
    cols = state_columns(schema, tree)
    if not cols:
        return []
    make = state_materializer(schema)
    return [make(None, cols, j) for j in range(len(cols[0]))]


def bucket_lengths(lengths: Sequence[int], buckets: Sequence[int]) -> dict[int, list[int]]:
    """Group aggregate indices into padded-length buckets (ragged batching).

    Returns {bucket_cap: [indices]} where each log fits its bucket. Logs longer than the
    largest bucket go into a final bucket rounded up to the next multiple of it.
    """
    if not buckets:
        raise ValueError("need at least one bucket size")
    caps = sorted(buckets)
    groups: dict[int, list[int]] = {}
    for idx, ln in enumerate(lengths):
        cap = next((c for c in caps if ln <= c), None)
        if cap is None:
            biggest = caps[-1]
            cap = ((ln + biggest - 1) // biggest) * biggest
        groups.setdefault(cap, []).append(idx)
    return groups
