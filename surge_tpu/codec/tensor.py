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
    # and ids), and its ``codec``: "slz", "raw", or "mixed" where the writer
    # compressed some of its payloads only
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


def _construct(cls: type, kwargs: dict[str, Any]) -> Any:
    """Build a dataclass instance, filling fields excluded from the tensor schema
    (e.g. aggregate-id strings) with neutral defaults."""
    import dataclasses

    for f in dataclasses.fields(cls):
        if f.name in kwargs:
            continue
        if f.default is not dataclasses.MISSING or f.default_factory is not dataclasses.MISSING:  # type: ignore[misc]
            continue
        ann = f.type if isinstance(f.type, type) else {"str": str, "int": int,
                                                       "float": float, "bool": bool}.get(str(f.type))
        kwargs[f.name] = _EXCLUDED_DEFAULTS.get(ann, None)
    return cls(**kwargs)


def encode_states(schema: StateSchema, states: Sequence[Any]) -> dict[str, np.ndarray]:
    """Batch scalar states into the dict-of-arrays carry pytree ``{name: [B]}``."""
    out: dict[str, np.ndarray] = {}
    for f in schema.fields:
        out[f.name] = np.asarray([getattr(s, f.name) for s in states], dtype=f.dtype)
    return out


def decode_states(schema: StateSchema, tree: Mapping[str, np.ndarray]) -> list[Any]:
    """Inverse of :func:`encode_states`."""
    arrays = {f.name: np.asarray(tree[f.name]) for f in schema.fields}
    b = len(next(iter(arrays.values()))) if arrays else 0
    return [schema.from_record({n: a[i] for n, a in arrays.items()}) for i in range(b)]


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
