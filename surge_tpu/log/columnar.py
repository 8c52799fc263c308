"""Columnar event-log segments: the bulk-replay storage format.

SURVEY.md §7 hard-part 3: folding a 100M-event topic cannot afford per-event Python
objects — the reference's restore path (Kafka Streams changelog scan) streams record
batches; the TPU-native equivalent streams **struct-of-arrays chunks** straight into
:meth:`surge_tpu.replay.ReplayEngine.replay_columnar`. This module is the durable
form of :class:`~surge_tpu.codec.tensor.ColumnarEvents`:

- A **segment file** holds a header (schema: columns, dtypes, derived-column
  declarations) and a sequence of chunks. Each chunk covers a disjoint, contiguous
  range of aggregates (aggregate-sorted), so chunks replay independently and their
  state columns concatenate.
- Column bytes are SLZ-compressed per column (csrc/segment.cc) when the native codec
  is built — event streams compress well (narrow dtypes, repeated patterns).
- ``build_segment_from_topic`` is the offline conversion job: read an events topic
  through the app's event format once, encode columnar, write the segment. Replays
  after that never touch Python objects again (the role of Kafka's compacted-restore
  optimization, performed once instead of per cold start).

Layout (little-endian):
    magic "SCOL" | u32 header_len | header JSON |
    per section: u32 marker | u32 meta_len | meta JSON | payloads
    - chunk section (marker "CHK1"): column payloads in meta order (raw or SLZ per
      meta); meta may also carry an "ids" payload (newline-joined aggregate-id
      strings) so replay can write folded states back to the keyed store
    - snapshot section (marker "SNP1"): one uvarint-framed key/value blob holding
      the latest state snapshots of aggregates ABSENT from the events topic
      (state-only publishes) — the checkpoint-carry that lets a segment restore
      skip the post-replay state-topic scan entirely
Header JSON: {"columns": {name: dtype_str}, "derived": {...}, "type_dtype": str,
              "extra": {...}} — "extra" carries build-time metadata such as the
source topic watermarks (see build_segment_from_topic).
Chunk meta JSON: {"num_aggregates": n, "num_events": m,
                  "cols": [[name, codec, stored_len, raw_len], ...],
                  "ids": [codec, stored_len, raw_len] | absent}  — cols includes the
implicit "agg_idx" and "type_ids" columns.
"""

from __future__ import annotations

import json
import struct
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional

import numpy as np

from surge_tpu.codec.tensor import ColumnarEvents
from surge_tpu.log import segment as seg

MAGIC = b"SCOL"
CHUNK_MARKER = 0x43484B31
SNAPSHOT_MARKER = 0x534E5031  # "SNP1"
WATERMARK_MARKER = 0x574D4B31  # "WMK1" — extend-time watermark override (no payload)
EXTEND_MARKER = 0x45585442  # "EXTB" — length-framed extend batch (crash guard)


def _encode_array(arr: np.ndarray):
    raw = np.ascontiguousarray(arr).tobytes()
    compressed = seg.slz_compress(raw)
    if compressed is not None:
        return seg.CODEC_SLZ, compressed, len(raw)
    return seg.CODEC_RAW, raw, len(raw)


#: threads that decompress one chunk's column payloads side by side
#: (:func:`read_segment`): the codec is native code that releases the
#: interpreter lock, and a chunk has a handful of payloads
_DECODE_THREADS = 4


def _decode_array(data: bytes, codec: int, raw_len: int, dtype: np.dtype) -> np.ndarray:
    """A stored column payload as its array: a raw one as a view of the bytes
    read, a compressed one decompressed straight into the array's own buffer
    (a column is tens of megabytes: a staging buffer and a copy out of it cost
    as much as the decompression)."""
    if codec != seg.CODEC_SLZ:
        return np.frombuffer(data, dtype=dtype)
    if raw_len % dtype.itemsize:
        raise ValueError(f"a payload of {raw_len} bytes is no whole {dtype} column")
    out = np.empty(raw_len // dtype.itemsize, dtype=dtype)
    seg.slz_decompress_into(data, out)
    return out


class ColumnarSegmentWriter:
    """Appends aggregate-range chunks of a model family's event log."""

    def __init__(self, path: str, extra_header: Optional[dict] = None) -> None:
        self.path = path
        self._file = None
        self._header_written = False
        self._schema: Optional[dict] = None
        self._extra = dict(extra_header or {})
        self._total_aggregates = 0
        self._total_events = 0
        self._extend_target: Optional[str] = None

    @classmethod
    def extend(cls, path: str) -> "ColumnarSegmentWriter":
        """Open an EXISTING segment for appending delta sections (incremental
        maintenance, SURVEY.md §5.4 compaction-as-checkpoint role). The header
        stays immutable; updated watermarks ride a WMK section (see
        :meth:`write_watermarks`) and chunks whose schema diverges from the
        header (e.g. delta chunks storing a column the base derives) carry
        per-chunk overrides in their meta.

        Crash safety: delta sections are staged in memory and appended on
        ``close()`` as ONE length-framed EXTB super-section (fsync'd). Readers
        validate the frame length, so a torn append is ignored wholesale — the
        segment is always either pre- or post-extend, never half."""
        import io

        with open(path, "rb") as f:
            head = f.read(8)
            if head[:4] != MAGIC:
                raise ValueError(f"{path}: not a columnar segment")
            (hlen,) = struct.unpack("<I", head[4:8])
            schema = json.loads(f.read(hlen))
        w = cls(path, extra_header=schema.get("extra"))
        w._schema = schema
        w._file = io.BytesIO()
        w._extend_target = path
        return w

    def write_watermarks(self, watermarks: dict,
                         state_watermarks: Optional[dict] = None) -> None:
        """Append a watermark-override section: readers treat the LAST one as
        authoritative over the header's build-time extra."""
        if self._file is None:
            raise ValueError("no open segment")
        meta_obj: dict = {"watermarks": {str(k): int(v)
                                         for k, v in watermarks.items()}}
        if state_watermarks is not None:
            meta_obj["state_watermarks"] = {str(k): int(v)
                                            for k, v in state_watermarks.items()}
        meta = json.dumps(meta_obj).encode()
        self._file.write(struct.pack("<II", WATERMARK_MARKER, len(meta)) + meta)

    def _write_header(self, schema: dict) -> None:
        # Fresh segment at this path: stamp a per-build identity into the
        # header (restore's sidecar wire cache keys on it — a rebuilt segment
        # whose chunk happens to share an ordinal+event-count with the old
        # build must never hit the old build's cached wires, ADVICE r4) and
        # drop any leftover sidecar cache from a previous build outright.
        # extend() never lands here, so extends keep the base build's id —
        # correct, since extends only APPEND chunks at new ordinals.
        import shutil
        import uuid

        self._extra.setdefault("build_id", uuid.uuid4().hex)
        shutil.rmtree(f"{self.path}.wires", ignore_errors=True)
        self._file = open(self.path, "wb")
        header = json.dumps(schema).encode()
        self._file.write(MAGIC + struct.pack("<I", len(header)) + header)
        self._schema = schema

    def append(self, colev: ColumnarEvents,
               partition: Optional[int] = None) -> None:
        """Append one chunk. Every chunk must share the first chunk's column schema;
        each holds its own disjoint aggregate range (ids are chunk-local 0..n).
        ``colev.aggregate_ids`` (if set) is persisted alongside the columns.
        ``partition`` records which source partition the chunk's aggregates belong
        to, enabling partition-scoped restore (SURVEY.md §3.3 per-task restore)."""
        colev = colev.sorted_by_aggregate()
        schema = {
            "columns": {name: str(col.dtype) for name, col in sorted(colev.cols.items())},
            "derived": dict(colev.derived_cols),
            "type_dtype": str(colev.type_ids.dtype),
            "agg_dtype": str(colev.agg_idx.dtype),
            "extra": self._extra,
        }
        overrides: dict = {}
        if self._file is None:
            self._write_header(schema)
        elif schema != self._schema:
            # a chunk may diverge from the header schema (delta chunks STORE a
            # column the base chunks derive on-device, since their events'
            # ordinals are absolute, not 1-based): persist per-chunk overrides
            # the reader prefers over the header
            overrides = {"dtypes": schema["columns"],
                         "chunk_derived": schema["derived"],
                         "type_dtype": schema["type_dtype"],
                         "agg_dtype": schema["agg_dtype"]}

        cols_meta = []
        payloads = []
        for name, arr in [("agg_idx", colev.agg_idx), ("type_ids", colev.type_ids)] + \
                sorted(colev.cols.items()):
            codec, stored, raw_len = _encode_array(arr)
            cols_meta.append([name, codec, len(stored), raw_len])
            payloads.append(stored)
        meta_obj = {
            "num_aggregates": colev.num_aggregates,
            "num_events": colev.num_events,
            "cols": cols_meta,
            **overrides,
        }
        if partition is not None:
            meta_obj["partition"] = int(partition)
        if colev.aggregate_ids is not None:
            if len(colev.aggregate_ids) != colev.num_aggregates:
                raise ValueError("aggregate_ids length != num_aggregates")
            if any("\n" in i or not i for i in colev.aggregate_ids):
                raise ValueError("aggregate ids must be non-empty and newline-free "
                                 "(newline is the id separator)")
            raw = "\n".join(colev.aggregate_ids).encode()
            compressed = seg.slz_compress(raw)
            if compressed is not None:
                meta_obj["ids"] = [seg.CODEC_SLZ, len(compressed), len(raw)]
                payloads.append(compressed)
            else:
                meta_obj["ids"] = [seg.CODEC_RAW, len(raw), len(raw)]
                payloads.append(raw)
        meta = json.dumps(meta_obj).encode()
        self._file.write(struct.pack("<II", CHUNK_MARKER, len(meta)) + meta)
        for p in payloads:
            self._file.write(p)
        self._total_aggregates += colev.num_aggregates
        self._total_events += colev.num_events

    def append_snapshots(self, items, partition: Optional[int] = None) -> None:
        """Write a snapshot section: latest serialized states of aggregates the
        events topic does not cover (state-only publishes). ``items`` is an
        iterable of ``(key: str, value: bytes)``; ``partition`` scopes the section
        to one source state partition for partition-scoped restore."""
        if self._file is None:
            raise ValueError("append at least one chunk before snapshots")
        blob = bytearray()
        count = 0
        for key, value in items:
            kb = key.encode()
            seg._put_uvarint(blob, len(kb))
            blob += kb
            seg._put_uvarint(blob, len(value))
            blob += value
            count += 1
        raw = bytes(blob)
        compressed = seg.slz_compress(raw)
        if compressed is not None:
            meta_obj = {"count": count, "blob": [seg.CODEC_SLZ, len(compressed), len(raw)]}
            payload = compressed
        else:
            meta_obj = {"count": count, "blob": [seg.CODEC_RAW, len(raw), len(raw)]}
            payload = raw
        if partition is not None:
            meta_obj["partition"] = int(partition)
        meta = json.dumps(meta_obj).encode()
        self._file.write(struct.pack("<II", SNAPSHOT_MARKER, len(meta)) + meta)
        self._file.write(payload)

    def close(self) -> None:
        if self._file is None:
            return
        if self._extend_target is not None:
            import os

            blob = self._file.getvalue()
            self._file = None
            if blob:
                frame = struct.pack("<II", EXTEND_MARKER, len(blob))
                with open(self._extend_target, "ab") as f:
                    f.write(frame + blob)
                    f.flush()
                    os.fsync(f.fileno())
            return
        self._file.flush()
        self._file.close()
        self._file = None

    def __enter__(self) -> "ColumnarSegmentWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_segment(path: str,
                 partitions: Optional[set] = None,
                 columns: Optional[Iterable[str]] = None
                 ) -> Iterator[ColumnarEvents]:
    """Stream the segment's chunks back as ColumnarEvents (a raw payload as a
    zero-copy view of the bytes read, a compressed one decompressed straight
    into its own array; a chunk's payloads are read in file order and
    decompressed side by side on ``_DECODE_THREADS`` threads, which last as
    long as the iteration). ``partitions`` keeps only chunks
    whose recorded source partition is in the set — chunks without partition
    metadata (pre-scoping segments) always pass, and their payloads are seeked
    past, not decompressed, when filtered out.

    ``columns`` is the query engine's projection pushdown: when given, only
    those union columns (plus the structural ``agg_idx``/``type_ids`` and the
    id payload) are decompressed — every other column payload is seeked past.
    The yielded chunks then carry exactly the projected ``cols``; callers that
    need the full schema must not pass ``columns``."""
    import os as _os

    if partitions is not None:
        partitions = {int(p) for p in partitions}
    wanted = None if columns is None else set(columns)
    with ThreadPoolExecutor(
            max_workers=_DECODE_THREADS,
            thread_name_prefix="surge-segment-decode") as decoders, \
            open(path, "rb") as f:
        size = _os.fstat(f.fileno()).st_size
        head = f.read(8)
        if head[:4] != MAGIC:
            raise ValueError(f"{path}: not a columnar segment")
        (hlen,) = struct.unpack("<I", head[4:8])
        header = json.loads(f.read(hlen))
        col_dtypes = {name: np.dtype(dt) for name, dt in header["columns"].items()}
        type_dtype = np.dtype(header["type_dtype"])
        agg_dtype = np.dtype(header["agg_dtype"])
        derived = dict(header.get("derived", {}))

        ordinal = -1  # global chunk ordinal (counts filtered chunks too)
        while True:
            prefix = f.read(8)
            if len(prefix) < 8:
                return  # end of file (or torn final append)
            marker, mlen = struct.unpack("<II", prefix)
            if marker == EXTEND_MARKER:
                if size - f.tell() < mlen:
                    return  # torn extend append: ignore wholesale (crash guard)
                continue  # validated: inner sections follow normally
            if marker not in (CHUNK_MARKER, SNAPSHOT_MARKER, WATERMARK_MARKER):
                raise ValueError(f"{path}: bad section marker {marker:#x}")
            meta = json.loads(f.read(mlen))
            if marker == WATERMARK_MARKER:  # no payload; segment_info reads it
                continue
            if marker == SNAPSHOT_MARKER:  # not a chunk; read via read_segment_snapshots
                f.seek(meta["blob"][1], 1)
                continue
            ordinal += 1
            if (partitions is not None and "partition" in meta
                    and meta["partition"] not in partitions):
                skip = sum(c[2] for c in meta["cols"])
                if "ids" in meta:
                    skip += meta["ids"][1]
                f.seek(skip, 1)
                continue
            # per-chunk schema overrides (delta chunks may store a column the
            # header declares derived)
            c_cols = ({n: np.dtype(d) for n, d in meta["dtypes"].items()}
                      if "dtypes" in meta else col_dtypes)
            c_type = np.dtype(meta["type_dtype"]) if "type_dtype" in meta else type_dtype
            c_agg = np.dtype(meta["agg_dtype"]) if "agg_dtype" in meta else agg_dtype
            c_derived = (dict(meta["chunk_derived"]) if "chunk_derived" in meta
                         else dict(derived))
            arrays = {}
            stored_bytes = raw_bytes = columns_skipped = 0
            codecs = set()
            for name, codec, stored_len, raw_len in meta["cols"]:
                if (wanted is not None
                        and name not in ("agg_idx", "type_ids")
                        and name not in wanted):
                    f.seek(stored_len, 1)  # projected out: never decompressed
                    columns_skipped += 1
                    continue
                dtype = (c_agg if name == "agg_idx"
                         else c_type if name == "type_ids"
                         else c_cols[name])
                # the payloads are read in file order and decompressed side
                # by side, while the ids below are read and split
                arrays[name] = decoders.submit(
                    _decode_array, f.read(stored_len), codec, raw_len, dtype)
                stored_bytes += stored_len
                raw_bytes += raw_len
                codecs.add(codec)
            ids = None
            if "ids" in meta:
                codec, stored_len, raw_len = meta["ids"]
                stored_bytes += stored_len
                raw_bytes += raw_len
                codecs.add(codec)
                raw = f.read(stored_len)
                if codec == seg.CODEC_SLZ:
                    raw = seg.slz_decompress(raw, raw_len)
                ids = raw.decode().split("\n") if raw else []
                if len(ids) != meta["num_aggregates"]:
                    raise ValueError(
                        f"{path}: id count {len(ids)} != aggregates "
                        f"{meta['num_aggregates']} — corrupt chunk")
            arrays = {name: decoded.result()
                      for name, decoded in arrays.items()}
            yield ColumnarEvents(
                num_aggregates=meta["num_aggregates"],
                agg_idx=arrays.pop("agg_idx"),
                type_ids=arrays.pop("type_ids"),
                cols=arrays,
                derived_cols=c_derived,
                aggregate_ids=ids,
                source_ordinal=ordinal,
                source_stored={
                    "stored_bytes": stored_bytes, "raw_bytes": raw_bytes,
                    "codec": ("mixed" if len(codecs) > 1 else
                              "slz" if codecs == {seg.CODEC_SLZ} else "raw"),
                    # column payloads decoded (agg_idx and type_ids among
                    # them) and seeked past under a projection
                    "columns_read": len(meta["cols"]) - columns_skipped,
                    "columns_skipped": columns_skipped})


def segment_info(path: str) -> dict:
    """Totals + schema without decompressing column payloads. The schema's
    ``extra`` watermarks reflect the LAST watermark-override section, so an
    incrementally extended segment reports its post-extend coverage."""
    import os as _os

    total_aggregates = total_events = num_chunks = num_snapshots = 0
    num_extends = 0
    with open(path, "rb") as f:
        size = _os.fstat(f.fileno()).st_size
        head = f.read(8)
        if head[:4] != MAGIC:
            raise ValueError(f"{path}: not a columnar segment")
        (hlen,) = struct.unpack("<I", head[4:8])
        header = json.loads(f.read(hlen))
        while True:
            prefix = f.read(8)
            if len(prefix) < 8:
                break  # end of file (or torn final append)
            marker, mlen = struct.unpack("<II", prefix)
            if marker == EXTEND_MARKER:
                if size - f.tell() < mlen:
                    break  # torn extend append: ignore wholesale
                num_extends += 1
                continue
            if marker not in (CHUNK_MARKER, SNAPSHOT_MARKER, WATERMARK_MARKER):
                raise ValueError(f"{path}: bad section marker {marker:#x}")
            meta = json.loads(f.read(mlen))
            if marker == WATERMARK_MARKER:
                header.setdefault("extra", {}).update(meta)
                continue
            if marker == SNAPSHOT_MARKER:
                f.seek(meta["blob"][1], 1)
                num_snapshots += meta["count"]
                continue
            skip = sum(c[2] for c in meta["cols"])
            if "ids" in meta:
                skip += meta["ids"][1]
            f.seek(skip, 1)
            total_aggregates += meta["num_aggregates"]
            total_events += meta["num_events"]
            num_chunks += 1
    return {"schema": header, "num_aggregates": total_aggregates,
            "num_events": total_events, "num_chunks": num_chunks,
            "num_snapshots": num_snapshots, "num_extends": num_extends}


def read_segment_snapshots(path: str,
                           partitions: Optional[set] = None) -> Iterator[tuple]:
    """Stream the snapshot sections' ``(key, value)`` pairs (state-only
    aggregates). ``partitions`` keeps only sections recorded for those source
    state partitions (sections without partition metadata always pass)."""
    import os as _os

    if partitions is not None:
        partitions = {int(p) for p in partitions}
    with open(path, "rb") as f:
        size = _os.fstat(f.fileno()).st_size
        head = f.read(8)
        if head[:4] != MAGIC:
            raise ValueError(f"{path}: not a columnar segment")
        (hlen,) = struct.unpack("<I", head[4:8])
        f.seek(hlen, 1)
        while True:
            prefix = f.read(8)
            if len(prefix) < 8:
                return  # end of file (or torn final append)
            marker, mlen = struct.unpack("<II", prefix)
            if marker == EXTEND_MARKER:
                if size - f.tell() < mlen:
                    return  # torn extend append: ignore wholesale
                continue
            if marker not in (CHUNK_MARKER, SNAPSHOT_MARKER, WATERMARK_MARKER):
                raise ValueError(f"{path}: bad section marker {marker:#x}")
            meta = json.loads(f.read(mlen))
            if marker == WATERMARK_MARKER:
                continue
            if marker != SNAPSHOT_MARKER:
                skip = sum(c[2] for c in meta["cols"])
                if "ids" in meta:
                    skip += meta["ids"][1]
                f.seek(skip, 1)
                continue
            if (partitions is not None and "partition" in meta
                    and meta["partition"] not in partitions):
                f.seek(meta["blob"][1], 1)
                continue
            codec, stored_len, raw_len = meta["blob"]
            raw = f.read(stored_len)
            if codec == seg.CODEC_SLZ:
                raw = seg.slz_decompress(raw, raw_len)
            pos = 0
            for _ in range(meta["count"]):
                klen, pos = seg._get_uvarint(raw, pos)
                key = raw[pos: pos + klen].decode()
                pos += klen
                vlen, pos = seg._get_uvarint(raw, pos)
                value = raw[pos: pos + vlen]
                pos += vlen
                yield key, value


def _drop_derived(colev: ColumnarEvents, derived_cols: dict) -> None:
    """Remove columns the device will re-derive — after VERIFYING the data really
    matches the derivation (an ordinal declaration over a column whose values are
    not positional would silently corrupt the replay)."""
    n = colev.num_events
    if n:
        starts = np.zeros(colev.num_aggregates + 1, dtype=np.int64)
        np.cumsum(np.bincount(colev.agg_idx, minlength=colev.num_aggregates),
                  out=starts[1:])
        ordinal = np.arange(n, dtype=np.int64) - starts[colev.agg_idx] + 1
    for name, kind in derived_cols.items():
        col = colev.cols.get(name)
        if col is not None:
            if kind == "ordinal" and n and not np.array_equal(
                    col.astype(np.int64), ordinal):
                raise ValueError(
                    f"column {name!r} declared derived as ordinal but its values "
                    f"are not positional — refusing to drop it")
            del colev.cols[name]
        colev.derived_cols[name] = kind


def build_segment_from_topic(log, topic: str, registry, deserialize_event,
                             path: str, partitions=None,
                             encode_event=None,
                             derived_cols: Optional[dict] = None,
                             chunk_aggregates: int = 65536,
                             state_topic: Optional[str] = None) -> dict:
    """Offline conversion job: events topic → columnar segment.

    Reads every partition's records once, groups events per aggregate (key),
    encodes them columnar via the registry, and writes aggregate-range chunks
    with their aggregate ids. ``encode_event`` maps raw events to tensor-schema
    form first (e.g. vocab dictionary encoding). Returns ``segment_info(path)``.

    The header's ``extra`` records the source watermarks at build time so a
    restore can prime the indexer exactly where the segment's coverage ends.
    When ``state_topic`` is given, the latest snapshots of aggregates ABSENT
    from the events topic (state-only publishes) are carried in a snapshot
    section, making the segment a complete cold-start image — the restore needs
    no state-topic scan (the Kafka Streams restore equivalent,
    AggregateStateStoreKafkaStreams.scala:53-178, performed once at build).
    """
    import os
    import shutil
    import tempfile

    from surge_tpu.codec.tensor import encode_events_columnar
    from surge_tpu.serialization import SerializedMessage

    from surge_tpu.log.transport import page_keyed_records

    if partitions is None:
        partitions = range(log.num_partitions(topic))
    partitions = list(partitions)

    # Watermarks are captured FIRST and every pass is clamped to them: on a
    # LIVE topic, records committed mid-build would otherwise be seen by the
    # spill pass but not the key census (KeyError on a brand-new key) or be
    # folded despite lying past the recorded watermark (double-applied when
    # the indexer resumes there). Clamping gives the build one consistent
    # snapshot; later records belong to the tailing indexer / a later extend.
    wm_int = {p: log.end_offset(topic, p) for p in partitions}
    watermarks = {str(p): off for p, off in wm_int.items()}

    def scan(p: int):
        """Paged snapshot scan (restore-consumer-max-poll-records role,
        common reference.conf:198-199) — a 100M-event topic never
        materializes as one Python list."""
        return page_keyed_records(log, topic, p, upto=wm_int[p])

    # Pass 1: key census only (key → source partition) — O(num_aggregates)
    # memory, no event objects.
    key_partition: dict[str, int] = {}
    for p in partitions:
        for r in scan(p):
            key_partition[r.key] = p
    # chunks are PER PARTITION (sorted keys within each) so a node can restore
    # only its assigned partitions' chunks (SURVEY.md §3.3 per-task restore)
    ordered: list[str] = []
    chunk_plan: list[tuple[int, list[str]]] = []  # (partition, keys)
    for p in partitions:
        keys_p = sorted(k for k, kp in key_partition.items() if kp == p)
        ordered.extend(keys_p)
        for start in range(0, len(keys_p), chunk_aggregates):
            chunk_plan.append((p, keys_p[start: start + chunk_aggregates]))
    chunk_of = {k: i for i, (_, ks) in enumerate(chunk_plan) for k in ks}
    num_chunks = len(chunk_plan)

    extra: dict = {"topic": topic, "watermarks": watermarks}
    snapshots_by_partition: dict[int, list[tuple]] = {}
    if state_topic is not None:
        state_watermarks: dict[str, int] = {}
        for p in range(log.num_partitions(state_topic)):
            for key, rec in log.latest_by_key(state_topic, p).items():
                if key not in key_partition and rec.value:
                    snapshots_by_partition.setdefault(p, []).append((key, rec.value))
            state_watermarks[str(p)] = log.end_offset(state_topic, p)
        extra["state_topic"] = state_topic
        extra["state_watermarks"] = state_watermarks

    # Pass 2: spill each record's raw bytes into its chunk-range file, then
    # encode one chunk at a time — peak footprint is ONE chunk's events plus the
    # key census, not the whole corpus (advisor r3 finding #4). Per-key event
    # order is preserved: a key lives in one partition and each partition is
    # scanned in offset order.
    spill_dir = tempfile.mkdtemp(prefix=".scol-build-",
                                 dir=os.path.dirname(path) or ".")
    try:
        spills = [open(os.path.join(spill_dir, f"c{i}"), "wb", buffering=1 << 20)
                  for i in range(num_chunks)]
        try:
            for p in partitions:
                for r in scan(p):
                    kb = r.key.encode()
                    frame = bytearray()
                    seg._put_uvarint(frame, len(kb))
                    frame += kb
                    seg._put_uvarint(frame, len(r.value))
                    frame += r.value
                    spills[chunk_of[r.key]].write(frame)
        finally:
            for f in spills:
                f.close()

        def chunk_events(i: int, chunk_ids: list) -> list:
            with open(os.path.join(spill_dir, f"c{i}"), "rb") as f:
                data = f.read()
            by_key: dict[str, list] = {k: [] for k in chunk_ids}
            pos = 0
            while pos < len(data):
                klen, pos = seg._get_uvarint(data, pos)
                key = data[pos: pos + klen].decode()
                pos += klen
                vlen, pos = seg._get_uvarint(data, pos)
                ev = deserialize_event(SerializedMessage(
                    key=key, value=data[pos: pos + vlen]))
                pos += vlen
                if encode_event is not None:
                    ev = encode_event(ev)
                by_key[key].append(ev)
            return [by_key[a] for a in chunk_ids]

        with ColumnarSegmentWriter(path, extra_header=extra) as writer:
            if not chunk_plan:  # empty topic: one empty schema-bearing chunk
                colev = encode_events_columnar(registry, [])
                if derived_cols:
                    _drop_derived(colev, derived_cols)
                colev.aggregate_ids = []
                writer.append(colev)
            for i, (p, chunk_ids) in enumerate(chunk_plan):
                colev = encode_events_columnar(registry, chunk_events(i, chunk_ids))
                if derived_cols:
                    _drop_derived(colev, derived_cols)
                colev.aggregate_ids = list(chunk_ids)
                writer.append(colev, partition=p)
            for p in sorted(snapshots_by_partition):
                writer.append_snapshots(snapshots_by_partition[p], partition=p)
    finally:
        shutil.rmtree(spill_dir, ignore_errors=True)
    return {"aggregate_order": ordered, **segment_info(path)}


def extend_segment_from_topic(log, topic: str, registry, deserialize_event,
                              path: str, encode_event=None,
                              chunk_aggregates: int = 65536,
                              state_topic: Optional[str] = None) -> dict:
    """Incremental segment maintenance (VERDICT r3 next #8): append DELTA chunks
    covering events between the segment's recorded watermarks and the topic's
    current end, plus a snapshot section for aggregates whose post-build changes
    were state-only, then a watermark-override section. A later cold start
    restores from segment + delta without any full rebuild; no-op (and cheap)
    when nothing new exists.

    Delta chunks do NOT declare derived columns: their events' ordinals are
    absolute continuations, so positional columns are stored explicitly (the
    chunk meta carries the schema override) and the restore continues each
    aggregate's fold from its already-restored state via ``init_carry``.
    """
    from surge_tpu.codec.tensor import encode_events_columnar
    from surge_tpu.serialization import SerializedMessage

    info = segment_info(path)
    extra = info["schema"].get("extra", {})
    base_wm = {int(p): int(off)
               for p, off in (extra.get("watermarks") or {}).items()}
    partitions = sorted(base_wm) if base_wm else list(
        range(log.num_partitions(topic)))

    # collect the delta per partition (small by construction: post-build only);
    # the new watermark is captured BEFORE the scan and clamps it, so a live
    # producer's mid-extend commits wait for the NEXT extend instead of being
    # folded past the recorded frontier (same snapshot discipline as the build)
    from surge_tpu.log.transport import page_keyed_records

    delta: dict[int, dict[str, list]] = {}
    new_wm: dict[str, int] = {}
    delta_keys: set[str] = set()
    for p in partitions:
        new_wm[str(p)] = log.end_offset(topic, p)
        per_key: dict[str, list] = {}
        for r in page_keyed_records(log, topic, p, start=base_wm.get(p, 0),
                                    upto=int(new_wm[str(p)])):
            ev = deserialize_event(SerializedMessage(key=r.key, value=r.value))
            if encode_event is not None:
                ev = encode_event(ev)
            per_key.setdefault(r.key, []).append(ev)
            delta_keys.add(r.key)
        if per_key:
            delta[p] = per_key

    state_wm: Optional[dict] = None
    snapshots_by_partition: dict[int, list[tuple]] = {}
    if state_topic is not None:
        base_state_wm = {int(p): int(off) for p, off in
                         (extra.get("state_watermarks") or {}).items()}
        state_wm = {}
        for p in range(log.num_partitions(state_topic)):
            # aggregates changed in the delta window WITHOUT delta events
            # (state-only publishes): carry their newest snapshot
            window_keys: set = set()
            offset = base_state_wm.get(p, 0)
            while True:
                batch = log.read(state_topic, p, from_offset=offset,
                                 max_records=10_000)
                if not batch:
                    break
                window_keys.update(r.key for r in batch
                                   if r.key is not None
                                   and r.key not in delta_keys)
                offset = batch[-1].offset + 1
            if window_keys:
                latest = log.latest_by_key(state_topic, p)
                items = [(k, latest[k].value) for k in sorted(window_keys)
                         if k in latest and latest[k].value]
                if items:
                    snapshots_by_partition[p] = items
            state_wm[str(p)] = log.end_offset(state_topic, p)

    if not delta and not snapshots_by_partition:
        return info  # nothing new since the last build/extend

    # a key living only in snapshot sections has no chunk state to continue a
    # fold from — its delta goes in as a fresh snapshot, not an event chunk
    snapshot_keys = {k for k, _ in read_segment_snapshots(path)}
    with ColumnarSegmentWriter.extend(path) as writer:
        for p in sorted(delta):
            keys = sorted(k for k in delta[p] if k not in snapshot_keys)
            demoted = sorted(k for k in delta[p] if k in snapshot_keys)
            if demoted and state_topic is not None:
                latest = log.latest_by_key(state_topic, p)
                snapshots_by_partition.setdefault(p, []).extend(
                    (k, latest[k].value) for k in demoted
                    if k in latest and latest[k].value)
            for start in range(0, len(keys), chunk_aggregates):
                chunk_ids = keys[start: start + chunk_aggregates]
                colev = encode_events_columnar(
                    registry, [delta[p][k] for k in chunk_ids])
                colev.aggregate_ids = list(chunk_ids)
                writer.append(colev, partition=p)
        for p in sorted(snapshots_by_partition):
            writer.append_snapshots(snapshots_by_partition[p], partition=p)
        writer.write_watermarks(new_wm, state_wm)
    return segment_info(path)
