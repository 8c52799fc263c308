"""Log segment block codec: framing + native compression binding.

The on-disk unit of :class:`surge_tpu.log.file.FileLog` is a **block**: one committed
transaction's records for one topic-partition, length-prefixed and CRC-checked, with
the payload compressed by the C++ SLZ codec (csrc/segment.cc — the first-party stand-in
for the reference's native lz4 producer compression, SURVEY.md §2.9 item 2). When the
native library isn't built, blocks are stored raw (codec byte 0) — files stay readable
either way because the codec is recorded per block.

Block layout (little-endian):
    magic "SSEG" | codec u8 | pad u8[3] | base_offset u64 | record_count u32 |
    uncompressed_len u32 | payload_len u32 | payload_crc32 u32 | payload
Record layout inside the (uncompressed) payload:
    flags u8 (bit0 has_key, bit1 tombstone) | key_len uvarint | key |
    [value_len uvarint | value]  (absent when tombstone) |
    n_headers uvarint | (k_len uvarint | k | v_len uvarint | v)* | timestamp f64
"""

from __future__ import annotations

import ctypes
import struct
import zlib
from typing import List, Optional, Tuple

from surge_tpu.log.common import SegmentRecordView
from surge_tpu.log.transport import LogRecord

MAGIC = b"SSEG"
CODEC_RAW = 0
CODEC_SLZ = 1
_HEADER = struct.Struct("<4sB3xQIIII")
HEADER_SIZE = _HEADER.size

#: ABI contract with csrc/segment.cc (checked by tests/test_abi_drift.py)
SEGMENT_SIGNATURES = {
    "surge_lz_bound": ((ctypes.c_size_t,), ctypes.c_size_t),
    "surge_lz_compress": ((ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
                           ctypes.c_size_t), ctypes.c_size_t),
    "surge_lz_decompress": ((ctypes.c_char_p, ctypes.c_size_t,
                             ctypes.c_char_p, ctypes.c_size_t),
                            ctypes.c_size_t),
    "surge_crc32": ((ctypes.c_char_p, ctypes.c_size_t), ctypes.c_uint32),
}

_lib = None
_lib_checked = False


def _load():
    global _lib, _lib_checked
    if _lib_checked:
        return _lib
    _lib_checked = True
    from surge_tpu.store.native import load_native_library

    _lib = load_native_library("libsurge_segment.so", SEGMENT_SIGNATURES)
    return _lib


def native_codec_available() -> bool:
    return _load() is not None


def slz_compress(data: bytes) -> Optional[bytes]:
    """Compress via the native codec; None when unavailable or not worthwhile."""
    lib = _load()
    if lib is None or not data:
        return None
    cap = lib.surge_lz_bound(len(data))
    dst = ctypes.create_string_buffer(cap)
    n = lib.surge_lz_compress(data, len(data), dst, cap)
    if n == 0 or n >= len(data):
        return None
    return dst.raw[:n]


def slz_decompress(data: bytes, uncompressed_len: int) -> bytes:
    lib = _load()
    if lib is None:
        raise RuntimeError("native segment codec not built (csrc/build.sh) but a "
                           "compressed block was encountered")
    out = ctypes.create_string_buffer(max(uncompressed_len, 1))
    n = lib.surge_lz_decompress(data, len(data), out, uncompressed_len)
    if n != uncompressed_len:
        raise ValueError(f"block decompression failed ({n} != {uncompressed_len})")
    return out.raw[:uncompressed_len]


def slz_decompress_into(data: bytes, out) -> None:
    """Decompress ``data`` straight into ``out``, a writable C-contiguous
    numpy array of exactly the uncompressed size: no zeroed staging buffer and
    no copy out of it, which :func:`slz_decompress` pays to hand back
    ``bytes``."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native segment codec not built (csrc/build.sh) but a "
                           "compressed block was encountered")
    if not (out.flags.c_contiguous and out.flags.writeable):
        raise ValueError("decompression needs a writable contiguous array")
    n = lib.surge_lz_decompress(
        data, len(data), ctypes.cast(out.ctypes.data, ctypes.c_char_p),
        out.nbytes)
    if n != out.nbytes:
        raise ValueError(f"block decompression failed ({n} != {out.nbytes})")


# -- record framing ---------------------------------------------------------------------


def _put_uvarint(buf: bytearray, n: int) -> None:
    while n >= 0x80:
        buf.append((n & 0x7F) | 0x80)
        n >>= 7
    buf.append(n)


def _get_uvarint(data: bytes, pos: int) -> Tuple[int, int]:
    shift = n = 0
    while True:
        b = data[pos]
        pos += 1
        n |= (b & 0x7F) << shift
        if not b & 0x80:
            return n, pos
        shift += 7


def encode_records(records) -> bytes:
    buf = bytearray()
    for r in records:
        flags = (1 if r.key is not None else 0) | (2 if r.value is None else 0)
        buf.append(flags)
        if r.key is not None:
            kb = r.key.encode()
            _put_uvarint(buf, len(kb))
            buf += kb
        if r.value is not None:
            _put_uvarint(buf, len(r.value))
            buf += r.value
        _put_uvarint(buf, len(r.headers))
        # headers frame in SORTED key order: a record decoded from protobuf
        # carries its map in backend-dependent iteration order (upb hashes;
        # the wire has yet another order) — canonicalizing here is what makes
        # native and Python appends byte-identical for the same record, and
        # leader/follower segment files converge regardless of which path
        # built them. UTF-8 byte order == codepoint order, so the C++ twin's
        # bytewise sort agrees with Python's str sort.
        for hk, hv in sorted(r.headers.items()):
            hkb, hvb = hk.encode(), hv.encode()
            _put_uvarint(buf, len(hkb))
            buf += hkb
            _put_uvarint(buf, len(hvb))
            buf += hvb
        buf += struct.pack("<d", r.timestamp)
    return bytes(buf)


def _native_index(payload: bytes, count: int, native=None):
    """Record-index table via csrc/txn.cc surge_seg_index (one native call
    replaces the per-byte uvarint walk): 7 int64s per record —
    [flags, key_off, key_len, val_off, val_len, hdr_off, hdr_cnt] — plus the
    timestamp array. None → caller decodes in Python (library unbuilt,
    surge.log.native.enabled=false, or malformed payload). ``native``
    overrides the ambient switch: a FileLog constructed with an explicit
    config passes its own flag so the kill-switch reaches reads too."""
    from surge_tpu.log import native_gate

    if native is None:
        if not native_gate.decode_enabled():
            return None
    elif not native or not native_gate.available():
        return None
    lib = native_gate._load()
    rows = (ctypes.c_int64 * (7 * count))()
    ts = (ctypes.c_double * count)()
    if lib.surge_seg_index(payload, len(payload), count, rows, ts) < 0:
        return None
    # bulk-slice to Python lists: per-element ctypes __getitem__ would cost
    # more than the uvarint walk it replaces
    return rows[:], ts[:]


def decode_records(payload: bytes, topic: str, partition: int,
                   base_offset: int, count: int,
                   native=None) -> List[LogRecord]:
    idx = _native_index(payload, count, native) if count else None
    if idx is not None:
        # lazy views over the indexed payload: key/value/headers decode on
        # access instead of one frozen-dataclass LogRecord per record —
        # observably identical (equality/repr; tests/test_reply_views.py)
        rows, ts = idx
        return [SegmentRecordView(payload, rows, i * 7, topic, partition,
                                  base_offset + i, ts[i])
                for i in range(count)]
    out = []
    pos = 0
    for i in range(count):
        flags = payload[pos]
        pos += 1
        key = None
        if flags & 1:
            klen, pos = _get_uvarint(payload, pos)
            key = payload[pos: pos + klen].decode()
            pos += klen
        value = None
        if not flags & 2:
            vlen, pos = _get_uvarint(payload, pos)
            value = payload[pos: pos + vlen]
            pos += vlen
        nh, pos = _get_uvarint(payload, pos)
        headers = {}
        for _ in range(nh):
            hklen, pos = _get_uvarint(payload, pos)
            hk = payload[pos: pos + hklen].decode()
            pos += hklen
            hvlen, pos = _get_uvarint(payload, pos)
            headers[hk] = payload[pos: pos + hvlen].decode()
            pos += hvlen
        (ts,) = struct.unpack_from("<d", payload, pos)
        pos += 8
        out.append(LogRecord(topic=topic, key=key, value=value, partition=partition,
                             headers=headers, offset=base_offset + i, timestamp=ts))
    return out


# -- block framing ----------------------------------------------------------------------


def encode_block(records, base_offset: int) -> bytes:
    payload = encode_records(records)
    codec = CODEC_RAW
    stored = payload
    compressed = slz_compress(payload)
    if compressed is not None:
        codec, stored = CODEC_SLZ, compressed
    header = _HEADER.pack(MAGIC, codec, base_offset, len(records), len(payload),
                          len(stored), zlib.crc32(stored))
    return header + stored


class BlockCorruptError(Exception):
    """A block failed its magic/CRC/length checks (truncated or damaged segment)."""


def header_payload_len(header: bytes) -> int:
    """Stored payload length from a bare block header (for seek-and-read access)."""
    if len(header) < HEADER_SIZE:
        raise BlockCorruptError("truncated header")
    magic, _, _, _, _, plen, _ = _HEADER.unpack_from(header, 0)
    if magic != MAGIC:
        raise BlockCorruptError("bad magic")
    return plen


def read_block_header(data: bytes, pos: int):
    """Parse the header at ``pos``; returns (codec, base_offset, count,
    uncompressed_len, payload_len, crc, payload_start) or raises BlockCorruptError."""
    if pos + HEADER_SIZE > len(data):
        raise BlockCorruptError("truncated header")
    magic, codec, base, count, unlen, plen, crc = _HEADER.unpack_from(data, pos)
    if magic != MAGIC:
        raise BlockCorruptError(f"bad magic at {pos}")
    if pos + HEADER_SIZE + plen > len(data):
        raise BlockCorruptError("truncated payload")
    return codec, base, count, unlen, plen, crc, pos + HEADER_SIZE


def decode_block(data: bytes, pos: int, topic: str, partition: int,
                 native=None) -> Tuple[List[LogRecord], int]:
    """Decode the block at ``pos``; returns (records, next_pos). ``native``
    (None = ambient config) pins the record decoder's native/Python choice —
    FileLog threads its per-instance kill-switch through here."""
    codec, base, count, unlen, plen, crc, start = read_block_header(data, pos)
    stored = data[start: start + plen]
    if zlib.crc32(stored) != crc:
        raise BlockCorruptError(f"crc mismatch at {pos}")
    payload = slz_decompress(stored, unlen) if codec == CODEC_SLZ else stored
    return (decode_records(payload, topic, partition, base, count, native),
            start + plen)
