"""Bit-packed wire format for event windows.

The format dates from a host→device link of 25 MB/s, where the transfer was the
replay engine's bottleneck (SURVEY.md §7 hard-part 2: a 100M-event log at 4
int32 columns is 1.6 GB on the wire; the fold itself is a few int ops per
event). It shrinks what the fold reads to the information actually present,
which is still what the device buffers hold and what a saved wire stores:

- The **type discriminant** and every union column with a declared ``FieldSpec.bits``
  width are packed into one little-endian word of ``ceil(total_bits/8)`` bytes per
  event (``packed``: uint8 ``[T, B, nbytes]``). The Counter fixture's events — type
  (3 bits incl. padding sentinel) + increment_by (2) + decrement_by (2) — fit in
  **one byte per event**, 16× less than the naive int32 columns.
- Columns without ``bits`` ride as full-width **side** arrays ``[T, B]`` (floats,
  wide ints).
- **Derived columns** never cross the wire at all: a data producer that knows a column
  is positional (``derived_cols={"sequence_number": "ordinal"}`` on
  ``ColumnarEvents``/``EncodedEvents``) lets the device recompute it as
  ``base + time_index + 1``. Event-sourced sequence numbers are ordinal by
  construction in the steady-state log (seq == offset within the aggregate's
  stream), so bulk replay of framework-written logs always qualifies; object-encoded
  test logs keep the explicit column.

On the attached chip the link is no longer the bottleneck (an int32 ``[N]``
column goes up at 11-12.6 GB/s, PERF.md) and the host's word pass was: so
*where* the word is built follows the input. The windowed fold and a wire
that must exist on the host (saved, cut into sub-wires, or made from int64,
strided or non-integer columns) pack in vectorized NumPy
(:meth:`WireFormat.pack_window`, :meth:`WireFormat.pack_blocks`); the resident
upload of columns that can go up as they lie builds the same word on the
device (``replay/engine.py:mk_word``, by :meth:`WireFormat._pack_words`'s own
expression). Unpacking is jitted JAX that the fold program fuses with the
scan, so decode costs no extra HBM round trip.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Mapping

import numpy as np

from surge_tpu.codec.schema import FieldSpec, SchemaRegistry

#: derivation kinds a producer may declare for a column
DERIVE_ORDINAL = "ordinal"

_MAX_PACKED_BITS = 32  # one uint32 word per event; wider layouts spill to side columns

#: events a block of the flat pack covers (:meth:`WireFormat.pack_blocks`,
#: :func:`grouped_lengths`). Sized from the columns' bytes: four int32 columns
#: of 2^18 events are 4 MiB and the block's word and masks under 1 MiB more, so
#: what one pass over a block writes the next pass still finds in cache.
FLAT_PACK_BLOCK = 1 << 18


def _as_unsigned(a: np.ndarray) -> np.ndarray:
    """A signed integer array reinterpreted as unsigned (no copy): a negative
    reads as a value past every declared width, so ONE compare against the
    upper end checks both ends of a range."""
    if a.dtype.kind == "i":
        return a.view(a.dtype.str.replace("i", "u"))
    return a


def _outside(a: np.ndarray, top: int) -> bool:
    """Whether any element of ``a`` lies outside ``[0, top]``: reductions only,
    no mask the size of ``a``."""
    if not a.size:
        return False
    if a.dtype.kind == "i" and top >> (8 * a.dtype.itemsize - 1):
        # a dtype so narrow that a negative, read as unsigned, stays under
        # ``top``: nothing in it reaches past ``top``, a negative is all
        return bool(a.min() < 0)
    if a.dtype.kind in "iu":
        return int(_as_unsigned(a).max()) > top
    return bool(a.min() < 0 or a.max() > top)


def grouped_lengths(agg_idx: np.ndarray, num_aggregates: int
                    ) -> np.ndarray | None:
    """Events per aggregate ``[B]`` int64 of a flat stream whose events are
    GROUPED per aggregate (``agg_idx`` non-decreasing), or ``None`` where they
    are not.

    One read of the ids, block by block with one element of overlap, and no
    temporary their size: a block's neighbours are compared once, for where
    the id changes. The stream is grouped where every change is a rise (the
    first block with a fall ends the walk), and the lengths are then the
    distances between the changes: an aggregate with no event, or an id the
    stream skips, gets length 0 as ``np.bincount(minlength=B)`` gives it. Ids
    outside ``[0, B)`` raise as they do there."""
    agg = np.asarray(agg_idx)
    n = agg.shape[0]
    lengths = np.zeros(num_aggregates, dtype=np.int64)
    if n == 0:
        return lengths
    bounds, ids = [np.zeros(1, dtype=np.int64)], [agg[:1]]
    for lo in range(0, n - 1, FLAT_PACK_BLOCK):
        hi = min(lo + FLAT_PACK_BLOCK, n - 1)
        cur, nxt = agg[lo:hi], agg[lo + 1:hi + 1]
        change = np.flatnonzero(nxt != cur)
        rose_to = nxt[change]
        if (rose_to < cur[change]).any():
            return None
        ids.append(rose_to)
        change += lo + 1
        bounds.append(change)
    bounds.append(np.full(1, n, dtype=np.int64))
    ids = np.concatenate(ids)
    if ids[0] < 0 or ids[-1] >= num_aggregates:
        raise ValueError(
            f"agg_idx spans [{int(ids[0])}, {int(ids[-1])}], outside the "
            f"{num_aggregates} aggregates declared")
    lengths[ids] = np.diff(np.concatenate(bounds))
    return lengths


@dataclass(frozen=True)
class _PackedField:
    name: str
    dtype: np.dtype
    bits: int
    shift: int

    @property
    def mask(self) -> int:
        return (1 << self.bits) - 1


def overflow_error(pf: _PackedField, whole: np.ndarray) -> ValueError:
    """What a packed column below 0 or past its declared width raises, from
    the whole column, wherever the word is built (:meth:`WireFormat.
    _pack_words` on the host, ``replay/engine.py:mk_word`` on the device)."""
    whole = np.asarray(whole)
    return ValueError(
        f"column {pf.name!r} overflows its declared {pf.bits}-bit "
        f"wire width (max value {int(whole.max())}, "
        f"min {int(whole.min())})")


class WireFormat:
    """Pack/unpack schedule for one (registry, derived-columns) pair."""

    def __init__(self, registry: SchemaRegistry,
                 derived: Mapping[str, str] | None = None) -> None:
        self.registry = registry
        self.derived = dict(derived or {})
        for name, kind in self.derived.items():
            if kind != DERIVE_ORDINAL:
                raise ValueError(f"unknown derivation {kind!r} for column {name!r}")

        num_types = registry.num_event_types
        self.num_types = num_types
        self.type_bits = max(int(num_types).bit_length(), 1)  # +1 value: pad sentinel
        self.pad_code = num_types

        shift = self.type_bits
        packed: list[_PackedField] = []
        side: list[FieldSpec] = []
        self.derived_fields: list[FieldSpec] = []
        for f in registry.union_columns():
            if f.name in self.derived:
                self.derived_fields.append(f)
            elif f.bits is not None and shift + f.bits <= _MAX_PACKED_BITS:
                packed.append(_PackedField(f.name, f.dtype, f.bits, shift))
                shift += f.bits
            else:
                side.append(f)
        self.packed_fields = tuple(packed)
        self.side_fields = tuple(side)
        self.total_bits = shift
        self.nbytes = (shift + 7) // 8
        # narrowest little-endian word that holds every packed bit: at bench
        # scale the build streams one intermediate per field, so a 1-byte wire
        # (counter) building in uint8 moves a quarter of the memory
        self.word_dtype = np.dtype("u1" if self.nbytes == 1 else
                                   "<u2" if self.nbytes == 2 else "<u4")
        # the byte pattern a padding slot must decode to: pad_code in the type bits,
        # zeros elsewhere
        self.pad_bytes = tuple((self.pad_code >> (8 * k)) & 0xFF
                               for k in range(self.nbytes))

    def wire_bytes_per_event(self) -> int:
        """Transfer cost per event slot (packed word + side columns)."""
        return self.nbytes + sum(f.dtype.itemsize for f in self.side_fields)

    def layout_fingerprint(self) -> dict:
        """A JSON-round-trippable description of the exact bit/byte layout.

        Persisted next to packed corpora (ResidentWire meta) so a consuming
        engine whose schema evolved — field widths, order, type count — is
        refused instead of decoding misaligned bits into silently-wrong
        states. Two schemas that pack to the same byte count but different bit
        positions produce different fingerprints."""
        return {
            "num_types": self.num_types,
            "type_bits": self.type_bits,
            "nbytes": self.nbytes,
            "packed": [[pf.name, str(np.dtype(pf.dtype)), pf.bits, pf.shift]
                       for pf in self.packed_fields],
            "side": [[f.name, str(np.dtype(f.dtype))]
                     for f in self.side_fields],
            "derived": sorted([k, v] for k, v in self.derived.items()),
        }

    # -- host side ----------------------------------------------------------------------

    def pack_window(self, type_ids: np.ndarray, cols: Mapping[str, np.ndarray],
                    start: int, stop: int, chunk: int, bs: int
                    ) -> tuple[np.ndarray, dict[str, np.ndarray]]:
        """Pack the time window ``[:, start:stop)`` of a batch-major ``[b, T]`` layout
        into time-major device-ready buffers padded to ``[chunk, bs]``.

        Returns ``(packed uint8 [chunk, bs, nbytes], side {name: [chunk, bs]})``.
        Fresh buffers every call (donation-safe). Padding slots decode to the pad
        sentinel. Raises if a packed field's value overflows its declared bits.
        """
        b = type_ids.shape[0]
        width = stop - start
        word = self._pack_words(type_ids[:, start:stop],
                                {pf.name: cols[pf.name][:, start:stop]
                                 for pf in self.packed_fields})

        packed = np.empty((chunk, bs, self.nbytes), dtype=np.uint8)
        for k in range(self.nbytes):
            packed[..., k] = self.pad_bytes[k]
            packed[:width, :b, k] = ((word >> np.asarray(8 * k, dtype=word.dtype))
                                     & np.asarray(0xFF, dtype=word.dtype)).T

        side: dict[str, np.ndarray] = {}
        for f in self.side_fields:
            buf = np.zeros((chunk, bs), dtype=f.dtype)
            buf[:width, :b] = cols[f.name][:, start:stop].T
            side[f.name] = buf
        return packed, side

    def _pack_words(self, type_ids: np.ndarray,
                    cols: Mapping[str, np.ndarray],
                    out: np.ndarray | None = None,
                    report: Mapping[str, np.ndarray] | None = None
                    ) -> np.ndarray:
        """The one word build, for a window (:meth:`pack_window`) and for a
        block of the flat stream (:meth:`pack_blocks`) alike: out-of-range
        ids — padding (-1) or corrupt positive values — pack as the pad
        sentinel so they carry state through (the same contract make_step_fn
        keeps for the unpacked path); a corrupt id must never spill into field
        bits. A packed column below 0 or past its declared width raises.

        Built in the narrowest word dtype (:attr:`word_dtype`), into ``out``
        where the caller has the words' final place. The range checks are
        reductions over an unsigned view (:func:`_outside`), so nothing the
        size of the input is made beyond the word and one cast per field; the
        error's max and min are computed on the failing path only, over
        ``report`` (the whole columns, where ``cols`` is one block of them)."""
        tid = np.asarray(type_ids)
        if tid.dtype.kind not in "iu":
            tid = tid.astype(np.int64)
        word = (out if out is not None
                else np.empty(tid.shape, dtype=self.word_dtype))
        # pad_code is num_types: every id past the last type, a negative read
        # as unsigned among them, clamps to it
        np.copyto(word, np.minimum(_as_unsigned(tid), self.pad_code),
                  casting="unsafe")
        for pf in self.packed_fields:
            col = np.asarray(cols[pf.name])
            if _outside(col, pf.mask):
                raise overflow_error(pf, (report or cols)[pf.name])
            bits = col.astype(self.word_dtype)
            bits <<= np.asarray(pf.shift, dtype=self.word_dtype)
            word |= bits
        return word

    def pack_blocks(self, type_ids: np.ndarray, cols: Mapping[str, np.ndarray],
                    guard: int = 0) -> tuple[np.ndarray, int]:
        """The flat pack as :meth:`ReplayEngine.pack_resident` runs it: the
        packed half of :meth:`pack_flat` plus ``guard`` zero rows, ``(packed
        uint8 [N + guard, nbytes], blocks)``, byte for byte what ``pack_flat``
        and an ``np.pad`` give.

        The buffer is allocated once and only its guard rows are zeroed. The
        stream is then walked in blocks of :data:`FLAT_PACK_BLOCK` events
        (``blocks`` of them; an input shorter than one is one block): per block
        the word build of :meth:`_pack_words` reads each column once, while
        its temporaries are still in cache, and stores the words where they
        finally live: through a word view of the buffer for a 1-, 2- or 4-byte
        wire, byte by byte within the block for a 3-byte one."""
        tid = np.asarray(type_ids)
        n = tid.shape[0]
        fields = {pf.name: np.asarray(cols[pf.name])
                  for pf in self.packed_fields}
        packed = np.empty((n + guard, self.nbytes), dtype=np.uint8)
        packed[n:] = 0
        whole_words = self.word_dtype.itemsize == self.nbytes
        words = packed[:n].view(self.word_dtype)[:, 0] if whole_words else None
        blocks = range(0, n, FLAT_PACK_BLOCK)
        for lo in blocks:
            hi = min(lo + FLAT_PACK_BLOCK, n)
            word = self._pack_words(
                tid[lo:hi], {k: v[lo:hi] for k, v in fields.items()},
                out=None if words is None else words[lo:hi], report=fields)
            if words is None:
                packed[lo:hi] = (word.view(np.uint8)
                                 .reshape(hi - lo, -1)[:, :self.nbytes])
        return packed, len(blocks)

    def side_columns(self, cols: Mapping[str, np.ndarray]
                     ) -> dict[str, np.ndarray]:
        """The side half of the flat pack, ``{name: [N]}`` in each column's
        wire dtype, by :meth:`split_flat`'s own expression: a column already
        in that dtype and contiguous is the caller's array itself (no host
        copy: :meth:`ReplayEngine.upload_resident` supplies the rows past
        ``N`` as device zeros), any other (an int64 or strided column from a
        decoder) is cast into a fresh ``[N]`` buffer."""
        return {f.name: np.ascontiguousarray(cols[f.name], dtype=f.dtype)
                for f in self.side_fields}

    def pack_flat(self, type_ids: np.ndarray, cols: Mapping[str, np.ndarray]
                  ) -> tuple[np.ndarray, dict[str, np.ndarray]]:
        """Pack a FLAT event stream ``[N]`` into ``(packed uint8 [N, nbytes],
        side {name: [N]})`` — the resident-corpus wire form: exactly
        ``wire_bytes_per_event()`` per real event, no window padding at all.
        The device slices per-aggregate slabs from it (see
        :meth:`decode_words`).

        Whole-column and plain: the reference the blocked pack
        (:meth:`pack_blocks`, :meth:`side_columns`) is held to, byte for byte,
        by ``tests/test_pack_blocked.py``. Nothing on the rebuild path calls
        it."""
        return self.split_flat(self.flat_words(type_ids, cols), cols)

    def flat_words(self, type_ids: np.ndarray, cols: Mapping[str, np.ndarray]
                   ) -> np.ndarray:
        """First half of :meth:`pack_flat`: one packed word per event ``[N]``,
        the whole stream in one build."""
        return self._pack_words(type_ids, {pf.name: cols[pf.name]
                                           for pf in self.packed_fields})

    def split_flat(self, word: np.ndarray, cols: Mapping[str, np.ndarray]
                   ) -> tuple[np.ndarray, dict[str, np.ndarray]]:
        """Second half of :meth:`pack_flat`: the words' bytes as ``[N,
        nbytes]`` and the side columns in their wire dtypes, whole-column."""
        n = word.shape[0]
        packed = np.empty((n, self.nbytes), dtype=np.uint8)
        for k in range(self.nbytes):
            packed[:, k] = ((word >> np.asarray(8 * k, dtype=word.dtype))
                            & np.asarray(0xFF, dtype=word.dtype))
        return packed, self.side_columns(cols)

    # -- device side ----------------------------------------------------------------------

    def expand_flat(self, packed: Any) -> Any:
        """One-time on-device expansion of the flat packed bytes to a u32 word
        array ``[N]`` (gather-friendly lanes; HBM-resident, never transferred)."""
        import jax.numpy as jnp

        word = packed[:, 0].astype(jnp.uint32)
        for k in range(1, self.nbytes):
            word = word | (packed[:, k].astype(jnp.uint32) << np.uint32(8 * k))
        return word

    def decode_words(self, word: Any, side_row: Mapping[str, Any], valid: Any,
                     ord_base: Any, t: Any) -> dict[str, Any]:
        """JAX-traceable decode of one scan step's word row ``[B]`` (extracted
        from a resident flat corpus by contiguous per-lane slabs): slots with
        ``valid`` false decode to the pad sentinel, and the derived ordinal is
        ``ord_base[b] + t + 1``."""
        import jax.numpy as jnp

        tid = (word & np.uint32((1 << self.type_bits) - 1)).astype(jnp.int32)
        tid = jnp.where(tid >= self.num_types, jnp.int32(-1), tid)
        events: dict[str, Any] = {
            "type_id": jnp.where(valid, tid, jnp.int32(-1))}
        for pf in self.packed_fields:
            raw = (word >> np.uint32(pf.shift)) & np.uint32(pf.mask)
            events[pf.name] = raw.astype(pf.dtype)
        for f in self.side_fields:
            events[f.name] = side_row[f.name]
        for f in self.derived_fields:
            events[f.name] = (ord_base.astype(jnp.int32) + t + 1).astype(f.dtype)
        return events

    def decode(self, packed: Any, side: Mapping[str, Any], ord_base: Any
               ) -> dict[str, Any]:
        """JAX-traceable unpack: ``[chunk, B, nbytes]`` uint8 (+side columns, +ordinal
        base ``[B]``) → the events dict the fold scan consumes, with ``type_id`` as
        int32 (padding → -1) and each field at its schema dtype.

        ``ord_base[b] + t + 1`` is the derived ordinal of the event at time row ``t``
        (0 for fresh replays; the already-folded event count when resuming).
        """
        import jax.numpy as jnp

        chunk = packed.shape[0]
        word = packed[..., 0].astype(jnp.uint32)
        for k in range(1, self.nbytes):
            word = word | (packed[..., k].astype(jnp.uint32) << np.uint32(8 * k))

        tid = (word & np.uint32((1 << self.type_bits) - 1)).astype(jnp.int32)
        events: dict[str, Any] = {
            "type_id": jnp.where(tid >= self.num_types, jnp.int32(-1), tid)}
        for pf in self.packed_fields:
            raw = (word >> np.uint32(pf.shift)) & np.uint32(pf.mask)
            events[pf.name] = raw.astype(pf.dtype)
        for f in self.side_fields:
            events[f.name] = side[f.name]
        if self.derived_fields:
            t_idx = jnp.arange(chunk, dtype=jnp.int32)[:, None]
            for f in self.derived_fields:
                ordinal = ord_base[None, :].astype(jnp.int32) + t_idx + 1
                events[f.name] = ordinal.astype(f.dtype)
        return events
