"""Core batched fold: vmap(switch-step) scanned over time-major event columns.

Scale discipline (SURVEY.md §7 hard-part 2, BASELINE.md 1M-aggregate/100M-event target):

- **B-chunking**: ``surge.replay.batch-size`` bounds the aggregates resident on device at
  once; larger batches stream through in fixed-size chunks so HBM usage is constant and
  one compiled program serves every chunk.
- **T-chunking**: ``surge.replay.time-chunk`` bounds the scanned window; tail windows are
  padded to full width (padding is masked inside the step), again pinning compiled shapes.
- **Donation safety**: caller-visible carries are always copied into fresh padded host
  buffers before entering the donated jit, so external arrays are never consumed.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field as dc_field
from typing import Any, Callable, Iterable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from surge_tpu.codec.tensor import (
    ColumnarEvents,
    EncodedEvents,
    bucket_lengths,
    columnar_to_batch,
    encode_states,
)
from surge_tpu.codec.wire import WireFormat, grouped_lengths, overflow_error
from surge_tpu.config import Config, default_config
from surge_tpu.engine.model import ReplaySpec, StateTree
from surge_tpu.replay.profiler import ReplayProfiler
from surge_tpu.tracing import SpanContext, default_tracer

#: the functions the cold path's ``jax.jit`` programs are made from, by name.
#: XLA names a program ``jit_<function>``, and the benchmark's trace reduction
#: maps programs to layers by those names (benchmarks/programs/cold-fold.json):
#: renaming one unmaps its program. Held by tests/test_replay_spans.py.
COLD_PATH_JIT_NAMES = ("fold", "finalize", "mk", "mk_bucket", "mk_wire",
                       "mk_word")

#: the checkout's own persistent compile cache (listed in .gitignore). The
#: path is part of every cache key, so it is fixed: never a temp, pid or
#: timestamp name, and independent of the working directory.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def ensure_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache before the first ``jit``.

    A cache placed from outside wins: with ``JAX_COMPILATION_CACHE_DIR`` set,
    jax reads the variable itself and this changes no config (returns None).
    Otherwise the cache goes to :data:`COMPILE_CACHE_DIR`. jax decides once,
    at its first compilation, whether the cache is in use — so every root of
    device programs (``ReplayEngine.__init__``, ``chip_smoke.py``,
    ``benchmarks/run.py``) calls this before building one. Idempotent."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    if jax.config.jax_compilation_cache_dir != COMPILE_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def make_step_fn(spec: ReplaySpec
                 ) -> Callable[[StateTree, Mapping[str, Any]], StateTree]:
    """One-event step for a single aggregate: dispatch on type_id, mask padding.

    The returned function is scalar over the batch dim (engine vmaps it). Any type_id
    outside ``[0, num_types)`` — padding (-1) or corrupt positive ids — carries state
    through unchanged rather than dispatching to an arbitrary handler. The
    dispatch is ``lax.switch`` on the (clipped) type id; under ``vmap`` XLA
    turns this into predicated branches.
    """
    num_types = spec.registry.num_event_types
    handlers = spec.handlers.ordered(num_types)
    state_fields = spec.registry.state.field_names

    def normalize(new: StateTree, old: StateTree) -> StateTree:
        # handlers may return partial dicts; missing columns carry through, and dtypes
        # are pinned to the schema so the scan carry shape is stable
        out = {}
        for name in state_fields:
            v = new.get(name, old[name])
            out[name] = jnp.asarray(v, dtype=old[name].dtype)
        return out

    def step(state: StateTree, event: Mapping[str, Any]) -> StateTree:
        tid = event["type_id"]
        branch = jnp.clip(tid, 0, num_types - 1)
        fields = {k: v for k, v in event.items() if k != "type_id"}
        wrapped = [
            (lambda h: lambda s: normalize(h(s, fields), s))(h) for h in handlers
        ]
        new_state = jax.lax.switch(branch, wrapped, state)
        is_real = (tid >= 0) & (tid < num_types)
        return {k: jnp.where(is_real, new_state[k], state[k]) for k in state}

    return step


def make_batch_fold(spec: ReplaySpec):
    """Batched fold: ``(carry {name:[B]}, events {col:[T,B]}) -> carry``.

    The per-aggregate fold of CommandModels.scala:20-21 / PersistentActor's applyEvents,
    vectorized: ``lax.scan`` over T of ``vmap``-over-B of the switch step. jit-compiled by
    the caller (ReplayEngine) with carry donation.
    """
    vstep = jax.vmap(make_step_fn(spec), in_axes=(0, 0))

    def fold(carry: StateTree, events: Mapping[str, jnp.ndarray]) -> StateTree:
        def scan_body(c, ev_t):
            return vstep(c, ev_t), None

        out, _ = jax.lax.scan(scan_body, carry, events)
        return out

    return fold


@dataclass
class ResidentCorpus:
    """A corpus uploaded once to the device for gather-based replay."""

    derived_key: dict
    flat_wire: Any  # packed u8 [N, nbytes] on device (word-expanded per tile)
    flat_side: dict  # {name: [N]} on device
    starts: np.ndarray  # i32 [B] (length-sorted order, host copy for planning)
    lengths: np.ndarray  # i32 [B]
    perm: Optional[np.ndarray]  # sorted-rank -> original index (None = identity)
    starts_dev: Any  # i32 [b_pad] on device
    lens_dev: Any  # i32 [b_pad] on device
    b_pad: int  # lane count padded to the dispatch batch
    num_events: int
    wire_bytes: int  # bytes actually shipped to the device
    upload_s: float
    #: per-corpus caches, filled lazily by the engine: the tile plan (keyed by
    #: its geometry) and the state pull's device inverse-perm
    cache: dict = dc_field(default_factory=dict)
    #: context of the ``replay.h2d`` span that uploaded it: a fold of this
    #: corpus continues that trace
    trace_ctx: Optional[SpanContext] = None


#: minimum guard rows appended past the wire corpus, so a wire packed under a
#: small tile-width cap still satisfies engines configured with a larger one
_WIRE_GUARD_MIN = 8192

def _make_fold_body(spec: ReplaySpec, wire: WireFormat, width: int,
                    tile_backend: str):
    """The tile-interior fold of the resident tiles (single-device and
    mesh-sharded): ``(carry {f: [bs]}, words u32 [width, bs],
    sides {n: [width, bs]}, lens [bs], ord_base [bs], t_base) -> carry``.

    Two lowerings per ``tile_backend``: the sequential XLA time scan, or —
    when the spec ships a law-checked ``AssociativeFold`` — a liftless-scan
    tree reduction (no per-step loop machinery at all)."""
    batch_step = jax.vmap(make_step_fn(spec), in_axes=(0, 0))
    afold = None
    if tile_backend == "assoc":
        from surge_tpu.replay.seqpar import ensure_validated

        afold = spec.associative
        if afold is None:
            raise ValueError(
                "surge.replay.tile-backend = assoc requires the ReplaySpec to "
                "carry an AssociativeFold (spec.associative) — this model "
                "only supports the sequential xla tile scan")
        if width & (width - 1):
            raise ValueError(
                f"assoc tile backend needs a power-of-two time width, got {width}")
        # same one-time law check as the time-sharded path: a wrong combine
        # must raise here, never silently corrupt a replay
        ensure_validated(afold, spec)

    def fold_body(carry, words, sides, lens, ord_base, t_base):
        if afold is not None:
            # no scan at all: lift every slot of the [width, bs] tile at once,
            # pairwise tree-reduce the summaries over TIME (combine is
            # associative but not commutative — adjacent-pair combining keeps
            # left-to-right order), then one apply. log2(width) full-vector
            # passes replace width sequential scan steps; per-tile
            # homomorphism (law 2) makes chained tiles equal chained
            # step-folds.
            ts2 = (jnp.arange(width, dtype=jnp.int32) + t_base)[:, None]
            valid = ts2 < lens[None, :]
            events = wire.decode_words(words, sides, valid,
                                       ord_base[None, :], ts2)
            s = afold.lift(events)  # padding (type_id -1) lifts to identity
            w = width
            while w > 1:
                s = afold.combine({k: v[0::2] for k, v in s.items()},
                                  {k: v[1::2] for k, v in s.items()})
                w //= 2
            out = afold.apply(carry, {k: v[0] for k, v in s.items()})
            return {k: out.get(k, carry[k]) for k in carry}

        ts = jnp.arange(width, dtype=jnp.int32) + t_base

        def body(c, xs):
            w_row, side_row, t = xs
            events = wire.decode_words(w_row, side_row, t < lens, ord_base, t)
            return batch_step(c, events), None

        out, _ = jax.lax.scan(body, carry, (words, sides, ts))
        return out

    return fold_body


#: events in one aligned row of the device's view of a flat buffer. A 1-D
#: array of 32-bit values lies in HBM in (8, 128) tiles of 1024 consecutive
#: elements, so ``[N] -> [N / 128, 128]`` is the same bytes (no copy), and a
#: row of it is what the chip's native gather moves with many in flight.
#: ``[N / 512, 512]`` is another layout: XLA copies the whole buffer to make it.
_LANE_ROW = 128


def _lane_gather() -> str:
    """How a tile's lane rows leave the flat wire (:func:`_make_lane_fetch`):
    ``rows`` on an accelerator, ``slices`` on a CPU host, where one slice a
    lane is a memcpy and the row fetch with its shift passes measured slower
    (1.25 times on an int32 column, 12 times on the one-byte word: PERF.md,
    PR 28). Decided per backend like ``tile_backend``: no config key selects
    it."""
    return "slices" if jax.default_backend() == "cpu" else "rows"


def _rows_per_lane(width: int, gather: str) -> int:
    """What one lane asks of one array for one tile: the aligned rows that
    cover any ``width`` events starting anywhere in a row, or the one slice."""
    if gather == "slices":
        return 1
    return (width + _LANE_ROW - 2) // _LANE_ROW + 1


def _slots_per_lane(width: int, gather: str) -> int:
    """The event slots of ONE array that one lane's fetch for one tile moves:
    its aligned rows of :data:`_LANE_ROW` events, or under ``slices`` the
    ``width`` events of the slice."""
    if gather == "slices":
        return width
    return _rows_per_lane(width, gather) * _LANE_ROW


def _tile_sizes(batch_size: int, lane: int, b: int) -> tuple[int, int]:
    """The two lane granularities of a resident plan over ``b`` lanes, as
    ``(bs_big, bs_small)``: the batch size (or all the lanes, if fewer) and an
    eighth of it, for the remainder of a round."""
    bs_big = min(batch_size, _round_up(max(b, 1), lane))
    bs_small = min(bs_big, max(lane, bs_big // 8))
    # bs_small MUST divide bs_big: the small-tile walk covers
    # [n_big*bs_big, active) in bs_small steps while the device buffer is
    # only padded to a bs_big multiple (upload_resident b_pad) — a
    # non-divisor's last tile would start within bs_small of the buffer
    # end, dynamic_slice would clamp the lane start, and the tile would
    # silently RE-APPLY events to lanes the previous tile already folded
    # (ADVICE r4). Today bs_big is always a multiple of 8*lane so
    # bs_big//8 divides exactly; this guard keeps the invariant explicit
    # against future knob/rounding changes.
    if bs_big % bs_small:
        bs_small = max(c for c in range(lane, bs_small + 1, lane)
                       if bs_big % c == 0)  # lane | bs_big, so non-empty
    assert bs_big % bs_small == 0, (bs_big, bs_small)
    return bs_big, bs_small


def _tile_width(lengths: np.ndarray, bs_big: int, bs_small: int,
                widths: Sequence[int], gather: str) -> int:
    """The tile width a resident plan over logs of these ``lengths`` folds
    at: of ``widths`` (ascending; the last is the widest tile allowed) the one
    whose plan costs least, the wider of two that cost the same. Host only,
    and a function of the lengths as a multiset: any order gives the same
    answer.

    The cost of a width is what its plan makes the device pass over, in
    events of one array (the fold reads every slot, and the fetch every row,
    in each array alike, so their number cancels): the padded slots its tiles
    fold (``w`` a lane and tile) and the events its fetch moves (a lane and
    tile: :func:`_rows_per_lane` aligned rows of :data:`_LANE_ROW` events, or
    under ``slices`` the ``w`` events of the slice, so that there the cost is
    the slots alone). A narrow tile stops close behind each lane's last event
    and a wide one fetches fewer rows an event, so on the chip logs of 100
    events fold at 128 (1.28 slots and 2.56 fetched events an event), not at
    512 (5.12 and 6.40) nor at 64 (1.28 and 5.12). That sum chose the width
    that measured best in each of eight sweeps on the chip (the ``assoc`` tree
    and the scan tile, one to eight arrays, logs of 30, 100 and 300 events:
    PERF.md, PR 34). The tiles are counted as
    :meth:`ReplayEngine._resident_plan` makes them over lanes sorted by
    length: each round's active lanes in ``bs_big`` tiles and the remainder
    in ``bs_small`` ones; one ``searchsorted`` a width."""
    b = lengths.shape[0]
    if b == 0 or len(widths) == 1:
        return widths[-1]
    # the wire's lanes lie longest first: one compare says so
    asc = (np.ascontiguousarray(lengths[::-1])
           if (lengths[:-1] >= lengths[1:]).all() else np.sort(lengths))
    longest = int(asc[-1])

    def cost(w: int) -> int:
        # lanes still folding at each round's first event (the offsets in
        # the lengths' own dtype, or searchsorted widens all of ``asc``)
        active = b - np.searchsorted(
            asc, np.arange(0, longest, w, dtype=asc.dtype), side="right")
        rest = active % bs_big
        tiled = int((active - rest + _round_up(rest, bs_small)).sum())
        return tiled * (w + _slots_per_lane(w, gather))

    return min(reversed(widths), key=cost)  # of equals the widest


def _make_lane_fetch(wire: WireFormat, width: int, gather: str):
    """How a tile's lane rows get from the flat wire into ``[width, bs]``.
    Returns ``(view, fetch)``:

    - ``view(flat_wire u8 [N, nbytes], side_flat {n: [N]}) -> buffers``, once
      a program, outside the tile loop;
    - ``fetch(buffers, p i32 [bs]) -> (words u32 [width, bs], sides {n:
      [width, bs]})``: column ``l`` holds events ``[p[l], p[l] + width)``,
      with ``p`` clamped into the buffer as ``dynamic_slice`` clamps it
      (finished and padding lanes: their garbage decodes under a False
      mask).

    Both lowerings give the same tile, element for element.

    ``slices``: one ``dynamic_slice`` a lane and array. The v5e runs that as
    a ``while`` loop of ``bs`` trips bound by trips, not bytes (1.2 us a lane
    for 512 B and 2 KB alike).

    ``rows``: every buffer is widened to 32-bit values once a program (the
    packed word by :meth:`WireFormat.expand_flat`) and viewed as ``[N / A,
    A]`` (``A`` = :data:`_LANE_ROW`). A lane whose window starts at ``p =
    q * A + o`` fetches the aligned rows ``q .. q + R - 1`` in one native
    gather over all lanes (each row index clamped on its own), and is brought
    back to offset 0 in time-major vector code: bit ``k`` of ``o`` selects
    between the tile and the tile ``2^k`` events on, seven passes for any
    ``o``, each over fewer rows than the last. 0.065 us a lane and array on
    the v5e (PERF.md, PR 28)."""
    nbytes = wire.nbytes

    if gather == "slices":
        def view(flat_wire, side_flat):
            return flat_wire, side_flat

        def fetch(buffers, p):
            flat_wire, side_flat = buffers
            bs = p.shape[0]
            word = jax.vmap(lambda s0: jax.lax.dynamic_slice(
                flat_wire, (s0, 0), (width, nbytes)))(p)
            word = wire.expand_flat(word.reshape(bs * width, nbytes))
            cut = jax.vmap(lambda arr, s0: jax.lax.dynamic_slice(
                arr, (s0,), (width,)), in_axes=(None, 0))
            return (word.reshape(bs, width).T,  # [width, bs]
                    {n: cut(arr, p).T for n, arr in side_flat.items()})

        return view, fetch

    a = _LANE_ROW
    r = _rows_per_lane(width, gather)

    def carrier(dtype):
        # narrower values ride as 32-bit ones: native (8, 128) rows
        dt = np.dtype(dtype)
        if dt.itemsize >= 4:
            return dt
        return np.dtype(np.float32 if dt.kind == "f" else np.int32)

    def view(flat_wire, side_flat):
        # whole rows: a power-of-two bucket, or upload_resident's rounding
        m = flat_wire.shape[0] // a
        return (wire.expand_flat(flat_wire).reshape(m, a),
                {name: arr.astype(carrier(arr.dtype)).reshape(m, a)
                 for name, arr in side_flat.items()})

    def fetch(buffers, p):
        word_rows, side_rows = buffers
        m = word_rows.shape[0]
        bs = p.shape[0]
        p = jnp.clip(p, 0, m * a - width)  # dynamic_slice's clamp
        q, o = p // a, p % a
        idx = q[:, None] + jnp.arange(r, dtype=jnp.int32)[None, :]

        def lanes(arr):
            # mode="clip": a row index past the buffer reads its last row
            x = jnp.take(arr, idx, axis=0, mode="clip")  # [bs, r, a]
            x = x.reshape(bs, r * a).T  # time-major [r * a, bs]
            s = a // 2
            while s:
                keep = x.shape[0] - s
                x = jnp.where((o & s) != 0, x[s:], x[:keep])
                s //= 2
            return x[:width]

        return (lanes(word_rows),
                {f.name: lanes(side_rows[f.name]).astype(f.dtype)
                 for f in wire.side_fields})

    return view, fetch


def _make_tile(spec: ReplaySpec, wire: WireFormat, width: int, bs: int,
               tile_backend: str, gather: str):
    """The tile of the resident programs (single-device AND
    mesh-sharded), as ``(view, tile)``: ``view(flat_wire u8 [N, nbytes],
    side_flat) -> buffers`` once a program, and ``tile(state_slab {f:
    [b_pad]}, buffers, starts [b_pad], lens [b_pad], ord_base [b_pad], i0,
    t_base) -> state_slab`` in its loop.

    One tile folds events ``[t_base, t_base+width)`` of lanes
    ``[i0, i0+bs)``: every lane's contiguous window out of the flat packed
    corpus (events of one aggregate are adjacent) as a time-major tile
    (:func:`_make_lane_fetch`), the shared fold body
    (:func:`_make_fold_body`), and a contiguous write-back into the state
    slab. ``i0``/``t_base`` are traced scalars."""
    fold_body = _make_fold_body(spec, wire, width, tile_backend)
    view, fetch = _make_lane_fetch(wire, width, gather)

    def tile(slab_state, buffers, starts_all, lens_all, ord_all, i0, t_base):
        starts = jax.lax.dynamic_slice(starts_all, (i0,), (bs,))
        lens = jax.lax.dynamic_slice(lens_all, (i0,), (bs,))
        ord_base = jax.lax.dynamic_slice(ord_all, (i0,), (bs,))
        carry = {k: jax.lax.dynamic_slice(v, (i0,), (bs,))
                 for k, v in slab_state.items()}
        words, sides = fetch(buffers, starts + t_base)
        out = fold_body(carry, words, sides, lens, ord_base, t_base)
        return {k: jax.lax.dynamic_update_slice(slab_state[k], out[k], (i0,))
                for k in slab_state}

    return view, tile


def _apply_perm(perm: Optional[np.ndarray],
                init_carry: Mapping[str, Any] | None,
                ordinal_base: np.ndarray | None):
    """Reorder caller inputs (original aggregate order) into the wire's
    length-sorted lane order."""
    init_sorted = None
    if init_carry is not None:
        init_sorted = {k: (np.asarray(v)[perm] if perm is not None
                           else np.asarray(v))
                       for k, v in init_carry.items()}
    ord_sorted = None
    if ordinal_base is not None:
        src = np.asarray(ordinal_base)
        ord_sorted = src[perm] if perm is not None else src
    return init_sorted, ord_sorted


def _unapply_perm(perm: Optional[np.ndarray],
                  out_sorted: dict) -> dict:
    """Scatter sorted-order state columns back to the original order."""
    if perm is None:
        return out_sorted
    out = {name: np.empty_like(col) for name, col in out_sorted.items()}
    for name, col in out_sorted.items():
        out[name][perm] = col
    return out


def _side_nbytes(side: Mapping[str, Any]) -> int:
    """Bytes of a wire's side columns."""
    return int(sum(v.nbytes for v in side.values()))


def _wire_nbytes(w: "ResidentWire") -> int:
    """Bytes of a wire's event buffers: the packed rows (by their shape: a
    packed buffer not yet built is not built for this) and the side columns."""
    rows, nbytes = w.packed_shape
    return rows * nbytes + _side_nbytes(w.side)


def _bucket_len(n: int) -> int:
    """Next power of two ≥ n (min 64Ki) — the bucketed buffer length."""
    target = 1 << 16
    while target < n:
        target <<= 1
    return target


#: rows of one piece of the bucketed upload (:func:`_bucket_pieces`). A power
#: of two no smaller than the least bucket, so it divides every bucket longer
#: than itself. Chosen once on the chip (PERF.md, PR 30); not a setting.
_PIECE_ROWS = 1 << 22

#: pieces whose transfer may be under way while the next is handed over: the
#: device holds the bucket and these, never a second wire. Four is where the
#: waits stopped costing on the chip (PERF.md, PR 30).
_PIECES_AHEAD = 4


def mk_bucket(rows: int, tail: tuple, dtype):
    """The device buffer of a bucketed upload, as zeros."""
    return jnp.zeros((rows, *tail), dtype)


def mk_wire(bucket, piece, at):
    """One piece of the wire placed at row ``at`` of its donated bucket."""
    return jax.lax.dynamic_update_slice(
        bucket, piece, (at,) + (0,) * (piece.ndim - 1))


#: compile keys (bucket rows, trailing shape, dtype) and (bucket shape, piece
#: shape, dtype): one program a bucket and array kind, whatever the length
_zero_bucket = jax.jit(mk_bucket, static_argnums=(0, 1, 2))
_place_piece = jax.jit(mk_wire, donate_argnums=0)


def _pad_rows(arr: np.ndarray, rows: int) -> np.ndarray:
    """``arr`` in a fresh buffer of ``rows`` rows, zeros after its own."""
    out = np.zeros((rows,) + arr.shape[1:], arr.dtype)
    out[:arr.shape[0]] = arr
    return out


def _bucket_pieces(arr: np.ndarray, piece_rows: int, rows: int | None = None):
    """The host's half of a bucketed upload: ``arr`` as the row pieces that
    go to the device, and the bytes copied to make them.

    The bucket (:func:`_bucket_len` of ``rows``, zeros after the array's own)
    is the device buffer's shape, for the compile keys of the programs that
    read it; the host holds no padded copy. ``rows`` is the array's own count
    unless the caller buckets it with a longer one (a side column of ``N``
    rows beside its packed buffer of ``N + guard``): the rows between are the
    bucket's zeros and make no piece. Where the bucket is longer than one
    piece the array goes up in pieces of ``piece_rows`` rows, contiguous views
    of ``arr`` itself (fresh, mmapped, the caller's own or a slice of any);
    only the last, partial piece is padded, in a buffer of its own. A bucket
    of at most a piece is one piece, the array padded to it."""
    whole = min(piece_rows, _bucket_len(arr.shape[0] if rows is None else rows))
    pieces = [arr[at: at + whole]
              for at in range(0, max(arr.shape[0], 1), whole)]
    if pieces[-1].shape[0] == whole:
        return pieces, 0
    pieces[-1] = _pad_rows(pieces[-1], whole)
    return pieces, pieces[-1].nbytes


def _put_pieces(pieces: list, bucket: int):
    """The device's half: the pieces of :func:`_bucket_pieces` as one buffer
    of ``bucket`` rows, element for element ``np.pad(arr, bucket)``. A piece
    that is the whole bucket is put as it is. Otherwise each is placed at its
    row offset by :func:`mk_wire` right after its put, into a bucket of
    device zeros, which also supplies every row past the last piece."""
    piece_rows = pieces[0].shape[0]
    if piece_rows == bucket:
        return jax.device_put(pieces[0])
    out = _zero_bucket(bucket, pieces[0].shape[1:], pieces[0].dtype)
    ahead: list = []
    for i, host in enumerate(pieces):
        if len(ahead) == _PIECES_AHEAD:
            # a piece is freed once placed; wait for an earlier transfer
            # before handing over another, or every piece is in flight
            ahead.pop(0).block_until_ready()
        piece = jax.device_put(host)
        out = _place_piece(out, piece, np.int32(i * piece_rows))
        ahead.append(piece)
    return out


def _make_mk_word(wire: WireFormat):
    """The jitted :func:`mk_word` of one wire layout: the device's build of
    the packed word, one compile a (bucket shape, piece shape, source dtypes).
    """
    fields, pad_code, nbytes = wire.packed_fields, wire.pad_code, wire.nbytes

    def unsigned(a):
        # 32 bits wide first, a signed column keeping its sign: a negative of
        # any width reads past every declared width and every type id
        wide = a.astype(jnp.int32 if a.dtype.kind == "i" else jnp.uint32)
        return jax.lax.bitcast_convert_type(wide, jnp.uint32)

    def mk_word(bucket, flags, type_ids, cols, at):
        """One piece of the word's sources built into the packed word, by
        :meth:`WireFormat._pack_words`'s own expression, and placed at row
        ``at`` of its donated ``[rows, nbytes]`` bucket. ``flags`` gains, a
        packed column, whether any element of this piece lies outside its
        declared width."""
        word = jnp.minimum(unsigned(type_ids), np.uint32(pad_code))
        outside = []
        for pf, col in zip(fields, cols):
            bits = unsigned(col)
            outside.append(jnp.any(bits > np.uint32(pf.mask)))
            word = word | (bits << np.uint32(pf.shift))
        piece = jnp.stack(
            [((word >> np.uint32(8 * k)) & np.uint32(0xFF)).astype(jnp.uint8)
             for k in range(nbytes)], axis=1)
        if outside:
            flags = flags | jnp.stack(outside)
        return jax.lax.dynamic_update_slice(bucket, piece, (at, 0)), flags

    return jax.jit(mk_word, donate_argnums=0)


def _put_word(place, sources: list, bucket: int, nbytes: int):
    """:func:`_put_pieces` for a word the device builds: ``sources`` holds the
    pieces (:func:`_bucket_pieces`) of the type ids and of each packed
    column. Piece ``i`` of each is put and ``place`` (a :func:`mk_word`)
    builds their word at its row offset into a bucket of device zeros, which
    is every row past the last piece; the zero rows of a padded last piece
    build word 0. The buffer is element for element ``np.pad`` of the host's
    packed one. Returns it and the columns' out-of-range flags."""
    piece_rows = sources[0][0].shape[0]
    out = _zero_bucket(bucket, (nbytes,), np.uint8)
    # put, not computed: an eager jnp.zeros is a device program of its own
    flags = jax.device_put(np.zeros((len(sources) - 1,), np.bool_))
    ahead: list = []
    for i, host in enumerate(zip(*sources)):
        if len(ahead) == _PIECES_AHEAD:
            jax.block_until_ready(ahead.pop(0))
        type_ids, *cols = piece = [jax.device_put(h) for h in host]
        out, flags = place(out, flags, type_ids, cols,
                           np.int32(i * piece_rows))
        ahead.append(piece)
    return out, flags


def _put_wire(host: list, bucket: int, wire: WireFormat, place):
    """The device's half of one wire's (or one shard's) upload: ``host`` is
    the pieces of its arrays, the word's first. ``place`` is None for a
    packed buffer, which goes up as it lies; else the word's arrays are its
    sources (the type ids and ``wire``'s packed columns) and ``place`` the
    :func:`mk_word` that builds it. Returns ``(flat_wire, side buffers,
    flags or None)``."""
    if place is None:
        n_word, flags = 1, None
        flat_wire = _put_pieces(host[0][0], bucket)
    else:
        n_word = 1 + len(wire.packed_fields)
        flat_wire, flags = _put_word(
            place, [ps for ps, _ in host[:n_word]], bucket, wire.nbytes)
    return flat_wire, [_put_pieces(ps, bucket) for ps, _ in host[n_word:]], flags


def _raise_outside(wire: WireFormat, flags, words: "WordSources") -> None:
    """Raise what the host's word build raises for the first packed column
    whose device flag is set: the same message, its max and min from the
    host column the wire still holds."""
    for pf, outside in zip(wire.packed_fields, np.asarray(flags)):
        if outside:
            raise overflow_error(pf, words.cols[pf.name])


def _goes_up_as_it_lies(a) -> bool:
    """Whether a source column of the word can be put on the device as it
    is: a C-contiguous ``[N]`` integer array of at most four bytes an
    element (jax without x64 would narrow an int64 silently)."""
    return (isinstance(a, np.ndarray) and a.ndim == 1
            and a.dtype.kind in "iu" and a.dtype.itemsize <= 4
            and a.dtype.isnative and a.flags.c_contiguous)


@dataclass
class WordSources:
    """What a wire's packed word is built from where the device builds it
    (:func:`mk_word`): the packed stream's type ids and its packed columns
    (``WireFormat.packed_fields``, in that order), each ``[N]`` and
    :func:`_goes_up_as_it_lies`, possibly the caller's own arrays. ``pack``
    is the host's build of the same word, for whoever reads
    :attr:`ResidentWire.packed`."""

    type_ids: np.ndarray
    cols: dict
    pack: Callable[[], np.ndarray]

    def arrays(self) -> tuple:
        return (self.type_ids, *self.cols.values())


@dataclass
class ResidentWire:
    """The host/disk wire form of a resident corpus (pure numpy, mmap-able).

    Produced by :meth:`ReplayEngine.pack_resident`; consumed by
    :meth:`ReplayEngine.upload_resident`. Saving this next to the log segment
    makes the pack a one-time build cost: every later cold start mmaps the
    wire bytes and streams them straight onto the device.

    ``packed`` carries ``guard`` zero rows past its ``num_events``. A wire
    that carries ``words`` (the word's source columns: ``pack_resident``
    hands them over where they can go up as they lie) holds no packed buffer
    until someone reads ``packed``: the first read builds it on the host,
    byte for byte what ``pack_resident`` would have built (inside a
    ``replay.encode.words`` stage; a column outside its declared width
    raises there), and keeps it. ``packed_shape`` is its shape without
    building it. The default upload of such a wire never reads it: the
    device builds the word from the sources.

    A side column needs no guard rows: it holds between ``num_events`` rows
    and the packed buffer's (:meth:`ReplayEngine.check_wire`), and the upload
    supplies the rest as device zeros. ``pack_resident`` makes it ``[N]``; a
    wire saved by an older build holds ``[N + guard]`` and still loads.

    Ownership: the side columns, and the type ids and packed columns in
    ``words``, may be the caller's own arrays
    (:meth:`ReplayEngine.pack_resident`): they must not be written between
    ``pack_resident`` and the return of ``upload_resident`` /
    ``prepare_resident_sharded``."""

    derived_key: dict
    #: u8 [N+guard, nbytes]; None (with ``words``): built on first read
    packed: Optional[np.ndarray] = dc_field(repr=False)
    side: dict  # {name: np [N]}, possibly the caller's arrays
    starts: np.ndarray  # i32 [B] (length-sorted order)
    lengths: np.ndarray  # i32 [B]
    perm: Optional[np.ndarray]  # sorted-rank -> original index
    guard: int
    num_events: int
    #: WireFormat.layout_fingerprint() of the packing schema; None only for
    #: wires saved before fingerprints existed (upload falls back to the
    #: structural byte/side checks)
    layout: Optional[dict] = None
    #: context of the ``replay.encode`` span that packed it, so the upload
    #: continues that trace; not saved (a loaded wire starts a new trace)
    trace_ctx: Optional[SpanContext] = None
    #: the word's sources, where the device is to build it; not saved
    words: Optional[WordSources] = None

    @property
    def host_packed(self) -> bool:
        """Whether the packed buffer exists on the host (given or built)."""
        return self._packed is not None

    @property
    def packed_shape(self) -> tuple:
        """``packed.shape``, known without building the buffer."""
        if self._packed is not None:
            return self._packed.shape
        return self.num_events + self.guard, int(self.layout["nbytes"])

    def save(self, root: str) -> None:
        import json
        import os

        os.makedirs(root, exist_ok=True)
        np.save(os.path.join(root, "packed.npy"), self.packed)
        np.save(os.path.join(root, "starts.npy"), self.starts)
        np.save(os.path.join(root, "lengths.npy"), self.lengths)
        if self.perm is not None:
            np.save(os.path.join(root, "perm.npy"), self.perm)
        for name, col in self.side.items():
            np.save(os.path.join(root, f"side_{name}.npy"), col)
        meta = {"derived_key": self.derived_key, "guard": self.guard,
                "num_events": self.num_events,
                "side_names": sorted(self.side),
                "has_perm": self.perm is not None,
                # layout fingerprint: a consuming engine whose schema evolved
                # must refuse the wire rather than decode misaligned bytes
                "nbytes": int(self.packed.shape[1]),
                "side_dtypes": {k: str(np.dtype(v.dtype))
                                for k, v in self.side.items()},
                "layout": self.layout}
        with open(os.path.join(root, "wire.json"), "w") as f:
            json.dump(meta, f)

    @classmethod
    def load(cls, root: str) -> "ResidentWire":
        import json
        import os

        with open(os.path.join(root, "wire.json")) as f:
            meta = json.load(f)
        mm = lambda name: np.load(os.path.join(root, name), mmap_mode="r")  # noqa: E731
        return cls(
            derived_key=dict(meta["derived_key"]),
            packed=mm("packed.npy"),
            side={name: mm(f"side_{name}.npy") for name in meta["side_names"]},
            starts=np.asarray(mm("starts.npy")),
            lengths=np.asarray(mm("lengths.npy")),
            perm=np.asarray(mm("perm.npy")) if meta["has_perm"] else None,
            guard=int(meta["guard"]), num_events=int(meta["num_events"]),
            layout=meta.get("layout"))


def _read_packed(self: ResidentWire) -> np.ndarray:
    if self._packed is None:
        self._packed = self.words.pack()
    return self._packed


def _write_packed(self: ResidentWire, packed: Optional[np.ndarray]) -> None:
    self._packed = packed


# a property over the dataclass's own field: ``ResidentWire(packed=...)``,
# ``dataclasses.replace`` and ``wire.packed = ...`` all keep working, and a
# read of a buffer not yet built builds it
ResidentWire.packed = property(_read_packed, _write_packed)


@dataclass
class ResidentPlan:
    """Tile schedule for one resident replay (two lane granularities)."""

    width: int
    bs_big: int
    bs_small: int
    big_i0: np.ndarray  # i32 [k_big]
    big_tb: np.ndarray  # i32 [k_big]
    small_i0: np.ndarray  # i32 [k_small]
    small_tb: np.ndarray  # i32 [k_small]

    @property
    def lanes_tiled(self) -> int:
        """The lane windows the plan's tiles cover, padding lanes and all."""
        return (len(self.big_i0) * self.bs_big
                + len(self.small_i0) * self.bs_small)

    @property
    def padded_slots(self) -> int:
        return self.lanes_tiled * self.width

    @property
    def tiles(self) -> int:
        return len(self.big_i0) + len(self.small_i0)

    @property
    def slots_small(self) -> int:
        """The padded slots the narrow granularity folds."""
        return len(self.small_i0) * self.bs_small * self.width

    @property
    def rounds(self) -> int:
        """Passes over time: the distinct tile offsets the plan visits."""
        return len(np.union1d(self.big_tb, self.small_tb))


@dataclass
class ReplayResult:
    """Folded states + accounting for throughput metrics."""

    states: dict[str, np.ndarray]  # {col: [B]} in the original aggregate order
    num_aggregates: int
    num_events: int
    padded_events: int  # B*T actually scanned (padding overhead indicator)
    # aggregate-id strings aligned with the state columns, when the inputs carried
    # them (segment chunks) — lets callers write states back to the keyed store
    aggregate_ids: Optional[list] = None


class ReplayEngine:
    """Drives batched replay for one model family.

    Equivalent role: the bulk-restore path of AggregateStateStoreKafkaStreams
    (common/.../kafka/streams/AggregateStateStoreKafkaStreams.scala:53-178) with
    ``replayBackend = tpu`` (BASELINE.json). Consumes ``EncodedEvents`` /
    ``ColumnarEvents`` batches (from surge_tpu.codec) and produces state columns; the
    KTable-equivalent store ingests the writeback.

    Parameters
    ----------
    spec: the model's ReplaySpec.
    config: batch size / time chunk / bucket knobs (``surge.replay.*``).
    mesh: optional ``jax.sharding.Mesh``; batch dim B is sharded over ``mesh_axis``.
    profiler: the :class:`~surge_tpu.replay.profiler.ReplayProfiler` every stage
        is timed through; ``None`` builds a counter-only one over the
        process-wide ring (:func:`surge_tpu.tracing.default_tracer`).
    """

    def __init__(self, spec: ReplaySpec, config: Config | None = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 mesh_axis: Optional[str] = None, profiler=None) -> None:
        ensure_compile_cache()
        self.spec = spec
        self.config = config or default_config()
        self.mesh = mesh
        self.profiler = (profiler if profiler is not None else
                         ReplayProfiler.counters(tracer=default_tracer()))
        # batch-axis name: explicit arg > surge.replay.mesh-axes (first entry)
        if mesh_axis is None:
            mesh_axis = (self.config.get_str("surge.replay.mesh-axes", "data")
                         .split(",")[0].strip() or "data")
        self.mesh_axis = mesh_axis
        self.donate_carry = self.config.get_bool("surge.replay.donate-carry", True)
        self.time_chunk = self.config.get_int("surge.replay.time-chunk")
        self.min_time_window = self.config.get_int("surge.replay.min-time-window", 8)
        self.sort_by_length = self.config.get_bool("surge.replay.sort-by-length", True)
        lane = self._lane_multiple()
        self.batch_size = _round_up(
            max(self.config.get_int("surge.replay.batch-size"), lane), lane)
        self.buckets = self.config.get_int_list("surge.replay.length-buckets", "64,256,1024,4096")

        self._tile_backend = self.config.get_str("surge.replay.tile-backend",
                                                 "auto")
        if self._tile_backend not in ("auto", "xla", "assoc"):
            raise ValueError(
                f"unknown surge.replay.tile-backend "
                f"{self._tile_backend!r} (auto|xla|assoc)")
        # "auto" resolves lazily (the choice is backend-dependent and reading
        # the backend here would initialize it in engine-constructing
        # processes that never dispatch)
        self._tile_backend_resolved: str | None = None
        # one (wire, jitted fold) per derived-column declaration the inputs carry —
        # in practice at most two: framework logs (ordinal seq) and object-test logs
        self._wire_folds: dict[frozenset, tuple[WireFormat, Any]] = {}
        # resident-corpus gather-folds, same keying
        self._resident_folds: dict[frozenset, Any] = {}
        # on-device fresh init-slab builders per b_pad (zero host transfers)
        self._slab_programs: dict = {}
        # the device's word builds (mk_word), one per wire layout
        self._word_programs: dict = {}
        # the state-pull finalize programs, one per set of full-width columns,
        # built once per engine — jax.jit's own shape cache handles differing
        # batch sizes (streamed pieces are rebuilt per call; a per-corpus
        # cache would re-jit them inside timed passes)
        self._finalize_programs: dict = {}
        # the integer columns the last state pull found too wide for 16 bits:
        # the next pull packs them full width from the start (_pull_states)
        self._pull_wide: frozenset = frozenset()
        # distinct (fold-variant, window-shape) signatures — every entry corresponds
        # to one XLA compilation (shapes are static under jit), counted without any
        # private JAX internals
        self._signatures: set = set()
        # host-side phase accounting (bench breakdown), fed by the profiler's
        # stages: seconds of the encode stages (a window's pack, the whole of
        # pack_resident), of the h2d stages (a window's transfer, the whole of
        # upload_resident), windows dispatched, and the lane-row fetches the
        # resident tiles asked for (_rows_fetched)
        self.stats = {"pack_s": 0.0, "h2d_s": 0.0, "windows": 0,
                      "rows_fetched": 0}
        if mesh is not None:
            pspec = jax.sharding.PartitionSpec(mesh_axis)
            self._sharding = jax.sharding.NamedSharding(mesh, pspec)
            self._packed_sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(None, mesh_axis, None))
            self._ev_sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(None, mesh_axis))
        else:
            self._sharding = None
            self._packed_sharding = None
            self._ev_sharding = None

    def _wire_fold(self, derived_cols: Mapping[str, str]
                   ) -> tuple[frozenset, WireFormat, Any]:
        """The (cache key, WireFormat, jitted fold) triple for one derived-column
        declaration.

        The fold consumes wire-packed windows directly — decode happens inside the
        jit so XLA fuses unpacking into the scan and only wire bytes cross the link:
        ``fold(carry {name:[B]}, packed u8 [T,B,nbytes], side {name:[T,B]},
        ord_base i32 [B]) -> carry``.
        """
        key = frozenset(dict(derived_cols).items())
        hit = self._wire_folds.get(key)
        if hit is not None:
            return (key, *hit)
        wire = WireFormat(self.spec.registry, derived_cols)
        batch_fold = make_batch_fold(self.spec)

        def fold(carry: StateTree, packed, side, ord_base) -> StateTree:
            return batch_fold(carry, wire.decode(packed, side, ord_base))

        donate = (0,) if self.donate_carry else ()
        if self.mesh is not None:
            carry_sh = jax.tree_util.tree_map(lambda _: self._sharding,
                                              self._carry_struct())
            jitted = jax.jit(fold, donate_argnums=donate,
                             in_shardings=(carry_sh, None, None, None),
                             out_shardings=carry_sh)
        else:
            jitted = jax.jit(fold, donate_argnums=donate)
        self._wire_folds[key] = (wire, jitted)
        return key, wire, jitted

    # -- helpers ------------------------------------------------------------------------

    def _carry_struct(self) -> StateTree:
        return {f.name: None for f in self.spec.registry.state.fields}

    def _lane_multiple(self) -> int:
        """Pad B to a multiple of device count (for even mesh sharding) × 8."""
        n = 1 if self.mesh is None else int(np.prod(self.mesh.devices.shape))
        return max(8 * n, n)

    def num_compiles(self) -> int:
        """Compiled-program count across fold variants (compile-stability
        instrumentation): the number of distinct static shape signatures dispatched.
        Under ``jax.jit`` each distinct signature triggers exactly one compilation,
        so this equals the XLA program count without relying on private JAX APIs
        (VERDICT r3 weak #6)."""
        return len(self._signatures)

    def init_carry_np(self, batch: int) -> dict[str, np.ndarray]:
        """Host-side initial carry columns ``{name: [batch]}``."""
        init = self.spec.init_state_tree()
        return {k: np.broadcast_to(np.asarray(v), (batch,)).copy()
                for k, v in init.items()}

    def init_carry(self, batch: int) -> StateTree:
        carry = self.init_carry_np(batch)
        return self._device_carry(carry)

    def _device_carry(self, carry: Mapping[str, np.ndarray]) -> StateTree:
        if self._sharding is not None:
            return {k: jax.device_put(np.asarray(v), self._sharding)
                    for k, v in carry.items()}
        return {k: jnp.asarray(np.asarray(v)) for k, v in carry.items()}

    def carry_from_states(self, states: Sequence[Any]) -> dict[str, np.ndarray]:
        """Resume from snapshots (checkpointed carry, SURVEY.md §5.4 TPU mapping)."""
        return encode_states(self.spec.registry.state, states)

    def _carry_slice(self, init_carry: Mapping[str, Any] | None,
                     start: int, stop: int, bp: int,
                     idxs: np.ndarray | None = None) -> StateTree:
        """Fresh padded device carry for aggregates [start:stop) — or the explicit
        ``idxs`` gather when the batch was length-reordered. Donation-safe:
        external arrays are copied to host buffers first, never handed to the jit."""
        if init_carry is None:
            return self._device_carry(self.init_carry_np(bp))
        defaults = self.init_carry_np(bp)
        out = {}
        for k, full in init_carry.items():
            piece = (np.asarray(full)[idxs] if idxs is not None
                     else np.asarray(full)[start:stop])
            buf = defaults[k]
            buf[: len(piece)] = piece
            out[k] = buf
        return self._device_carry(out)

    def _device_window(self, packed: np.ndarray, side: Mapping[str, np.ndarray],
                       ord_base: np.ndarray):
        if self._ev_sharding is not None:
            return (jax.device_put(packed, self._packed_sharding),
                    {k: jax.device_put(v, self._ev_sharding) for k, v in side.items()},
                    jax.device_put(ord_base, self._sharding))
        return packed, side, ord_base

    # -- core entry points --------------------------------------------------------------

    def replay_encoded(self, enc: EncodedEvents,
                       init_carry: Mapping[str, Any] | None = None,
                       ordinal_base: np.ndarray | None = None) -> ReplayResult:
        """Fold one encoded batch. The aggregate axis is chunked to
        ``surge.replay.batch-size`` and the time axis to ``surge.replay.time-chunk`` so
        arbitrarily large batches and arbitrarily long (padded) logs stream through a
        fixed-size compiled program with bounded HBM.

        When resuming (``init_carry`` from a snapshot) and the batch declares derived
        ordinal columns, ``ordinal_base`` must carry each aggregate's already-folded
        event count ``[B]`` so the derived ordinals continue rather than restart."""
        b, t = enc.batch_size, enc.max_len
        bs = min(self.batch_size, _round_up(max(b, 1), self._lane_multiple()))
        state_fields = self.spec.registry.state.fields
        out = {f.name: np.zeros((b,), dtype=f.dtype) for f in state_fields}
        padded = 0

        for start in range(0, max(b, 1), bs):
            stop = min(start + bs, b)
            if stop <= start:
                break
            carry = self._carry_slice(init_carry, start, stop, bs)
            carry, scanned = self._fold_window(
                carry, enc.type_ids[start:stop],
                {k: v[start:stop] for k, v in enc.cols.items()}, bs,
                derived_cols=enc.derived_cols,
                ordinal_base=None if ordinal_base is None else ordinal_base[start:stop])
            with self.profiler.stage("fetch"):
                for name in out:
                    out[name][start:stop] = np.asarray(carry[name])[: stop - start]
            padded += bs * scanned

        return ReplayResult(states=out, num_aggregates=b,
                            num_events=int(enc.lengths.sum()), padded_events=padded)

    def replay_columnar(self, colev: ColumnarEvents,
                        init_carry: Mapping[str, Any] | None = None,
                        ordinal_base: np.ndarray | None = None) -> ReplayResult:
        """Fold a flat columnar log (the log-segment storage layout) directly.

        Densifies per B-chunk, never the whole batch: each chunk pads only to its own
        max log length, so host memory stays bounded by ``batch-size × local max T``
        even when one aggregate's log dwarfs the rest.

        With ``surge.replay.sort-by-length`` (default on) aggregates are ordered by
        log length before B-chunking, so a chunk's local max ≈ its members' lengths
        — together with the tail-window ladder this is the pad_ratio lever (VERDICT
        r3 next #2). Output state columns stay in the caller's aggregate order."""
        b = colev.num_aggregates
        bs = min(self.batch_size, _round_up(max(b, 1), self._lane_multiple()))
        # ordering only changes chunk composition when there IS more than one chunk
        if self.sort_by_length and b > bs:
            lengths_all = np.bincount(colev.agg_idx, minlength=b).astype(np.int64)
            perm = np.argsort(lengths_all, kind="stable").astype(np.int32)
            if np.array_equal(perm, np.arange(b, dtype=np.int32)):
                perm = None  # already length-ordered: skip the O(N) relabel
            else:
                inv = np.empty_like(perm)
                inv[perm] = np.arange(b, dtype=np.int32)
                # relabel each event's aggregate to its length rank; the stable
                # aggregate sort below then groups by rank while preserving each
                # aggregate's time order
                colev = ColumnarEvents(
                    num_aggregates=b, agg_idx=inv[colev.agg_idx],
                    type_ids=colev.type_ids, cols=colev.cols,
                    derived_cols=dict(colev.derived_cols))
        else:
            perm = None
        sorted_ev = colev.sorted_by_aggregate()
        state_fields = self.spec.registry.state.fields
        out = {f.name: np.zeros((b,), dtype=f.dtype) for f in state_fields}
        padded = 0
        total_events = 0
        for start in range(0, max(b, 1), bs):
            stop = min(start + bs, b)
            if stop <= start:
                break
            idxs = None if perm is None else perm[start:stop]
            enc = columnar_to_batch(sorted_ev.slice_aggregates(start, stop))
            carry = self._carry_slice(init_carry, start, stop, bs, idxs=idxs)
            ob = (None if ordinal_base is None else
                  np.asarray(ordinal_base)[idxs] if idxs is not None
                  else ordinal_base[start:stop])
            carry, scanned = self._fold_window(carry, enc.type_ids, enc.cols, bs,
                                               derived_cols=enc.derived_cols,
                                               ordinal_base=ob)
            with self.profiler.stage("fetch"):
                for name in out:
                    chunk_states = np.asarray(carry[name])[: stop - start]
                    if idxs is None:
                        out[name][start:stop] = chunk_states
                    else:
                        out[name][idxs] = chunk_states
            padded += bs * scanned
            total_events += int(enc.lengths.sum())
        return ReplayResult(states=out, num_aggregates=b,
                            num_events=total_events, padded_events=padded)

    def _window_plan(self, t: int) -> list[tuple[int, int]]:
        """Decompose a T-length window into ``(start, padded_width)`` pieces.

        Full pieces are ``time-chunk`` wide; the tail descends a power-of-two
        ladder down to ``min-time-window`` instead of padding to a full chunk —
        the T-quantization half of the pad_ratio lever (VERDICT r3 weak #2).
        Every width in the ladder is a distinct compiled program, so the program
        count stays bounded at ``1 + log2(chunk/min)`` per fold variant."""
        if t <= 0:
            return []  # nothing to fold: no dispatch (and no all-pad program)
        chunk = self.time_chunk if self.time_chunk > 0 else t
        plan = []
        s = 0
        while t - s >= chunk:
            plan.append((s, chunk))
            s += chunk
        rem = t - s
        if rem > 0 and self.min_time_window <= 0:
            plan.append((s, chunk))  # ladder disabled: full-pad tail
        elif rem > 0:
            # bit-decompose the tail into descending ladder windows so scanned
            # slots ≈ round_up(tail, min) — a single covering window would waste
            # up to 2× on the tail, which dominates when logs are much shorter
            # than a full time-chunk. Widths always come from ladder_widths()
            # (min × powers of two), never from halving the chunk, so a
            # non-power-of-two time-chunk cannot produce sub-min or
            # unpredictable widths.
            ladder = self.ladder_widths()
            w = ladder[-1]
            while rem > 0:
                while w > ladder[0] and w > rem:
                    w //= 2
                plan.append((s, w))
                take = min(w, rem)
                s += take
                rem -= take
        return plan

    def ladder_widths(self) -> list[int]:
        """The tail-window widths _window_plan can dispatch (ascending):
        ``min-time-window × 2^k``, strictly below the time-chunk (a tail is
        always < chunk, so a chunk-sized ladder entry could never fire). Every
        entry is a distinct compiled program; warm-up should cover all of them
        plus the full chunk (see bench.py)."""
        min_w = max(self.min_time_window, 1)
        chunk = self.time_chunk if self.time_chunk > 0 else min_w
        ladder = [min_w]
        while ladder[-1] * 2 < chunk:
            ladder.append(ladder[-1] * 2)
        return ladder

    def _fold_window(self, carry: StateTree, type_ids: np.ndarray,
                     cols: Mapping[str, np.ndarray], bs: int,
                     derived_cols: Mapping[str, str] | None = None,
                     t_base: int = 0,
                     ordinal_base: np.ndarray | None = None
                     ) -> tuple[StateTree, int]:
        """Fold one [b?, T] window (b? ≤ bs) through T-chunked fixed-width programs;
        returns ``(carry, scanned_t)`` where scanned_t is the padded slot count per
        aggregate actually dispatched.

        Each chunk is wire-packed on the host (uint8 word + side columns) and decoded
        inside the fold jit. The ordinal base of device-derived positional columns is
        ``ordinal_base[b] + t_base + s``: per-aggregate already-folded event counts
        (resume) plus the window's global time offset (replay_stream's cumulative
        width of prior chunks)."""
        key, wire, fold = self._wire_fold(derived_cols or {})
        b, t = type_ids.shape
        base = np.zeros((bs,), dtype=np.int32)
        if ordinal_base is not None:
            base[:b] = np.asarray(ordinal_base, dtype=np.int32)[:b]
        scanned = 0
        stage = self.profiler.stage
        for s, width in self._window_plan(t):
            e = min(s + width, t)
            with stage("encode") as enc:
                packed, side = wire.pack_window(type_ids, cols, s, e, width, bs)
                ord_base = base + np.int32(t_base + s)
            with stage("h2d") as h2d:
                window = self._device_window(packed, side, ord_base)
            self.stats["pack_s"] += enc.seconds
            self.stats["h2d_s"] += h2d.seconds
            self.stats["windows"] += 1
            self.profiler.count_windows()
            scanned += width
            sig = (key, packed.shape,
                   tuple((k, v.shape) for k, v in sorted(side.items())))
            first_dispatch = sig not in self._signatures
            self._signatures.add(sig)
            # a fresh signature means this dispatch pays the XLA compile;
            # steady dispatches only pay the async host-side handoff
            with stage("compile" if first_dispatch else "dispatch",
                       width=width, batch=bs):
                carry = fold(carry, *window)
        return carry, scanned

    # -- resident-corpus path (single upload, on-device gather) -------------------------

    def pack_resident(self, colev: ColumnarEvents) -> "ResidentWire":
        """Host-side half of :meth:`prepare_resident`: length-sort, flat-pack
        and guard-pad the corpus into its device wire form. The result is pure
        numpy and :meth:`ResidentWire.save`-able — a log segment built once can
        be mmapped and uploaded on every later cold start without re-packing
        (the pack is one-time work, like the reference's log compaction).

        Fast path: an input whose events are already GROUPED per aggregate
        (``agg_idx`` non-decreasing — every encode/segment path produces this)
        is packed in ITS OWN event order and lanes point at their segments by
        indirection (``starts[k] = start of aggregate perm[k]``). Nothing in
        the device fold requires lane slabs to be buffer-contiguous — each
        tile gathers from per-lane bases — so no event is moved; only the O(B)
        length argsort remains, and it is skipped where every log is as long
        as the next.

        Where the word's sources (the packed stream's type ids and every
        packed column) can go up as they lie (:func:`_goes_up_as_it_lies`:
        C-contiguous integer arrays of at most four bytes an element), the
        host packs nothing: the wire carries them (:class:`WordSources`) and
        the upload has the device build the word (:func:`mk_word`). Any other
        input (an int64 or strided column from a decoder, a float, an object
        array) is packed here, each input column read once and the wire
        written once, in cache-sized blocks
        (``codec/wire.py:FLAT_PACK_BLOCK`` events; nothing is kept from one
        call to the next). The four stages:

        - ``encode.lanes``: :func:`grouped_lengths`, the blocked neighbour
          compare and the lengths from the segment boundaries. Ungrouped input
          (rare: interleaved hand-built columns) falls back to ``bincount``
          and the stable re-sort, whole-column.
        - ``encode.words``: the test of the sources and their hand-over, or
          :meth:`WireFormat.pack_blocks`, the word build per block, stored
          straight into the ``[N + guard, nbytes]`` buffer. The same name
          spans the host build wherever a reader of
          :attr:`ResidentWire.packed` later asks for it.
        - ``encode.bytes``: :meth:`WireFormat.side_columns`, the side columns
          ``[N]`` in their wire dtypes: a column that already is one is handed
          over as it is, any other is cast into a fresh buffer (the counter
          has none).
        - ``encode.guard``: ``starts``, the lane view under ``perm``, the
          :class:`ResidentWire`. The guard rows are the word buffer's alone,
          part of what the word build allocates; nothing is copied to
          append them, and a side column has none (the upload's bucket of
          device zeros is what the fold reads past its last row).

        A packed column below 0 or past its declared width raises
        ``ValueError``: here where the host packs; from
        :meth:`upload_resident` / :meth:`prepare_resident_sharded`, with the
        same message and before any corpus exists, where the device does
        (and from whoever first reads ``packed`` of such a wire).
        Out-of-range type ids pack as the pad sentinel on both.

        Ownership: the wire's side columns, and the type ids and packed
        columns it carries for the device, may be ``colev``'s own arrays.
        They must not be written between this call and the return of
        :meth:`upload_resident` / :meth:`prepare_resident_sharded`; after it
        the device holds its own copy.

        The ``replay.encode`` span says which way it went: ``grouped``,
        ``lanes_from`` (``boundaries`` or ``bincount``), ``words_from``
        (``device`` or ``host``), ``blocks`` (how many blocks the word pass
        ran here: 0 where nothing was packed on the host), ``side_aliased``
        (side columns handed over as the caller's arrays) and
        ``side_copied_bytes`` (bytes cast into fresh buffers)."""
        stage = self.profiler.stage
        b = colev.num_aggregates
        given = colev.cols  # the caller's arrays, whatever is packed below
        with stage("encode", events=colev.num_events, aggregates=b) as enc:
            with stage("encode.lanes"):
                lengths = grouped_lengths(colev.agg_idx, b)
                grouped = lengths is not None
                if not grouped:
                    lengths = np.bincount(colev.agg_idx,
                                          minlength=b).astype(np.int64)
                perm = None
                if (self.sort_by_length and b > 1
                        and lengths.min() != lengths.max()):
                    # DESCENDING by length: the lanes still active after t
                    # events form a prefix, so each tile round dispatches a
                    # contiguous lane range
                    perm = np.argsort(-lengths, kind="stable").astype(np.int32)
                    if np.array_equal(perm, np.arange(b, dtype=np.int32)):
                        perm = None
                if grouped:
                    to_pack = colev
                else:
                    # ungrouped input: materialize the sorted order (rare —
                    # interleaved hand-built columns); lanes end up
                    # buffer-contiguous
                    if perm is not None:
                        inv = np.empty_like(perm)
                        inv[perm] = np.arange(b, dtype=np.int32)
                        colev = ColumnarEvents(
                            num_aggregates=b, agg_idx=inv[colev.agg_idx],
                            type_ids=colev.type_ids, cols=colev.cols,
                            derived_cols=dict(colev.derived_cols))
                        lengths = lengths[perm]
                    to_pack = colev.sorted_by_aggregate()

            wire = WireFormat(self.spec.registry, dict(to_pack.derived_cols))
            # tail padding so every [start + t_base, width) slab slice stays
            # in bounds without clamping (clamped slices would shift lane
            # data); content is irrelevant — slots past lens decode to the pad
            # sentinel
            guard = max(self.resident_tile_width(), _WIRE_GUARD_MIN)
            with stage("encode.words"):
                type_ids = to_pack.type_ids
                fields = {pf.name: to_pack.cols[pf.name]
                          for pf in wire.packed_fields}
                packed, blocks, words = None, 0, None
                if all(map(_goes_up_as_it_lies, (type_ids, *fields.values()))):
                    def pack_on_host() -> np.ndarray:
                        with stage("encode.words", follows=enc.context):
                            return wire.pack_blocks(type_ids, fields, guard)[0]

                    words = WordSources(type_ids, fields, pack_on_host)
                else:
                    packed, blocks = wire.pack_blocks(type_ids, fields, guard)
            with stage("encode.bytes"):
                side_flat = wire.side_columns(to_pack.cols)
            with stage("encode.guard"):
                # lengths/starts are in the PACKED stream's aggregate-id
                # order; the grouped path then permutes the lane VIEW only
                # (indirection), the ungrouped path already permuted the
                # stream itself
                starts = np.zeros(b + 1, dtype=np.int64)
                np.cumsum(lengths, out=starts[1:])
                starts_lane, lens_lane = starts[:-1], lengths
                if grouped and perm is not None:
                    starts_lane = starts_lane[perm]
                    lens_lane = lengths[perm]
                out = ResidentWire(
                    derived_key=dict(to_pack.derived_cols), packed=packed,
                    side=side_flat, starts=starts_lane.astype(np.int32),
                    lengths=lens_lane.astype(np.int32), perm=perm, guard=guard,
                    num_events=to_pack.num_events,
                    layout=wire.layout_fingerprint(), trace_ctx=enc.context,
                    words=words)
            enc.set_attribute("wire_bytes", _wire_nbytes(out))
            enc.set_attribute("side_bytes", _side_nbytes(side_flat))
            copied = [v.nbytes for k, v in side_flat.items()
                      if not np.may_share_memory(v, given[k])]
            enc.set_attribute("side_aliased", len(side_flat) - len(copied))
            enc.set_attribute("side_copied_bytes", sum(copied))
            enc.set_attribute("blocks", blocks)
            enc.set_attribute("words_from",
                              "host" if words is None else "device")
            enc.set_attribute("grouped", grouped)
            enc.set_attribute("lanes_from",
                              "boundaries" if grouped else "bincount")
        self.stats["pack_s"] += enc.seconds
        return out

    def check_wire(self, w: "ResidentWire") -> WireFormat:
        """Validate a (possibly disk-loaded) wire against this engine: the
        packed buffer's guard rows cover the tile width, every side column
        holds the wire's events and no more rows than the packed buffer, and
        the packing layout matches the engine's schema bit-for-bit. Returns
        the engine's WireFormat for the wire's derived-column declaration.
        Shared by the single-device and sharded upload paths — a stale wire
        must never decode silently-wrong states."""
        if w.guard < self.resident_tile_width():
            raise ValueError(
                f"wire guard {w.guard} is smaller than the engine's tile width "
                f"{self.resident_tile_width()}; repack or lower "
                "surge.replay.time-chunk")
        rows, nbytes = w.packed_shape
        if rows < w.num_events + w.guard:
            raise ValueError(
                f"wire holds {rows} packed rows, fewer than its {w.num_events} "
                f"events and {w.guard} guard rows; rebuild the wire")
        for name, col in w.side.items():
            if not w.num_events <= col.shape[0] <= rows:
                raise ValueError(
                    f"wire side column {name!r} holds {col.shape[0]} rows: "
                    f"need at least the wire's {w.num_events} events and at "
                    f"most the packed buffer's {rows}; rebuild the wire")
        # layout fingerprint check: never decode a wire packed under a
        # different schema (misaligned BITS would fold silently-wrong states —
        # the fingerprint pins field order, widths, shifts and type count, not
        # just the total byte width)
        wire = WireFormat(self.spec.registry, dict(w.derived_key))
        if w.layout is not None and w.layout != wire.layout_fingerprint():
            raise ValueError(
                f"wire layout mismatch: corpus was packed as {w.layout}, "
                f"engine schema packs {wire.layout_fingerprint()}; "
                "rebuild the wire with pack_resident")
        if wire.nbytes != nbytes:  # also guards corrupted buffers
            raise ValueError(
                f"wire layout mismatch: corpus packed {nbytes} "
                f"byte(s)/event but the engine's schema packs {wire.nbytes}; "
                "rebuild the wire with pack_resident")
        want_sides = {f.name: np.dtype(f.dtype) for f in wire.side_fields}
        got_sides = {k: np.dtype(v.dtype) for k, v in w.side.items()}
        if want_sides != got_sides:
            raise ValueError(
                f"wire side-column mismatch: corpus has {got_sides}, engine "
                f"schema expects {want_sides}; rebuild the wire")
        return wire

    def upload_resident(self, w: "ResidentWire") -> "ResidentCorpus":
        """Device-side half of :meth:`prepare_resident`: ship a packed wire
        corpus (fresh or mmapped from disk) and return the replay handle.

        The device buffers' lengths are bucketed to powers of two, so
        consecutive uploads of different-sized corpora — segment chunks in a
        restore — reuse one compiled program per bucket instead of
        recompiling per exact length.
        The bucket is the device buffer's, and one bucket serves the whole
        wire: the packed buffer's (``N + guard`` rows), which a side column
        of ``N`` rows shares, so every buffer a fold reads has one shape and
        zeros after the last event. The wire's own buffers go up as they are,
        in fixed-shape row pieces where the bucket is longer than one, and
        the host copies at most one piece an array (:func:`_bucket_pieces`,
        :func:`_put_pieces`).

        A wire that carries the word's sources (:class:`WordSources`) and
        no packed buffer yet sends each source column up as a side column
        goes, and :func:`mk_word` (``jit_mk_word``) builds the word from each
        piece into the bucket the fold reads: the device buffer is element
        for element what the host-packed wire's would be. A packed column
        outside its declared width, which :meth:`pack_resident` raises for
        where the host packs, raises from here on that path: the device
        flags it, the flags are read once every buffer is ready, and the
        ``ValueError`` (the host's message) leaves no corpus behind. A wire
        whose packed buffer exists (loaded, built for a ``save``, hand-made)
        goes up as it lies.

        The wire's side columns, type ids and packed columns may be the
        caller's arrays (:meth:`pack_resident`): every buffer has landed in
        a device buffer of its own before this returns, so the caller may
        write them after.

        Spans: ``h2d.bucket`` is what the host still copies (``starts`` /
        ``lens`` and the padded last pieces: ``copied_bytes``); ``h2d.put``
        is every put and placement through ``block_until_ready`` of the
        wire's buffers (``put_bytes``: the bytes handed to ``device_put``;
        ``pieces``: the puts, 1 an array where the bucket is one piece).
        ``h2d`` carries all three and ``word_source_bytes``: the bytes of
        the type-id and packed-column pieces put in place of the word, 0
        where the packed buffer went up."""
        if self.mesh is not None:
            raise NotImplementedError(
                "this engine is mesh-backed; use prepare_resident_sharded / "
                "replay_resident_sharded for the resident path")
        wire = self.check_wire(w)
        stage = self.profiler.stage
        b = w.lengths.shape[0]
        with stage("h2d", follows=w.trace_ctx, wire_bytes=_wire_nbytes(w),
                   side_bytes=_side_nbytes(w.side)) as h2d:
            with stage("h2d.bucket") as bucket:
                bs = min(self.batch_size,
                         _round_up(max(b, 1), self._lane_multiple()))
                chunks = 1
                while chunks * bs < b:
                    chunks *= 2
                b_pad = chunks * bs
                starts_p = _pad_rows(w.starts, b_pad)
                lens_p = _pad_rows(w.lengths, b_pad)
                copied_bytes = starts_p.nbytes + lens_p.nbytes
                # the word: its sources where the device is to build it,
                # else the packed buffer
                on_device = not w.host_packed
                word = w.words.arrays() if on_device else (w.packed,)
                # one bucket a wire, the packed buffer's: a side column of
                # fewer rows gets the same device shape
                rows = w.packed_shape[0]
                host = [_bucket_pieces(arr, _PIECE_ROWS, rows)
                        for arr in (*word, *w.side.values())]
                copied_bytes += sum(copied for _, copied in host)
                pieces = sum(len(ps) for ps, _ in host)
                put_bytes = sum(p.nbytes for ps, _ in host for p in ps)
                source_bytes = sum(p.nbytes for ps, _ in host[:len(word)]
                                   for p in ps) if on_device else 0
                bucket.set_attribute("copied_bytes", copied_bytes)
            with stage("h2d.put", put_bytes=put_bytes, pieces=pieces):
                flat_wire, sides, flags = _put_wire(
                    host, _bucket_len(rows), wire,
                    self._word_program(wire) if on_device else None)
                flat_side = dict(zip(w.side, sides))
                starts_dev = jax.device_put(starts_p)
                lens_dev = jax.device_put(lens_p)
                # every buffer, not the packed one alone: a column may be
                # the caller's array, theirs to write once this returns
                jax.block_until_ready((flat_wire, sides))
                if on_device:
                    _raise_outside(wire, flags, w.words)
            h2d.set_attribute("put_bytes", put_bytes)
            h2d.set_attribute("pieces", pieces)
            h2d.set_attribute("copied_bytes", copied_bytes)
            h2d.set_attribute("word_source_bytes", source_bytes)
        self.stats["h2d_s"] += h2d.seconds
        return ResidentCorpus(
            derived_key=dict(w.derived_key), flat_wire=flat_wire,
            flat_side=flat_side, starts=w.starts,
            lengths=w.lengths, perm=w.perm,
            starts_dev=starts_dev, lens_dev=lens_dev, b_pad=b_pad,
            num_events=w.num_events, wire_bytes=put_bytes,
            upload_s=h2d.seconds, trace_ctx=h2d.context)

    def _word_program(self, wire: WireFormat):
        """The :func:`mk_word` of ``wire``'s layout, made once an engine and
        layout, so that a restore's chunks compile it once a bucket."""
        key = repr(wire.layout_fingerprint())
        if key not in self._word_programs:
            self._word_programs[key] = _make_mk_word(wire)
        return self._word_programs[key]

    def prepare_resident_sharded(self, source):
        """Mesh form of :meth:`prepare_resident`: cut the packed corpus at
        event-count boundaries into one contiguous slice of its buffers a
        device of the mesh axis and upload each through the pieces of
        :meth:`upload_resident` (surge_tpu.replay.resident_mesh). ``source``
        is a ColumnarEvents or an already-packed ResidentWire."""
        from surge_tpu.replay.resident_mesh import ShardedResident

        wire = (source if isinstance(source, ResidentWire)
                else self.pack_resident(source))
        return ShardedResident(self, wire)

    def replay_resident_sharded(self, sharded,
                                init_carry: Mapping[str, Any] | None = None,
                                ordinal_base: np.ndarray | None = None
                                ) -> ReplayResult:
        """Fold a :meth:`prepare_resident_sharded` corpus across the mesh —
        the tile-loop design with one shard_map dispatch per granularity and
        one device→host pull, no collectives (lanes are independent)."""
        from surge_tpu.replay.resident_mesh import replay_resident_sharded

        return replay_resident_sharded(self, sharded, init_carry=init_carry,
                                       ordinal_base=ordinal_base)

    def prepare_resident(self, colev: ColumnarEvents) -> "ResidentCorpus":
        """Upload the WHOLE corpus once as a flat wire buffer (exactly
        ``wire_bytes_per_event()`` per event — zero padding crosses the link)
        and return a handle for :meth:`replay_resident`.

        Every subsequent fold dispatch gathers its window on-device from the
        resident buffer, so per-window transfer drops to the B-chunk's
        starts/lens (KBs) — the right shape wherever the device link, not
        the fold, is the bottleneck, and replay becomes one streaming
        upload. For a corpus replayed more than once, :meth:`pack_resident`
        + :meth:`ResidentWire.save` persist the pack so later cold starts
        skip straight to the upload."""
        if self.mesh is not None:
            raise NotImplementedError(
                "this engine is mesh-backed; use prepare_resident_sharded / "
                "replay_resident_sharded for the resident path")
        return self.upload_resident(self.pack_resident(colev))

    def _resident_plan(self, resident: "ResidentCorpus",
                       width: int | None = None) -> "ResidentPlan":
        """Host-side tile schedule. Tile k of a granularity folds events
        ``[t_bases[k], t_bases[k]+width)`` of lanes ``[i0s[k], i0s[k]+bs)``.

        ``width`` is the corpus's own: chosen from its lengths among the
        widths up to :meth:`resident_tile_width`, the widest tile allowed
        (:func:`_tile_width`), unless the caller has chosen one for several
        plans that share a program (a mesh: one width for every device).

        Lanes are length-sorted descending, so the lanes still active in round
        r form a shrinking prefix. Each round covers it with full-width
        ``bs_big`` tiles plus narrow ``bs_small`` tiles over the remainder —
        the narrow granularity caps per-round lane padding at ``bs_small``
        instead of ``bs_big``. A lane only ever moves big→small as the prefix
        shrinks, so running ALL big tiles (in round order) before ALL small
        tiles (in round order) preserves per-lane event order."""
        b = resident.lengths.shape[0]
        bs_big, bs_small = _tile_sizes(self.batch_size, self._lane_multiple(),
                                       b)
        if width is None:
            width = self._chosen_width(resident.lengths)
        lens_host = resident.lengths
        max_len = int(lens_host.max(initial=0)) if b else 0
        sorted_desc = bool((np.diff(lens_host) <= 0).all()) if b > 1 else True
        big_i0: list[int] = []
        big_tb: list[int] = []
        small_i0: list[int] = []
        small_tb: list[int] = []
        if sorted_desc:
            # every round's active lanes in one searchsorted (the offsets in
            # the lengths' own dtype, or it widens the whole array)
            lens_asc = np.ascontiguousarray(lens_host[::-1])
            t_bases = np.arange(0, max_len, width, dtype=lens_asc.dtype)
            actives = b - np.searchsorted(lens_asc, t_bases, side="right")
            for t_base, active in zip(t_bases.tolist(), actives.tolist()):
                n_big = active // bs_big
                for k in range(n_big):
                    big_i0.append(k * bs_big)
                    big_tb.append(t_base)
                for i0 in range(n_big * bs_big, active, bs_small):
                    small_i0.append(i0)
                    small_tb.append(t_base)
        else:
            # unsorted corpus: schedule each contiguous lane range only up to
            # its own local max length (the streaming path's per-chunk bound),
            # not the global max — lanes stay in one range, so ascending
            # t_base per range preserves per-lane event order
            for i0 in range(0, b, bs_big):
                local_max = int(lens_host[i0: i0 + bs_big].max(initial=0))
                for t_base in range(0, local_max, width):
                    big_i0.append(i0)
                    big_tb.append(t_base)
        return ResidentPlan(
            width=width, bs_big=bs_big, bs_small=bs_small,
            big_i0=np.asarray(big_i0, dtype=np.int32),
            big_tb=np.asarray(big_tb, dtype=np.int32),
            small_i0=np.asarray(small_i0, dtype=np.int32),
            small_tb=np.asarray(small_tb, dtype=np.int32))

    @staticmethod
    def _plan_cap(k: int) -> int:
        """Work-list buffer length bucket (next power of two ≥ 64): entries past
        the traced trip count are never read, so one compiled program serves
        every plan in the bucket."""
        cap = 64
        while cap < k:
            cap *= 2
        return cap

    def replay_resident(self, resident: "ResidentCorpus",
                        init_carry: Mapping[str, Any] | None = None,
                        ordinal_base: np.ndarray | None = None) -> ReplayResult:
        """Fold a prepared resident corpus. Results are in the ORIGINAL
        aggregate order of the ColumnarEvents given to :meth:`prepare_resident`.

        Design: a chained dispatch is cheap while every host⇄device
        synchronization stalls the pipeline, so the ENTIRE fold pass is ONE
        dispatch: a ``fori_loop`` over a device-resident work list of
        (lane-range, time-offset) tiles, mutating a state slab
        ``{field: [b_pad]}``, with exactly one device→host pull of the
        folded states at the end."""
        if self.mesh is not None:
            raise NotImplementedError(
                "this engine is mesh-backed; use prepare_resident_sharded / "
                "replay_resident_sharded for the resident path")
        b = resident.lengths.shape[0]
        if b == 0:
            return ReplayResult(states={f.name: np.zeros((0,), dtype=f.dtype)
                                        for f in self.spec.registry.state.fields},
                                num_aggregates=0, num_events=0, padded_events=0)
        stage = self.profiler.stage
        with stage("resident", follows=resident.trace_ctx, aggregates=b,
                   events=resident.num_events) as umbrella:
            slab, padded = self._dispatch_resident(resident, init_carry,
                                                   ordinal_base, umbrella)
            # the fetch stage IS the single sync of the whole replay: a real
            # device→host pull whose data dependency closes every chained
            # tile program (fetch-barrier discipline — never
            # block_until_ready)
            with stage("fetch", aggregates=b):
                states = self._pull_states(slab, b, resident.perm,
                                           resident.cache)
        return ReplayResult(
            states=states,
            num_aggregates=b, num_events=resident.num_events,
            padded_events=padded)

    def fold_resident_slab(self, resident: "ResidentCorpus",
                           init_carry: Mapping[str, Any] | None = None,
                           ordinal_base: np.ndarray | None = None
                           ) -> tuple[dict, int]:
        """Fold a prepared resident corpus and return the DEVICE state slab
        instead of pulling states to the host: ``({field: [b_pad] device
        array}, padded_slots)``. Rows are in the corpus's SORTED lane order
        (``resident.perm`` maps sorted rank → original aggregate index; None =
        identity) and rows past ``b`` are padding.

        This is the seeding half of the resident state plane
        (surge_tpu.replay.resident_state): a cold-start replay whose result
        STAYS on device — the caller gathers rows into its own slab with zero
        device→host traffic. ``init_carry``/``ordinal_base`` are in the
        original aggregate order, exactly like :meth:`replay_resident`."""
        with self.profiler.stage(
                "resident", follows=resident.trace_ctx,
                aggregates=resident.lengths.shape[0],
                events=resident.num_events) as umbrella:
            return self._dispatch_resident(resident, init_carry, ordinal_base,
                                           umbrella)

    def _pull_states(self, slab: Mapping[str, Any], b: int,
                     perm: Optional[np.ndarray],
                     cache: Optional[dict] = None) -> dict[str, np.ndarray]:
        """One-round-trip state pull: un-perm + truncate + pack every column
        into a single u16 buffer ON DEVICE, fetch once, unpack on the host.
        Each materialization of a computed device buffer is a device→host
        round trip; per-field ``np.asarray`` paid it once per column.
        ``cache`` (a per-corpus dict) memoizes the device inverse-perm; omit
        it for throwaway corpora (streamed pieces).

        The result transfer grows with the aggregate count, so integer and
        bool columns ride a half-width wire: two bytes a value, with a fit
        flag per column computed on the device. Correctness never depends on
        a guess: a column packed narrow whose flag says it overflowed 16 bits
        is fetched again, full width. The engine remembers from the flags of
        its last pull which columns did not fit (:attr:`_pull_wide`) and packs
        those full width at once, so a schema with a column that never fits
        (a cart's ``total_cents``) pays the second round trip on its first
        pull only; a column that fits again goes back to two bytes after the
        pull that showed it. Float columns are always full width."""
        stage = self.profiler.stage
        fields = self.spec.registry.state.fields
        if any(np.dtype(f.dtype).itemsize > 4 for f in fields):
            # >32-bit columns don't fit the u32 packing — per-field pull
            with stage("fetch.wait") as wait:
                out_sorted = {name: np.asarray(col)[:b]
                              for name, col in slab.items()}
                wait.set_attribute("bytes", sum(
                    int(col.nbytes) for col in out_sorted.values()))
            with stage("fetch.decode"):
                return _unapply_perm(perm, out_sorted)
        inv = cache.get("invperm") if cache is not None else None
        if inv is None:
            if perm is not None:
                invp = np.empty((b,), np.int32)
                invp[perm] = np.arange(b, dtype=np.int32)
            else:
                invp = np.arange(b, dtype=np.int32)
            inv = jnp.asarray(invp)
            if cache is not None:
                cache["invperm"] = inv
        names = [f.name for f in fields]
        floats = frozenset(f.name for f in fields
                           if np.issubdtype(np.dtype(f.dtype), np.floating))

        def fetch(wide: frozenset):
            kind = ("narrow" if not wide else
                    "wide" if len(wide) == len(names) else "mixed")
            with stage("fetch.wait", wire=kind) as wait:
                # the one device→host fetch
                buf = np.asarray(self._finalize_program(wide)(slab, inv))
                wait.set_attribute("bytes", int(buf.nbytes))
            return buf, dict(zip(names, buf[-len(names):]))

        wide = floats | self._pull_wide
        buf, fits = fetch(wide)
        overflowed = frozenset(n for n in names
                               if n not in wide and not fits[n])
        if overflowed:
            # the memory was wrong (or empty): refetch with the columns that
            # overflowed full width — an extra round trip, still exact
            wide |= overflowed
            buf, fits = fetch(wide)
        self._pull_wide = frozenset(n for n in names if not fits[n]) - floats
        with stage("fetch.decode"):
            out: dict[str, np.ndarray] = {}
            at = 0
            for f in self._finalize_order(wide):
                dt = np.dtype(f.dtype)
                if f.name in wide:
                    bits = (buf[at: at + b].astype(np.uint32)
                            | (buf[at + b: at + 2 * b].astype(np.uint32)
                               << np.uint32(16)))
                    at += 2 * b
                    if np.issubdtype(dt, np.floating):
                        col = bits.view(np.float32).astype(dt)
                    elif np.issubdtype(dt, np.signedinteger):
                        col = bits.view(np.int32).astype(dt)
                    else:
                        col = bits.astype(dt)
                else:
                    raw = buf[at: at + b]
                    at += b
                    col = (raw.view(np.int16).astype(dt)
                           if np.issubdtype(dt, np.signedinteger)
                           else raw.astype(dt))
                out[f.name] = col
            return {name: out[name] for name in names}

    def _finalize_order(self, wide: frozenset) -> list:
        """The state fields in the order the pull's buffer holds them: the
        full-width columns first, so that each starts on a four-byte
        boundary."""
        fields = self.spec.registry.state.fields
        return ([f for f in fields if f.name in wide]
                + [f for f in fields if f.name not in wide])

    def _finalize_program(self, wide: frozenset):
        """The jitted state-pull program for one set of full-width columns:
        ``(slab {f: [b_pad]}, inv [b]) -> u16 [(2 * len(wide) + narrow) * b +
        fields]``. A column in ``wide`` is the low halves of its 32-bit
        patterns, then the high halves (f16/bf16 ride exactly as widened f32
        patterns, small signed integers sign-extended); any other is its
        values wrapped to 16 bits (the host sign-extends). The tail holds one
        flag per field, in field order: whether every value of the column
        fits 16 bits (a float's never does) — one flat buffer, since a second
        buffer (or a full flag ROW) costs its own round trip / megabytes."""
        prog = self._finalize_programs.get(wide)
        if prog is not None:
            return prog
        order = [(f.name, np.dtype(f.dtype))
                 for f in self._finalize_order(wide)]
        names = [f.name for f in self.spec.registry.state.fields]

        def finalize(sl, ip):
            parts, fits = [], {}
            for name, dt in order:
                # gather = un-perm + [:b] in one op; a mesh's [n_dev, b_pad]
                # slab is read flat (resident_mesh.ShardedResident.slab_rows)
                v = sl[name].reshape(-1)[ip]
                if np.issubdtype(dt, np.floating):
                    fits[name] = jnp.bool_(False)
                    bits = jax.lax.bitcast_convert_type(
                        v.astype(jnp.float32), jnp.uint32)
                elif dt == np.bool_:
                    fits[name] = jnp.bool_(True)
                    bits = v.astype(jnp.uint32)
                elif np.issubdtype(dt, np.signedinteger):
                    fits[name] = jnp.all((v >= -32768) & (v <= 32767))
                    bits = jax.lax.bitcast_convert_type(
                        v.astype(jnp.int32), jnp.uint32)
                else:
                    fits[name] = jnp.all(v <= 65535)
                    bits = v.astype(jnp.uint32)
                if name in wide:
                    parts.append((bits & np.uint32(0xFFFF)).astype(jnp.uint16))
                    parts.append((bits >> np.uint32(16)).astype(jnp.uint16))
                else:
                    parts.append(bits.astype(jnp.uint16))  # wraps
            flags = jnp.stack([fits[n] for n in names]).astype(jnp.uint16)
            return jnp.concatenate(parts + [flags])

        prog = jax.jit(finalize)
        self._finalize_programs[wide] = prog
        return prog

    def _dispatch_resident(self, resident: "ResidentCorpus",
                           init_carry: Mapping[str, Any] | None,
                           ordinal_base: np.ndarray | None,
                           umbrella=None) -> tuple[dict, int]:
        """Dispatch the whole fold of one resident corpus WITHOUT syncing:
        returns the (device) state slab and the padded-slot count. ``init``/
        ``ordinal`` inputs are in the order ``resident.perm`` maps from (the
        original aggregate order; a corpus without a perm takes them as its
        lanes lie). ``umbrella``, the caller's open ``replay.resident`` span,
        is given the plan's counts."""
        stage = self.profiler.stage
        b = resident.lengths.shape[0]
        b_pad = resident.b_pad
        key = frozenset(resident.derived_key.items())

        with stage("plan"):
            init_sorted, ord_sorted = _apply_perm(resident.perm, init_carry,
                                                  ordinal_base)
            plan = self._plan_for(resident)
            if umbrella is not None:
                umbrella.set_attribute("width", plan.width)
                umbrella.set_attribute("width_cap", self.resident_tile_width())
                umbrella.set_attribute("padded_slots", plan.padded_slots)
                umbrella.set_attribute("tiles", plan.tiles)
                umbrella.set_attribute("rounds", plan.rounds)
                umbrella.set_attribute("tiles_small", len(plan.small_i0))
                umbrella.set_attribute("slots_small", plan.slots_small)
                # the steps a sequential tile takes one after the other: a
                # tile's width, every tile; the assoc tree takes none
                umbrella.set_attribute(
                    "scan_steps", 0 if self.tile_backend == "assoc"
                    else plan.tiles * plan.width)
            if init_sorted is None and ord_sorted is None:
                # fresh replay: build the init slab ON DEVICE (no host
                # transfer on the replay's critical path); its dispatch is
                # part of the plan stage
                slab, ord_d = self._fresh_slab(b_pad)
            else:
                ord_p = np.zeros((b_pad,), dtype=np.int32)
                if ord_sorted is not None:
                    ord_p[:b] = np.asarray(ord_sorted).astype(np.int32)
                slab_np = self.init_carry_np(b_pad)
                if init_sorted is not None:
                    for k, full in init_sorted.items():
                        slab_np[k][:b] = np.asarray(full)
                slab = {k: jnp.asarray(v) for k, v in slab_np.items()}
                ord_d = jnp.asarray(ord_p)
            # one work list per lane granularity
            work = []
            for bs, i0s, t_bases in ((plan.bs_big, plan.big_i0, plan.big_tb),
                                     (plan.bs_small, plan.small_i0,
                                      plan.small_tb)):
                k_n = len(i0s)
                if k_n == 0:
                    continue
                k_cap = self._plan_cap(k_n)
                i0s_p = np.zeros((k_cap,), dtype=np.int32)
                i0s_p[:k_n] = i0s
                tb_p = np.zeros((k_cap,), dtype=np.int32)
                tb_p[:k_n] = t_bases
                work.append((bs, k_n, k_cap,
                             jnp.asarray(i0s_p), jnp.asarray(tb_p)))

        # two chained dispatches (big tiles, then small); per-lane order holds
        # because a lane only ever migrates big→small as the prefix shrinks
        rows_fetched = self._rows_fetched(resident, plan.width,
                                          plan.lanes_tiled)
        self.stats["rows_fetched"] += rows_fetched
        for bs, k_n, k_cap, i0s_d, tbs_d in work:
            self.stats["windows"] += k_n
            self.profiler.count_windows(k_n)
            fold = self._resident_program(key, plan.width, bs, k_cap)
            sig = self._resident_signature(resident, key, plan.width, bs,
                                           k_cap)
            # a fresh signature means this dispatch pays the XLA compile
            first_dispatch = sig not in self._signatures
            self._signatures.add(sig)
            with stage("compile" if first_dispatch else "dispatch",
                       tiles=k_n, batch=bs):
                slab = fold(slab, resident.flat_wire, resident.flat_side,
                            resident.starts_dev, resident.lens_dev, ord_d,
                            i0s_d, tbs_d, np.int32(k_n))
        if umbrella is not None:
            umbrella.set_attribute("gather", self.lane_gather)
            umbrella.set_attribute("rows_fetched", rows_fetched)
            umbrella.set_attribute("fetched_slots", self._fetched_slots(plan))
        return slab, plan.padded_slots

    @property
    def lane_gather(self) -> str:
        """How this engine's tiles fetch their lane rows: ``rows`` or
        ``slices`` (:func:`_lane_gather`)."""
        return _lane_gather()

    def _rows_fetched(self, resident: "ResidentCorpus", width: int,
                      lanes: int) -> int:
        """The fetches ``lanes`` lane windows ask of a corpus's buffers:
        aligned rows under ``rows``, slices under ``slices``, of the word and
        of every side column (before XLA drops a column no handler reads)."""
        return (lanes * _rows_per_lane(width, self.lane_gather)
                * (1 + len(resident.flat_side)))

    def _fetched_slots(self, plan: "ResidentPlan") -> int:
        """The slots of ONE array that a plan's fetches ask for: what
        ``rows_fetched`` is over the arrays fetched, times the slots a row
        (or slice) holds. Beside ``padded_slots``, the slots folded."""
        return plan.lanes_tiled * _slots_per_lane(plan.width,
                                                  self.lane_gather)

    @property
    def tile_backend(self) -> str:
        """The resolved tile backend. ``auto`` picks the scanless assoc tree
        fold only where it measured faster: models shipping a (law-checked)
        ``AssociativeFold``, power-of-two tile width, and a non-CPU backend —
        on chip the scan is step-bound (about 10 µs a step of 8192 lanes
        under the mixed spec's nine-way switch: ``scan_step_us`` in the cell
        ``rebuild-mixed-opaque``; some 20,500 steps for its 100M events in
        tiles of 128, 64,000 when every tile was 512 wide: PERF.md, PR 34;
        the assoc tree folds the counter's 100M in 0.033 s), while
        the 1-core host runs the scan ~2× FASTER than the tree (401M vs 188M
        ev/s). The width tested is the widest tile allowed: every narrower
        one a plan may choose is a power of two with it. Only an EXPLICIT
        ``tile-backend = assoc`` raises on an unsupported spec/width."""
        if self._tile_backend != "auto":
            return self._tile_backend
        if self._tile_backend_resolved is None:
            w = self.resident_tile_width()
            self._tile_backend_resolved = (
                "assoc" if getattr(self.spec, "associative", None) is not None
                and (w & (w - 1)) == 0
                and jax.default_backend() != "cpu" else "xla")
        return self._tile_backend_resolved

    def _plan_for(self, resident: "ResidentCorpus") -> "ResidentPlan":
        """The corpus's tile plan, cached on the corpus (plan geometry only
        depends on engine config + corpus lengths; recomputing the host-side
        bucketing every pass costs tens of ms at 1M lanes)."""
        pkey = ("plan", self.resident_tile_width(), self.batch_size)
        plan = resident.cache.get(pkey)
        if plan is None:
            plan = self._resident_plan(resident)
            resident.cache[pkey] = plan
        return plan

    def _fresh_slab(self, b_pad: int):
        """Fresh init state slab + zero ordinal base, built by a jitted
        on-device program (fresh buffers every call, so carry donation can
        never invalidate a cached one)."""
        prog = self._slab_programs.get(b_pad)
        if prog is None:
            init = self.spec.init_state_tree()
            fields = [(f.name, f.dtype) for f in self.spec.registry.state.fields]

            def mk():
                slab = {name: jnp.full((b_pad,), init[name], dtype=dt)
                        for name, dt in fields}
                return slab, jnp.zeros((b_pad,), jnp.int32)

            prog = jax.jit(mk)
            self._slab_programs[b_pad] = prog
        return prog()

    def replay_resident_streamed(self, w: "ResidentWire", *,
                                 segments: int | None = None,
                                 init_carry: Mapping[str, Any] | None = None,
                                 ordinal_base: np.ndarray | None = None
                                 ) -> ReplayResult:
        """Upload AND fold a packed wire in lane segments: segment s's tiles
        dispatch right after its upload initiates, so on backends that overlap
        transfers with compute the fold of earlier segments hides later
        segments' uploads — and on backends that don't, nothing is lost but
        per-segment overhead. Segments split at event-count boundaries
        (balanced bytes) and each piece is a zero-copy contiguous slice of the
        buffer: for a contiguous wire the piece's lanes are a lane RANGE; for
        an indirect wire (the grouped-input fast pack, whose lane slabs tile
        the buffer in buffer order, not lane order) the piece's lanes are the
        subset whose slabs fall in the slice, re-sorted desc for the tile
        plan. A wire whose slabs do not tile its buffer at all (hand-built
        subset/overlap) falls back to the plain single-upload path. Results
        are in the original aggregate order either way.

        ``segments`` defaults to ``surge.replay.upload-stream-segments``
        (0/1 = plain upload+replay)."""
        if segments is None:
            segments = self.config.get_int(
                "surge.replay.upload-stream-segments", 0)
        b = w.lengths.shape[0]
        if segments <= 1 or b == 0:
            return self.replay_resident(self.upload_resident(w),
                                        init_carry=init_carry,
                                        ordinal_base=ordinal_base)
        self.check_wire(w)
        perm = w.perm
        init_sorted, ord_sorted = _apply_perm(perm, init_carry, ordinal_base)
        state_fields = self.spec.registry.state.fields

        starts64 = w.starts.astype(np.int64)
        lens64 = w.lengths.astype(np.int64)
        cum = np.zeros(b + 1, dtype=np.int64)
        np.cumsum(lens64, out=cum[1:])
        total = int(cum[-1])
        contiguous = np.array_equal(starts64, cum[:-1])
        zero_lanes = np.array([], dtype=np.int64)
        if contiguous:
            # lanes tile the buffer in lane order: pieces are lane ranges
            lane_order = None
            piece_starts = cum
            n_lanes = b
        else:
            # indirect wire (grouped-input fast pack): lane slabs tile the
            # buffer in BUFFER order, not lane order — walk the NONZERO lanes
            # by start so each piece is still one zero-copy contiguous slice.
            # Zero-length lanes occupy no rows (their start is wherever the
            # next slab begins), so they are excluded from the tiling walk and
            # tacked onto the first piece, whose plan skips them.
            nz = np.nonzero(lens64 > 0)[0]
            zero_lanes = np.nonzero(lens64 == 0)[0]
            if nz.size == 0:
                return self.replay_resident(self.upload_resident(w),
                                            init_carry=init_carry,
                                            ordinal_base=ordinal_base)
            lane_order = nz[np.argsort(starts64[nz], kind="stable")]
            piece_starts = np.zeros(lane_order.size + 1, dtype=np.int64)
            np.cumsum(lens64[lane_order], out=piece_starts[1:])
            if not np.array_equal(starts64[lane_order], piece_starts[:-1]):
                # slabs don't tile the buffer (subset/overlapping wire):
                # stream piecewise is meaningless — plain path
                return self.replay_resident(self.upload_resident(w),
                                            init_carry=init_carry,
                                            ordinal_base=ordinal_base)
            n_lanes = lane_order.size

        # piece boundaries at ~equal event counts
        bounds = [0]
        for s in range(1, segments):
            cut = int(np.searchsorted(piece_starts, total * s // segments))
            bounds.append(min(max(cut, bounds[-1]), n_lanes))
        bounds.append(n_lanes)

        # every piece's upload, dispatches and pull under one umbrella
        rows_before = self.stats["rows_fetched"]
        with self.profiler.stage("resident", follows=w.trace_ctx,
                                 aggregates=b, events=w.num_events,
                                 segments=segments,
                                 gather=self.lane_gather) as umbrella:
            pieces: list = []
            padded = fetched = 0
            first_piece = True
            for lo, hi in zip(bounds[:-1], bounds[1:]):
                if hi <= lo:
                    continue
                base = int(piece_starts[lo])
                end = int(piece_starts[hi])
                if lane_order is None:
                    lanes = np.arange(lo, hi)
                    sub_starts = starts64[lo:hi] - base
                    sub_lens = w.lengths[lo:hi]
                else:
                    lanes = lane_order[lo:hi]
                    if first_piece and zero_lanes.size:
                        lanes = np.concatenate([lanes, zero_lanes])
                    # piece-local DESC length order so the tile plan keeps its
                    # shrinking-prefix schedule (zero lanes sort last, fold no-op)
                    lanes = lanes[np.argsort(-lens64[lanes], kind="stable")]
                    sub_starts = np.where(lens64[lanes] > 0,
                                          starts64[lanes] - base, 0)
                    sub_lens = w.lengths[lanes]
                first_piece = False
                # a side column ends with the events: the last piece's
                # slice of one is short of guard rows, which the upload fills
                sub = ResidentWire(
                    derived_key=dict(w.derived_key),
                    packed=w.packed[base: end + w.guard],
                    side={k: v[base: end + w.guard] for k, v in w.side.items()},
                    starts=sub_starts.astype(np.int32),
                    lengths=sub_lens, perm=None, guard=w.guard,
                    num_events=end - base, layout=w.layout)
                piece = self.upload_resident(sub)  # upload initiates...
                slab, pad = self._dispatch_resident(
                    piece,
                    None if init_sorted is None else
                    {k: v[lanes] for k, v in init_sorted.items()},
                    None if ord_sorted is None else ord_sorted[lanes])
                padded += pad
                fetched += self._fetched_slots(self._plan_for(piece))
                # hold ONLY what the sync pass needs — keeping the piece corpus
                # itself would pin every piece's wire buffers in HBM at once
                pieces.append((lanes, slab))  # ...fold dispatched, NOT synced
            umbrella.set_attribute(
                "rows_fetched", self.stats["rows_fetched"] - rows_before)
            umbrella.set_attribute("fetched_slots", fetched)
            # one sync pass over every piece — a single packed fetch per piece
            # (every materialized buffer is its own device→host round trip; the
            # old per-piece-per-field np.asarray paid pieces × fields of them),
            # then global unsort
            out_sorted = {f.name: np.empty((b,), dtype=f.dtype)
                          for f in state_fields}
            for lanes, slab in pieces:
                with self.profiler.stage("fetch"):
                    piece_states = self._pull_states(slab, int(lanes.shape[0]), None)
                for name, col in piece_states.items():
                    out_sorted[name][lanes] = col
            return ReplayResult(states=_unapply_perm(perm, out_sorted),
                                num_aggregates=b,
                                num_events=w.num_events, padded_events=padded)

    def resident_cap_width(self) -> int:
        """Largest tile width the HBM budget allows (pow2 multiple of the min
        window): one tile materializes a [batch, width] u32 slab and its
        transpose, so width is capped by resident-slab-cap-mb."""
        budget = self.config.get_int("surge.replay.resident-slab-cap-mb", 512)
        w = max(self.min_time_window, 1)
        while w * 2 * self.batch_size * 8 <= budget * 1_000_000:
            w *= 2
        return w

    def resident_tile_width(self) -> int:
        """The widest tile a :meth:`replay_resident` plan may fold at: the
        time-chunk rounded up to a power of two, inside the HBM cap. A plan
        folds at the width its corpus's lengths make cheapest, this one or a
        narrower (:meth:`_resident_plan`); one width a plan → one compiled
        program a tile size for the whole replay. The wire's guard rows cover
        this width, whatever a plan chooses."""
        return self._tile_widths()[-1]

    def _tile_widths(self) -> list[int]:
        """The widths a plan chooses among, ascending: the min window doubled
        up to :meth:`resident_tile_width`."""
        widths = [max(self.min_time_window, 1)]
        target = max(self.time_chunk, 1)
        cap = self.resident_cap_width()
        while widths[-1] < target and widths[-1] < cap:
            widths.append(widths[-1] * 2)
        return widths

    def _chosen_width(self, lengths: np.ndarray) -> int:
        """The tile width of a plan over logs of these ``lengths``:
        :func:`_tile_width` under this engine's tile sizes and gather."""
        bs_big, bs_small = _tile_sizes(self.batch_size, self._lane_multiple(),
                                       lengths.shape[0])
        return _tile_width(lengths, bs_big, bs_small, self._tile_widths(),
                           self.lane_gather)

    @staticmethod
    def _resident_signature(resident: "ResidentCorpus", key: frozenset,
                            width: int, bs: int, k_cap: int) -> tuple:
        """The static shapes one resident fold program compiles for."""
        return ("resident", key, width, bs, k_cap, resident.b_pad,
                int(resident.flat_wire.shape[0]))

    def _resident_program(self, key: frozenset, width: int, bs: int,
                          k_cap: int):
        """The jitted whole-replay program for one derived-column declaration:
        ``(state_slab {f: [b_pad]}, flat_wire u8 [N, nbytes], side_flat,
        starts [b_pad], lens [b_pad], ord_base [b_pad], i0s [k_cap],
        t_bases [k_cap], k_n) -> state_slab``.

        A ``fori_loop`` over the tile work list; tile k folds events
        ``[t_bases[k], t_bases[k]+width)`` of lanes ``[i0s[k], i0s[k]+bs)``:
        every lane's contiguous window out of the flat packed corpus (events
        of one aggregate are adjacent) as a time-major tile
        (:func:`_make_lane_fetch`; the buffers viewed once, before the loop),
        the fold body, and a contiguous write-back into the state slab. The
        trip count is traced, so one compiled program serves every corpus in
        the k_cap bucket and the whole replay crosses the host⇄device boundary
        exactly twice (dispatch in, states out)."""
        cache_key = (key, width, bs, k_cap)
        hit = self._resident_folds.get(cache_key)
        if hit is not None:
            return hit
        import jax

        wire = WireFormat(self.spec.registry, dict(key))
        view, tile = _make_tile(self.spec, wire, width, bs,
                                self.tile_backend, self.lane_gather)

        def fold(slab_state, flat_wire, side_flat, starts_all, lens_all,
                 ord_all, i0s, t_bases, k_n):
            buffers = view(flat_wire, side_flat)

            def body(k, st):
                return tile(st, buffers, starts_all, lens_all, ord_all,
                            i0s[k], t_bases[k])

            return jax.lax.fori_loop(0, k_n, body, slab_state)

        donate = (0,) if self.donate_carry else ()
        jitted = jax.jit(fold, donate_argnums=donate)
        self._resident_folds[cache_key] = jitted
        return jitted

    def replay_ragged(self, logs: Sequence[Sequence[Any]],
                      encode: Callable[[Any], Any] | None = None,
                      init_carry: Mapping[str, Any] | None = None) -> ReplayResult:
        """Length-bucketed replay of ragged logs (SURVEY.md §5.7).

        Groups aggregates by log length into padded buckets, folds each bucket, and
        scatters results back into original order. ``encode`` (if given) maps each raw
        event to its tensor-schema form first — e.g. bank_account's host-side Vocab
        dictionary encoding. ``init_carry`` (``{field: [len(logs)]}``, e.g. from
        :meth:`carry_from_states` over checkpoint snapshots) resumes each
        aggregate's fold from its snapshot instead of the init record — the
        bounded tail fold of a checkpointed cold start.
        """
        from surge_tpu.codec.tensor import encode_events

        if encode is not None:
            logs = [[encode(e) for e in log] for log in logs]
        lengths = [len(l) for l in logs]
        groups = bucket_lengths(lengths, self.buckets)
        state_fields = self.spec.registry.state.fields
        out = {f.name: np.zeros((len(logs),), dtype=f.dtype) for f in state_fields}
        total_events = 0
        padded = 0
        for cap in sorted(groups):
            idxs = groups[cap]
            sub = [logs[i] for i in idxs]
            enc = encode_events(self.spec.registry, sub, pad_to=cap)
            sub_init = (None if init_carry is None else
                        {k: np.asarray(v)[idxs] for k, v in init_carry.items()})
            res = self.replay_encoded(enc, init_carry=sub_init)
            for name in out:
                out[name][idxs] = res.states[name]
            total_events += res.num_events
            padded += res.padded_events
        return ReplayResult(states=out, num_aggregates=len(logs),
                            num_events=total_events, padded_events=padded)

    def replay_columnar_chunks(self, chunks: Iterable[ColumnarEvents]) -> ReplayResult:
        """Fold a stream of aggregate-range chunks (each covering a DISJOINT set of
        aggregates — the columnar segment layout, surge_tpu.log.columnar): chunks
        replay independently and their state columns concatenate in order. The
        whole-log array never materializes in host memory at once."""
        state_fields = self.spec.registry.state.fields
        parts: dict[str, list[np.ndarray]] = {f.name: [] for f in state_fields}
        total_aggregates = total_events = padded = 0
        ids: list = []
        saw_ids = True
        for colev in chunks:
            res = self.replay_columnar(colev)
            for name in parts:
                parts[name].append(res.states[name])
            total_aggregates += res.num_aggregates
            total_events += res.num_events
            padded += res.padded_events
            if colev.aggregate_ids is None:
                saw_ids = False
            elif saw_ids:
                ids.extend(colev.aggregate_ids)
        if total_aggregates == 0:
            return ReplayResult(states={f.name: np.zeros((0,), dtype=f.dtype)
                                        for f in state_fields},
                                num_aggregates=0, num_events=0, padded_events=0,
                                aggregate_ids=[] if saw_ids else None)
        return ReplayResult(
            states={name: np.concatenate(arrs) for name, arrs in parts.items()},
            num_aggregates=total_aggregates, num_events=total_events,
            padded_events=padded, aggregate_ids=ids if saw_ids else None)

    def replay_stream(self, chunks: Iterable[EncodedEvents], batch: int,
                      init_carry: Mapping[str, Any] | None = None,
                      ordinal_base: np.ndarray | None = None) -> ReplayResult:
        """Fold a stream of EncodedEvents chunks (same B, consecutive time windows),
        carrying state across chunks — the 100M-event-log path where the whole encoded
        log never exists in HBM at once. Every window is padded to ``time-chunk`` width
        so one compiled program serves the entire stream."""
        bs = min(self.batch_size, _round_up(max(batch, 1), self._lane_multiple()))
        n_bchunks = max((batch + bs - 1) // bs, 1)
        carries: list[StateTree | None] = [None] * n_bchunks
        total_events = 0
        padded = 0
        t_cursor = 0  # global time offset of the current chunk (ordinal base)
        for enc in chunks:
            if enc.batch_size != batch:
                raise ValueError(f"stream chunk batch {enc.batch_size} != {batch}")
            t = enc.max_len
            for ci in range(n_bchunks):
                start, stop = ci * bs, min((ci + 1) * bs, batch)
                if carries[ci] is None:
                    carries[ci] = self._carry_slice(init_carry, start, stop, bs)
                carries[ci], scanned = self._fold_window(
                    carries[ci], enc.type_ids[start:stop],
                    {k: v[start:stop] for k, v in enc.cols.items()}, bs,
                    derived_cols=enc.derived_cols, t_base=t_cursor,
                    ordinal_base=None if ordinal_base is None
                    else ordinal_base[start:stop])
                padded += bs * scanned
            total_events += int(enc.lengths.sum())
            t_cursor += t
        if carries[0] is None:
            raise ValueError("empty chunk stream")
        state_fields = self.spec.registry.state.fields
        out = {f.name: np.zeros((batch,), dtype=f.dtype) for f in state_fields}
        for ci in range(n_bchunks):
            start, stop = ci * bs, min((ci + 1) * bs, batch)
            for name in out:
                out[name][start:stop] = np.asarray(carries[ci][name])[: stop - start]
        return ReplayResult(states=out, num_aggregates=batch,
                            num_events=total_events, padded_events=padded)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m if m > 0 else n
