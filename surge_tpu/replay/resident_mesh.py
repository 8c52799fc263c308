"""Mesh-sharded resident replay: the single-sync tile design across devices.

Entity parallelism (SURVEY.md §2.10 row 1) for the resident path. The packed
wire is cut at event-count boundaries into one contiguous slice of its buffer
a device (:func:`_deal`): no event is copied on the host for a wire whose lane
slabs tile its buffer, which both forms ``pack_resident`` makes do. Each slice
goes to its device as the one-chip upload's pieces (``engine._bucket_pieces``
/ ``_put_pieces``), the per-device buffers are joined into the arrays one
``shard_map``-wrapped dispatch a tile size reads — no collectives in the
fold, because aggregate folds are independent; per-device tile counts ride in
as data, so devices with different work loop independently inside the same
SPMD program — and the states leave through the one-chip pull
(``engine._pull_states``), in the original aggregate order.

Every stage is a span of the engine's profiler, one trace id a rebuild:
``replay.shard`` (the deal and the per-device plans), ``replay.h2d`` with
``h2d.bucket`` / ``h2d.put``, and ``replay.resident`` with ``plan``,
``compile`` / ``dispatch`` and ``fetch`` (``fetch.wait``, ``fetch.decode``).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Mapping, Optional

import numpy as np

from surge_tpu.codec.wire import WireFormat
from surge_tpu.replay import engine as _engine
from surge_tpu.replay.engine import (
    ReplayResult,
    ResidentPlan,
    ResidentWire,
    _apply_perm,
    _bucket_len,
    _bucket_pieces,
    _make_tile,
    _put_wire,
    _raise_outside,
    _round_up,
    _side_nbytes,
    _wire_nbytes,
)


def _buffer_order(w: ResidentWire, lens: np.ndarray):
    """``(order, first_row)``: the wire's non-empty lanes in the order their
    slabs lie in the packed buffer and the row each begins at (one more for
    the end), where the slabs tile the buffer from row 0, each beginning where
    the last ended; None for any other wire. Tried: the aggregates' order
    (the grouped fast pack, whose lanes point into the log as it came, under
    ``perm``) and the lanes' own (a contiguous wire)."""
    b = lens.shape[0]
    orders = [np.arange(b)]
    if w.perm is not None:
        by_aggregate = np.empty(b, dtype=np.int64)
        by_aggregate[w.perm] = orders[0]
        orders.insert(0, by_aggregate)
    some_empty = bool(b) and int(lens.min()) == 0
    for order in orders:
        lens_o = lens[order]
        if some_empty:
            order, lens_o = order[lens_o > 0], lens_o[lens_o > 0]
        first_row = np.zeros(order.shape[0] + 1, dtype=np.int64)
        np.cumsum(lens_o, out=first_row[1:])
        if np.array_equal(w.starts[order], first_row[:-1]):
            return order, first_row
    return None


def _deal(w: ResidentWire, n_dev: int):
    """Cut the wire into ``n_dev`` shards of about equal event counts, at lane
    boundaries. Returns ``(deals, shards, rows, copied_bytes)``:

    - ``deals[d]``: the lanes (sorted ranks) of device ``d``, ascending, so on
      a length-sorted wire the longest first, as the tile plan wants them.
      Empty lanes hold no rows and are dealt round-robin.
    - ``shards[d] = (word, side, starts)``: the device's rows of every
      buffer and its lanes' starts within them; ``word`` is the arrays the
      word goes up as: the packed buffer alone, or the word's sources (type
      ids, then the packed columns) where the wire carries them and no
      packed buffer yet. For a wire whose slabs tile
      its buffer (:func:`_buffer_order`) the rows are a contiguous slice of
      the wire's own arrays, the caller's columns included. Where a
      shard is longer than one piece of the upload, every slice is widened to
      the same whole number of pieces, forwards into the next shard's events
      or, at the log's end, backwards into the last one's: a device may hold
      rows it never reads, and the host pads no piece. Any other wire
      (hand-built: a subset, overlapping slabs) has its lanes' rows gathered
      into fresh buffers, lane after lane, in whole-column index arithmetic,
      from the packed buffer (built on the host if need be).
    - ``rows``: the rows a device's buffers need, guard included: the
      upload's bucket of device zeros supplies what a shard lacks of them.
    - ``copied_bytes``: the event bytes gathered, 0 for a tiling wire.
    """
    lens = w.lengths.astype(np.int64)
    tiling = _buffer_order(w, lens)
    tiles = tiling is not None
    word = (w.words.arrays() if tiles and not w.host_packed
            else (w.packed,))
    if tiles:
        order, first_row = tiling
    else:
        order = np.flatnonzero(lens)
        first_row = np.zeros(order.shape[0] + 1, dtype=np.int64)
        np.cumsum(lens[order], out=first_row[1:])
    total = int(first_row[-1])
    cuts = np.searchsorted(first_row, [total * d // n_dev
                                       for d in range(1, n_dev)])
    bounds = np.concatenate([[0], np.maximum.accumulate(cuts),
                             [order.shape[0]]]).astype(np.int64)
    most = int(np.diff(first_row[bounds]).max())  # the longest shard's events
    whole = min(_engine._PIECE_ROWS, _bucket_len(most + w.guard))
    # several pieces a shard: whole ones only
    span = _round_up(most, whole) if most + w.guard > whole else None
    empty = np.flatnonzero(lens == 0)
    deals, shards, copied = [], [], 0
    for d, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
        lanes = np.sort(np.concatenate([order[lo:hi], empty[d::n_dev]]))
        ln = lens[lanes]
        if tiles:
            base, end = int(first_row[lo]), int(first_row[hi]) + w.guard
            if span is not None:
                base = max(min(base, total - span), 0)
                end = min(base + span, total)
            rows = slice(base, end)
            starts = np.where(ln > 0, w.starts[lanes] - base, 0)
        else:
            starts = np.cumsum(ln) - ln
            rows = (np.repeat(w.starts[lanes] - starts, ln)
                    + np.arange(int(ln.sum()), dtype=np.int64))
        shard = tuple(a[rows] for a in word), {k: v[rows]
                                               for k, v in w.side.items()}
        if not tiles:
            copied += shard[0][0].nbytes + _side_nbytes(shard[1])
        deals.append(lanes)
        shards.append((*shard, starts))
    return deals, shards, (span or most) + w.guard, copied


class ShardedResident:
    """Device-resident sharded corpus + plan, ready for
    :func:`replay_resident_sharded` / :func:`fold_resident_sharded`.

    ``deals[d][j]`` is the sorted-rank lane that row ``[d, j]`` of the folded
    slab holds (``wire_host.perm`` maps a rank to its original index)."""

    def __init__(self, engine, wire: ResidentWire) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if engine.mesh is None:
            raise ValueError("ShardedResident requires a mesh-backed engine")
        fmt = engine.check_wire(wire)  # layout/guard safety, as upload_resident
        self.engine = engine
        self.wire_host = wire
        mesh = engine.mesh
        axis = engine.mesh_axis
        devices = list(mesh.devices.flat)
        n_dev = len(devices)
        self.n_dev = n_dev
        b = wire.lengths.shape[0]
        self.b = b
        self.num_events = wire.num_events
        #: per-corpus cache of the state pull (its device inverse index)
        self.cache: dict = {}
        stage = engine.profiler.stage

        with stage("shard", follows=wire.trace_ctx, aggregates=b,
                   events=wire.num_events, devices=n_dev) as shard:
            # one shard_map program folds every device's tiles: one width,
            # chosen from the whole corpus's lengths before they are dealt
            self.width = engine._chosen_width(wire.lengths)
            deals, shards, rows, copied = _deal(wire, n_dev)
            self.deals = deals
            b_local_max = max(len(lanes) for lanes in deals)
            bs = min(engine.batch_size, _round_up(max(b_local_max, 1),
                                                  engine._lane_multiple()))
            self.bs = bs
            b_pad = _round_up(max(b_local_max, 1), bs)
            self.b_pad = b_pad
            starts_l = np.zeros((n_dev, b_pad), dtype=np.int32)
            lens_l = np.zeros((n_dev, b_pad), dtype=np.int32)
            for d, (lanes, (_, _, starts)) in enumerate(zip(deals, shards)):
                starts_l[d, : len(lanes)] = starts
                lens_l[d, : len(lanes)] = wire.lengths[lanes]

            # per-device tile plans (shared shapes, data-driven trip count).
            # Plans see the FULL padded [b_pad] length row (zero tails are
            # still descending and schedule no rounds), so every device
            # derives the same bs and the shared compiled program's static
            # shapes hold everywhere.
            plan_fn = type(engine)._resident_plan  # unbound: the view's bs
            plans: list[ResidentPlan] = [
                plan_fn(_PlanView(engine, bs), _FakeResident(lens_l[d]),
                        self.width)
                for d in range(n_dev)]
            self.plans = plans
            assert all(p.bs_big == bs for p in plans)
            self.bs_small = plans[0].bs_small
            assert all(p.bs_small == self.bs_small for p in plans)
            self.k_caps = {}
            for kind in ("big", "small"):
                k_max = max(len(getattr(p, f"{kind}_i0")) for p in plans)
                self.k_caps[kind] = engine._plan_cap(k_max) if k_max else 0
            self.padded_slots = sum(p.padded_slots for p in plans)
            events = [int(lens_l[d].sum()) for d in range(n_dev)]
            for name, per_dev in (("lanes", [len(x) for x in deals]),
                                  ("events", events),
                                  ("tiles", [p.tiles for p in plans])):
                shard.set_attribute(f"{name}_min", min(per_dev))
                shard.set_attribute(f"{name}_max", max(per_dev))
            shard.set_attribute("copied_bytes", copied)

        with stage("h2d", follows=shard.context,
                   wire_bytes=_wire_nbytes(wire),
                   side_bytes=_side_nbytes(wire.side), devices=n_dev) as h2d:
            # the word's arrays, a shard: the sources the device builds it
            # from, unless the wire holds its packed buffer (the one-chip
            # upload's rule; a deal that had to read ``packed`` has built it)
            on_device = not wire.host_packed
            n_word = 1 + len(fmt.packed_fields) if on_device else 1
            with stage("h2d.bucket") as bucket:
                # one bucket for every array of every device: one shape a
                # program. A shard's own rows go up as they lie, in the
                # one-chip upload's pieces; the bucket's device zeros are
                # whatever of ``rows`` a shard lacks
                host = [[_bucket_pieces(arr, _engine._PIECE_ROWS, rows)
                         for arr in (*word, *side.values())]
                        for word, side, _ in shards]
                copied_bytes = (starts_l.nbytes + lens_l.nbytes + sum(
                    c for per_dev in host for _, c in per_dev))
                pieces = sum(len(ps) for per_dev in host for ps, _ in per_dev)
                put_bytes = sum(p.nbytes for per_dev in host
                                for ps, _ in per_dev for p in ps)
                source_bytes = sum(
                    p.nbytes for per_dev in host for ps, _ in per_dev[:n_word]
                    for p in ps) if on_device else 0
                bucket.set_attribute("copied_bytes", copied_bytes)
            with stage("h2d.put", put_bytes=put_bytes, pieces=pieces):
                whole = _bucket_len(rows)
                place = engine._word_program(fmt) if on_device else None

                def put(d: int) -> tuple:
                    # the one-chip upload, on this device: a thread each, so
                    # that every device's link is fed at once
                    with jax.default_device(devices[d]):
                        flat_wire, sides, flags = _put_wire(
                            host[d], whole, fmt, place)
                    return [flat_wire, *sides], flags

                with ThreadPoolExecutor(n_dev) as pool:
                    placed, flags = zip(*pool.map(put, range(n_dev)))
                # the per-device buffers ARE the shards of the arrays the
                # program reads: [n_dev * whole, ...] split over the axis
                joined = [jax.make_array_from_single_device_arrays(
                    (n_dev * whole, *arrs[0].shape[1:]),
                    NamedSharding(mesh, P(axis, *([None] * (arrs[0].ndim - 1)))),
                    list(arrs)) for arrs in zip(*placed)]
                self.flat_wire = joined[0]
                self.flat_side = dict(zip(wire.side, joined[1:]))
                shard2 = NamedSharding(mesh, P(axis, None))
                self.starts_dev = jax.device_put(starts_l, shard2)
                self.lens_dev = jax.device_put(lens_l, shard2)
                # every buffer: a column may be the caller's array, theirs
                # to write once this returns
                jax.block_until_ready(joined)
                if on_device:
                    # a packed column outside its width on any device: the
                    # host's error, before any fold can run on the word
                    _raise_outside(fmt, np.any(flags, axis=0), wire.words)
            h2d.set_attribute("put_bytes", put_bytes)
            h2d.set_attribute("pieces", pieces)
            h2d.set_attribute("copied_bytes", copied_bytes)
            h2d.set_attribute("word_source_bytes", source_bytes)
        engine.stats["h2d_s"] += h2d.seconds
        self.wire_bytes = put_bytes
        #: context of the ``replay.h2d`` span: a fold continues that trace
        self.trace_ctx = h2d.context

    def worklists(self, kind: str) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Stacked per-device (i0s [D,k_cap], t_bases [D,k_cap], k_n [D])."""
        k_cap = self.k_caps[kind]
        i0s = np.zeros((self.n_dev, k_cap), dtype=np.int32)
        tbs = np.zeros((self.n_dev, k_cap), dtype=np.int32)
        kn = np.zeros((self.n_dev,), dtype=np.int32)
        for d, p in enumerate(self.plans):
            a = getattr(p, f"{kind}_i0")
            t = getattr(p, f"{kind}_tb")
            i0s[d, : len(a)] = a
            tbs[d, : len(t)] = t
            kn[d] = len(a)
        return i0s, tbs, kn

    def slab_rows(self) -> np.ndarray:
        """``[b]``: the row of the flattened ``[n_dev * b_pad]`` slab that
        holds original aggregate ``i`` (through the deal, then ``perm``)."""
        of_rank = np.empty((self.b,), dtype=np.int32)
        for d, lanes in enumerate(self.deals):
            of_rank[lanes] = d * self.b_pad + np.arange(len(lanes),
                                                        dtype=np.int32)
        perm = self.wire_host.perm
        if perm is None:
            return of_rank
        rows = np.empty_like(of_rank)
        rows[perm] = of_rank
        return rows


class _FakeResident:
    """Minimal duck-type for engine._resident_plan (lengths only)."""

    def __init__(self, lengths: np.ndarray) -> None:
        self.lengths = np.asarray(lengths, dtype=np.int32)


class _PlanView:
    """Engine facade pinning the plan's batch size to the sharded local bs."""

    def __init__(self, engine, bs: int) -> None:
        self._engine = engine
        self.batch_size = bs

    def __getattr__(self, name):
        return getattr(self._engine, name)


def _state_specs(engine, spec) -> dict:
    return {f.name: spec for f in engine.spec.registry.state.fields}


def _sharded_program(engine, key: frozenset, width: int, bs: int, k_cap: int):
    """jit(shard_map(tile loop)) over the device axis; cached on the engine."""
    cache_key = ("sharded", key, width, bs, k_cap)
    hit = engine._resident_folds.get(cache_key)
    if hit is not None:
        return hit
    import jax
    from jax.sharding import PartitionSpec as P

    wire = WireFormat(engine.spec.registry, dict(key))
    view, tile = _make_tile(engine.spec, wire, width, bs,
                            engine.tile_backend, engine.lane_gather)

    # the one-chip program's name: XLA calls both ``jit_fold``, and the
    # benchmark's trace reduction maps that name to the cold fold's layer
    def fold(slab_state, flat_wire, side_flat, starts_all, lens_all,
             ord_all, i0s, t_bases, k_n):
        # a device's block of a buffer is its own upload, whole; the lane
        # vectors arrive with the device axis (size 1) still on: drop it
        slab0 = {k: v[0] for k, v in slab_state.items()}
        buffers = view(flat_wire, side_flat)

        def body(k, st):
            return tile(st, buffers, starts_all[0], lens_all[0], ord_all[0],
                        i0s[0, k], t_bases[0, k])

        out = jax.lax.fori_loop(0, k_n[0], body, slab0)
        return {k: v[None] for k, v in out.items()}

    axis = engine.mesh_axis
    p2 = P(axis, None)
    mapped = jax.shard_map(
        fold, mesh=engine.mesh,
        in_specs=(_state_specs(engine, p2), p2,
                  {f.name: P(axis) for f in wire.side_fields},
                  p2, p2, p2, p2, p2, P(axis)),
        out_specs=_state_specs(engine, p2),
        # handlers may return literal columns (e.g. created=True) whose
        # varying-manual-axes type differs per switch branch; everything here
        # is per-device-local anyway (no collectives), so skip the VMA check
        check_vma=False)
    donate = (0,) if engine.donate_carry else ()
    jitted = jax.jit(mapped, donate_argnums=donate)
    engine._resident_folds[cache_key] = jitted
    return jitted


def _fresh_slab(engine, n_dev: int, b_pad: int):
    """The mesh form of ``engine._fresh_slab``: the init slab ``{f: [n_dev,
    b_pad]}`` and the zero ordinal base, built on their devices."""
    prog = engine._slab_programs.get(("sharded", n_dev, b_pad))
    if prog is None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        init = engine.spec.init_state_tree()
        fields = [(f.name, f.dtype) for f in engine.spec.registry.state.fields]

        def mk():
            slab = {name: jnp.full((n_dev, b_pad), init[name], dtype=dt)
                    for name, dt in fields}
            return slab, jnp.zeros((n_dev, b_pad), jnp.int32)

        shard2 = NamedSharding(engine.mesh, P(engine.mesh_axis, None))
        prog = jax.jit(mk, out_shardings=(_state_specs(engine, shard2), shard2))
        engine._slab_programs[("sharded", n_dev, b_pad)] = prog
    return prog()


def _dispatch_sharded(engine, sharded: ShardedResident,
                      init_carry: Mapping[str, Any] | None,
                      ordinal_base: Optional[np.ndarray], umbrella) -> dict:
    """Dispatch the whole fold WITHOUT syncing: the mesh form of
    ``engine._dispatch_resident``. ``umbrella``, the caller's open
    ``replay.resident`` span, is given the plans' counts."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    stage = engine.profiler.stage
    n_dev, b_pad = sharded.n_dev, sharded.b_pad
    key = frozenset(sharded.wire_host.derived_key.items())
    shard2 = NamedSharding(engine.mesh, P(engine.mesh_axis, None))
    shard1 = NamedSharding(engine.mesh, P(engine.mesh_axis))

    with stage("plan"):
        plans = sharded.plans
        umbrella.set_attribute("width", sharded.width)
        umbrella.set_attribute("width_cap", engine.resident_tile_width())
        umbrella.set_attribute("padded_slots", sharded.padded_slots)
        umbrella.set_attribute("tiles", sum(p.tiles for p in plans))
        umbrella.set_attribute("rounds", max(p.rounds for p in plans))
        umbrella.set_attribute("tiles_small",
                               sum(len(p.small_i0) for p in plans))
        umbrella.set_attribute("slots_small",
                               sum(p.slots_small for p in plans))
        # the steps ONE device takes one after the other: the busiest's
        umbrella.set_attribute(
            "scan_steps", 0 if engine.tile_backend == "assoc"
            else max(p.tiles for p in plans) * sharded.width)
        init_sorted, ord_sorted = _apply_perm(sharded.wire_host.perm,
                                              init_carry, ordinal_base)
        if init_sorted is None and ord_sorted is None:
            slab_dev, ord_dev = _fresh_slab(engine, n_dev, b_pad)
        else:
            ord_l = np.zeros((n_dev, b_pad), dtype=np.int32)
            slab = {name: np.tile(col, (n_dev, 1)) for name, col in
                    engine.init_carry_np(b_pad).items()}
            for d, lanes in enumerate(sharded.deals):
                if ord_sorted is not None:
                    ord_l[d, : len(lanes)] = ord_sorted[lanes]
                if init_sorted is not None:
                    for k, full in init_sorted.items():
                        slab[k][d, : len(lanes)] = full[lanes]
            slab_dev = {k: jax.device_put(v, shard2) for k, v in slab.items()}
            ord_dev = jax.device_put(ord_l, shard2)
        work = []
        for kind in ("big", "small"):
            k_cap = sharded.k_caps[kind]
            if k_cap == 0:
                continue
            i0s, tbs, kn = sharded.worklists(kind)
            work.append((sharded.bs if kind == "big" else sharded.bs_small,
                         k_cap, int(kn.sum()), jax.device_put(i0s, shard2),
                         jax.device_put(tbs, shard2),
                         jax.device_put(kn, shard1)))

    # each granularity runs its OWN program: small tiles sliced bs-wide
    # would overlap/clamp and re-fold the same lanes' windows
    rows_fetched = sum(
        engine._rows_fetched(sharded, sharded.width, p.lanes_tiled)
        for p in plans)
    for bs, k_cap, tiles, i0s_d, tbs_d, kn_d in work:
        engine.stats["windows"] += tiles
        engine.profiler.count_windows(tiles)
        fold = _sharded_program(engine, key, sharded.width, bs, k_cap)
        sig = ("resident-sharded", key, sharded.width, bs, k_cap, b_pad,
               int(sharded.flat_wire.shape[0]))
        first_dispatch = sig not in engine._signatures
        engine._signatures.add(sig)
        with stage("compile" if first_dispatch else "dispatch",
                   tiles=tiles, batch=bs):
            slab_dev = fold(slab_dev, sharded.flat_wire, sharded.flat_side,
                            sharded.starts_dev, sharded.lens_dev, ord_dev,
                            i0s_d, tbs_d, kn_d)
    engine.stats["rows_fetched"] += rows_fetched
    umbrella.set_attribute("gather", engine.lane_gather)
    umbrella.set_attribute("rows_fetched", rows_fetched)
    umbrella.set_attribute("fetched_slots",
                           sum(engine._fetched_slots(p) for p in plans))
    return slab_dev


def _umbrella(engine, sharded: ShardedResident):
    return engine.profiler.stage(
        "resident", follows=sharded.trace_ctx, aggregates=sharded.b,
        events=sharded.num_events, devices=sharded.n_dev)


def fold_resident_sharded(engine, sharded: ShardedResident,
                          init_carry: Mapping[str, Any] | None = None,
                          ordinal_base: Optional[np.ndarray] = None):
    """Fold a :class:`ShardedResident` and return the DEVICE slab —
    ``{field: [n_dev, b_pad] sharded array}`` — without the host pull.

    Row ``[d, j]`` holds sorted-rank lane ``sharded.deals[d][j]`` (rows past
    each deal's length are padding). The mesh half of
    :meth:`ReplayEngine.fold_resident_slab`, used by the resident state plane
    to keep a cold-start replay's states on device; ``replay_resident_sharded``
    is this plus the pull."""
    with _umbrella(engine, sharded) as umbrella:
        return _dispatch_sharded(engine, sharded, init_carry, ordinal_base,
                                 umbrella)


def replay_resident_sharded(engine, sharded: ShardedResident,
                            init_carry: Mapping[str, Any] | None = None,
                            ordinal_base: Optional[np.ndarray] = None
                            ) -> ReplayResult:
    """Fold a :class:`ShardedResident` across the engine's mesh. Results come
    back in the ORIGINAL aggregate order of the packed corpus."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    b = sharded.b
    state_fields = engine.spec.registry.state.fields
    if b == 0:
        return ReplayResult(states={f.name: np.zeros((0,), dtype=f.dtype)
                                    for f in state_fields},
                            num_aggregates=0, num_events=0, padded_events=0)
    stage = engine.profiler.stage
    with _umbrella(engine, sharded) as umbrella:
        slab_dev = _dispatch_sharded(engine, sharded, init_carry,
                                     ordinal_base, umbrella)
        # the one-chip pull over the flattened slab: its finalize program
        # gathers original aggregate i from row slab_rows()[i], whichever
        # device holds it, into the one u16 buffer the host fetches
        with stage("fetch", aggregates=b):
            if any(np.dtype(f.dtype).itemsize > 4 for f in state_fields):
                # _pull_states' per-field path reads a slab's first b rows
                rows = sharded.slab_rows()
                states = {name: np.asarray(col).reshape(-1)[rows]
                          for name, col in slab_dev.items()}
            else:
                if "invperm" not in sharded.cache:
                    sharded.cache["invperm"] = jax.device_put(
                        sharded.slab_rows(), NamedSharding(engine.mesh, P()))
                states = engine._pull_states(slab_dev, b, None, sharded.cache)
    return ReplayResult(states=states, num_aggregates=b,
                        num_events=sharded.num_events,
                        padded_events=sharded.padded_slots)
