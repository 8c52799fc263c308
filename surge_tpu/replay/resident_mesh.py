"""Mesh-sharded resident replay: the single-sync tile design across devices.

Entity parallelism (SURVEY.md §2.10 row 1) for the resident path: lanes are
dealt round-robin across the mesh axis (descending length order, so every
device draws the same length distribution and finishes together), each device
holds its shard of the flat wire corpus, and one ``shard_map``-wrapped
dispatch runs the per-device tile loop — no collectives anywhere, because
aggregate folds are independent. Per-device tile counts ride in as data, so
devices with slightly different work loop independently inside the same SPMD
program. The whole replay still crosses the host⇄device boundary exactly
twice per granularity (dispatch in, states out).
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

import numpy as np

from surge_tpu.codec.wire import WireFormat
from surge_tpu.replay.engine import (
    ReplayResult,
    ResidentWire,
    _apply_perm,
    _bucket_len,
    _make_tile,
    _round_up,
    _unapply_perm,
)


def _deal(b: int, n_dev: int) -> list[np.ndarray]:
    """Round-robin lane deal: device d gets sorted-rank lanes d, d+D, d+2D…"""
    return [np.arange(d, b, n_dev, dtype=np.int64) for d in range(n_dev)]


class ShardedResident:
    """Device-resident sharded corpus + plan, ready for :func:`replay`."""

    def __init__(self, engine, wire: ResidentWire) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if engine.mesh is None:
            raise ValueError("ShardedResident requires a mesh-backed engine")
        engine.check_wire(wire)  # layout/guard safety, same as upload_resident
        self.engine = engine
        self.wire_host = wire
        mesh = engine.mesh
        axis = engine.mesh_axis
        n_dev = int(np.prod(mesh.devices.shape))
        self.n_dev = n_dev
        b = wire.lengths.shape[0]
        self.b = b
        self.num_events = wire.num_events

        # --- partition lanes (sorted desc) round-robin across devices -------
        deals = _deal(max(b, 1), n_dev) if b else [np.zeros(0, np.int64)
                                                  for _ in range(n_dev)]
        self.deals = deals
        b_local_max = max((len(d) for d in deals), default=0)
        bs = min(engine.batch_size, _round_up(max(b_local_max, 1),
                                              engine._lane_multiple()))
        self.bs = bs
        b_pad = _round_up(max(b_local_max, 1), bs)
        self.b_pad = b_pad
        width = engine.resident_tile_width()
        self.width = width

        # --- per-device flat corpora (contiguous lane spans, re-packed) -----
        guard = wire.guard
        n_locals = [int(wire.lengths[d].sum()) for d in deals]
        n_rows = _bucket_len(max(n_locals, default=0) + guard)
        nbytes = wire.packed.shape[1]
        flat = np.zeros((n_dev, n_rows, nbytes), dtype=np.uint8)
        side = {k: np.zeros((n_dev, n_rows), dtype=v.dtype)
                for k, v in wire.side.items()}
        starts_l = np.zeros((n_dev, b_pad), dtype=np.int32)
        lens_l = np.zeros((n_dev, b_pad), dtype=np.int32)
        for d, lanes in enumerate(deals):
            pos = 0
            for j, lane in enumerate(lanes):
                ln = int(wire.lengths[lane])
                s0 = int(wire.starts[lane])
                flat[d, pos: pos + ln] = wire.packed[s0: s0 + ln]
                for k, col in side.items():
                    col[d, pos: pos + ln] = wire.side[k][s0: s0 + ln]
                starts_l[d, j] = pos
                lens_l[d, j] = ln
                pos += ln

        # --- per-device tile plans (shared shapes, data-driven trip count) --
        # Plans see the FULL padded [b_pad] length row (zero tails are still
        # descending and schedule no rounds), so every device derives the same
        # bs and the shared compiled program's static shapes hold everywhere.
        from surge_tpu.replay.engine import ResidentPlan

        plan_fn = type(engine)._resident_plan  # unbound: sees the view's bs
        plans: list[ResidentPlan] = []
        for d in range(n_dev):
            fake = _FakeResident(lens_l[d])
            plans.append(plan_fn(_PlanView(engine, bs), fake))
        self.plans = plans
        assert all(p.bs_big == bs for p in plans)
        self.bs_small = plans[0].bs_small if plans else bs
        assert all(p.bs_small == self.bs_small for p in plans)
        self.k_caps = {}
        for kind in ("big", "small"):
            k_max = max((len(getattr(p, f"{kind}_i0")) for p in plans),
                        default=0)
            self.k_caps[kind] = engine._plan_cap(k_max) if k_max else 0
        self.padded_slots = sum(p.padded_slots for p in plans)

        # --- upload, sharded ------------------------------------------------
        shard = NamedSharding(mesh, P(axis, *([None] * 2)))
        shard2 = NamedSharding(mesh, P(axis, None))
        self.flat_dev = jax.device_put(flat, shard)
        self.side_dev = {k: jax.device_put(v, shard2) for k, v in side.items()}
        self.starts_dev = jax.device_put(starts_l, shard2)
        self.lens_dev = jax.device_put(lens_l, shard2)
        self.wire_bytes = flat.nbytes + sum(v.nbytes for v in side.values())

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


def _sharded_program(engine, key: frozenset, width: int, bs: int, k_cap: int):
    """jit(shard_map(tile loop)) over the device axis; cached on the engine."""
    cache_key = ("sharded", key, width, bs, k_cap)
    hit = engine._resident_folds.get(cache_key)
    if hit is not None:
        return hit
    import jax
    from jax.sharding import PartitionSpec as P

    wire = WireFormat(engine.spec.registry, dict(key))
    view, tile = _make_tile(engine.spec, wire, width, bs, engine._unroll,
                            engine._dispatch, engine.tile_backend,
                            engine.lane_gather)

    def local_fold(slab_state, flat_wire, side_flat, starts_all, lens_all,
                   ord_all, i0s, t_bases, k_n):
        # local blocks arrive with the device axis (size 1) still on; drop it
        slab0 = {k: v[0] for k, v in slab_state.items()}
        buffers = view(flat_wire[0], {k: v[0] for k, v in side_flat.items()})

        def body(k, st):
            return tile(st, buffers, starts_all[0], lens_all[0], ord_all[0],
                        i0s[0, k], t_bases[0, k])

        out = jax.lax.fori_loop(0, k_n[0], body, slab0)
        return {k: v[None] for k, v in out.items()}

    axis = engine.mesh_axis
    p2 = P(axis, None)
    p3 = P(axis, None, None)
    mapped = jax.shard_map(
        local_fold, mesh=engine.mesh,
        in_specs=({k: p2 for k in
                   (f.name for f in engine.spec.registry.state.fields)},
                  p3, {k: p2 for k in sorted(
                      f.name for f in wire.side_fields)}, p2, p2, p2, p2, p2,
                  P(axis)),
        out_specs={k: p2 for k in
                   (f.name for f in engine.spec.registry.state.fields)},
        # handlers may return literal columns (e.g. created=True) whose
        # varying-manual-axes type differs per switch branch; everything here
        # is per-device-local anyway (no collectives), so skip the VMA check
        check_vma=False)
    donate = (0,) if engine.donate_carry else ()
    jitted = jax.jit(mapped, donate_argnums=donate)
    engine._resident_folds[cache_key] = jitted
    return jitted


def fold_resident_sharded(engine, sharded: ShardedResident,
                          init_carry: Mapping[str, Any] | None = None,
                          ordinal_base: Optional[np.ndarray] = None):
    """Fold a :class:`ShardedResident` and return the DEVICE slab —
    ``{field: [n_dev, b_pad] sharded array}`` — without the host pull.

    Row ``[d, j]`` holds sorted-rank lane ``sharded.deals[d][j]`` (rows past
    each deal's length are padding). The mesh half of
    :meth:`ReplayEngine.fold_resident_slab`, used by the resident state plane
    to keep a cold-start replay's states on device; ``replay_resident_sharded``
    is this plus one pull + reassembly."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    w = sharded.wire_host
    b = sharded.b
    state_fields = engine.spec.registry.state.fields
    perm = w.perm
    n_dev, b_pad = sharded.n_dev, sharded.b_pad
    key = frozenset(w.derived_key.items())

    ord_l = np.zeros((n_dev, b_pad), dtype=np.int32)
    slab = {f.name: np.zeros((n_dev, b_pad), dtype=f.dtype)
            for f in state_fields}
    init_tree = engine.spec.init_state_tree()
    for name, col in slab.items():
        col[:] = init_tree[name]
    init_sorted, src_ord = _apply_perm(perm, init_carry, ordinal_base)
    for d, lanes in enumerate(sharded.deals):
        if src_ord is not None:
            ord_l[d, : len(lanes)] = src_ord[lanes].astype(np.int32)
        if init_sorted is not None:
            for k, full in init_sorted.items():
                slab[k][d, : len(lanes)] = full[lanes]

    shard2 = NamedSharding(engine.mesh, P(engine.mesh_axis, None))
    shard1 = NamedSharding(engine.mesh, P(engine.mesh_axis))
    slab_dev = {k: jax.device_put(v, shard2) for k, v in slab.items()}
    ord_dev = jax.device_put(ord_l, shard2)

    for kind in ("big", "small"):
        k_cap = sharded.k_caps[kind]
        if k_cap == 0:
            continue
        # each granularity runs its OWN program: small tiles sliced bs-wide
        # would overlap/clamp and re-fold the same lanes' windows
        bs_kind = sharded.bs if kind == "big" else sharded.bs_small
        i0s, tbs, kn = sharded.worklists(kind)
        fold = _sharded_program(engine, key, sharded.width, bs_kind, k_cap)
        engine._signatures.add(("resident-sharded", key, sharded.width,
                               bs_kind, k_cap, b_pad,
                               int(sharded.flat_dev.shape[1])))
        engine.stats["windows"] += int(kn.sum())
        slab_dev = fold(slab_dev, sharded.flat_dev, sharded.side_dev,
                        sharded.starts_dev, sharded.lens_dev, ord_dev,
                        jax.device_put(i0s, shard2),
                        jax.device_put(tbs, shard2),
                        jax.device_put(kn, shard1))
    return slab_dev


def replay_resident_sharded(engine, sharded: ShardedResident,
                            init_carry: Mapping[str, Any] | None = None,
                            ordinal_base: Optional[np.ndarray] = None
                            ) -> ReplayResult:
    """Fold a :class:`ShardedResident` across the engine's mesh. Results come
    back in the ORIGINAL aggregate order of the packed corpus."""
    b = sharded.b
    state_fields = engine.spec.registry.state.fields
    if b == 0:
        return ReplayResult(states={f.name: np.zeros((0,), dtype=f.dtype)
                                    for f in state_fields},
                            num_aggregates=0, num_events=0, padded_events=0)
    perm = sharded.wire_host.perm
    slab_dev = fold_resident_sharded(engine, sharded, init_carry=init_carry,
                                     ordinal_base=ordinal_base)
    # single pull; reassemble original order through deal + perm
    out_sorted = {name: np.empty((b,), dtype=f.dtype)
                  for name, f in ((f.name, f) for f in state_fields)}
    host = {name: np.asarray(v) for name, v in slab_dev.items()}
    for d, lanes in enumerate(sharded.deals):
        for name in out_sorted:
            out_sorted[name][lanes] = host[name][d, : len(lanes)]
    return ReplayResult(states=_unapply_perm(perm, out_sorted),
                        num_aggregates=b,
                        num_events=sharded.num_events,
                        padded_events=sharded.padded_slots)
