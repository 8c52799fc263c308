"""Mesh-native resident plane: the sharded slab with device-local gather lanes.

The single-device :class:`~surge_tpu.replay.resident_state.ResidentStatePlane`
holds its KTable slab as ``{field: [capacity+1]}`` on one device. Its original
mesh wiring just ``device_put`` the same 1-D columns with a sharded layout and
kept the plain-``jit`` programs — so every batched read's arbitrary-index
gather made XLA REPLICATE the slab across the mesh, and every refresh scatter
ran as full-slab SPMD work on all devices (``n_dev×`` the single-device cost).
That legacy layout survives as the ``surge.replay.mesh.gather = replicated``
arm (the paired-bench baseline and the rollback switch).

This module is the first-class path (``= local``, the default): slot
ownership is explicit and every program runs under ``shard_map``.

- **Layout.** Capacity rounds up to a device multiple; the slab is
  ``{field: [n_dev, per_dev+1]}`` sharded ``P(axis, None)``. Global slot
  ``s`` lives on device ``s // per_dev`` at local row ``s % per_dev``; each
  shard's last row is its own scratch (absorbing every padding / non-owned
  write, exactly like the single-device scratch row).
- **Refresh (one sharded h2d, zero d2h, 1/n_dev work per device).** The host
  deals a fold group's lanes to their owning shards and packs PER-DEVICE
  window tensors ``[n_dev, width, lanes_local, nbytes]``; ``device_put`` with
  a ``P(axis, …)`` sharding ships each device only its shard's bytes. Inside
  ``shard_map`` each device admits, gathers carries, decodes and folds ONLY
  its own lanes and scatters back locally — no collectives, no cross-device
  traffic, total fold work equal to the single-device plane's.
- **Reads (one cross-device collective per batched-read round).** A gather of
  ``k`` slots runs device-local: each device gathers the rows it owns (masked
  zeros elsewhere) and ONE ``psum`` combines the partials into the replicated
  ``[words, k]`` result every reader decodes — the slab itself never moves.
  The u16 narrow wire and its fit-flag contract are preserved bit for bit
  (the sum happens on exact u32/i32 partials; the narrow pack runs after the
  collective).

Byte-identity against the single-device golden replay — across evict /
re-admit cycles and a partition rebalance — is held by
tests/test_resident_mesh_plane.py on the forced-8-device CPU mesh.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Tuple

import numpy as np

__all__ = ["MeshPlane"]


def _pow2(n: int, lo: int = 8) -> int:
    cap = lo
    while cap < n:
        cap *= 2
    return cap


class MeshPlane:
    """Device programs + host lane-dealing for one plane's sharded slab."""

    def __init__(self, plane) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.plane = plane
        self.mesh = plane.mesh
        self.axis = plane.engine.mesh_axis
        self.n_dev = int(np.prod(self.mesh.devices.shape))
        # plane.capacity is already rounded to a device multiple (plane init)
        assert plane.capacity % self.n_dev == 0, (plane.capacity, self.n_dev)
        self.per_dev = plane.capacity // self.n_dev
        self.rows = self.per_dev + 1  # +1: each shard's own scratch row
        self._fields = plane._fields
        self._sh2 = NamedSharding(self.mesh, P(self.axis, None))
        self._sh3 = NamedSharding(self.mesh, P(self.axis, None, None))
        self._sh4 = NamedSharding(self.mesh, P(self.axis, None, None, None))
        self._rep = NamedSharding(self.mesh, P())
        self._programs: dict = {}
        #: lane-deal sizes of the last refresh round — the device
        #: observatory's shard-skew source (max/mean over these)
        self.last_deal: List[int] = []

    # -- layout helpers -------------------------------------------------------------

    def owners(self, slots: np.ndarray) -> np.ndarray:
        """Owning device of each global slot (scratch → last device, whose
        local index then lands past per_dev and resolves to local scratch)."""
        return np.minimum(slots // self.per_dev, self.n_dev - 1)

    def init_slab(self):
        """Fresh sharded slab + ordinal columns ({field: [n_dev, rows]})."""
        import jax

        init = self.plane.spec.init_state_tree()
        slab = {f.name: jax.device_put(
            np.full((self.n_dev, self.rows), init[f.name], dtype=f.dtype),
            self._sh2) for f in self._fields}
        ords = jax.device_put(
            np.zeros((self.n_dev, self.rows), dtype=np.int32), self._sh2)
        return slab, ords

    # -- refresh: host lane deal + sharded fold -------------------------------------

    def _deal(self, slots: np.ndarray, bucket_lo: int = 8
              ) -> Tuple[List[np.ndarray], int]:
        """Deal global-slot positions to their owners: per-device index lists
        (positions into the input arrays) + the shared local lane bucket.
        Scratch-sentinel entries (pure padding) are dropped — they fold
        nothing and own no shard."""
        cap = self.plane.capacity
        live = slots < cap
        owner = self.owners(slots)
        deals = [np.nonzero(live & (owner == d))[0] for d in range(self.n_dev)]
        # pow2 local lane bucket: the global arrays already arrive at pow2
        # (bucketed) or pow8 (dense) lane buckets, so the per-shard ladder
        # stays bounded without re-coarsening a small bucket's deal to 8×
        width = _pow2(max((len(d) for d in deals), default=1), bucket_lo)
        return deals, width

    def refresh(self, slab, ords, admit_idx: np.ndarray,
                admit_vals: Mapping[str, np.ndarray], admit_ord: np.ndarray,
                lane_slots: np.ndarray, counts: np.ndarray,
                packed: np.ndarray, side: Mapping[str, np.ndarray]):
        """One refresh window against the sharded slab. Host inputs are the
        single-device plane's global arrays (slots in [0, capacity] with the
        scratch sentinel); the deal + per-device re-pack happens here, then
        ONE sharded ``device_put`` per tensor ships each device its shard's
        lanes and the shard_map program folds them locally."""
        import jax

        a_deals, a_b = self._deal(admit_idx)
        l_deals, l_b = self._deal(lane_slots)
        self.last_deal = [len(d) for d in l_deals]
        per_dev, n_dev = self.per_dev, self.n_dev
        width = packed.shape[0]
        nbytes = packed.shape[2]

        adm_loc = np.full((n_dev, a_b), per_dev, dtype=np.int32)
        adm_ord = np.zeros((n_dev, a_b), dtype=np.int32)
        adm_vals = {f.name: np.zeros((n_dev, a_b), dtype=f.dtype)
                    for f in self._fields}
        for d, sel in enumerate(a_deals):
            adm_loc[d, : len(sel)] = admit_idx[sel] - d * per_dev
            adm_ord[d, : len(sel)] = admit_ord[sel]
            for k, col in adm_vals.items():
                col[d, : len(sel)] = admit_vals[k][sel]

        lane_loc = np.full((n_dev, l_b), per_dev, dtype=np.int32)
        cnt_l = np.zeros((n_dev, l_b), dtype=np.int32)
        packed_l = np.zeros((n_dev, width, l_b, nbytes), dtype=packed.dtype)
        side_l = {k: np.zeros((n_dev, width, l_b), dtype=v.dtype)
                  for k, v in side.items()}
        for d, sel in enumerate(l_deals):
            lane_loc[d, : len(sel)] = lane_slots[sel] - d * per_dev
            cnt_l[d, : len(sel)] = counts[sel]
            packed_l[d, :, : len(sel)] = packed[:, sel]
            for k, col in side_l.items():
                col[d, :, : len(sel)] = side[k][:, sel]

        prog = self._refresh_program(a_b, l_b, width, nbytes,
                                     tuple(sorted(side_l)))
        return prog(
            slab, ords,
            jax.device_put(adm_loc, self._sh2),
            {k: jax.device_put(v, self._sh2) for k, v in adm_vals.items()},
            jax.device_put(adm_ord, self._sh2),
            jax.device_put(lane_loc, self._sh2),
            jax.device_put(cnt_l, self._sh2),
            jax.device_put(packed_l, self._sh4),
            {k: jax.device_put(v, self._sh3) for k, v in side_l.items()})

    def _refresh_program(self, a_b: int, l_b: int, width: int, nbytes: int,
                         side_names: tuple):
        key = ("refresh", a_b, l_b, width, nbytes, side_names)
        hit = self._programs.get(key)
        if hit is not None:
            return hit
        import jax
        from jax.sharding import PartitionSpec as P

        from surge_tpu.replay.engine import make_batch_fold

        plane = self.plane
        wire = plane._wire
        fold = make_batch_fold(plane.spec)
        fnames = [f.name for f in self._fields]

        def local(slab_d, ords_d, adm_loc, adm_vals, adm_ord, lane_loc,
                  cnt, packed, side):
            # local blocks keep the (size-1) device axis; drop it
            slab0 = {k: v[0] for k, v in slab_d.items()}
            ords0 = ords_d[0]
            al, ao = adm_loc[0], adm_ord[0]
            ll, cn = lane_loc[0], cnt[0]
            pk = packed[0]
            sd = {k: v[0] for k, v in side.items()}
            # 1. admission scatter (spilled carries / init rows re-enter);
            # non-owned and padding entries all land on the local scratch row
            slab0 = {k: v.at[al].set(adm_vals[k][0]) for k, v in slab0.items()}
            ords0 = ords0.at[al].set(ao)
            # 2. gather this shard's lane carries, decode+fold its window
            carry = {k: v[ll] for k, v in slab0.items()}
            events = wire.decode(pk, sd, ords0[ll])
            out = fold(carry, events)
            # 3. scatter back + advance ordinals, all shard-local
            slab0 = {k: v.at[ll].set(out[k]) for k, v in slab0.items()}
            ords0 = ords0.at[ll].add(cn)
            return ({k: v[None] for k, v in slab0.items()}, ords0[None])

        axis = self.axis
        p2 = P(axis, None)
        mapped = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=({k: p2 for k in fnames}, p2, p2,
                      {k: p2 for k in fnames}, p2, p2, p2,
                      P(axis, None, None, None),
                      {k: P(axis, None, None) for k in side_names}),
            out_specs=({k: p2 for k in fnames}, p2),
            # handlers may return literal columns whose varying-manual-axes
            # type differs per switch branch; everything here is
            # per-device-local (no collectives), so skip the VMA check
            check_vma=False)
        # sharded slab+ordinal donation (surge.replay.donate-refresh): each
        # shard's refresh scatter consumes the columns it rewrites instead of
        # copying them every window — the plane republishes its handle after
        # every donated dispatch (resident_state._dispatch_plan)
        prog = jax.jit(mapped, donate_argnums=(
            (0, 1) if plane._donate_refresh else ()))
        self._programs[key] = prog
        return prog

    # -- seeding --------------------------------------------------------------------

    def seed_rows(self, slab, ords, vals: Mapping[str, np.ndarray],
                  dst_slots: np.ndarray, lens: np.ndarray):
        """Scatter host state rows into the sharded slab (the mesh cold-start
        admission): values ride replicated, each device keeps its own."""
        import jax

        k_b = len(dst_slots)
        prog = self._seed_program(k_b)
        return prog(slab, ords,
                    {k: jax.device_put(np.asarray(v), self._rep)
                     for k, v in vals.items()},
                    jax.device_put(np.asarray(dst_slots, np.int32),
                                   self._rep),
                    jax.device_put(np.asarray(lens, np.int32), self._rep))

    def _seed_program(self, k_b: int):
        key = ("seed", k_b)
        hit = self._programs.get(key)
        if hit is not None:
            return hit
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        fnames = [f.name for f in self._fields]
        per_dev = self.per_dev
        axis = self.axis

        def local(slab_d, ords_d, vals, dst, lens):
            d = jax.lax.axis_index(axis)
            loc = dst - d * per_dev
            own = (loc >= 0) & (loc < per_dev)
            pos = jnp.where(own, jnp.clip(loc, 0, per_dev - 1), per_dev)
            slab0 = {k: v[0].at[pos].set(vals[k]) for k, v in slab_d.items()}
            ords0 = ords_d[0].at[pos].set(lens)
            return ({k: v[None] for k, v in slab0.items()}, ords0[None])

        p2 = P(axis, None)
        mapped = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=({k: p2 for k in fnames}, p2, {k: P() for k in fnames},
                      P(), P()),
            out_specs=({k: p2 for k in fnames}, p2), check_vma=False)
        prog = jax.jit(mapped)
        self._programs[key] = prog
        return prog

    # -- reads: device-local gather + ONE collective ---------------------------------

    def gather_wide(self, slab, ords, idx: np.ndarray):
        """The wide (u32-matrix) gather: each device contributes the rows it
        owns, one psum replicates the result. Signature-compatible with the
        single-device ``_gather_wide`` jit."""
        import jax

        prog = self._gather_program(len(np.asarray(idx)), narrow=False)
        return prog(slab, ords, jax.device_put(
            np.asarray(idx, np.int32), self._rep))

    def gather_narrow(self, slab, idx: np.ndarray):
        """The u16 narrow read wire: exact partials psum first, the narrow
        pack + fit flags run post-collective — identical buffer layout and
        overflow contract to the single-device program."""
        import jax

        prog = self._gather_program(len(np.asarray(idx)), narrow=True)
        return prog(slab, jax.device_put(np.asarray(idx, np.int32),
                                         self._rep))

    def _gather_program(self, k_b: int, narrow: bool):
        key = ("gather-narrow" if narrow else "gather-wide", k_b)
        hit = self._programs.get(key)
        if hit is not None:
            return hit
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        plane = self.plane
        names = [f.name for f in self._fields]
        dts = [plane._dev_dts[n] for n in names]
        per_dev = self.per_dev
        axis = self.axis
        p2 = P(axis, None)

        def local_wide(slab_d, ords_d, idx):
            d = jax.lax.axis_index(axis)
            loc = idx - d * per_dev
            own = (loc >= 0) & (loc < per_dev)
            locc = jnp.clip(loc, 0, per_dev - 1)
            cols = []
            for name, dt in zip(names, dts):
                v = slab_d[name][0][locc]
                if np.issubdtype(dt, np.floating) and dt.itemsize < 4:
                    v = jax.lax.bitcast_convert_type(
                        v.astype(jnp.float32), jnp.uint32)
                elif dt == np.bool_ or dt.itemsize < 4:
                    v = v.astype(jnp.uint32)
                elif dt != np.dtype(np.uint32):
                    v = jax.lax.bitcast_convert_type(v, jnp.uint32)
                if v.ndim == 2:  # 64-bit column: one row per u32 word
                    for j in range(v.shape[1]):
                        cols.append(jnp.where(own, v[:, j], 0))
                else:
                    cols.append(jnp.where(own, v, 0))
            # the ordinal row rides the same matrix: exactly ONE collective
            # per batched-read round
            cols.append(jnp.where(own, ords_d[0][locc].astype(jnp.uint32), 0))
            both = jax.lax.psum(jnp.stack(cols), axis)
            return both[:-1], both[-1].astype(jnp.int32)

        if not narrow:
            mapped = jax.shard_map(
                local_wide, mesh=self.mesh,
                in_specs=({k: p2 for k in names}, p2, P()),
                out_specs=(P(), P()), check_vma=False)
            prog = jax.jit(mapped)
            self._programs[key] = prog
            return prog

        def local_narrow(slab_d, idx):
            d = jax.lax.axis_index(axis)
            loc = idx - d * per_dev
            own = (loc >= 0) & (loc < per_dev)
            locc = jnp.clip(loc, 0, per_dev - 1)
            # exact i32 partials cross ONE collective; the u16 pack and its
            # fit flags run on the REPLICATED true values after the psum, so
            # the overflow contract matches the single-device wire exactly
            # (narrow_ok already excludes floats and >4-byte columns)
            part = jnp.stack([
                jnp.where(own, slab_d[name][0][locc].astype(jnp.int32), 0)
                for name in names])
            mat = jax.lax.psum(part, axis)
            cols16, flags = [], []
            for i, dt in enumerate(dts):
                v = mat[i]
                if dt == np.bool_:
                    fits = jnp.bool_(True)
                elif np.issubdtype(dt, np.signedinteger):
                    fits = jnp.all((v >= -32768) & (v <= 32767))
                else:  # unsigned: a >2^31 source wrapped negative — refetch
                    fits = jnp.all((v >= 0) & (v <= 65535))
                cols16.append(v.astype(jnp.uint16).ravel())
                flags.append(fits.astype(jnp.uint16))
            return jnp.concatenate(cols16 + [jnp.stack(flags)])

        mapped = jax.shard_map(
            local_narrow, mesh=self.mesh,
            in_specs=({k: p2 for k in names}, P()),
            out_specs=P(), check_vma=False)
        prog = jax.jit(mapped)
        self._programs[key] = prog
        return prog
