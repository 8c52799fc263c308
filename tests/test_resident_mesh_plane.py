"""Mesh-native resident plane (surge_tpu.replay.plane_mesh) on the forced
8-device CPU mesh — tier-1 runs these on every pass (the ``mesh8`` fixture
FAILS rather than skips when the platform lost its devices).

The load-bearing proof is golden byte-identity: the sharded slab with
device-local gather lanes, driven through incremental refresh rounds,
evict/re-admit cycles AND a partition revoke/re-grant rebalance, must serve
every aggregate byte-identical to a single-device full cold-start replay over
the same log. The assoc tree fold under ``shard_map``
(``tile-backend = assoc``) is held to the same bar."""

import asyncio

import numpy as np
import pytest

from surge_tpu.models import counter
from surge_tpu.replay.resident_state import ResidentStatePlane

from tests.test_resident_state import (
    EVT,
    NPART,
    STATE,
    TOPIC,
    Expected,
    append_events,
    cold_restore_bytes,
    make_log,
    part_of,
    wait_caught_up,
)


def _mesh_plane(log, mesh, **kw):
    """make_plane with the mesh attached (the plane wires MeshPlane when
    surge.replay.mesh.gather=local, the legacy replicated programs else)."""
    from surge_tpu.config import default_config

    overrides = kw.pop("overrides", None) or {}
    cfg = default_config().with_overrides({
        "surge.replay.resident.capacity": kw.pop("capacity", 8),
        "surge.replay.resident.max-lag-records": kw.pop("max_lag", 4096),
        "surge.replay.resident.refresh-interval-ms": 10,
        "surge.replay.batch-size": 16,
        "surge.replay.time-chunk": 8,
        **overrides,
    })
    from surge_tpu.serialization import SerializedMessage

    return ResidentStatePlane(
        log, TOPIC, counter.make_replay_spec(), config=cfg, mesh=mesh,
        deserialize_event=lambda raw: EVT.read_event(
            SerializedMessage(key="", value=raw)),
        serialize_state=lambda a, s: STATE.write_state(s).value, **kw)


@pytest.mark.parametrize("gather", ["local", "replicated"])
def test_mesh_plane_golden_byte_identity(mesh8, gather):
    """Incremental refresh rounds across evictions, re-admissions AND a
    partition revoke/re-grant — every tracked aggregate byte-identical to the
    single-device full replay, on both mesh arms."""
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(30)]
        evs = []
        for i, agg in enumerate(aggs):
            evs.extend(exp.events(agg, 3 + i % 5, decrement_every=4))
        append_events(log, evs)
        plane = _mesh_plane(log, mesh8, capacity=10,
                            overrides={"surge.replay.mesh.gather": gather})
        # the operator floor rounds UP to a device multiple (8 devs: 10→16)
        assert plane.capacity == 16
        assert plane._mesh_local == (gather == "local")
        await plane.start()
        try:
            for rnd in range(3):
                evs = []
                for i, agg in enumerate(aggs):
                    if (i + rnd) % 3 == 0:
                        evs.extend(exp.events(agg, 2 + rnd,
                                              decrement_every=3))
                append_events(log, evs)
                await wait_caught_up(plane)
                if rnd == 1:
                    # indexer-style rebalance mid-tail: revoke partition 1,
                    # then re-grant — purge, re-anchor at 0, refold without
                    # double-folding (the sharded slab included)
                    plane.set_partitions([0, 2, 3])
                    assert all(part_of(a) != 1 for a in plane.resident_ids())
                    plane.set_partitions([0, 1, 2, 3])
                    await wait_caught_up(plane)
            assert plane.stats["evictions"] > 0, \
                "capacity 16 with 30 aggregates must have churned the slab"
            golden = cold_restore_bytes(log)
            for agg in aggs:
                hit, data = await plane.read_bytes(agg)
                assert hit, agg
                assert data == golden[agg], agg
            assert plane.snapshot_states() == exp.states
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_device_local_gather_correctness_across_rebalance(mesh8):
    """Batched reads resolve on the owning shard: a read_many spanning every
    shard coalesces into device-local gathers + one collective, stays correct
    across a rebalance, and the revoked partition's rows are never servable."""
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(32)]
        for agg in aggs:
            append_events(log, exp.events(agg, 4, decrement_every=3))
        plane = _mesh_plane(log, mesh8, capacity=32)
        await plane.start()
        try:
            assert plane._mesh_local and plane._meshp is not None
            # slots span every shard (32 slots / 8 devices = 4 rows each)
            owners = {int(plane._meshp.owners(np.asarray([s]))[0])
                      for s in plane._dir.values()}
            assert owners == set(range(8)), owners
            got = await plane.read_many(aggs)
            assert got == {a: exp.states[a] for a in aggs}
            assert plane.stats["gathers"] >= 1
            # rebalance: revoke partition 2 — its rows must MISS, the rest
            # keep serving from their shards
            plane.set_partitions([0, 1, 3])
            got = await plane.read_many(aggs)
            assert set(got) == {a for a in aggs if part_of(a) != 2}
            for a in aggs:
                hit, st = await plane.read_state(a)
                assert hit == (part_of(a) != 2)
                if hit:
                    assert st == exp.states[a]
            # re-grant: refold from 0 through fresh admissions; reads match
            plane.set_partitions([0, 1, 2, 3])
            await wait_caught_up(plane)
            got = await plane.read_many(aggs)
            assert got == {a: exp.states[a] for a in aggs}
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_mesh_narrow_overflow_refetches_wide(mesh8):
    """The u16 narrow wire under the sharded slab: the fit flags are computed
    on the psum'd TRUE values, so an overflowing row still reads exactly
    (one wide refetch, same contract as the single-device plane)."""
    async def scenario():
        log = make_log()
        plane = _mesh_plane(log, mesh8, capacity=8)
        plane._ensure_device_state()
        assert plane._gather_narrow is not None  # all-integer counter schema
        big = counter.State("agg-big", 70_000, 3)     # overflows u16
        neg = counter.State("agg-neg", -40_000, 2)    # overflows i16
        small = counter.State("agg-small", 7, 1)
        states = {"count": np.array([s.count for s in (big, neg, small)],
                                    dtype=np.int32),
                  "version": np.array([s.version for s in (big, neg, small)],
                                      dtype=np.int32)}
        plane._seed_from_host_rows(
            ["agg-big", "agg-neg", "agg-small"], states,
            np.array([3, 2, 1], dtype=np.int32),
            {"agg-big": 0, "agg-neg": 0, "agg-small": 0})
        plane._watermarks = {p: 0 for p in range(NPART)}
        plane._seeded = True
        for expect in (big, neg, small):
            hit, st = await plane.read_state(expect.aggregate_id)
            assert hit and st == expect, (st, expect)

    asyncio.run(scenario())


def test_mesh_plane_assoc_tile_backend_byte_identity(mesh8):
    """The assoc tree fold under shard_map, end to end through the PLANE:
    mesh seed (fold_resident_sharded with tile-backend=assoc) + incremental
    rounds, byte-identical to the single-device golden replay."""
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(20)]
        evs = []
        for i, agg in enumerate(aggs):
            evs.extend(exp.events(agg, 2 + i % 6, decrement_every=3))
        append_events(log, evs)
        plane = _mesh_plane(log, mesh8, capacity=24, overrides={
            "surge.replay.tile-backend": "assoc",
        })
        assert plane.engine.tile_backend == "assoc"
        await plane.start()
        try:
            evs = []
            for agg in aggs[::2]:
                evs.extend(exp.events(agg, 3, decrement_every=2))
            append_events(log, evs)
            await wait_caught_up(plane)
            golden = cold_restore_bytes(log)
            for agg in aggs:
                hit, data = await plane.read_bytes(agg)
                assert hit and data == golden[agg], agg
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_refresh_round_keeps_sharded_h2d_zero_d2h(mesh8):
    """The per-shard incremental invariant: a refresh round ships each shard
    only its lanes (one sharded h2d) and pulls nothing back — the only d2h
    the plane ever does outside reads is the eviction spill."""
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(16)]
        for agg in aggs:
            append_events(log, exp.events(agg, 3))
        plane = _mesh_plane(log, mesh8, capacity=16)
        await plane.start()
        try:
            meshp = plane._meshp
            append_events(log, [ev for agg in aggs
                                for ev in exp.events(agg, 2)])
            await wait_caught_up(plane)
            # the deal really split the lanes: every shard owns 2 rows of
            # the 16 slots, so per-device lane buckets stay at the 8 floor
            # instead of the global 512-bucket the replicated arm dispatches
            refresh_keys = [k for k in meshp._programs if k[0] == "refresh"]
            assert refresh_keys, "refresh rounds must go through MeshPlane"
            assert all(k[2] <= 8 for k in refresh_keys), refresh_keys
            assert plane.snapshot_states() == exp.states
        finally:
            await plane.stop()

    asyncio.run(scenario())
