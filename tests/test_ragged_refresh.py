"""Bucketed ragged refresh dispatch (ISSUE 18): length-bucketed refresh
programs and donated slab scatters.

The load-bearing proofs mirror the plane's golden bar: byte-identity vs the
full cold-start replay across evict/re-admit and a partition rebalance, on
cpu AND the forced 8-device mesh, with the plane seeded through the XLA tile
and through the Pallas tile scan.
On top of that: the compile-signature set stays bounded by the layout's
bucket table under 100 adversarial rounds (dense and bucketed), a donated
refresh round never surfaces a deleted buffer to any read path (batched
gather, project, evict spill, view fold — with the `donate-refresh` kill
switch as the paired arm), and the steady-ragged shape's padding waste drops
≥ 3x vs the dense rectangle."""

import asyncio

import pytest

from surge_tpu.replay.ledger import ReplayLedger

from tests.test_resident_state import (
    EVT,
    STATE,
    TOPIC,
    Expected,
    append_events,
    cold_restore_bytes,
    make_log,
    part_of,
    wait_caught_up,
)


def make_plane(log, *, capacity=64, ledger=None, mesh=None, overrides=None):
    from surge_tpu.config import default_config
    from surge_tpu.models import counter
    from surge_tpu.replay.resident_state import ResidentStatePlane
    from surge_tpu.serialization import SerializedMessage

    cfg = default_config().with_overrides({
        "surge.replay.resident.capacity": capacity,
        "surge.replay.resident.refresh-interval-ms": 10,
        "surge.replay.batch-size": 16,
        "surge.replay.time-chunk": 8,
        **(overrides or {}),
    })
    return ResidentStatePlane(
        log, TOPIC, counter.make_replay_spec(), config=cfg, mesh=mesh,
        deserialize_event=lambda raw: EVT.read_event(
            SerializedMessage(key="", value=raw)),
        serialize_state=lambda a, s: STATE.write_state(s).value,
        ledger=ledger)


def _refresh_sigs(plane):
    return {s for s in plane._signatures if s[0] == "refresh"}


# -- golden byte-identity: bucketed refresh over an xla- and an assoc-seeded plane ----


@pytest.mark.parametrize("overrides", [
    {"surge.replay.resident.refresh-dispatch": "bucketed"},
    {"surge.replay.resident.refresh-dispatch": "bucketed",
     "surge.replay.tile-backend": "assoc"},
], ids=["bucketed", "bucketed-assoc-seed"])
def test_bucketed_refresh_golden_byte_identity(overrides):
    """Incremental bucketed refresh rounds — across evictions, re-admissions
    AND a partition revoke/re-grant — byte-identical to the full cold-start
    replay, with the round anatomy carrying per-bucket occupancy."""
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(30)]
        evs = []
        for i, agg in enumerate(aggs):
            evs.extend(exp.events(agg, 3 + i % 5, decrement_every=4))
        append_events(log, evs)
        led = ReplayLedger(name="engine:t")
        plane = make_plane(log, capacity=8, ledger=led, overrides=overrides)
        await plane.start()
        try:
            for rnd in range(4):
                evs = []
                for i, agg in enumerate(aggs):
                    if (i + rnd) % 3 == 0:
                        evs.extend(exp.events(agg, 2 + rnd,
                                              decrement_every=3))
                append_events(log, evs)
                await wait_caught_up(plane)
                if rnd == 1:
                    plane.set_partitions([0, 2, 3])
                    assert all(part_of(a) != 1
                               for a in plane.resident_ids())
                    plane.set_partitions([0, 1, 2, 3])
                    await wait_caught_up(plane)
            assert plane.stats["evictions"] > 0
            golden = cold_restore_bytes(log)
            for agg in aggs:
                hit, data = await plane.read_bytes(agg)
                assert hit, agg
                assert data == golden[agg], agg
            assert plane.snapshot_states() == exp.states
            # the ledger carried bucket anatomy: every round names its
            # occupied buckets and the bounded table; lanes never exceed
            # the bucket's pow2 lane capacity
            rounds = [e for e in led.events() if e["type"] == "round"]
            assert rounds and all(e["buckets"] for e in rounds)
            for e in rounds:
                assert e["bucket_table"] == len(plane.bucket_table)
                for bk in e["buckets"]:
                    assert 0 < bk["lanes"] <= bk["lanes_b"]
                    assert (bk["lanes_b"], bk["width"]) in plane.bucket_table
            assert led.summary()["bucket_programs"] == sum(
                len(e["buckets"]) for e in rounds)
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_mesh_bucketed_golden_byte_identity(mesh8):
    """The bucketed dispatch on the sharded mesh plane: per-shard deals ride
    the pow2 lane buckets and stay byte-identical across evict/re-admit and
    a rebalance (the mesh arm of the tentpole's golden bar)."""
    from tests.test_resident_mesh_plane import _mesh_plane

    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(30)]
        evs = []
        for i, agg in enumerate(aggs):
            evs.extend(exp.events(agg, 3 + i % 5, decrement_every=4))
        append_events(log, evs)
        led = ReplayLedger(name="engine:t")
        plane = _mesh_plane(log, mesh8, capacity=10, ledger=led, overrides={
            "surge.replay.resident.refresh-dispatch": "bucketed"})
        assert plane.capacity == 16 and plane._mesh_local
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
                    plane.set_partitions([0, 2, 3])
                    plane.set_partitions([0, 1, 2, 3])
                    await wait_caught_up(plane)
            assert plane.stats["evictions"] > 0
            golden = cold_restore_bytes(log)
            for agg in aggs:
                hit, data = await plane.read_bytes(agg)
                assert hit and data == golden[agg], agg
            rounds = [e for e in led.events() if e["type"] == "round"]
            assert rounds and all(e["buckets"] for e in rounds)
        finally:
            await plane.stop()

    asyncio.run(scenario())


# -- compile-cache bound --------------------------------------------------------------


@pytest.mark.parametrize("dispatch", ["bucketed", "dense"])
def test_compile_cache_bounded_by_bucket_table(dispatch):
    """100 refresh rounds with adversarially varied lane counts and tail
    lengths compile at most len(bucket_table) refresh signatures — shape
    churn cannot blow the jit cache on either dispatch arm, and every
    compiled (lanes_b, width) draws from the table."""
    async def scenario():
        log = make_log()
        exp = Expected()
        plane = make_plane(log, capacity=64, overrides={
            "surge.replay.resident.refresh-dispatch": dispatch})
        plane._ensure_device_state()
        plane.seed_from_log()
        for i in range(100):
            lanes = (i * 7) % 37 + 1
            tail = (i * 3) % 9 + 1
            evs = []
            for j in range(lanes):
                evs.extend(exp.events(f"agg-{j}", tail))
            append_events(log, evs)
            assert await plane._refresh_once()
        sigs = _refresh_sigs(plane)
        assert 1 <= len(sigs) <= len(plane.bucket_table), sigs
        for s in sigs:
            assert (s[1], s[2]) in plane.bucket_table, s
        await plane.stop()

    asyncio.run(scenario())


# -- donation safety ------------------------------------------------------------------


@pytest.mark.parametrize("donate", [True, False], ids=["donated", "copying"])
def test_donated_refresh_keeps_every_read_path_live(donate):
    """After donated refresh rounds the plane's handle is rebound to the
    donated result: batched gathers, project, the evict spill d2h and the
    view fold all see the NEW slab and no deleted-buffer error surfaces.
    The donate-refresh=False arm is the kill switch: identical results."""
    from surge_tpu.replay.query import Aggregate, ScanQuery
    from surge_tpu.replay.views import MaterializedViews, ViewDef
    from surge_tpu.models import counter
    from surge_tpu.config import default_config

    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(30)]
        evs = []
        for i, agg in enumerate(aggs):
            evs.extend(exp.events(agg, 2 + i % 4, decrement_every=3))
        append_events(log, evs)
        overrides = {"surge.replay.donate-refresh": donate,
                     "surge.query.chunk-events": 1024}
        # capacity 8 << 30 aggregates: every round evicts (the spill d2h
        # reads the slab the round just donated)
        plane = make_plane(log, capacity=8, overrides=overrides)
        assert plane._donate_refresh is donate
        cfg = default_config().with_overrides(overrides)
        views = MaterializedViews(counter.make_replay_spec(), config=cfg)
        plane.attach_views(views)
        plane.register_view(ViewDef(
            name="totals",
            query=ScanQuery(aggregates=(Aggregate("count"),
                                        Aggregate("sum", "increment_by")))))
        await plane.start()
        try:
            for rnd in range(3):
                evs = []
                for i, agg in enumerate(aggs):
                    if (i + rnd) % 2 == 0:
                        evs.extend(exp.events(agg, 2, decrement_every=2))
                append_events(log, evs)
                await wait_caught_up(plane)
                # read paths interleaved with donating rounds: batched
                # gather + the project alias, both must see the live slab
                got = await plane.read_many(aggs)
                assert got == {a: exp.states[a] for a in aggs}
                proj = await plane.project(aggs[:5])
                assert proj == {a: exp.states[a] for a in aggs[:5]}
            assert plane.stats["evictions"] > 0
            golden = cold_restore_bytes(log)
            for agg in aggs:
                hit, data = await plane.read_bytes(agg)
                assert hit and data == golden[agg], agg
            # the view fold rode the same donated rounds
            snap = views.snapshot("totals")
            assert snap["rows"], snap
        finally:
            await plane.stop()

    asyncio.run(scenario())


# -- the waste reduction itself -------------------------------------------------------


def test_steady_ragged_waste_drops_3x_bucketed():
    """The acceptance number on the steady-ragged shape (10 lanes, short
    tails): the bucketed arm's padding-waste ratio is ≥ 3x below the dense
    rectangle's on the identical workload."""
    async def one_round(dispatch):
        log = make_log()
        exp = Expected()
        led = ReplayLedger(name="engine:t")
        plane = make_plane(log, ledger=led, overrides={
            "surge.replay.resident.refresh-dispatch": dispatch})
        plane._ensure_device_state()
        plane.seed_from_log()
        evs = []
        for i in range(10):
            evs.extend(exp.events(f"agg-{i}", 5))
        append_events(log, evs)
        assert await plane._refresh_once()
        s = led.summary()
        assert s["events"] == 50 and s["occupied_slots"] == 50
        await plane.stop()
        return s["waste_ratio"]

    async def scenario():
        dense = await one_round("dense")
        bucketed = await one_round("bucketed")
        assert dense / bucketed >= 3.0, (dense, bucketed)
        assert bucketed < 3.0, bucketed

    asyncio.run(scenario())


# -- CLI rendering --------------------------------------------------------------------


def test_chaos_renders_bucket_anatomy():
    """`chaos.py replay-ledger`'s stderr bucket table off a dumped envelope:
    per-bucket fill/waste lines for rounds that carried anatomy, empty for
    dense/pre-bucketing dumps (stdout stays the parseable JSON envelope)."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import chaos

    led = ReplayLedger(name="engine:t")
    led.record_round(
        events=50, lanes=10, windows=2, dispatched=128, occupied=50,
        batch=8, width=8, feed_us=10.0, encode_us=5.0, dispatch_us=100.0,
        bucket_table=12,
        buckets=[{"width": 4, "lanes_b": 8, "lanes": 6, "windows": 1,
                  "dispatched": 32, "occupied": 20},
                 {"width": 8, "lanes_b": 8, "lanes": 4, "windows": 1,
                  "dispatched": 96, "occupied": 30}])
    text = chaos._render_bucket_anatomy(led.dump())
    assert "bucket_table=12" in text
    assert "w4×8: lanes 6/8" in text
    assert "w8×8: lanes 4/8" in text
    # a dense dump renders nothing
    dense = ReplayLedger(name="engine:t")
    dense.record_round(events=50, lanes=10, windows=1, dispatched=512,
                       occupied=50, batch=64, width=8, feed_us=1.0,
                       encode_us=1.0, dispatch_us=1.0)
    assert chaos._render_bucket_anatomy(dense.dump()) == ""
