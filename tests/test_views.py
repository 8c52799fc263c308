"""Incremental materialized views + changefeeds (surge_tpu.replay.views).

The streaming half of the KTable analogy: views registered against the
resident plane's refresh feed fold every committed round into per-partition
grouped-aggregate partials, and subscribers ride per-round delta changefeeds.

The load-bearing test is the golden byte-equality one: after N incremental
fold rounds — across evictions, re-admissions, a partition rebalance and a
mid-round failure re-anchor — every view must be byte-equal to a from-scratch
``scan_chunks`` over the log at the same fold watermark, on cpu AND mesh8.
The changefeed's contract rides the same bar: resume-from-watermark delivers
exactly the missed deltas (no gap, no dup), a gap beyond the delta ring (or a
failover to a fresh node) is answered with ONE reconciling snapshot, and
applying a subscriber's entries in order reconstructs the polled snapshot."""

import asyncio
import random

import numpy as np
import pytest

from surge_tpu.codec.tensor import encode_events_columnar
from surge_tpu.config import default_config
from surge_tpu.log import InMemoryLog, LogRecord, TopicSpec
from surge_tpu.metrics import Metrics, engine_metrics
from surge_tpu.models import counter
from surge_tpu.replay.ledger import ReplayLedger
from surge_tpu.replay.query import Aggregate, Predicate, QueryEngine, ScanQuery
from surge_tpu.replay.resident_state import ResidentStatePlane
from surge_tpu.replay.views import MaterializedViews, ViewDef, select_top_k
from surge_tpu.serialization import SerializedMessage

EVT = counter.event_formatting()
STATE = counter.state_formatting()
TOPIC = "counter-events"
NPART = 4
SPEC = counter.make_replay_spec()

#: every aggregate op at once, keyed by aggregate id
TOTALS_Q = ScanQuery(aggregates=(Aggregate("count"),
                                 Aggregate("sum", "increment_by"),
                                 Aggregate("min", "increment_by"),
                                 Aggregate("max", "sequence_number")))
#: group-by-event-column rollup with typed pushdown + an OR group (CNF)
GROUP_Q = ScanQuery(
    aggregates=(Aggregate("count"), Aggregate("sum", "sequence_number")),
    event_types=("CountIncremented", "CountDecremented"),
    or_groups=((Predicate("increment_by", "==", 1),
                Predicate("increment_by", ">=", 3)),),
    group_by="increment_by")
#: plain count+sum view for the changefeed tests
SIMPLE_Q = ScanQuery(aggregates=(Aggregate("count"),
                                 Aggregate("sum", "increment_by")))


def part_of(agg: str) -> int:
    return int(agg.rsplit("-", 1)[1]) % NPART


def append_events(log, events):
    prod = log.transactional_producer("seed")
    prod.begin()
    for ev in events:
        msg = EVT.write_event(ev)
        prod.send(LogRecord(topic=TOPIC, partition=part_of(ev.aggregate_id),
                            key=msg.key, value=msg.value))
    prod.commit()


def make_log():
    log = InMemoryLog()
    log.create_topic(TopicSpec(TOPIC, NPART))
    return log


def make_plane_with_views(log, *, capacity=64, mesh=None, overrides=None,
                          metrics=None, flight=None, ledger=None):
    cfg = default_config().with_overrides({
        "surge.replay.resident.capacity": capacity,
        "surge.replay.resident.max-lag-records": 4096,
        "surge.replay.resident.refresh-interval-ms": 10,
        "surge.replay.batch-size": 16,
        "surge.replay.time-chunk": 8,
        "surge.query.chunk-events": 1024,
        **(overrides or {}),
    })
    plane = ResidentStatePlane(
        log, TOPIC, SPEC, config=cfg,
        deserialize_event=lambda raw: EVT.read_event(
            SerializedMessage(key="", value=raw)),
        serialize_state=lambda a, s: STATE.write_state(s).value,
        mesh=mesh, metrics=metrics, flight=flight)
    views = MaterializedViews(SPEC, config=cfg, mesh=mesh, metrics=metrics,
                              ledger=ledger, flight=flight)
    plane.attach_views(views)
    return plane, views


class EventGen:
    """Deterministic event storms over a fixed aggregate population."""

    def __init__(self, seed=0, naggs=30):
        self.rng = random.Random(seed)
        self.aggs = [f"agg-{i}" for i in range(naggs)]
        self.seqs = {a: 0 for a in self.aggs}

    def burst(self, agg, n):
        out = []
        for _ in range(n):
            self.seqs[agg] += 1
            kind = self.rng.randrange(3)
            if kind == 0:
                out.append(counter.CountIncremented(
                    agg, self.rng.randrange(1, 4), self.seqs[agg]))
            elif kind == 1:
                out.append(counter.CountDecremented(
                    agg, self.rng.randrange(1, 4), self.seqs[agg]))
            else:
                out.append(counter.NoOpEvent(agg, self.seqs[agg]))
        return out

    def storm(self, rnd, every=3, n=2):
        evs = []
        for i, a in enumerate(self.aggs):
            if (i + rnd) % every == 0:
                evs.extend(self.burst(a, n + rnd % 3))
        return evs


async def wait_caught_up(plane, timeout=20.0):
    deadline = asyncio.get_running_loop().time() + timeout
    while plane.lag_records() > 0:
        assert asyncio.get_running_loop().time() < deadline, \
            f"refresh loop never caught up (lag {plane.lag_records()})"
        await asyncio.sleep(0.02)


async def wait_views_current(log, plane, views, names, timeout=20.0):
    """Wait until every named view's fold watermarks reach the log's end
    offsets (the plane's watermark advance and the views' leg of the round
    are separate steps — lag 0 alone doesn't mean the last fold landed)."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while True:
        await wait_caught_up(plane, timeout)
        ends = {p: log.end_offset(TOPIC, p) for p in range(NPART)}
        by_name = {v["view"]: v for v in views.summary()}
        ok = True
        for name in names:
            v = by_name[name]
            if v["error"]:
                continue
            wms = {int(p): w for p, w in v["watermarks"].items()}
            if not v["active"] or any(e and wms.get(p, 0) < e
                                      for p, e in ends.items()):
                ok = False
        if ok:
            return
        assert loop.time() < deadline, \
            f"views never caught up: {by_name} vs {ends}"
        await asyncio.sleep(0.02)


def scan_at(log, watermarks, query, *, mesh=None):
    """From-scratch reference: one batch ``scan_chunks`` over every event the
    log holds below the view's fold watermarks, served in the same canonical
    sorted-key order."""
    logs = {}
    for p_str, wm in watermarks.items():
        for rec in log.read(TOPIC, int(p_str), 0):
            if rec.offset >= wm:
                break
            ev = EVT.read_event(SerializedMessage(key="", value=rec.value))
            logs.setdefault(rec.key, []).append(ev)
    if not logs:
        return [], {}
    colev = encode_events_columnar(SPEC.registry, list(logs.values()))
    colev.aggregate_ids = list(logs)
    eng = QueryEngine(SPEC, config=default_config().with_overrides(
        {"surge.query.chunk-events": 1024}), mesh=mesh)
    res = eng.scan_chunks([colev], query)
    order = sorted(range(res.num_aggregates),
                   key=lambda j: res.aggregate_ids[j])
    return ([res.aggregate_ids[j] for j in order],
            {n: np.asarray(res.columns[n])[order] for n in res.columns})


def assert_view_golden(views, name, query, log, *, mesh=None):
    """The golden bar: snapshot byte-equal to the from-scratch scan at the
    same watermark."""
    snap = views.snapshot(name)
    assert "error" not in snap, snap
    keys, cols = scan_at(log, snap["watermarks"], query, mesh=mesh)
    assert snap["keys"] == keys, name
    assert set(snap["columns"]) == set(cols), name
    for n in cols:
        assert np.array_equal(snap["columns"][n], cols[n]), (name, n)
    return snap


def apply_entry(state, entry):
    """A subscriber's state machine: reset replaces, deltas upsert by key."""
    if entry.get("reset"):
        state.clear()
    for row in entry["rows"]:
        state[row["key"]] = row


# -- the golden acceptance test --------------------------------------------------------


def test_view_golden_across_evict_readmit_and_rebalance():
    """Views registered before the seed must stay byte-equal to a
    from-scratch scan through N fold rounds that churn the slab (capacity 8
    over 30 aggregates) and a revoke/re-grant rebalance — both the
    aggregate-id-keyed and the group-by/OR-group view."""
    async def scenario():
        log = make_log()
        gen = EventGen(seed=5)
        append_events(log, [e for a in gen.aggs for e in gen.burst(a, 3)])
        registry = Metrics()
        ledger = ReplayLedger(name="engine:t")
        plane, views = make_plane_with_views(
            log, capacity=8, metrics=engine_metrics(registry), ledger=ledger)
        plane.register_view(ViewDef(name="totals", query=TOTALS_Q))
        plane.register_view(ViewDef(name="by-delta", query=GROUP_Q))
        await plane.start()
        try:
            names = ["totals", "by-delta"]
            for rnd in range(4):
                append_events(log, gen.storm(rnd))
                await wait_views_current(log, plane, views, names)
                if rnd == 1:
                    # indexer-style rebalance mid-tail: the revoke drops the
                    # views' partition-1 partials, the re-grant refolds them
                    plane.set_partitions([0, 2, 3])
                    plane.set_partitions([0, 1, 2, 3])
                    await wait_views_current(log, plane, views, names)
            assert plane.stats["evictions"] > 0, \
                "capacity 8 with 30 aggregates must have churned the slab"
            snap = assert_view_golden(views, "totals", TOTALS_Q, log)
            assert snap["keys"] == sorted(gen.aggs)
            assert_view_golden(views, "by-delta", GROUP_Q, log)
            # observability joined the round: ledger view-rounds + metrics
            assert ledger.totals["view_rounds"] > 0
            assert any(e["type"] == "view-round" for e in ledger.events())
            vals = registry.get_metrics()
            assert vals["surge.replay.views.delta-rows"] > 0
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_view_golden_mesh_sharded(mesh8):
    """The same golden bar with the plane AND the views' scans sharded over
    the 8-device mesh — view folds ride plane_mesh exactly like batch
    scans, and must equal the single-device from-scratch reference."""
    async def scenario():
        log = make_log()
        gen = EventGen(seed=9, naggs=20)
        append_events(log, [e for a in gen.aggs for e in gen.burst(a, 4)])
        plane, views = make_plane_with_views(log, capacity=16, mesh=mesh8)
        plane.register_view(ViewDef(name="totals", query=TOTALS_Q))
        plane.register_view(ViewDef(name="by-delta", query=GROUP_Q))
        await plane.start()
        try:
            names = ["totals", "by-delta"]
            for rnd in range(2):
                append_events(log, gen.storm(rnd))
                await wait_views_current(log, plane, views, names)
            plane.set_partitions([0, 1, 3])  # rebalance leg on mesh too
            plane.set_partitions([0, 1, 2, 3])
            await wait_views_current(log, plane, views, names)
            assert_view_golden(views, "totals", TOTALS_Q, log)
            assert_view_golden(views, "by-delta", GROUP_Q, log)
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_view_golden_after_mid_round_failure():
    """A refresh round dying AFTER some fold groups committed re-anchors the
    polled partitions (purge + refold from 0) — the views' partials for
    those partitions must drop with the slab and refold to byte-equality,
    never double-folding an event; subscribers see a reset entry."""
    async def scenario():
        log = make_log()
        gen = EventGen(seed=11, naggs=24)
        append_events(log, [e for a in gen.aggs for e in gen.burst(a, 3)])
        plane, views = make_plane_with_views(log, capacity=8)
        plane.register_view(ViewDef(name="totals", query=TOTALS_Q))
        await plane.start()
        try:
            await wait_views_current(log, plane, views, ["totals"])
            sub = views.subscribe("totals")
            real = plane._fold_group
            calls = {"n": 0}

            async def dying(group, logs, parts, gens):
                calls["n"] += 1
                if calls["n"] == 2:  # the round's SECOND group: one committed
                    raise RuntimeError("injected mid-round fold failure")
                return await real(group, logs, parts, gens)

            plane._fold_group = dying
            append_events(log, [e for a in gen.aggs
                                for e in gen.burst(a, 2)])
            deadline = asyncio.get_running_loop().time() + 10.0
            while calls["n"] < 2:
                assert asyncio.get_running_loop().time() < deadline, \
                    "injected failure never fired"
                await asyncio.sleep(0.02)
            plane._fold_group = real
            await wait_views_current(log, plane, views, ["totals"])
            assert_view_golden(views, "totals", TOTALS_Q, log)
            # the re-anchor reached the changefeed as reset entries (a fold
            # publishes through call_soon_threadsafe: the entries of a round
            # that ended since this task last yielded are not queued yet)
            await asyncio.sleep(0.05)
            entries = []
            while not sub.queue.empty():
                entries.append(sub.queue.get_nowait())
            assert entries and entries[0]["reset"] is True  # subscribe snap
            assert any(e.get("reset") for e in entries[1:]), \
                "re-anchor must publish a reconciling reset"
            # applying the whole feed reconstructs the polled snapshot
            state = {}
            for e in entries:
                apply_entry(state, e)
            snap = views.snapshot("totals")
            assert state == {r["key"]: r for r in snap["rows"]}
            views.unsubscribe(sub)
        finally:
            await plane.stop()

    asyncio.run(scenario())


# -- changefeed: resume semantics ------------------------------------------------------


def test_changefeed_resume_exact_missed_deltas_no_gap_no_dup():
    """A subscriber that disconnects mid-storm and resumes from its fold
    watermark receives exactly the missed deltas — versions strictly
    ascending past its watermark, no reset — and applying its whole entry
    stream reconstructs the same final view as polling."""
    async def scenario():
        log = make_log()
        gen = EventGen(seed=21, naggs=16)
        append_events(log, [e for a in gen.aggs for e in gen.burst(a, 2)])
        plane, views = make_plane_with_views(log)
        plane.register_view(ViewDef(name="v", query=SIMPLE_Q))
        await plane.start()
        try:
            await wait_views_current(log, plane, views, ["v"])
            sub = views.subscribe("v")
            first = await asyncio.wait_for(sub.get(), 5)
            assert first["reset"] is True
            state = {}
            apply_entry(state, first)
            applied = first["version"]
            # consume part of the storm live...
            for rnd in range(3):
                append_events(log, gen.storm(rnd, every=2))
                await wait_views_current(log, plane, views, ["v"])
            while not sub.queue.empty():
                e = sub.queue.get_nowait()
                assert e["version"] > applied, "dup delta"
                apply_entry(state, e)
                applied = e["version"]
            views.unsubscribe(sub)  # ...disconnect mid-storm
            for rnd in range(3, 6):  # the storm keeps going without us
                append_events(log, gen.storm(rnd, every=2))
                await wait_views_current(log, plane, views, ["v"])
            # resume from the fold watermark: exactly the missed deltas
            sub2 = views.subscribe("v", from_version=applied)
            missed = []
            while not sub2.queue.empty():
                missed.append(sub2.queue.get_nowait())
            assert missed, "disconnected rounds must have produced deltas"
            versions = [e["version"] for e in missed]
            assert versions == sorted(set(versions)), "gap/dup in replay"
            assert all(v > applied for v in versions)
            assert not any(e.get("reset") for e in missed), \
                "an in-ring resume must replay deltas, not reconcile"
            for e in missed:
                apply_entry(state, e)
            snap = views.snapshot("v")
            assert versions[-1] == snap["version"]
            assert state == {r["key"]: r for r in snap["rows"]}, \
                "delta stream must reconstruct the polled view"
            assert_view_golden(views, "v", SIMPLE_Q, log)
            views.unsubscribe(sub2)
            assert views.subscriber_count() == 0
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_changefeed_resume_beyond_ring_reconciles_with_snapshot():
    """A resume watermark older than the delta ring cannot be replayed
    exactly — the subscriber gets ONE reconciling snapshot (reset) equal to
    the polled view, and the gap width lands on the resume-gap gauge."""
    async def scenario():
        log = make_log()
        gen = EventGen(seed=31, naggs=12)
        append_events(log, [e for a in gen.aggs for e in gen.burst(a, 2)])
        registry = Metrics()
        plane, views = make_plane_with_views(
            log, metrics=engine_metrics(registry),
            overrides={"surge.replay.views.changefeed-rounds": 2})
        plane.register_view(ViewDef(name="v", query=SIMPLE_Q))
        await plane.start()
        try:
            await wait_views_current(log, plane, views, ["v"])
            for rnd in range(5):  # 5 change rounds >> ring capacity 2
                append_events(log, gen.storm(rnd, every=2))
                await wait_views_current(log, plane, views, ["v"])
            snap = views.snapshot("v")
            assert snap["version"] > 3
            sub = views.subscribe("v", from_version=1)  # long gone
            entry = sub.queue.get_nowait()
            assert entry["reset"] is True
            state = {}
            apply_entry(state, entry)
            assert state == {r["key"]: r for r in snap["rows"]}
            vals = registry.get_metrics()
            assert vals["surge.replay.views.resume-gap-rounds"] >= 1
            views.unsubscribe(sub)
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_changefeed_resume_after_kill_failover():
    """Kill-failover: the node dies, a fresh node (new plane + new views
    over the same log — the failed-over owner) seeds from scratch, and an
    old subscriber resumes with a watermark from the PREVIOUS incarnation.
    The new node's version counter restarted, so the resume is answered
    with a reconciling snapshot — byte-equal to the from-scratch scan."""
    async def scenario():
        log = make_log()
        gen = EventGen(seed=41, naggs=12)
        append_events(log, [e for a in gen.aggs for e in gen.burst(a, 2)])
        plane, views = make_plane_with_views(log)
        plane.register_view(ViewDef(name="v", query=SIMPLE_Q))
        await plane.start()
        old_version = 0
        try:
            for rnd in range(4):
                append_events(log, gen.storm(rnd, every=2))
                await wait_views_current(log, plane, views, ["v"])
            old_version = views.snapshot("v")["version"]
            assert old_version > 1
        finally:
            await plane.stop()  # the kill
        # failover: the replacement owner seeds the same log from 0
        registry = Metrics()
        plane2, views2 = make_plane_with_views(
            log, metrics=engine_metrics(registry))
        plane2.register_view(ViewDef(name="v", query=SIMPLE_Q))
        await plane2.start()
        try:
            await wait_views_current(log, plane2, views2, ["v"])
            assert views2.snapshot("v")["version"] < old_version
            sub = views2.subscribe("v", from_version=old_version)
            entry = sub.queue.get_nowait()
            assert entry["reset"] is True, \
                "a from-the-future watermark must reconcile, not replay"
            state = {}
            apply_entry(state, entry)
            snap = assert_view_golden(views2, "v", SIMPLE_Q, log)
            assert state == {r["key"]: r for r in snap["rows"]}
            assert registry.get_metrics()[
                "surge.replay.views.resume-gap-rounds"] >= 1
            # post-failover the feed is live again: new rounds reach the
            # resumed subscriber as ordinary deltas
            append_events(log, gen.storm(9, every=2))
            await wait_views_current(log, plane2, views2, ["v"])
            delta = await asyncio.wait_for(sub.get(), 5)
            assert delta["reset"] is False
            apply_entry(state, delta)
            snap = views2.snapshot("v")
            assert state == {r["key"]: r for r in snap["rows"]}
            views2.unsubscribe(sub)
        finally:
            await plane2.stop()

    asyncio.run(scenario())


# -- registration lifecycle ------------------------------------------------------------


def test_register_while_running_backfills_committed_prefix():
    """A view registered on a live, seeded plane parks pending and is
    backfilled between refresh rounds — then keeps folding new rounds, and
    ends byte-equal to the from-scratch scan."""
    async def scenario():
        log = make_log()
        gen = EventGen(seed=51, naggs=16)
        append_events(log, [e for a in gen.aggs for e in gen.burst(a, 3)])
        plane, views = make_plane_with_views(log)
        await plane.start()
        try:
            await wait_caught_up(plane)
            append_events(log, gen.storm(0, every=2))
            await wait_caught_up(plane)
            plane.register_view(ViewDef(name="late", query=TOTALS_Q))
            assert views.has_pending
            await wait_views_current(log, plane, views, ["late"])
            summary = views.summary()[0]
            assert summary["active"] and summary["version"] >= 1
            assert_view_golden(views, "late", TOTALS_Q, log)
            # and it now rides normal rounds like any seed-registered view
            append_events(log, gen.storm(1, every=2))
            await wait_views_current(log, plane, views, ["late"])
            assert_view_golden(views, "late", TOTALS_Q, log)
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_top_k_serving_is_exact():
    """top_k limits what the view SERVES (descending rank, ties by
    ascending key) while the full group set stays materialized — the cut
    must equal the same cut of the from-scratch reference."""
    async def scenario():
        log = make_log()
        gen = EventGen(seed=61, naggs=20)
        append_events(log, [e for a in gen.aggs for e in gen.burst(a, 4)])
        plane, views = make_plane_with_views(log)
        plane.register_view(ViewDef(name="top", query=SIMPLE_Q, top_k=5,
                                    top_k_by="sum_increment_by"))
        await plane.start()
        try:
            append_events(log, gen.storm(0, every=2))
            await wait_views_current(log, plane, views, ["top"])
            snap = views.snapshot("top")
            assert len(snap["keys"]) == 5
            keys, cols = scan_at(log, snap["watermarks"], SIMPLE_Q)
            want_keys, want_cols = select_top_k(keys, cols, 5,
                                                "sum_increment_by")
            assert snap["keys"] == want_keys
            for n in want_cols:
                assert np.array_equal(snap["columns"][n], want_cols[n]), n
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_group_cap_degrades_one_view_not_the_plane():
    """A view whose group set overflows surge.replay.views.max-groups
    degrades to an error state — served as such, error entry on its feed —
    while sibling views and the plane itself keep folding."""
    async def scenario():
        log = make_log()
        gen = EventGen(seed=71, naggs=30)
        append_events(log, [e for a in gen.aggs for e in gen.burst(a, 2)])
        plane, views = make_plane_with_views(
            log, overrides={"surge.replay.views.max-groups": 4})
        plane.register_view(ViewDef(name="wide", query=TOTALS_Q))  # 30 keys
        plane.register_view(ViewDef(name="narrow", query=GROUP_Q))  # <= 4
        await plane.start()
        try:
            await wait_views_current(log, plane, views, ["narrow"])
            snap = views.snapshot("wide")
            assert "group cap exceeded" in snap["error"]
            by_name = {v["view"]: v for v in views.summary()}
            assert by_name["wide"]["error"] and not by_name["narrow"]["error"]
            assert_view_golden(views, "narrow", GROUP_Q, log)
            # the plane's own slab is untouched by the view failure
            await wait_caught_up(plane)
            assert plane.lag_records() == 0
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_registration_validation_and_unregister():
    async def scenario():
        log = make_log()
        plane, views = make_plane_with_views(log)
        with pytest.raises(ValueError):
            plane.register_view(ViewDef(name="bad", query=ScanQuery(
                aggregates=(Aggregate("sum", "no_such_column"),))))
        with pytest.raises(ValueError):
            plane.register_view(ViewDef(name="bad", query=ScanQuery(
                aggregates=(Aggregate("count"),),
                event_types=("NoSuchEvent",))))
        with pytest.raises(ValueError):
            ViewDef(name="", query=SIMPLE_Q)
        with pytest.raises(ValueError):
            ViewDef(name="v", query=SIMPLE_Q, top_k=0)
        with pytest.raises(ValueError):
            ViewDef(name="v", query=SIMPLE_Q, top_k=3, top_k_by="nope")
        vd = ViewDef(name="v", query=SIMPLE_Q, top_k=3)
        assert ViewDef.from_json(vd.as_json()) == vd
        assert vd.rank_by == "sum_increment_by"  # first non-count aggregate
        plane.register_view(vd)
        with pytest.raises(ValueError):
            plane.register_view(vd)  # duplicate name
        await plane.start()
        try:
            sub = views.subscribe("v")
            assert views.unregister("v") and not views.unregister("v")
            # the subscriber got a terminal entry; the stream is over
            await asyncio.wait_for(sub.get(), 5)  # initial snapshot
            closed = await asyncio.wait_for(sub.get(), 5)
            assert closed.get("closed") == "unregistered"
            with pytest.raises(KeyError):
                views.snapshot("v")
            with pytest.raises(KeyError):
                views.subscribe("v")
        finally:
            await plane.stop()

    asyncio.run(scenario())


# -- engine + RPC end to end -----------------------------------------------------------


def test_engine_view_rpcs_end_to_end(tmp_path):
    """The whole stack: commands through a real engine, views folding off
    its resident plane, the admin QueryView/SubscribeView RPCs, and the
    multilanguage sidecar's QueryStates/QueryView/SubscribeView twins."""
    import grpc

    from surge_tpu import SurgeCommandBusinessLogic, create_engine
    from surge_tpu.admin import AdminClient, AdminServer
    from surge_tpu.multilanguage.gateway import MultilanguageGatewayServer
    from surge_tpu.multilanguage.sdk import SerDeser, SurgeClient

    cfg = default_config().with_overrides({
        "surge.producer.flush-interval-ms": 5,
        "surge.state-store.commit-interval-ms": 20,
        "surge.engine.num-partitions": 2,
        "surge.replay.resident.enabled": True,
        "surge.replay.resident.refresh-interval-ms": 10,
        "surge.replay.segment-path": str(tmp_path / "counter.scol"),
    })

    async def scenario():
        engine = create_engine(SurgeCommandBusinessLogic(
            aggregate_name="counter", model=counter.CounterModel(),
            state_format=counter.state_formatting(),
            event_format=counter.event_formatting()), config=cfg)
        engine.register_view({"name": "totals", "query": SIMPLE_Q.as_json()})
        await engine.start()
        admin = AdminServer(engine)
        gateway = MultilanguageGatewayServer(engine)
        channel = gw_channel = None
        try:
            for i in range(6):
                ref = engine.aggregate_for(f"q-{i}")
                for _ in range(i + 1):
                    await ref.send_command(counter.Increment(f"q-{i}"))
            port = await admin.start()
            channel = grpc.aio.insecure_channel(f"127.0.0.1:{port}")
            client = AdminClient(channel)

            async def poll_until(fetch, pred, timeout=15.0):
                deadline = asyncio.get_running_loop().time() + timeout
                while True:
                    payload = await fetch()
                    if pred(payload):
                        return payload
                    assert asyncio.get_running_loop().time() < deadline, \
                        f"never satisfied: {payload}"
                    await asyncio.sleep(0.05)

            snap = await poll_until(
                lambda: client.query_view("totals"),
                lambda p: len(p.get("rows", ())) == 6
                and sum(r["count"] for r in p["rows"]) == 21)
            assert snap["keys"] == [f"q-{i}" for i in range(6)]
            assert "columns" not in snap  # numpy stays in-process
            summary = await client.query_view()
            assert [v["view"] for v in summary["views"]] == ["totals"]
            assert summary["views"][0]["active"]
            with pytest.raises(RuntimeError):
                await client.query_view("no-such-view")

            # the admin changefeed: snapshot first, then a live delta
            feed = client.subscribe_view("totals")
            first = await asyncio.wait_for(feed.__anext__(), 10)
            assert first["reset"] is True
            state = {}
            apply_entry(state, first)
            await engine.aggregate_for("q-0").send_command(
                counter.Increment("q-0"))
            entry = await asyncio.wait_for(feed.__anext__(), 10)
            while not any(r["key"] == "q-0" for r in entry["rows"]):
                apply_entry(state, entry)
                entry = await asyncio.wait_for(feed.__anext__(), 10)
            apply_entry(state, entry)
            assert state["q-0"]["count"] == 2

            # register-while-running through the engine surface
            engine.register_view(ViewDef(name="late", query=TOTALS_Q))
            await poll_until(
                lambda: client.query_view(),
                lambda p: {v["view"]: v["active"] for v in p["views"]}
                == {"late": True, "totals": True})

            # the sidecar twins
            gw_port = await gateway.start()
            gw_channel = grpc.aio.insecure_channel(f"127.0.0.1:{gw_port}")
            ident = SerDeser(*([lambda b: b] * 6))
            app = SurgeClient(gw_channel, ident)
            payload = await app.query_view("totals")
            assert sum(r["count"] for r in payload["rows"]) == 22
            assert [v["view"] for v in (await app.query_view())["views"]] \
                == ["late", "totals"]
            with pytest.raises(RuntimeError):
                await app.query_view("no-such-view")
            sq = {"select": ["count"], "predicates": [
                {"column": "count", "op": ">=", "value": 4}]}
            rows = (await app.query_states(sq))["rows"]
            assert sorted(r["aggregate_id"] for r in rows) \
                == ["q-3", "q-4", "q-5"]
            # resume from the admin feed's snapshot version: the sidecar
            # replays the SAME deltas the admin feed delivered live, so
            # starting from that snapshot it reconstructs the same state
            gw_feed = app.subscribe_view("totals",
                                         from_version=first["version"])
            gw_state = {}
            apply_entry(gw_state, first)
            async for e in gw_feed:
                apply_entry(gw_state, e)
                if gw_state.get("q-0", {}).get("count") == 2:
                    break
            assert gw_state == state
        finally:
            if gw_channel is not None:
                await gw_channel.close()
            await gateway.stop()
            if channel is not None:
                await channel.close()
            await admin.stop()
            await engine.stop()

    asyncio.run(scenario())
