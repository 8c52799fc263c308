"""Device-resident materialized state plane (surge_tpu.replay.resident_state).

The on-chip KTable: cold-start seed that never leaves the device, the standing
incremental refresh loop, capacity-bounded admission/eviction with exact-fold-
point spill, the batched-gather read lane with its staleness fallback, and the
rebalance contract (revoke purges, re-grant refolds — never double-folds).

The load-bearing test is the golden byte-identity one: after N incremental
refresh rounds — across evictions, re-admissions and an indexer-style
partition rebalance — every tracked aggregate's serialized state must be
byte-identical to a full cold-start replay over the same log (cpu backend,
fetch-barriered pulls)."""

import asyncio
import threading

import numpy as np
import pytest

from surge_tpu.config import default_config
from surge_tpu.engine.model import fold_events
from surge_tpu.log import InMemoryLog, LogRecord, TopicSpec
from surge_tpu.metrics import Metrics, engine_metrics
from surge_tpu.models import counter
from surge_tpu.replay.resident_state import ResidentStatePlane
from surge_tpu.serialization import SerializedMessage
from surge_tpu.store import InMemoryKeyValueStore, StateStoreIndexer
from surge_tpu.store.restore import restore_from_events

EVT = counter.event_formatting()
STATE = counter.state_formatting()
TOPIC = "counter-events"
NPART = 4


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


def make_plane(log, *, capacity=64, max_lag=4096, metrics=None, profiler=None,
               partitions=None, overrides=None, flight=None):
    cfg = default_config().with_overrides({
        "surge.replay.resident.capacity": capacity,
        "surge.replay.resident.max-lag-records": max_lag,
        "surge.replay.resident.refresh-interval-ms": 10,
        "surge.replay.batch-size": 16,
        "surge.replay.time-chunk": 8,
        **(overrides or {}),
    })
    return ResidentStatePlane(
        log, TOPIC, counter.make_replay_spec(), config=cfg,
        partitions=partitions,
        deserialize_event=lambda raw: EVT.read_event(
            SerializedMessage(key="", value=raw)),
        serialize_state=lambda a, s: STATE.write_state(s).value,
        metrics=metrics, profiler=profiler, flight=flight)


class Expected:
    """Scalar-fold oracle mirroring every event appended to the log."""

    def __init__(self):
        self.model = counter.CounterModel()
        self.states = {}
        self.seqs = {}

    def events(self, agg: str, n: int, decrement_every: int = 0):
        out = []
        for k in range(n):
            seq = self.seqs.get(agg, 0) + 1
            self.seqs[agg] = seq
            if decrement_every and k % decrement_every == decrement_every - 1:
                ev = counter.CountDecremented(agg, 1, seq)
            else:
                ev = counter.CountIncremented(agg, 1, seq)
            self.states[agg] = fold_events(self.model, self.states.get(agg), [ev])
            out.append(ev)
        return out


async def wait_caught_up(plane, timeout=20.0):
    deadline = asyncio.get_running_loop().time() + timeout
    while plane.lag_records() > 0:
        assert asyncio.get_running_loop().time() < deadline, \
            f"refresh loop never caught up (lag {plane.lag_records()})"
        await asyncio.sleep(0.02)


def cold_restore_bytes(log):
    """Full cold-start replay over the same log (cpu backend) — the golden
    reference the resident slab must match byte for byte."""
    store = InMemoryKeyValueStore()
    restore_from_events(
        log, TOPIC, store,
        deserialize_event=lambda raw: EVT.read_event(
            SerializedMessage(key="", value=raw)),
        serialize_state=lambda a, s: STATE.write_state(s).value,
        model=counter.CounterModel(), replay_spec=counter.make_replay_spec(),
        config=default_config().with_overrides({
            "surge.replay.backend": "cpu"}))
    return dict(store.all_items())


# -- seeding ---------------------------------------------------------------------------


def test_seed_from_log_matches_scalar_fold():
    async def scenario():
        log = make_log()
        exp = Expected()
        evs = []
        for i in range(20):
            evs.extend(exp.events(f"agg-{i}", i + 1, decrement_every=3))
        append_events(log, evs)
        plane = make_plane(log)
        await plane.start()
        try:
            assert plane.occupancy() == 20
            assert plane.snapshot_states() == exp.states
            # anchored at the captured end offsets: nothing left to fold
            assert plane.lag_records() == 0
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_seed_overflow_spills_and_still_serves():
    """Aggregates past capacity are pulled once into the host spill at seed
    time and stay readable (longest logs stay resident)."""
    async def scenario():
        log = make_log()
        exp = Expected()
        evs = []
        for i in range(24):
            evs.extend(exp.events(f"agg-{i}", i + 1))
        append_events(log, evs)
        plane = make_plane(log, capacity=8)
        await plane.start()
        try:
            assert plane.occupancy() == 8
            # longest-log-first admission: the 8 longest logs are resident
            assert plane.resident_ids() == sorted(
                f"agg-{i}" for i in range(16, 24))
            assert plane.snapshot_states() == exp.states
            for agg in ("agg-2", "agg-20"):  # one spilled, one resident
                hit, st = await plane.read_state(agg)
                assert hit and st == exp.states[agg]
        finally:
            await plane.stop()

    asyncio.run(scenario())


# -- the golden acceptance test --------------------------------------------------------


def test_incremental_refresh_golden_byte_identity():
    """N incremental refresh rounds — forcing evictions, re-admissions AND a
    partition revoke/re-grant rebalance mid-tail — must leave every tracked
    aggregate byte-identical to a full cold-start replay over the same log."""
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(30)]
        evs = []
        for i, agg in enumerate(aggs):
            evs.extend(exp.events(agg, 3 + i % 5, decrement_every=4))
        append_events(log, evs)
        # capacity 8 << 30 aggregates: every refresh round churns the slab
        plane = make_plane(log, capacity=8)
        await plane.start()
        try:
            for rnd in range(4):
                evs = []
                # rotate the touched set so rounds admit/evict different rows
                for i, agg in enumerate(aggs):
                    if (i + rnd) % 3 == 0:
                        evs.extend(exp.events(agg, 2 + rnd, decrement_every=3))
                append_events(log, evs)
                await wait_caught_up(plane)
                if rnd == 1:
                    # indexer-style rebalance mid-tail: revoke partition 1,
                    # then re-grant it — the plane must purge, re-anchor at 0
                    # and refold WITHOUT double-folding any event
                    plane.set_partitions([0, 2, 3])
                    assert all(part_of(a) != 1 for a in plane.resident_ids())
                    plane.set_partitions([0, 1, 2, 3])
                    await wait_caught_up(plane)
            assert plane.stats["evictions"] > 0, \
                "capacity 8 with 30 aggregates must have churned the slab"
            golden = cold_restore_bytes(log)
            # the plane read path serializes through the identical chain —
            # every aggregate, resident or spilled, byte for byte
            for agg in aggs:
                hit, data = await plane.read_bytes(agg)
                assert hit, agg
                assert data == golden[agg], agg
            assert plane.snapshot_states() == exp.states
        finally:
            await plane.stop()

    asyncio.run(scenario())


# -- eviction / re-admission -----------------------------------------------------------


def test_eviction_spills_exact_fold_point_and_readmits():
    async def scenario():
        log = make_log()
        exp = Expected()
        first = [f"agg-{i}" for i in range(0, 8)]
        second = [f"agg-{i}" for i in range(8, 16)]
        evs = []
        for agg in first:
            evs.extend(exp.events(agg, 5))
        append_events(log, evs)
        from surge_tpu.observability import FlightRecorder

        flight = FlightRecorder(name="engine:t", role="engine")
        plane = make_plane(log, capacity=8,  # 8 is the plane's floor
                           flight=flight)
        await plane.start()
        try:
            assert plane.resident_ids() == sorted(first)
            # a round of brand-new aggregates evicts the old set to spill
            evs = []
            for agg in second:
                evs.extend(exp.events(agg, 5))
            append_events(log, evs)
            await wait_caught_up(plane)
            assert plane.stats["evictions"] == 8
            assert plane.resident_ids() == sorted(second)
            # the seed and the eviction are incident-timeline material
            types = [e["type"] for e in flight.events()]
            assert "resident.seed" in types and "resident.evict" in types
            evict = next(e for e in flight.events()
                         if e["type"] == "resident.evict")
            assert evict["count"] == 8 and evict["spilled"] == 8
            # evicted rows re-admit at their exact fold point on their next
            # event: 5 seeded + 2 incremental = scalar fold of all 7
            evs = []
            for agg in first:
                evs.extend(exp.events(agg, 2, decrement_every=2))
            append_events(log, evs)
            await wait_caught_up(plane)
            assert plane.resident_ids() == sorted(first)
            assert plane.snapshot_states() == exp.states
        finally:
            await plane.stop()

    asyncio.run(scenario())


# -- rebalance + prime handoff ---------------------------------------------------------


def test_rebalance_revoke_purges_regrant_refolds():
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(8)]
        evs = []
        for agg in aggs:
            evs.extend(exp.events(agg, 4))
        append_events(log, evs)
        from surge_tpu.observability import FlightRecorder

        flight = FlightRecorder(name="engine:t", role="engine")
        plane = make_plane(log, flight=flight)
        await plane.start()
        try:
            victim = [a for a in aggs if part_of(a) == 1]
            assert victim
            plane.set_partitions([0, 2, 3])
            reanchor = [e for e in flight.events()
                        if e["type"] == "resident.re-anchor"]
            assert reanchor and reanchor[-1]["revoked"] == [1]
            # a revoked partition's aggregates must never be servable
            for agg in victim:
                hit, _ = await plane.read_state(agg)
                assert not hit, agg
            assert plane.stats["fallbacks"] >= len(victim)
            # re-grant: anchored at 0, the refresh loop refolds the whole
            # partition — exact equality proves nothing double-folded
            plane.set_partitions([0, 1, 2, 3])
            await wait_caught_up(plane)
            assert plane.snapshot_states() == exp.states
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_regrant_racing_inflight_fold_reanchors():
    """A revoke→re-grant pair landing while a fold round is IN FLIGHT (first
    refresh windows compile for 100ms+ — slow rounds are the norm, not the
    exception) must not let that round's commit overwrite the re-grant's
    0-anchor: the round polled at the OLD watermark, so committing its
    watermark would silently skip the whole-partition refold and later
    fresh admissions would fold tail-only states (wrong count, right
    version — version rides the event's own sequence_number)."""
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(8)]
        evs = []
        for agg in aggs:
            evs.extend(exp.events(agg, 4, decrement_every=3))
        append_events(log, evs)
        plane = make_plane(log)
        plane._ensure_device_state()
        plane.seed_from_log()

        # a committed tail: the raced round has something real to fold
        evs = []
        for agg in aggs:
            evs.extend(exp.events(agg, 3))
        append_events(log, evs)

        loop = asyncio.get_running_loop()
        in_flight = asyncio.Event()
        rebalanced = threading.Event()
        orig = plane._encode_pack_group

        def stalled(event_logs):
            # executor side: park the round between its poll and its commit
            loop.call_soon_threadsafe(in_flight.set)
            assert rebalanced.wait(10), "test deadlock"
            return orig(event_logs)

        plane._encode_pack_group = stalled
        round_task = asyncio.ensure_future(plane._refresh_once())
        await in_flight.wait()
        plane._encode_pack_group = orig  # only the in-flight round stalls
        plane.set_partitions([0, 2, 3])      # revoke partition 1...
        plane.set_partitions([0, 1, 2, 3])   # ...and re-grant: anchor at 0
        rebalanced.set()
        assert await round_task is True

        # the raced round's commit must leave the re-grant anchor intact
        # and partition 1's aggregates rolled back, not half-committed
        assert plane._watermarks[1] == 0
        victims = [a for a in aggs if part_of(a) == 1]
        assert victims
        for agg in victims:
            assert agg not in plane._dir and agg not in plane._spill, agg

        await plane.start()  # refresh loop refolds partition 1 from 0
        try:
            await wait_caught_up(plane)
            assert plane.snapshot_states() == exp.states
            golden = cold_restore_bytes(log)
            for agg in aggs:
                hit, data = await plane.read_bytes(agg)
                assert hit, agg
                assert data == golden[agg], agg
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_commit_between_empty_read_and_end_offset_is_folded_not_skipped():
    """A poll reads a partition's tail (empty) and then asks for its end
    offset; records made durable between the two calls are no compaction hole.
    The round must fold them, never move the watermark past them: a skipped
    event is gone from the slab for good while ``lag_records()`` says 0."""

    class CommitsAfterOneEmptyRead:
        """The plane's log; one empty tail read of partition 1 is followed,
        before the plane sees it, by a commit to that partition."""

        def __init__(self, log, commit):
            self._log, self._commit, self.armed = log, commit, False

        def __getattr__(self, name):
            return getattr(self._log, name)

        def read(self, topic, partition, from_offset=0, **kw):
            recs = self._log.read(topic, partition, from_offset, **kw)
            if self.armed and not recs and partition == 1:
                self.armed = False
                self._commit()
            return recs

    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(8)]
        evs = []
        for agg in aggs:
            evs.extend(exp.events(agg, 2))
        append_events(log, evs)
        racing = CommitsAfterOneEmptyRead(
            log, lambda: append_events(log, exp.events("agg-1", 1)))
        plane = make_plane(racing)
        plane._ensure_device_state()
        plane.seed_from_log()
        racing.armed = True
        assert await plane._refresh_once() is True  # folded, not skipped
        assert not racing.armed
        append_events(log, exp.events("agg-1", 1))
        assert await plane._refresh_once() is True
        assert plane.lag_records() == 0
        assert plane.snapshot_states() == exp.states
        hit, st = await plane.read_state("agg-1")
        assert hit and st == exp.states["agg-1"] and st.version == 4
        await plane.stop()

    asyncio.run(scenario())


def test_prime_watermark_handoff_no_double_fold():
    """The StateStoreIndexer.prime analog: after an out-of-band seed covered
    a window, prime() must fast-forward the fold watermarks so the refresh
    loop never re-folds (and never skips) a record."""
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(6)]
        evs = []
        for agg in aggs:
            evs.extend(exp.events(agg, 4))
        append_events(log, evs)
        plane = make_plane(log)
        plane._ensure_device_state()
        plane.seed_from_log()  # anchors watermarks at the captured ends
        anchored = dict(plane._watermarks)
        # priming BACKWARD must be a no-op (max semantics) — otherwise the
        # refresh loop would double-fold the seeded window
        plane.prime({p: 0 for p in range(NPART)})
        assert plane._watermarks == anchored
        # tail past the seed, then start the loop: it folds exactly the tail
        evs = []
        for agg in aggs:
            evs.extend(exp.events(agg, 3, decrement_every=2))
        append_events(log, evs)
        await plane.start()
        try:
            await wait_caught_up(plane)
            assert plane.snapshot_states() == exp.states
            # forward prime skips records an out-of-band seed already covers:
            # events applied to the oracle but primed OVER never fold twice
            ghost = []
            for agg in aggs[:2]:
                ghost.extend(exp.events(agg, 1))
            before = {a: plane.snapshot_states()[a] for a in aggs[:2]}
            plane.prime({p: log.end_offset(TOPIC, p) + 1 for p in range(NPART)})
            append_events(log, ghost)
            await asyncio.sleep(0.15)
            snap = plane.snapshot_states()
            for agg in aggs[:2]:
                assert snap[agg] == before[agg], \
                    "primed-over records must not fold"
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_indexer_rebalance_mid_tail_keeps_store_consistent():
    """StateStoreIndexer.set_partitions mid-tail (the assignment the plane
    follows): a revoke keeps already-indexed keys servable, a re-grant resumes
    from the kept watermark — no record is applied twice or skipped."""
    async def scenario():
        log = InMemoryLog()
        log.create_topic(TopicSpec("state", NPART, compacted=True))
        cfg = default_config().with_overrides(
            {"surge.state-store.commit-interval-ms": 10})
        idx = StateStoreIndexer(log, "state", config=cfg)

        def put(agg, value):
            prod = log.transactional_producer("t")
            prod.begin()
            prod.send(LogRecord(topic="state", partition=part_of(agg),
                                key=agg, value=value))
            prod.commit()

        for i in range(8):
            put(f"agg-{i}", b"v1-%d" % i)
        await idx.start()
        try:
            async def settle():
                for _ in range(200):
                    if idx.total_lag() == 0:
                        return
                    await asyncio.sleep(0.01)
                raise AssertionError("indexer never caught up")

            await settle()
            wm_before = idx.indexed_watermark("state", 1)
            idx.set_partitions([0, 2, 3])
            # mid-tail: records keep landing on the revoked partition
            put("agg-1", b"v2-1")
            await asyncio.sleep(0.05)
            # revoked keys stay servable at their last-indexed value
            assert idx.get_aggregate_bytes("agg-1") == b"v1-1"
            # re-grant resumes from the kept watermark and applies the miss
            idx.set_partitions([0, 1, 2, 3])
            assert idx.indexed_watermark("state", 1) == wm_before
            await settle()
            assert idx.get_aggregate_bytes("agg-1") == b"v2-1"
        finally:
            await idx.stop()

    asyncio.run(scenario())


# -- read path -------------------------------------------------------------------------


def test_staleness_bound_and_require_current():
    async def scenario():
        log = make_log()
        exp = Expected()
        append_events(log, exp.events("agg-0", 4))
        plane = make_plane(log, max_lag=4)
        plane._ensure_device_state()
        plane.seed_from_log()  # no refresh loop: lag only grows
        hit, st = await plane.read_state("agg-0")
        assert hit and st == exp.states["agg-0"]
        # within the bound: bounded-staleness reads still hit, but the
        # entity-init contract (require_current) demands lag 0
        stale = exp.events("agg-0", 3)
        append_events(log, stale)
        hit, _ = await plane.read_state("agg-0")
        assert hit
        hit, _ = await plane.read_state("agg-0", require_current=True)
        assert not hit
        # beyond max-lag-records: even bounded-staleness reads fall back
        append_events(log, exp.events("agg-0", 3))
        hit, _ = await plane.read_state("agg-0")
        assert not hit
        assert plane.stats["fallbacks"] == 2
        # a STOPPED plane must miss outright: its freshness view is frozen
        # while the log moves on, so served hits would grow silently stale
        await plane.stop()
        hit, _ = await plane.read_state("agg-0")
        assert not hit
        assert (await plane.read_many(["agg-0"])) == {}

    asyncio.run(scenario())


def test_concurrent_reads_coalesce_into_batched_gathers():
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(32)]
        evs = []
        for agg in aggs:
            evs.extend(exp.events(agg, 3))
        append_events(log, evs)
        registry = Metrics()
        plane = make_plane(log, metrics=engine_metrics(registry))
        await plane.start()
        try:
            results = await asyncio.gather(
                *(plane.read_state(a) for a in aggs for _ in range(4)))
            assert all(hit for hit, _ in results)
            assert {st.aggregate_id for _, st in results} == set(aggs)
            # 128 concurrent reads ride far fewer device gathers
            assert plane.stats["gathered_rows"] == 128
            assert plane.stats["gathers"] < 128
            snap = registry.get_metrics()
            assert snap["surge.replay.resident.gather-batch-size"] > 1
            # project() batches a whole id list in one sweep
            proj = await plane.project(aggs + ["ghost-1"])
            assert proj == {a: exp.states[a] for a in aggs}
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_unschema_event_poisons_aggregate_not_the_plane():
    """An event outside the replay schema (ExceptionThrowingEvent is
    deliberately unregistered) must degrade only ITS aggregate to the host
    path; every other aggregate keeps folding on device."""
    async def scenario():
        log = make_log()
        exp = Expected()
        append_events(log, exp.events("agg-0", 3))
        append_events(log, exp.events("agg-1", 3))
        plane = make_plane(log)
        await plane.start()
        try:
            prod = log.transactional_producer("poison")
            prod.begin()
            msg = EVT.write_event(counter.ExceptionThrowingEvent("agg-0", 4, "boom"))
            prod.send(LogRecord(topic=TOPIC, partition=part_of("agg-0"),
                                key=msg.key, value=msg.value))
            prod.commit()
            append_events(log, exp.events("agg-1", 2))
            await wait_caught_up(plane)
            hit, _ = await plane.read_state("agg-0")
            assert not hit  # poisoned: host store owns it now
            hit, st = await plane.read_state("agg-1")
            assert hit and st == exp.states["agg-1"]
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_narrow_u16_overflow_triggers_wide_refetch():
    """The u16 read wire is a guess: values past the 16-bit range must flip
    the device-computed fit flag and refetch wide — correctness can never
    depend on the narrow guess."""
    async def scenario():
        log = make_log()
        plane = make_plane(log)
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

# -- failure containment ---------------------------------------------------------------


def test_partial_round_failure_reanchors_no_double_fold():
    """A refresh round that dies AFTER some fold groups committed leaves the
    slab folded past the round's (never-advanced) watermarks. The failure
    path must re-anchor every polled partition through the re-grant route
    (purge + 0-anchor), so the retry refolds from scratch instead of folding
    the committed groups' events a second time."""
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(24)]
        evs = []
        for agg in aggs:
            evs.extend(exp.events(agg, 3))
        append_events(log, evs)
        plane = make_plane(log, capacity=8)  # 24 aggregates -> 3 groups/round
        await plane.start()
        try:
            await wait_caught_up(plane)
            real = plane._fold_group
            calls = {"n": 0}

            async def dying(group, logs, parts, gens):
                calls["n"] += 1
                if calls["n"] == 2:  # the round's SECOND group: one committed
                    raise RuntimeError("injected mid-round fold failure")
                return await real(group, logs, parts, gens)

            plane._fold_group = dying
            evs = []
            for agg in aggs:
                evs.extend(exp.events(agg, 2, decrement_every=2))
            append_events(log, evs)
            deadline = asyncio.get_running_loop().time() + 10.0
            while calls["n"] < 2:
                assert asyncio.get_running_loop().time() < deadline, \
                    "injected failure never fired"
                await asyncio.sleep(0.02)
            plane._fold_group = real
            await wait_caught_up(plane)
            golden = cold_restore_bytes(log)
            for agg in aggs:
                hit, data = await plane.read_bytes(agg)
                assert hit, agg
                assert data == golden[agg], agg
            assert plane.snapshot_states() == exp.states
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_gather_error_fails_reads_over_to_host_not_hang():
    """A device/decode failure in the gather lane must resolve every queued
    future as a host-fallback miss — an entity init awaiting a stranded
    future would hang forever — and the lane must heal for later reads."""
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(8)]
        evs = []
        for agg in aggs:
            evs.extend(exp.events(agg, 3))
        append_events(log, evs)
        plane = make_plane(log)
        await plane.start()
        try:
            await wait_caught_up(plane)
            real = plane._drain_batch

            async def boom(loop, batch):
                raise RuntimeError("injected gather failure")

            plane._drain_batch = boom
            before = plane.stats["fallbacks"]
            results = await asyncio.wait_for(
                asyncio.gather(*(plane.read_state(a) for a in aggs)), 5.0)
            assert all(r == (False, None) for r in results)
            assert plane.stats["fallbacks"] >= before + len(aggs)
            # read_many rides the same lane: the whole group fails over as {}
            out = await asyncio.wait_for(plane.read_many(aggs), 5.0)
            assert out == {}
            # the lane heals: the next drain serves reads again
            plane._drain_batch = real
            hit, st = await plane.read_state(aggs[0])
            assert hit and st == exp.states[aggs[0]]
        finally:
            await plane.stop()

    asyncio.run(scenario())


# -- wide wire: device dtypes ----------------------------------------------------------


def test_decode_wide_follows_device_dtypes_and_words():
    """The wide read wire is keyed on the DEVICE dtypes: a 64-bit schema
    column canonicalized to 32-bit on device (jax_enable_x64 off, the
    default) decodes one u32 word and widens back to the schema dtype; a
    genuine device-64-bit column occupies two u32 word-rows."""
    from types import SimpleNamespace

    plane = object.__new__(ResidentStatePlane)
    plane._fields = [SimpleNamespace(name="a"), SimpleNamespace(name="b"),
                     SimpleNamespace(name="c")]
    plane._dtypes = {"a": np.dtype(np.int64), "b": np.dtype(np.int64),
                     "c": np.dtype(np.bool_)}
    plane._dev_dts = {"a": np.dtype(np.int32),  # canonicalized on device
                      "b": np.dtype(np.int64),  # genuine 64-bit (x64 on)
                      "c": np.dtype(np.bool_)}
    plane._wide_words = [max(plane._dev_dts[f.name].itemsize // 4, 1)
                         for f in plane._fields]
    assert plane._wide_words == [1, 2, 1]
    a = np.array([1, -2, 2**31 - 1], dtype=np.int32)
    b = np.array([2**40 + 7, -(2**35), 11], dtype=np.int64)
    c = np.array([True, False, True])
    bw = b.view(np.uint32).reshape(3, 2)  # little-endian u32 word pairs
    rows = [a.view(np.uint32), bw[:, 0], bw[:, 1], c.astype(np.uint32)]
    k, k_b = 3, 8
    mat = np.zeros((len(rows), k_b), dtype=np.uint32)
    for i, r in enumerate(rows):
        mat[i, :k] = r
    out = plane._decode_wide(mat, k)
    assert out["a"].dtype == np.int64 and (out["a"] == a).all()
    assert out["b"].dtype == np.int64 and (out["b"] == b).all()
    assert out["c"].dtype == np.bool_ and (out["c"] == c).all()


# -- remote log: freshness off the loop ------------------------------------------------


def test_remote_log_freshness_check_rides_executor():
    """Against a remote (broker) log every end_offset is a blocking RPC: the
    read path's freshness check must ride the executor, never the event loop
    it shares with the command path."""
    async def scenario():
        log = make_log()
        exp = Expected()
        append_events(log, exp.events("agg-0", 3))
        end_offset_threads = set()

        class RemoteFacade:
            is_remote = True  # the GrpcLogTransport marker

            def __getattr__(self, name):
                return getattr(log, name)

            def end_offset(self, topic, partition):
                end_offset_threads.add(threading.get_ident())
                return log.end_offset(topic, partition)

        plane = make_plane(RemoteFacade())
        assert plane._remote_log
        await plane.start()
        try:
            await wait_caught_up(plane)  # calls end_offset on the loop (test)
            end_offset_threads.clear()
            loop_thread = threading.get_ident()
            hit, st = await plane.read_state("agg-0")
            assert hit and st == exp.states["agg-0"]
            assert await plane.read_many(["agg-0"]) == {
                "agg-0": exp.states["agg-0"]}
            assert end_offset_threads
            assert loop_thread not in end_offset_threads
        finally:
            await plane.stop()

    asyncio.run(scenario())


def test_revoke_landing_mid_seed_is_not_resurrected():
    """The cold-start seed runs in the executor; a rebalance revoking a
    partition WHILE the seed flies must not be undone by the seed's commit —
    the post-seed reconcile purges any partition whose anchor generation
    moved, so its rows are never servable and its watermark is dropped."""
    async def scenario():
        log = make_log()
        exp = Expected()
        aggs = [f"agg-{i}" for i in range(12)]
        evs = []
        for agg in aggs:
            evs.extend(exp.events(agg, 3))
        append_events(log, evs)
        plane = make_plane(log)
        real = plane.engine.fold_resident_slab

        def folding(corpus):
            plane.set_partitions([0, 2, 3])  # the revoke lands mid-seed
            return real(corpus)

        plane.engine.fold_resident_slab = folding
        await plane.start()
        try:
            victims = [a for a in aggs if part_of(a) == 1]
            assert victims
            assert all(part_of(a) != 1 for a in plane.resident_ids())
            assert 1 not in plane._watermarks
            for a in victims:
                hit, _ = await plane.read_state(a)
                assert not hit, a
            await wait_caught_up(plane)
            for a in aggs:
                if part_of(a) != 1:
                    hit, st = await plane.read_state(a)
                    assert hit and st == exp.states[a], a
        finally:
            await plane.stop()

    asyncio.run(scenario())
