#!/usr/bin/env python
"""Command-path and served-plane benchmarks of one host.

The cold fold and the projection rebuild are measured by ``python3 -m
benchmarks.run`` (BENCHMARK.json); this script measures what no cell holds yet.
Every phase runs on the host CPU platform.

The default run is the steady-state command latency (p50/p99/commands-per-sec
through the full engine with the reference's 50 ms flush tick and
fsync-on-commit FileLog) and the producer sweep, then, with
SURGE_BENCH_RESTORE=1, full vs checkpointed cold start. One of the SURGE_BENCH_*
mode switches below runs that mode alone instead: LADDER (with LANE or NATIVE),
FAILOVER, ANATOMY, SOAK, SAGA, HANDOFF, MESH, RAGGED, RESIDENT, RESIDENT_FEED,
VIEWS.

Prints one JSON line to stdout (a failed run prints one with ``error`` and exits 1):
    {"metric": "commands_per_sec", "value": N, "unit": "commands/s",
     "command_p50_ms": ..., "command_p99_ms": ..., ...}

Env knobs: SURGE_BENCH_LATENCY_SECONDS (5; 0 skips the latency phase),
SURGE_BENCH_LATENCY_WORKERS (64), SURGE_BENCH_SWEEP (1), SURGE_BENCH_RESTORE (0).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


#: the last payload printed to stdout — the terminal failure handler re-emits this
#: (with the error attached) so a late crash can never clobber a measured result
#: with a value-0 line under the driver's last-line-wins parse
_last_printed: dict | None = None


def emit(payload: dict) -> None:
    global _last_printed
    _last_printed = dict(payload)
    print(json.dumps(payload), flush=True)


# --------------------------------------------------------------------------------------
# steady-state command latency (no accelerator involved)
# --------------------------------------------------------------------------------------

def steady_state_latency(seconds: float, overrides: dict | None = None,
                         ladder: list | None = None) -> dict:
    """The full command path on one node, reference-default envelope: concurrent
    per-aggregate workers issue sequential Increment commands through
    ``aggregate_for().send_command`` against a FileLog (fsync on commit) with the
    event-driven group-commit publisher, so each command's latency = handling +
    adaptive linger + one durable group-commit transaction — directly comparable
    to the reference's flush-interval + Kafka txn commit envelope (core
    reference.conf:20-21; the fixed 50 ms flush tick this phase used to measure
    is now the `linger_ms=50, max_in_flight=1` row of ``producer_sweep``).

    A WORKER LADDER shows the per-partition group commits breaking past the
    one-command-per-envelope floor (VERDICT r4 weak #3 / next #8): each lane
    commits its accumulated commands in ONE durable txn whose journal fsync is
    shared across lanes (FileLog group-commit round), so commands/s scales
    with concurrency at a near-flat p50 until the host's event loop saturates —
    ``commands_per_txn`` measures the batching directly (journal commits
    counted at the FileLog). ``overrides``/``ladder`` parameterize the
    producer-knob sweep rows."""
    import asyncio
    import shutil
    import tempfile

    from surge_tpu import (
        CommandSuccess,
        SurgeCommandBusinessLogic,
        create_engine,
        default_config,
    )
    from surge_tpu.log.file import FileLog
    from surge_tpu.models import counter

    # server tuning (documented in docs/operations.md): the command path
    # hands off between the event loop, the journal group-sync thread and
    # executor threads constantly; the default 5 ms GIL switch interval turns
    # every handoff into a latency cliff on a busy loop
    sys.setswitchinterval(0.0005)

    base_workers = int(os.environ.get("SURGE_BENCH_LATENCY_WORKERS", 64))
    default_ladder = [base_workers, 256, 1024]
    if ladder is None:
        ladder = []
        for tok in os.environ.get("SURGE_BENCH_LATENCY_LADDER", "").split(","):
            try:
                w = int(tok)
            except ValueError:
                continue  # empty element / typo: skip, never void the phase
            if w > 0:
                ladder.append(w)
    if not ladder:
        ladder = default_ladder
    cfg = default_config()
    if overrides:
        cfg = cfg.with_overrides(overrides)
    flush_ms = cfg.get_int("surge.producer.flush-interval-ms")
    linger_ms = cfg.get_int("surge.producer.linger-ms")
    max_in_flight = cfg.get_int("surge.producer.max-in-flight")
    root = tempfile.mkdtemp(prefix="surge-bench-latency-")

    broker = (overrides or {}).get("bench.broker", "inproc")

    async def scenario() -> dict:
        flog = FileLog(os.path.join(root, "log"), config=cfg)
        journal = flog._journal_path
        log_server = None
        transport = None
        engine_log = flog
        if broker == "grpc":
            # the over-the-wire command path: a loopback LogServer over the
            # same durable FileLog, so max-in-flight's pipelined Transact
            # window (client seq dispatch + broker in-order gate) is actually
            # exercised — in-process logs collapse to one commit in flight
            from surge_tpu.log.client import GrpcLogTransport
            from surge_tpu.log.server import LogServer

            log_server = LogServer(flog, port=0, config=cfg)
            port = log_server.start()
            transport = GrpcLogTransport(f"127.0.0.1:{port}", config=cfg)
            engine_log = transport
        engine = create_engine(
            SurgeCommandBusinessLogic(
                aggregate_name="counter", model=counter.CounterModel(),
                state_format=counter.state_formatting(),
                event_format=counter.event_formatting()),
            log=engine_log, config=cfg)
        await engine.start()

        latencies: list = []

        async def worker(i: int, stop_at: float) -> None:
            agg = f"bench-{i}"
            ref = engine.aggregate_for(agg)
            while time.perf_counter() < stop_at:
                t0 = time.perf_counter()
                r = await ref.send_command(counter.Increment(agg))
                if not isinstance(r, CommandSuccess):
                    raise RuntimeError(f"command failed: {r}")
                latencies.append(time.perf_counter() - t0)

        def journal_commits() -> int:
            with open(journal, "rb") as f:
                return sum(1 for _ in f)

        rungs = []
        for workers in ladder:
            # warmup (entity init + first flushes), then the measured window
            await asyncio.gather(*(worker(i, time.perf_counter() + 1.0)
                                   for i in range(workers)))
            latencies.clear()
            commits0 = journal_commits()
            t0 = time.perf_counter()
            await asyncio.gather(*(worker(i, t0 + seconds)
                                   for i in range(workers)))
            elapsed = time.perf_counter() - t0
            txns = journal_commits() - commits0
            lat_ms = sorted(1000.0 * x for x in latencies)
            n = len(lat_ms)
            rungs.append({
                "workers": workers,
                "commands_per_sec": round(n / elapsed),
                "p50_ms": round(lat_ms[n // 2], 2),
                "p99_ms": round(lat_ms[min(n - 1, (99 * n) // 100)], 2),
                "txn_commits_per_sec": round(txns / elapsed, 1),
                "commands_per_txn": round(n / max(txns, 1), 1),
                "commands": n,
            })
        pstats = engine.producer_stats()
        await engine.stop()
        if transport is not None:
            transport.close()
        if log_server is not None:
            log_server.stop()
        flog.close()

        base = rungs[0]
        return {
            "command_p50_ms": base["p50_ms"],
            "command_p99_ms": base["p99_ms"],
            "commands_per_sec": base["commands_per_sec"],
            "latency_commands": base["commands"],
            "latency_workers": base["workers"],
            "peak_commands_per_sec": max(r["commands_per_sec"] for r in rungs),
            "throughput_ladder": rungs,
            "num_partitions": cfg.get_int("surge.engine.num-partitions"),
            "host_cores": os.cpu_count(),
            "flush_interval_ms": flush_ms,
            "linger_ms": linger_ms,
            "max_in_flight": max_in_flight,
            "broker": broker,
            "producer_stats": pstats,
        }

    try:
        return asyncio.run(scenario())
    finally:
        shutil.rmtree(root, ignore_errors=True)


def native_paired_ladder(seconds: float, rounds: int = 3,
                         rungs=(64, 1024), broker: str = "inproc") -> dict:
    """PAIRED interleaved native-on vs native-off command-path ladder (the
    BENCH_NOTES round-6 protocol: single runs swing 2-3x on this host's 9p
    fsync + 2-vCPU GIL, so only same-host interleaved medians count). Each
    round runs BOTH arms back to back against fresh FileLogs; medians over
    >= 3 rounds per rung decide. The native arm is csrc/txn.cc end to end
    (batch decode + WAL format + staged journal + lazy segments + native
    read decode); the off arm pins surge.log.native.enabled=false AND the
    ambient read-decode switch, i.e. the bit-identical pure-Python path."""
    import statistics as _st

    from surge_tpu.log import native_gate

    arms = {"native_on": True, "native_off": False}
    if not native_gate.available():
        log("native library unbuilt: the on-arm would silently measure the "
            "Python path — run csrc/build.sh first")
    raw: dict = {a: {w: [] for w in rungs} for a in arms}
    for rnd in range(rounds):
        # alternate arm order per round: this host's episodic collapses
        # (CPU steal; BENCH_NOTES round-6) would otherwise bias whichever
        # arm systematically runs adjacent to them
        order = list(arms.items()) if rnd % 2 == 0 else \
            list(arms.items())[::-1]
        for arm, enabled in order:  # interleaved within each round
            native_gate.set_decode_enabled(enabled)
            try:
                stats = steady_state_latency(
                    seconds,
                    overrides={"surge.log.native.enabled": enabled,
                               "bench.broker": broker},
                    ladder=list(rungs))
            finally:
                native_gate.set_decode_enabled(None)
            for rung in stats["throughput_ladder"]:
                raw[arm][rung["workers"]].append(rung)
            log(f"round {rnd + 1}/{rounds} {arm}: " + ", ".join(
                f"{r['workers']}w {r['commands_per_sec']} cmd/s "
                f"p50 {r['p50_ms']}ms"
                for r in stats["throughput_ladder"]))
    med = lambda xs: round(_st.median(xs), 2)  # noqa: E731
    out = {"protocol": {"rounds": rounds, "seconds_per_rung": seconds,
                        "rungs": list(rungs), "broker": broker,
                        "native_available": native_gate.available(),
                        "interleaved": True, "medians": True},
           "rungs": []}
    for w in rungs:
        row = {"workers": w}
        for arm in arms:
            samples = raw[arm][w]
            row[arm] = {
                "commands_per_sec_median": med(
                    [s["commands_per_sec"] for s in samples]),
                "p50_ms_median": med([s["p50_ms"] for s in samples]),
                "p99_ms_median": med([s["p99_ms"] for s in samples]),
                "commands_per_txn_median": med(
                    [s["commands_per_txn"] for s in samples]),
                "rounds": [s["commands_per_sec"] for s in samples],
            }
        off = row["native_off"]["commands_per_sec_median"]
        row["speedup_median"] = round(
            row["native_on"]["commands_per_sec_median"] / max(off, 1), 3)
        out["rungs"].append(row)
        log(f"{w}w medians: native_on "
            f"{row['native_on']['commands_per_sec_median']} cmd/s vs "
            f"native_off {off} cmd/s -> {row['speedup_median']}x")
    return out


def lane_paired_ladder(seconds: float, rounds: int = 3,
                       rungs=(64, 1024), brokers=("inproc", "grpc")) -> dict:
    """PAIRED interleaved command-lane ladder (ISSUE 12, the r08 protocol):
    ``surge.producer.command-lane=direct`` (batch-level ack futures + slim
    timer waits, this PR's lane) vs ``classic`` (the PR-3 per-command
    machinery) — both arms native-on, over the inproc AND grpc rungs, arm
    order alternating per round, medians only (this host's 2-3x run swing,
    BENCH_NOTES round 6)."""
    import statistics as _st

    arms = ("direct", "classic")
    raw: dict = {b: {a: {w: [] for w in rungs} for a in arms}
                 for b in brokers}
    for rnd in range(rounds):
        order = arms if rnd % 2 == 0 else arms[::-1]
        for broker in brokers:
            for arm in order:
                stats = steady_state_latency(
                    seconds,
                    overrides={"surge.producer.command-lane": arm,
                               "bench.broker": broker},
                    ladder=list(rungs))
                for rung in stats["throughput_ladder"]:
                    raw[broker][arm][rung["workers"]].append(rung)
                log(f"round {rnd + 1}/{rounds} {broker}/{arm}: " + ", ".join(
                    f"{r['workers']}w {r['commands_per_sec']} cmd/s "
                    f"p50 {r['p50_ms']}ms"
                    for r in stats["throughput_ladder"]))
    med = lambda xs: round(_st.median(xs), 2)  # noqa: E731
    out = {"protocol": {"rounds": rounds, "seconds_per_rung": seconds,
                        "rungs": list(rungs), "brokers": list(brokers),
                        "interleaved": True, "medians": True},
           "ladders": {}}
    for broker in brokers:
        rows = []
        for w in rungs:
            row = {"workers": w}
            for arm in arms:
                samples = raw[broker][arm][w]
                row[arm] = {
                    "commands_per_sec_median": med(
                        [s["commands_per_sec"] for s in samples]),
                    "p50_ms_median": med([s["p50_ms"] for s in samples]),
                    "p99_ms_median": med([s["p99_ms"] for s in samples]),
                    "rounds": [s["commands_per_sec"] for s in samples],
                }
            base = row["classic"]["commands_per_sec_median"]
            row["speedup_median"] = round(
                row["direct"]["commands_per_sec_median"] / max(base, 1), 3)
            rows.append(row)
            log(f"{broker} {w}w medians: direct "
                f"{row['direct']['commands_per_sec_median']} vs classic "
                f"{base} cmd/s -> {row['speedup_median']}x")
        out["ladders"][broker] = rows
    return out


def resident_feed_paired() -> dict:
    """PAIRED interleaved resident sustained-fold arms (ISSUE 12): the
    native feed (batched JSON decode over native record-index read views)
    vs the per-event Python feed, against the SAME pre-committed FileLog
    tail — the refresh loop refolds it from a 0-anchor per arm, so both
    arms fold identical bytes. Medians over >=3 rounds.

    Knobs: SURGE_BENCH_FEED_EVENTS (40000), _AGGREGATES (2048),
    _ROUNDS (3), _PARTITIONS (4), _MAX_POLL (8192)."""
    import asyncio
    import statistics as _st

    from surge_tpu.config import default_config
    from surge_tpu.log import LogRecord, TopicSpec
    from surge_tpu.log import native_gate
    from surge_tpu.log.file import FileLog
    from surge_tpu.models import counter
    from surge_tpu.replay.resident_state import ResidentStatePlane
    from surge_tpu.serialization import SerializedMessage

    import shutil
    import tempfile

    fold_events = int(os.environ.get("SURGE_BENCH_FEED_EVENTS", 40_000))
    n_agg = int(os.environ.get("SURGE_BENCH_FEED_AGGREGATES", 2048))
    rounds = max(int(os.environ.get("SURGE_BENCH_FEED_ROUNDS", 3)), 1)
    nparts = int(os.environ.get("SURGE_BENCH_FEED_PARTITIONS", 4))
    max_poll = int(os.environ.get("SURGE_BENCH_FEED_MAX_POLL", 8192))
    evt_fmt = counter.event_formatting()
    aggs = [f"agg-{i}" for i in range(n_agg)]

    root = tempfile.mkdtemp(prefix="surge-bench-feed-")
    flog = FileLog(os.path.join(root, "log"), config=default_config())
    flog.create_topic(TopicSpec("events", nparts))
    prod = flog.transactional_producer("feed-bench")
    seqs = {a: 0 for a in aggs}
    prod.begin()
    for i in range(fold_events):
        a = aggs[(i * 7919) % n_agg]
        seqs[a] += 1
        prod.send(LogRecord(
            topic="events", key=a,
            value=evt_fmt.write_event(
                counter.CountIncremented(a, 1, seqs[a])).value,
            partition=hash(a) % nparts))
        if i % 5000 == 4999:
            prod.commit()
            prod.begin()
    prod.commit()

    def one_arm(native_feed: bool) -> float:
        native_gate.set_decode_enabled(native_feed)

        async def scenario() -> float:
            cfg = default_config().with_overrides({
                "surge.replay.resident.capacity": max(n_agg, 8),
                "surge.replay.resident.refresh-interval-ms": 10,
                "surge.replay.resident.refresh-max-poll-records": max_poll,
                "surge.replay.resident.native-feed": native_feed,
            })
            plane = ResidentStatePlane(
                flog, "events", counter.make_replay_spec(), config=cfg,
                partitions=[],  # no seed; the refresh loop refolds from 0
                deserialize_event=lambda b: evt_fmt.read_event(
                    SerializedMessage(key="", value=b)),
                deserialize_events=evt_fmt.read_events_batch,
                serialize_state=lambda a, s: b"")
            await plane.start()
            t0 = time.perf_counter()
            plane.set_partitions(list(range(nparts)))
            while plane.lag_records() > 0:
                await asyncio.sleep(0.005)
            rate = plane.stats["folded_events"] / (time.perf_counter() - t0)
            await plane.stop()
            return rate

        try:
            return asyncio.run(scenario())
        finally:
            native_gate.set_decode_enabled(None)

    raw = {"native_feed": [], "python_feed": []}
    try:
        one_arm(True)  # warmup: compile the fold programs outside the rounds
        for rnd in range(rounds):
            order = (("native_feed", True), ("python_feed", False))
            if rnd % 2:
                order = order[::-1]
            for name, enabled in order:
                rate = one_arm(enabled)
                raw[name].append(round(rate))
                log(f"feed round {rnd + 1}/{rounds} {name}: "
                    f"{rate:,.0f} ev/s sustained")
    finally:
        flog.close()
        shutil.rmtree(root, ignore_errors=True)
    nat = _st.median(raw["native_feed"])
    pyf = _st.median(raw["python_feed"])
    return {"protocol": {"rounds": rounds, "fold_events": fold_events,
                        "aggregates": n_agg, "partitions": nparts,
                        "max_poll": max_poll, "interleaved": True,
                        "medians": True,
                        "native_available": native_gate.available()},
            "native_feed_events_per_sec_median": round(nat),
            "python_feed_events_per_sec_median": round(pyf),
            "speedup_median": round(nat / max(pyf, 1), 3),
            "rounds": raw}


def views_paired() -> dict:
    """PAIRED interleaved view-read vs scan-per-read reader ladder (ISSUE
    17): N concurrent readers all want the SAME grouped-aggregate answer —
    arm A reads the materialized view the resident plane keeps folded (one
    host merge of per-partition partials per read), arm B answers each read
    with a from-scratch query-engine scan of the same committed events (the
    batch ``query()`` path, pre-encoded so the scan arm pays no segment IO).
    Both arms run back to back per round against the same corpus in the same
    process, order alternating per round; medians only.

    Knobs: SURGE_BENCH_VIEWS_EVENTS (50000), _AGGREGATES (1024),
    _ROUNDS (3), _PARTITIONS (4), _LADDER (16,64,256,1024)."""
    import asyncio
    import statistics as _st

    from surge_tpu.codec.tensor import encode_events_columnar
    from surge_tpu.config import default_config
    from surge_tpu.log import InMemoryLog, LogRecord, TopicSpec
    from surge_tpu.models import counter
    from surge_tpu.replay.query import Aggregate, QueryEngine, ScanQuery
    from surge_tpu.replay.resident_state import ResidentStatePlane
    from surge_tpu.replay.views import MaterializedViews, ViewDef
    from surge_tpu.serialization import SerializedMessage

    n_events = int(os.environ.get("SURGE_BENCH_VIEWS_EVENTS", 50_000))
    n_agg = int(os.environ.get("SURGE_BENCH_VIEWS_AGGREGATES", 1024))
    rounds = max(int(os.environ.get("SURGE_BENCH_VIEWS_ROUNDS", 3)), 1)
    nparts = int(os.environ.get("SURGE_BENCH_VIEWS_PARTITIONS", 4))
    ladder = [int(t) for t in os.environ.get(
        "SURGE_BENCH_VIEWS_LADDER", "16,64,256,1024").split(",") if t]

    evt_fmt = counter.event_formatting()
    spec = counter.make_replay_spec()
    aggs = [f"agg-{i}" for i in range(n_agg)]
    query = ScanQuery(aggregates=(Aggregate("count"),
                                  Aggregate("sum", "increment_by"),
                                  Aggregate("max", "sequence_number")))

    mlog = InMemoryLog()
    mlog.create_topic(TopicSpec("events", nparts))
    prod = mlog.transactional_producer("views-bench")
    prod.begin()
    seqs = {a: 0 for a in aggs}
    by_agg: dict = {}
    for i in range(n_events):
        a = aggs[(i * 7919) % n_agg]
        seqs[a] += 1
        ev = counter.CountIncremented(a, 1, seqs[a])
        by_agg.setdefault(a, []).append(ev)
        prod.send(LogRecord(topic="events", key=a,
                            value=evt_fmt.write_event(ev).value,
                            partition=hash(a) % nparts))
    prod.commit()

    # arm B's corpus: the identical committed events as one columnar chunk
    colev = encode_events_columnar(spec.registry, list(by_agg.values()))
    colev.aggregate_ids = list(by_agg)
    qe = QueryEngine(spec, config=default_config())

    async def scenario() -> dict:
        cfg = default_config().with_overrides({
            "surge.replay.resident.capacity": max(n_agg, 8),
            "surge.replay.resident.refresh-interval-ms": 10,
        })
        plane = ResidentStatePlane(
            mlog, "events", spec, config=cfg,
            deserialize_event=lambda b: evt_fmt.read_event(
                SerializedMessage(key="", value=b)),
            serialize_state=lambda a, s: b"")
        views = MaterializedViews(spec, config=cfg)
        plane.attach_views(views)
        plane.register_view(ViewDef(name="totals", query=query))
        await plane.start()
        while plane.lag_records() > 0:
            await asyncio.sleep(0.005)
        loop = asyncio.get_running_loop()

        def view_read():
            views.snapshot("totals")

        def scan_read():
            qe.scan_chunks([colev], query)

        async def arm(n_readers: int, fn) -> float:
            t0 = time.perf_counter()
            await asyncio.gather(*(loop.run_in_executor(None, fn)
                                   for _ in range(n_readers)))
            return n_readers / (time.perf_counter() - t0)

        view_read()
        scan_read()  # warmup: compile/cache both read paths off the clock
        rungs = []
        try:
            for n in ladder:
                raw = {"view_read": [], "scan_per_read": []}
                for rnd in range(rounds):
                    order = (("view_read", view_read),
                             ("scan_per_read", scan_read))
                    if rnd % 2:
                        order = order[::-1]
                    for name, fn in order:
                        raw[name].append(round(await arm(n, fn), 1))
                view = _st.median(raw["view_read"])
                scan = _st.median(raw["scan_per_read"])
                rungs.append({
                    "readers": n,
                    "view_read": {"reads_per_sec_median": view,
                                  "rounds": raw["view_read"]},
                    "scan_per_read": {"reads_per_sec_median": scan,
                                      "rounds": raw["scan_per_read"]},
                    "speedup_median": round(view / max(scan, 1e-9), 3)})
                log(f"{n} readers medians: view {view:,.0f} reads/s, "
                    f"scan-per-read {scan:,.0f} reads/s "
                    f"({rungs[-1]['speedup_median']}x)")
        finally:
            await plane.stop()
        return {"protocol": {"events": n_events, "aggregates": n_agg,
                             "partitions": nparts, "rounds": rounds,
                             "interleaved": True, "medians": True},
                "rungs": rungs}

    return asyncio.run(scenario())


def anatomy_bench() -> dict:
    """SURGE_BENCH_ANATOMY=1: traced command phase → the per-leg critical-path
    attribution table alongside the phase's latency medians (ISSUE 14).

    One engine drives a FileLog-backed gRPC broker with tracing + tail
    sampling wired on BOTH sides (tail latency threshold 0: every completed
    trace is kept, budget raised accordingly), closed-loop workers send
    commands for a few seconds, then both trace rings are dumped, assembled
    across the process boundary and attributed. Reported:

    - ``command_p50_ms`` / ``command_p99_ms`` — the phase's command-latency
      medians (same closed-loop shape as the ladder arms, so the table reads
      against numbers of the usual kind);
    - ``anatomy`` — the attribution table (per-leg p50/p99/total/share);
    - ``anatomy_dominant`` / ``anatomy_dominant_share`` — where the time
      went. The next perf PR starts from this, not from guesses.

    Env: SURGE_BENCH_ANATOMY_SECONDS (3), SURGE_BENCH_ANATOMY_WORKERS (16).
    """
    import asyncio
    import socket
    import tempfile

    from surge_tpu import SurgeCommandBusinessLogic, create_engine
    from surge_tpu.config import Config
    from surge_tpu.log import GrpcLogTransport, LogServer
    from surge_tpu.log.file import FileLog
    from surge_tpu.models import counter
    from surge_tpu.observability.anatomy import (assemble_traces,
                                                 attribution_table)
    from surge_tpu.tracing import Tracer

    seconds = float(os.environ.get("SURGE_BENCH_ANATOMY_SECONDS", 3.0))
    workers = int(os.environ.get("SURGE_BENCH_ANATOMY_WORKERS", 16))
    cfg = Config(overrides={
        "surge.producer.flush-interval-ms": 5,
        "surge.producer.ktable-check-interval-ms": 5,
        "surge.state-store.commit-interval-ms": 20,
        "surge.aggregate.init-retry-interval-ms": 5,
        "surge.engine.num-partitions": 4,
        "surge.trace.tail.latency-ms": 0,       # keep every completed trace
        "surge.trace.tail.keep-budget": 100_000,
        "surge.trace.ring-capacity": 4096,
    })
    logic = SurgeCommandBusinessLogic(
        aggregate_name="anatomy", model=counter.CounterModel(),
        state_format=counter.state_formatting(),
        event_format=counter.event_formatting())
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    tmp = tempfile.mkdtemp(prefix="surge-anatomy-")
    broker_tracer = Tracer(service="broker")
    server = LogServer(FileLog(os.path.join(tmp, "log"), fsync="commit",
                               config=cfg),
                       port=port, config=cfg, tracer=broker_tracer)
    server.start()
    engine_tracer = Tracer(service="engine")
    log = GrpcLogTransport(f"127.0.0.1:{port}", config=cfg,
                           tracer=engine_tracer)
    latencies: list = []

    async def phase() -> None:
        engine = create_engine(logic, log=log, config=cfg,
                               tracer=engine_tracer)
        await engine.start()
        deadline = time.monotonic() + seconds

        async def worker(i: int) -> None:
            ref = engine.aggregate_for(f"agg{i}")
            while time.monotonic() < deadline:
                t0 = time.perf_counter()
                await ref.send_command(counter.Increment(f"agg{i}"))
                latencies.append((time.perf_counter() - t0) * 1000.0)

        await asyncio.gather(*(worker(i) for i in range(workers)))
        await engine.stop()
        # the rings belong to the tracers, which outlive the engine: dump
        # after stop so in-flight flush spans have finished
        self_dump = engine.trace_ring.dump()
        stats["engine_dump"] = self_dump

    stats: dict = {}
    try:
        asyncio.run(phase())
    finally:
        broker_dump = (server.trace_ring.dump()
                       if server.trace_ring is not None else {"traces": []})
        server.stop()
    latencies.sort()

    def pct(q: float) -> float:
        if not latencies:
            return 0.0
        return latencies[min(int(q * (len(latencies) - 1)), len(latencies) - 1)]

    table = attribution_table(assemble_traces(
        [stats.get("engine_dump", {"traces": []}), broker_dump]))
    return {"anatomy_commands": len(latencies),
            "command_p50_ms": round(pct(0.50), 3),
            "command_p99_ms": round(pct(0.99), 3),
            "anatomy": table["legs"],
            "anatomy_traces": table["traces"],
            "anatomy_dominant": table["dominant"],
            "anatomy_dominant_share": table["dominant_share"]}


def failover_bench() -> dict:
    """SURGE_BENCH_FAILOVER=1: kill the replicated log leader under load and
    measure the unavailability window while PROVING zero-loss/zero-duplicate
    delivery (docs/operations.md failover runbook).

    A leader⇄follower broker pair runs with auto-promotion armed; worker
    threads drive sequential commits through the publisher-protocol retry
    ladder (verbatim retry, reopen-on-fence — the txn-seq dedup window owns
    exactly-once); mid-run the leader is hard-killed. Reported:

    - ``failover_unavailability_ms`` — the longest gap between consecutive
      successful acks across all workers (the outage the client actually saw);
    - ``acked_commits`` / ``lost`` / ``duplicated`` — ledger vs the promoted
      leader's log (both MUST be 0);
    - ``failover_timeline`` — the machine-readable failover story merged from
      BOTH brokers' flight recorders (host-monotonic timestamps): promotion
      decision → promotion → fence → truncation → first acked post-failover
      commit. The fence/truncation legs come from restarting the killed
      ex-leader against the new leader after the load phase — the full
      KIP-101 rejoin, reconstructed without reading a single log line.

    Env: SURGE_BENCH_FAILOVER_WORKERS (16), SURGE_BENCH_FAILOVER_SECONDS (6;
    the kill lands ~40% in)."""
    import threading

    from surge_tpu.config import Config
    from surge_tpu.log import (GrpcLogTransport, InMemoryLog, LogRecord,
                               LogServer, TopicSpec)
    from surge_tpu.log.transport import NotLeaderError, ProducerFencedError

    workers = int(os.environ.get("SURGE_BENCH_FAILOVER_WORKERS", 16))
    seconds = float(os.environ.get("SURGE_BENCH_FAILOVER_SECONDS", 6.0))
    cfg = Config(overrides={
        "surge.log.replication-ack-timeout-ms": 1_500,
        "surge.log.replication-isr-timeout-ms": 2_000,
        "surge.log.failover.probe-interval-ms": 150,
        "surge.log.failover.probe-failures": 2,
    })
    lport, fport = _free_ports(2)
    follower = LogServer(InMemoryLog(), port=fport,
                         follower_of=f"127.0.0.1:{lport}", auto_promote=True,
                         config=cfg)
    follower.start()
    leader = LogServer(InMemoryLog(), port=lport,
                       replicate_to=[f"127.0.0.1:{fport}"], config=cfg)
    leader.start()
    targets = f"127.0.0.1:{lport},127.0.0.1:{fport}"
    setup = GrpcLogTransport(targets, config=cfg)
    setup.create_topic(TopicSpec("ev", 1))

    stop_at = time.monotonic() + seconds
    kill_at = time.monotonic() + 0.4 * seconds
    acked_lock = threading.Lock()
    acked: list = []          # payloads acked to the "user"
    ack_times: list = []      # monotonic stamps of every successful ack

    def worker(w: int) -> None:
        client = GrpcLogTransport(targets, config=cfg)
        producer = None
        i = 0
        try:
            while time.monotonic() < stop_at:
                payload = f"w{w}-{i}".encode()
                deadline = time.monotonic() + 30.0
                while True:
                    try:
                        if producer is None:
                            producer = client.transactional_producer(
                                f"bench-fo-{w}")
                        producer.begin()
                        producer.send(LogRecord(topic="ev", key=f"w{w}",
                                                value=payload, partition=0))
                        producer.commit()
                        break
                    except (ProducerFencedError, NotLeaderError):
                        producer = None
                    except Exception:  # noqa: BLE001 — broker mid-failover
                        if producer is not None and producer.in_transaction:
                            producer.abort()
                        time.sleep(0.05)
                    if time.monotonic() > deadline:
                        return  # counted as in-doubt, never acked
                with acked_lock:
                    acked.append(payload)
                    ack_times.append(time.monotonic())
                i += 1
        finally:
            client.close()

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(workers)]
    for t in threads:
        t.start()
    killed_at = None
    while time.monotonic() < stop_at:
        if killed_at is None and time.monotonic() >= kill_at:
            leader.kill()
            killed_at = time.monotonic()
            log("failover bench: leader killed")
        time.sleep(0.02)
    for t in threads:
        t.join(60.0)

    if killed_at is not None:
        deadline = time.monotonic() + 30
        while follower.role != "leader" and time.monotonic() < deadline:
            time.sleep(0.02)
    # rejoin leg: restart the killed ex-leader (same inner log, same flight
    # recorder) against the new leader — its split-brain guard finds the
    # higher epoch BEFORE serving, records the fence, truncates the divergent
    # tail and catches up, completing the flight-recorded failover story
    relit = None
    if killed_at is not None and follower.role == "leader":
        if leader.kill_done is not None:
            leader.kill_done.wait(10)
        try:
            relit = LogServer(leader.log, port=lport,
                              replicate_to=[f"127.0.0.1:{fport}"],
                              flight=leader.flight, config=cfg)
            relit.start()
            deadline = time.monotonic() + 20
            while relit.role != "follower" and time.monotonic() < deadline:
                time.sleep(0.05)
        except Exception as exc:  # noqa: BLE001 — timeline then incomplete
            log(f"failover bench: ex-leader rejoin failed: {exc!r}")
    # unavailability: the longest gap between consecutive acks anywhere
    # (covers the kill → promotion → first post-failover ack window)
    gaps = [b - a for a, b in zip(ack_times, ack_times[1:])]
    unavailability_ms = round(max(gaps) * 1000.0, 1) if gaps else None
    present: dict = {}
    for r in follower.log.read("ev", 0):
        present[r.value] = present.get(r.value, 0) + 1
    lost = sum(1 for p in acked if present.get(p, 0) == 0)
    duplicated = sum(1 for p in acked if present.get(p, 0) > 1)
    # the failover timeline, reconstructed from both brokers' black boxes
    from surge_tpu.observability import merge_dumps, reconstruct_failover

    dumps = [leader.flight.dump(), follower.flight.dump()]
    merged = merge_dumps(dumps)
    recon = reconstruct_failover(merged)
    setup.close()
    if relit is not None:
        relit.stop()
    leader.stop()
    follower.stop()
    out = {
        "failover_unavailability_ms": unavailability_ms,
        "acked_commits": len(acked),
        "lost": lost,
        "duplicated": duplicated,
        "promoted": follower.role == "leader",
        "epoch": follower.epoch,
        "workers": workers,
        "seconds": seconds,
        "failover_timeline": {
            "events": merged,
            "phases": recon["phases"],
            "complete": recon["complete"],
            "decision_to_first_ack_ms": recon["span_ms"],
        },
    }
    if lost or duplicated:
        out["FAILED"] = "acked-record loss or duplication detected"
    log(f"failover bench: {len(acked)} acked, lost={lost} "
        f"duplicated={duplicated}, unavailability "
        f"{unavailability_ms}ms, promoted={out['promoted']}, "
        f"timeline complete={recon['complete']} "
        f"(decision->first-ack {recon['span_ms']}ms)")
    return out


def soak_bench() -> dict:
    """SURGE_BENCH_SOAK=1: the sustained self-healing soak
    (surge_tpu.cluster.soak) across several seeded chaos schedules on a
    3+-broker spread cluster — rolling kills (coordinator on odd seeds,
    partition leaders on even), seeded link faults, AddBroker/RemoveBroker
    churn, Zipf hot-key skew — each scored by a federated scrape + the SLO
    burn-rate engine and the autobalancer driving planned per-partition
    handoffs.

    Env: SURGE_BENCH_SOAK_SEEDS (comma list; default 41,42,43,44),
    SURGE_BENCH_SOAK_SECONDS (12 per seed), SURGE_BENCH_SOAK_BROKERS (3),
    SURGE_BENCH_SOAK_PARTITIONS (6), SURGE_BENCH_SOAK_WRITERS (4).

    The verdict aggregates every seed: total acked commits, 0 lost / 0
    duplicated, exactly-one-leader-per-partition convergence, every SLO page
    cleared, and the autobalancer decision/move counts from the merged
    flight timelines."""
    from surge_tpu.cluster.soak import run_soak

    seeds = [int(s) for s in os.environ.get(
        "SURGE_BENCH_SOAK_SEEDS", "41,42,43,44").split(",") if s.strip()]
    seconds = float(os.environ.get("SURGE_BENCH_SOAK_SECONDS", 12.0))
    brokers = int(os.environ.get("SURGE_BENCH_SOAK_BROKERS", 3))
    partitions = int(os.environ.get("SURGE_BENCH_SOAK_PARTITIONS", 6))
    writers = int(os.environ.get("SURGE_BENCH_SOAK_WRITERS", 4))
    rounds = []
    for seed in seeds:
        log(f"soak bench: seed {seed} ({seconds:.0f}s schedule)")
        rounds.append(run_soak(seed, brokers=brokers, partitions=partitions,
                               seconds=seconds, writers=writers))
    verdict_ok = all(
        r["lost"] == 0 and r["duplicated"] == 0 and r["leaders"]["ok"]
        and r["converged"] and r["slo_pages"]["cleared"]
        and not r["writer_errors"] for r in rounds)
    return {
        "soak_rounds": rounds,
        "soak_seeds": seeds,
        "soak_acked_commits": sum(r["acked_commits"] for r in rounds),
        "soak_lost": sum(r["lost"] for r in rounds),
        "soak_duplicated": sum(r["duplicated"] for r in rounds),
        "soak_pages_raised": sum(r["slo_pages"]["raised"] for r in rounds),
        "soak_pages_cleared": all(r["slo_pages"]["cleared"] for r in rounds),
        "soak_balancer_moves": sum(r["balancer_moves"] for r in rounds),
        "soak_verdict": "ok: self-healed every schedule" if verdict_ok
        else "DEGRADED: see soak_rounds",
    }


def saga_bench() -> dict:
    """SURGE_BENCH_SAGA=1: the saga-storm chaos soak
    (surge_tpu.cluster.soak.run_saga_soak) — a storm of two-step transfer
    sagas (a seeded fraction poisoned into the compensation walk) against a
    3-broker spread cluster under a rolling broker kill, seeded link faults
    and a mid-storm SagaManager restart, per seed.

    Env: SURGE_BENCH_SAGA_SEEDS (comma list; default 61,62,63),
    SURGE_BENCH_SAGA_SECONDS (14 per seed), SURGE_BENCH_SAGA_COUNT (400
    sagas per seed), SURGE_BENCH_SAGA_BROKERS (3), SURGE_BENCH_SAGA_PARTITIONS
    (6), SURGE_BENCH_SAGA_ACCOUNTS (48), SURGE_BENCH_SAGA_POISON (0.3).

    The verdict aggregates every seed: **0 lost / 0 duplicated / 0
    half-compensated** — every acked saga terminal, every account balance
    equal to what the saga rows' own committed/compensated masks predict,
    and the ledger-reconciliation invariant clean over every row — with the
    whole story reconstructable from the merged flight timelines."""
    from surge_tpu.cluster.soak import run_saga_soak

    seeds = [int(s) for s in os.environ.get(
        "SURGE_BENCH_SAGA_SEEDS", "61,62,63").split(",") if s.strip()]
    seconds = float(os.environ.get("SURGE_BENCH_SAGA_SECONDS", 14.0))
    count = int(os.environ.get("SURGE_BENCH_SAGA_COUNT", 400))
    brokers = int(os.environ.get("SURGE_BENCH_SAGA_BROKERS", 3))
    partitions = int(os.environ.get("SURGE_BENCH_SAGA_PARTITIONS", 6))
    accounts = int(os.environ.get("SURGE_BENCH_SAGA_ACCOUNTS", 48))
    poison = float(os.environ.get("SURGE_BENCH_SAGA_POISON", 0.3))
    rounds = []
    for seed in seeds:
        log(f"saga storm: seed {seed} ({count} sagas, {seconds:.0f}s "
            "schedule)")
        rounds.append(run_saga_soak(
            seed, brokers=brokers, partitions=partitions, seconds=seconds,
            sagas=count, accounts=accounts, poison_fraction=poison))
    verdict_ok = all(
        r["lost"] == 0 and r["duplicated"] == 0
        and r["half_compensated"] == 0 and r["reconcile"]["ok"]
        for r in rounds)
    return {
        "saga_rounds": rounds,
        "saga_seeds": seeds,
        "saga_started": sum(r["started"] for r in rounds),
        "saga_poisoned": sum(r["poisoned"] for r in rounds),
        "saga_lost": sum(r["lost"] for r in rounds),
        "saga_duplicated": sum(r["duplicated"] for r in rounds),
        "saga_half_compensated": sum(r["half_compensated"] for r in rounds),
        "saga_dead_letter": sum(r["reconcile"]["dead_letter"]
                                for r in rounds),
        "saga_verdict": "ok: 0 lost / 0 duplicated / 0 half-compensated"
        if verdict_ok else "DEGRADED: see saga_rounds",
    }


def handoff_bench() -> dict:
    """SURGE_BENCH_HANDOFF=1: paired interleaved ladder (medians only, per
    the BENCH_NOTES round-6 protocol — single runs swing 2-3x on this host)
    comparing the three ways a partition leader moves:

    - ``handoff`` — planned HandoffPartition under load: bulk slice ship
      while serving, then fence -> journal-tail ship -> dedup push ->
      promote -> demote. Unavailability = the longest gap in the POOLED ack
      stream of all workers (the cluster-wide write outage, same metric as
      the failover bench — a single worker's private stall inside its retry
      ladder does not register); the fenced span is bounded by the TAIL
      appended during the bulk phase, never by log size.
    - ``kill`` — the PR-4 kill-failover under the same load: hard-kill the
      leader, prober-declared death, promotion. The unavailability floor
      includes the probe-failure detection window a planned handoff skips.
    - ``replay`` — full-replay cold start: how long an EMPTY standby takes
      to catch_up the whole preloaded log (the log-size-bound transfer a
      handoff performs UNFENCED). Runs with NO worker load — it measures
      pure transfer time against an idle leader, a different quantity than
      the two unavailability arms, compared only for its log-size scaling.

    Every round runs all three arms interleaved against fresh broker pairs
    with the same preload. Env: SURGE_BENCH_HANDOFF_WORKERS (8),
    SURGE_BENCH_HANDOFF_SECONDS (4), SURGE_BENCH_HANDOFF_PRELOAD (3000),
    SURGE_BENCH_HANDOFF_ROUNDS (3)."""
    import statistics
    import threading

    from surge_tpu.config import Config
    from surge_tpu.log import (GrpcLogTransport, InMemoryLog, LogRecord,
                               LogServer, TopicSpec)
    from surge_tpu.log.transport import NotLeaderError, ProducerFencedError

    workers = int(os.environ.get("SURGE_BENCH_HANDOFF_WORKERS", 8))
    seconds = float(os.environ.get("SURGE_BENCH_HANDOFF_SECONDS", 4.0))
    preload = int(os.environ.get("SURGE_BENCH_HANDOFF_PRELOAD", 3000))
    rounds = int(os.environ.get("SURGE_BENCH_HANDOFF_ROUNDS", 3))
    cfg = Config(overrides={
        "surge.log.replication-ack-timeout-ms": 1_500,
        "surge.log.replication-isr-timeout-ms": 2_000,
        "surge.log.failover.probe-interval-ms": 150,
        "surge.log.failover.probe-failures": 2,
    })

    def build_pair():
        lport, fport = _free_ports(2)
        follower = LogServer(InMemoryLog(), port=fport,
                             follower_of=f"127.0.0.1:{lport}",
                             auto_promote=True, config=cfg)
        follower.start()
        leader = LogServer(InMemoryLog(), port=lport,
                           replicate_to=[f"127.0.0.1:{fport}"], config=cfg)
        leader.start()
        setup = GrpcLogTransport(f"127.0.0.1:{lport}", config=cfg)
        setup.create_topic(TopicSpec("ev", 1))
        producer = setup.transactional_producer("preload")
        done = 0
        while done < preload:
            n = min(500, preload - done)
            producer.begin()
            for i in range(n):
                producer.send(LogRecord(topic="ev", key=f"p{done + i}",
                                        value=b"x" * 64, partition=0))
            producer.commit()
            done += n
        setup.close()
        return leader, follower, lport, fport

    def run_arm(kind: str) -> dict:
        leader, follower, lport, fport = build_pair()
        targets = f"127.0.0.1:{lport},127.0.0.1:{fport}"
        stop_at = time.monotonic() + seconds
        move_at = time.monotonic() + 0.4 * seconds
        acked_lock = threading.Lock()
        acked: list = []
        ack_times: list = []

        def worker(w: int) -> None:
            client = GrpcLogTransport(targets, config=cfg)
            producer = None
            i = 0
            try:
                while time.monotonic() < stop_at:
                    payload = f"{kind}-w{w}-{i}".encode()
                    deadline = time.monotonic() + 30.0
                    while True:
                        try:
                            if producer is None:
                                producer = client.transactional_producer(
                                    f"ho-{kind}-{w}")
                            producer.begin()
                            producer.send(LogRecord(
                                topic="ev", key=f"w{w}", value=payload,
                                partition=0))
                            producer.commit()
                            break
                        except (ProducerFencedError, NotLeaderError):
                            producer = None
                        except Exception:  # noqa: BLE001 — mid-transition
                            if producer is not None and producer.in_transaction:
                                producer.abort()
                            time.sleep(0.05)
                        if time.monotonic() > deadline:
                            return
                    with acked_lock:
                        acked.append(payload)
                        ack_times.append(time.monotonic())
                    i += 1
            finally:
                client.close()

        out: dict = {"kind": kind}
        threads = []
        if kind != "replay":
            threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                       for w in range(workers)]
            for t in threads:
                t.start()
        moved = False
        admin = None
        try:
            while time.monotonic() < stop_at:
                if not moved and time.monotonic() >= move_at:
                    moved = True
                    if kind == "handoff":
                        admin = GrpcLogTransport(f"127.0.0.1:{lport}",
                                                 config=cfg)
                        out["handoff_stats"] = admin.handoff_partition(
                            f"127.0.0.1:{fport}")
                    elif kind == "kill":
                        leader.kill()
                    else:  # replay: full cold start of an EMPTY standby
                        (sport,) = _free_ports(1)
                        standby = LogServer(InMemoryLog(), port=sport,
                                            config=cfg)
                        t0 = time.perf_counter()
                        copied = standby.catch_up(f"127.0.0.1:{lport}")
                        out["replay_cold_start_ms"] = round(
                            (time.perf_counter() - t0) * 1000.0, 1)
                        out["replay_records"] = copied
                        standby.stop()
                        break
                time.sleep(0.02)
            for t in threads:
                t.join(60.0)
            if kind != "replay":
                deadline = time.monotonic() + 30
                winner = follower  # the destination/promoted broker
                while winner.role != "leader" and time.monotonic() < deadline:
                    time.sleep(0.02)
                gaps = [b - a for a, b in zip(ack_times, ack_times[1:])]
                out["unavailability_ms"] = (round(max(gaps) * 1000.0, 1)
                                            if gaps else None)
                out["acked"] = len(acked)
                present: dict = {}
                for r in winner.log.read("ev", 0):
                    present[r.value] = present.get(r.value, 0) + 1
                out["lost"] = sum(1 for p in acked
                                  if present.get(p, 0) == 0)
                out["duplicated"] = sum(1 for p in acked
                                        if present.get(p, 0) > 1)
                out["promoted"] = winner.role == "leader"
        finally:
            if admin is not None:
                admin.close()
            leader.stop()
            follower.stop()
        return out

    arms: dict = {"handoff": [], "kill": [], "replay": []}
    for rnd in range(rounds):
        for kind in ("handoff", "kill", "replay"):  # interleaved, paired
            try:
                row = run_arm(kind)
            except Exception as exc:  # noqa: BLE001 — one arm, not the ladder
                log(f"handoff bench round {rnd} {kind} FAILED: {exc!r}")
                row = {"kind": kind, "error": repr(exc)}
            row["round"] = rnd
            arms[kind].append(row)
            log(f"handoff bench round {rnd} {kind}: "
                f"{ {k: v for k, v in row.items() if k != 'handoff_stats'} }")
    med = lambda rows, k: statistics.median(  # noqa: E731
        r[k] for r in rows if r.get(k) is not None)
    out = {
        "workers": workers, "seconds": seconds, "preload": preload,
        "rounds": rounds, "arms": arms,
        "handoff_unavailability_ms_median": med(arms["handoff"],
                                                "unavailability_ms"),
        "kill_unavailability_ms_median": med(arms["kill"],
                                             "unavailability_ms"),
        "replay_cold_start_ms_median": med(arms["replay"],
                                           "replay_cold_start_ms"),
        "handoff_fence_ms_median": statistics.median(
            r["handoff_stats"]["fence_ms"] for r in arms["handoff"]
            if "handoff_stats" in r),
        "handoff_tail_records_median": statistics.median(
            r["handoff_stats"].get("tail_records", 0)
            for r in arms["handoff"] if "handoff_stats" in r),
        "lost": sum(r.get("lost", 0) for rows in arms.values()
                    for r in rows),
        "duplicated": sum(r.get("duplicated", 0) for rows in arms.values()
                          for r in rows),
    }
    log(f"handoff bench medians: planned {out['handoff_unavailability_ms_median']}ms "
        f"(fence {out['handoff_fence_ms_median']}ms, tail "
        f"{out['handoff_tail_records_median']} records) vs kill "
        f"{out['kill_unavailability_ms_median']}ms vs full-replay cold start "
        f"{out['replay_cold_start_ms_median']}ms over {preload} records; "
        f"lost={out['lost']} duplicated={out['duplicated']}")
    return out


def _free_ports(n: int) -> list:
    import socket

    socks = []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def producer_sweep(seconds: float) -> list:
    """Sweep the group-commit knobs at one fixed rung — the before/after
    evidence for the adaptive publisher. The ``linger_ms=50, max_in_flight=1,
    broker=inproc`` row approximates the retired fixed 50 ms flush tick with
    one serial transaction lane; the grpc rows exercise the pipelined
    Transact window against a loopback broker (in-process logs always run
    one commit in flight, so in-flight only moves the wire rows).

    Env: SURGE_BENCH_SWEEP_WORKERS (256), SURGE_BENCH_SWEEP_SECONDS
    (min(seconds, 3))."""
    workers = int(os.environ.get("SURGE_BENCH_SWEEP_WORKERS", 256))
    secs = float(os.environ.get("SURGE_BENCH_SWEEP_SECONDS",
                                min(seconds, 3.0)))
    combos = [
        (50, 1, "inproc"),  # the pre-group-commit fixed-tick envelope
        (5, 1, "inproc"),
        (2, 1, "inproc"),   # the shipped default
        (0, 1, "inproc"),
        (2, 1, "grpc"),     # pipelining off, over the wire
        (2, 4, "grpc"),     # the shipped default window, over the wire
        (2, 8, "grpc"),
    ]
    rows = []
    for linger, inflight, broker in combos:
        try:
            stats = steady_state_latency(secs, overrides={
                "surge.producer.linger-ms": linger,
                "surge.producer.max-in-flight": inflight,
                "bench.broker": broker,
            }, ladder=[workers])
        except Exception as exc:  # noqa: BLE001 — one combo must not void the sweep
            log(f"sweep combo linger={linger} in_flight={inflight} "
                f"broker={broker} failed: {exc!r}")
            rows.append({"linger_ms": linger, "max_in_flight": inflight,
                         "broker": broker,
                         "error": f"{type(exc).__name__}: {exc}"})
            continue
        rung = stats["throughput_ladder"][0]
        row = {"linger_ms": linger, "max_in_flight": inflight,
               "broker": broker, **rung}
        rows.append(row)
        log(f"sweep linger={linger}ms in_flight={inflight} broker={broker}: "
            f"{rung['commands_per_sec']} cmds/s p50 {rung['p50_ms']}ms "
            f"p99 {rung['p99_ms']}ms ({rung['commands_per_txn']} cmds/txn)")
    return rows


def restore_bench() -> dict:
    """SURGE_BENCH_RESTORE=1: full vs checkpointed cold start (docs/compaction.md).

    Builds an events topic, checkpoints it at the head, appends a tail, then
    times ``restore_from_events`` from offset 0 against the checkpoint+tail
    route — reporting events folded and wall seconds for each, asserting the
    stores come out byte-identical and the checkpointed route folds strictly
    fewer events. Knobs: SURGE_BENCH_RESTORE_EVENTS (total, default 200k),
    SURGE_BENCH_RESTORE_TAIL (tail fraction, default 0.1),
    SURGE_BENCH_RESTORE_BACKEND (cpu|tpu, default the platform's replay
    backend: cpu here in the parent)."""
    import random
    import shutil
    import tempfile

    from surge_tpu.config import default_config
    from surge_tpu.log import InMemoryLog, LogRecord, TopicSpec
    from surge_tpu.models import counter
    from surge_tpu.serialization import SerializedMessage
    from surge_tpu.store import CheckpointStore, CheckpointWriter, restore_from_events
    from surge_tpu.store.kv import InMemoryKeyValueStore

    total = int(os.environ.get("SURGE_BENCH_RESTORE_EVENTS", 200_000))
    tail_frac = float(os.environ.get("SURGE_BENCH_RESTORE_TAIL", 0.1))
    backend = os.environ.get("SURGE_BENCH_RESTORE_BACKEND", "cpu")
    n_agg = max(total // 10, 1)
    model = counter.CounterModel()
    evt_fmt = counter.event_formatting()
    state_fmt = counter.state_formatting()
    deserialize_event = lambda b: evt_fmt.read_event(  # noqa: E731
        SerializedMessage(key="", value=b))
    serialize_state = lambda a, s: state_fmt.write_state(s).value  # noqa: E731

    log_t = InMemoryLog()
    log_t.create_topic(TopicSpec("events", 4))
    prod = log_t.transactional_producer("bench")
    rng = random.Random(11)
    seqs: dict = {}

    def publish(n: int) -> None:
        prod.begin()
        for i in range(n):
            a = f"agg-{rng.randrange(n_agg)}"
            seqs[a] = seqs.get(a, 0) + 1
            ev = (counter.CountIncremented(a, 1, seqs[a])
                  if rng.random() < 0.8
                  else counter.CountDecremented(a, 1, seqs[a]))
            prod.send(LogRecord(topic="events", key=a,
                                value=evt_fmt.write_event(ev).value,
                                partition=hash(a) % 4))
            if i % 5000 == 4999:
                prod.commit()
                prod.begin()
        prod.commit()

    head = total - int(total * tail_frac)
    publish(head)
    ck_dir = tempfile.mkdtemp(prefix="surge-bench-ckpt-")
    out: dict = {}
    try:
        writer = CheckpointWriter(
            log_t, "events", model, CheckpointStore(ck_dir, fsync=False),
            serialize_state=serialize_state,
            deserialize_event=deserialize_event,
            deserialize_state=state_fmt.read_state)
        t0 = time.perf_counter()
        ckpt = writer.write_now()
        out["restore_checkpoint_write_s"] = round(time.perf_counter() - t0, 3)
        publish(total - head)

        cfg = default_config().with_overrides({
            "surge.replay.backend": backend,
            "surge.replay.restore-spill-events": -1})
        full_store, ckpt_store = InMemoryKeyValueStore(), InMemoryKeyValueStore()
        t0 = time.perf_counter()
        full = restore_from_events(
            log_t, "events", full_store, deserialize_event=deserialize_event,
            serialize_state=serialize_state, model=model,
            replay_spec=counter.make_replay_spec(), config=cfg)
        full_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        tail = restore_from_events(
            log_t, "events", ckpt_store, deserialize_event=deserialize_event,
            serialize_state=serialize_state, model=model,
            replay_spec=counter.make_replay_spec(), config=cfg,
            checkpoint=ckpt, deserialize_state=state_fmt.read_state)
        ckpt_s = time.perf_counter() - t0
        mismatch = sum(
            1 for k in set(full_store._data) | set(ckpt_store._data)
            if full_store.get(k) != ckpt_store.get(k))
        if mismatch or tail.num_events >= full.num_events:
            raise AssertionError(
                f"checkpointed restore invariant broken: {mismatch} mismatched "
                f"aggregates, {tail.num_events} vs {full.num_events} events")
        out.update({
            "restore_backend": backend,
            "restore_full_events_folded": full.num_events,
            "restore_full_s": round(full_s, 3),
            "restore_ckpt_events_folded": tail.num_events,
            "restore_ckpt_s": round(ckpt_s, 3),
            "restore_speedup": round(full_s / ckpt_s, 2) if ckpt_s else 0.0,
        })
        log(f"restore bench ({backend}): full {full.num_events} events "
            f"{full_s:.2f}s vs checkpointed {tail.num_events} events "
            f"{ckpt_s:.2f}s ({out['restore_speedup']}x, byte-identical)")
        return out
    finally:
        shutil.rmtree(ck_dir, ignore_errors=True)


def resident_bench() -> dict:
    """SURGE_BENCH_RESIDENT=1 fast path: the device-resident state plane
    (docs/replay.md "Resident state plane").

    Three measurements, each PAIRED + INTERLEAVED per the BENCH_NOTES.md
    round-6 protocol (this host's single runs swing 2-3x; only same-round
    pairs and cross-round medians count):

    1. **Read ladder** — k concurrent readers issuing read-side projections
       (batches of SURGE_BENCH_RESIDENT_BATCH aggregates, the read-heavy
       workload the plane exists for): the batched-gather lane (every
       concurrent call coalesces into one device gather + a single
       fetch-barriered pull + a batch-materialized decode) vs the host KV
       path (per-key store bytes + state deserialize, exactly the engine's
       fallback — measured sync, its best case). Medians over >=3
       interleaved rounds per rung; a secondary single-read row records the
       per-getState surface, whose per-call asyncio cost the host path does
       not pay.
    2. **Refresh-loop sustained folds** — committed batches appended while
       the standing refresh loop folds them into the slab; events/s over the
       whole append->caught-up window.
    3. **Command-path guard** — one BENCH_LADDER-style rung with the plane
       enabled vs disabled, interleaved: the refresh loop must not regress
       the write path it shares the event loop with.

    Knobs: SURGE_BENCH_RESIDENT_AGGREGATES (4096), _EVENTS_PER (8),
    _ROUNDS (3), _BATCH (projection size, 256), _LOOPS (projections per
    worker per rung, 2), _READS (single reads per worker, 30), _LADDER
    ("16,64,256,1024"), _FOLD_EVENTS (60000), _GUARD (1; 0 skips phase 3),
    _GUARD_SECONDS (3.0), _GUARD_WORKERS (64)."""
    import asyncio
    import statistics

    from surge_tpu.config import default_config
    from surge_tpu.log import InMemoryLog, LogRecord, TopicSpec
    from surge_tpu.models import counter
    from surge_tpu.replay.ledger import ReplayLedger
    from surge_tpu.replay.profiler import ReplayProfiler
    from surge_tpu.replay.resident_state import ResidentStatePlane
    from surge_tpu.serialization import SerializedMessage
    from surge_tpu.store.kv import InMemoryKeyValueStore
    from surge_tpu.store.restore import restore_from_events

    n_agg = int(os.environ.get("SURGE_BENCH_RESIDENT_AGGREGATES", 4096))
    events_per = int(os.environ.get("SURGE_BENCH_RESIDENT_EVENTS_PER", 8))
    rounds = max(int(os.environ.get("SURGE_BENCH_RESIDENT_ROUNDS", 3)), 1)
    batch = int(os.environ.get("SURGE_BENCH_RESIDENT_BATCH", 256))
    loops = int(os.environ.get("SURGE_BENCH_RESIDENT_LOOPS", 2))
    reads_per_worker = int(os.environ.get("SURGE_BENCH_RESIDENT_READS", 30))
    ladder = [int(w) for w in os.environ.get(
        "SURGE_BENCH_RESIDENT_LADDER", "16,64,256,1024").split(",") if w]
    fold_events = int(os.environ.get("SURGE_BENCH_RESIDENT_FOLD_EVENTS", 60_000))

    evt_fmt = counter.event_formatting()
    state_fmt = counter.state_formatting()
    npart = 4
    aggs = [f"agg-{i}" for i in range(n_agg)]
    seqs = {a: 0 for a in aggs}

    log_t = InMemoryLog()
    log_t.create_topic(TopicSpec("events", npart))
    prod = log_t.transactional_producer("bench")

    def publish(agg_events) -> None:
        prod.begin()
        for i, (a, ev) in enumerate(agg_events):
            prod.send(LogRecord(topic="events", key=a,
                                value=evt_fmt.write_event(ev).value,
                                partition=hash(a) % npart))
            if i % 5000 == 4999:
                prod.commit()
                prod.begin()
        prod.commit()

    def make_batch(n: int):
        batch = []
        for i in range(n):
            a = aggs[(i * 7919) % n_agg]
            seqs[a] += 1
            batch.append((a, counter.CountIncremented(a, 1, seqs[a])))
        return batch

    publish(make_batch(n_agg * events_per))

    # the host read path the engine falls back to: indexed KV bytes + the
    # state deserialize chain
    host_store = InMemoryKeyValueStore()
    restore_from_events(
        log_t, "events", host_store,
        deserialize_event=lambda b: evt_fmt.read_event(
            SerializedMessage(key="", value=b)),
        serialize_state=lambda a, s: state_fmt.write_state(s).value,
        model=counter.CounterModel(), replay_spec=counter.make_replay_spec(),
        config=default_config().with_overrides(
            {"surge.replay.backend": "cpu"}))

    def host_read(agg: str):
        return state_fmt.read_state(host_store.get(agg))

    out: dict = {"resident_aggregates": n_agg,
                 "resident_seed_events": n_agg * events_per,
                 "resident_rounds": rounds}

    async def scenario() -> None:
        # the device observatory rides the measured plane (the production
        # default since ISSUE 16): the ledger's per-round accounting is what
        # the waste-ratio / per-stage rows below read, and the overhead arm
        # detaches it to prove the riding costs nothing
        ledger = ReplayLedger(name="bench:resident")
        observatory = ReplayProfiler.counters()
        plane = ResidentStatePlane(
            log_t, "events", counter.make_replay_spec(),
            config=default_config().with_overrides({
                "surge.replay.resident.capacity": max(n_agg, 8),
                "surge.replay.resident.refresh-interval-ms": 10,
            }),
            deserialize_event=lambda b: evt_fmt.read_event(
                SerializedMessage(key="", value=b)),
            serialize_state=lambda a, s: state_fmt.write_state(s).value,
            profiler=observatory, ledger=ledger)
        t0 = time.perf_counter()
        await plane.start()
        out["resident_seed_s"] = round(time.perf_counter() - t0, 2)
        log(f"resident plane seeded: {plane.occupancy()} aggregates in "
            f"{out['resident_seed_s']}s")

        def ids_for(w: int, j: int):
            return [aggs[(w * batch + j * 137 + x) % n_agg]
                    for x in range(batch)]

        async def dev_worker(w: int) -> None:
            for j in range(loops):
                got = await plane.read_many(ids_for(w, j))
                if len(got) != batch:
                    raise RuntimeError("resident projection missed")

        async def host_worker(w: int) -> None:
            for j in range(loops):
                for a in ids_for(w, j):
                    if host_read(a) is None:
                        raise RuntimeError("host read missed")

        async def dev_single(w: int) -> None:
            for j in range(reads_per_worker):
                hit, st = await plane.read_state(aggs[(w * 9176 + j * 31) % n_agg])
                if not hit or st is None:
                    raise RuntimeError("resident read missed")

        async def rung(workers: int, fn, per_worker: int) -> float:
            t0 = time.perf_counter()
            await asyncio.gather(*(fn(w) for w in range(workers)))
            return workers * per_worker / (time.perf_counter() - t0)

        # warmup: compile every rung's padded gather bucket outside the
        # measured rounds (jit caches per shape)
        for w in ladder:
            await rung(w, dev_worker, loops * batch)
        await rung(max(ladder), dev_single, reads_per_worker)

        per_rung: dict = {w: {"device": [], "host": []} for w in ladder}
        singles = []
        for rnd in range(rounds):
            for w in ladder:
                # alternate intra-round order so neither side always runs
                # into the other's cache/GC wake
                order = (("host", host_worker), ("device", dev_worker))
                if rnd % 2:
                    order = order[::-1]
                for name, fn in order:
                    per_rung[w][name].append(
                        await rung(w, fn, loops * batch))
            singles.append(await rung(max(ladder), dev_single,
                                      reads_per_worker))
        gathers0, rows0 = plane.stats["gathers"], plane.stats["gathered_rows"]
        out["resident_read_batch"] = batch
        out["resident_read_ladder"] = [{
            "workers": w,
            "device_reads_per_sec": round(statistics.median(per_rung[w]["device"])),
            "host_reads_per_sec": round(statistics.median(per_rung[w]["host"])),
            "device_vs_host": round(statistics.median(per_rung[w]["device"])
                                    / statistics.median(per_rung[w]["host"]), 2),
            "device_rounds": [round(x) for x in per_rung[w]["device"]],
            "host_rounds": [round(x) for x in per_rung[w]["host"]],
        } for w in ladder]
        out["resident_single_reads_per_sec"] = round(statistics.median(singles))
        out["resident_gather_rows_per_gather"] = round(rows0 / max(gathers0, 1), 1)
        for r in out["resident_read_ladder"]:
            log(f"read ladder @{r['workers']}x{batch}: device "
                f"{r['device_reads_per_sec']} vs host "
                f"{r['host_reads_per_sec']} reads/s ({r['device_vs_host']}x)")
        log(f"single-read surface @{max(ladder)}: "
            f"{out['resident_single_reads_per_sec']} reads/s")

        # -- sustained incremental folds through the standing refresh loop --
        folded0 = plane.stats["folded_events"]
        t0 = time.perf_counter()
        publish(make_batch(fold_events))
        while plane.lag_records() > 0:
            await asyncio.sleep(0.01)
        fold_s = time.perf_counter() - t0
        folded = plane.stats["folded_events"] - folded0
        out["resident_fold_events"] = folded
        out["resident_fold_s"] = round(fold_s, 2)
        out["resident_fold_events_per_sec"] = round(folded / fold_s)
        out["resident_fold_rounds"] = plane.stats["rounds"]
        log(f"refresh loop: {folded} events folded in {fold_s:.2f}s "
            f"({out['resident_fold_events_per_sec']} ev/s sustained)")

        # -- the device observatory's read of the same fold window --------
        summ = ledger.summary()
        stages = ledger.round_stages_us()
        med_us = lambda k: (round(statistics.median(stages[k]))  # noqa: E731
                            if stages[k] else 0)
        out["resident_waste_ratio"] = round(summ["waste_ratio"], 2)
        out["resident_us_per_slot"] = round(summ["us_per_slot"], 2)
        out["resident_stage_medians_us"] = {
            "feed": med_us("feed_us"), "encode": med_us("encode_us"),
            "dispatch": med_us("dispatch_us")}
        s = out["resident_stage_medians_us"]
        log(f"observatory: waste {out['resident_waste_ratio']}x, "
            f"{out['resident_us_per_slot']} us/slot, round medians "
            f"feed {s['feed']} / encode {s['encode']} / "
            f"dispatch {s['dispatch']} us")

        # -- observatory overhead: ledger+profiler on vs OFF, interleaved --
        # (the always-on claim: counters are perf_counter pairs + dict adds;
        # the paired medians must sit inside this host's noise band)
        obs_cycles = int(os.environ.get("SURGE_BENCH_RESIDENT_OBS_CYCLES", 4))
        obs_events = int(os.environ.get(
            "SURGE_BENCH_RESIDENT_OBS_EVENTS", 10_000))
        if obs_cycles:
            obs: dict = {"on": [], "off": []}
            for rnd in range(rounds):
                order = ("off", "on") if rnd % 2 else ("on", "off")
                for name in order:
                    plane.ledger = ledger if name == "on" else None
                    plane.profiler = observatory if name == "on" else None
                    t0 = time.perf_counter()
                    for _ in range(obs_cycles):
                        publish(make_batch(obs_events))
                        while plane.lag_records() > 0:
                            await asyncio.sleep(0.01)
                    obs[name].append(obs_cycles * obs_events
                                     / (time.perf_counter() - t0))
            out["resident_observatory_overhead"] = {
                "events_per_cycle": obs_events, "cycles": obs_cycles,
                "on_events_per_sec": round(statistics.median(obs["on"])),
                "off_events_per_sec": round(statistics.median(obs["off"])),
                "on_vs_off": round(statistics.median(obs["on"])
                                   / statistics.median(obs["off"]), 3),
                "on_rounds": [round(x) for x in obs["on"]],
                "off_rounds": [round(x) for x in obs["off"]],
            }
            o = out["resident_observatory_overhead"]
            log(f"observatory overhead: on {o['on_events_per_sec']} vs off "
                f"{o['off_events_per_sec']} ev/s ({o['on_vs_off']}x, "
                f"medians over {rounds} interleaved rounds)")
        await plane.stop()

    asyncio.run(scenario())

    # -- command-path guard: the refresh loop must not cost the write path --
    if os.environ.get("SURGE_BENCH_RESIDENT_GUARD", "1") == "1":
        secs = float(os.environ.get("SURGE_BENCH_RESIDENT_GUARD_SECONDS", 3.0))
        workers = int(os.environ.get("SURGE_BENCH_RESIDENT_GUARD_WORKERS", 64))
        guard: dict = {"off": [], "on": []}
        for rnd in range(rounds):
            order = (("off", False), ("on", True))
            if rnd % 2:
                order = order[::-1]
            for name, enabled in order:
                stats = steady_state_latency(secs, overrides={
                    "surge.replay.resident.enabled": enabled,
                }, ladder=[workers])
                guard[name].append({"commands_per_sec": stats["commands_per_sec"],
                                    "p50_ms": stats["command_p50_ms"]})
        med = lambda rows, k: statistics.median(r[k] for r in rows)  # noqa: E731
        out["resident_command_guard"] = {
            "workers": workers, "seconds": secs, "rounds": guard,
            "plane_off_commands_per_sec": round(med(guard["off"], "commands_per_sec")),
            "plane_on_commands_per_sec": round(med(guard["on"], "commands_per_sec")),
            "plane_off_p50_ms": round(med(guard["off"], "p50_ms"), 2),
            "plane_on_p50_ms": round(med(guard["on"], "p50_ms"), 2),
        }
        g = out["resident_command_guard"]
        log(f"command guard @{workers}w: plane on "
            f"{g['plane_on_commands_per_sec']} vs off "
            f"{g['plane_off_commands_per_sec']} cmds/s (medians, "
            f"p50 {g['plane_on_p50_ms']} vs {g['plane_off_p50_ms']} ms)")
    return out


def mesh_bench() -> dict:
    """SURGE_BENCH_MESH=1: the mesh-native resident plane + sharded scans on
    a forced 8-device host mesh (the tier-1 topology; on silicon the same
    arms run over real chips).

    Three measurements, each PAIRED + INTERLEAVED per the BENCH_NOTES round-6
    protocol (single runs on this host swing 2-3×; only same-round pairs and
    cross-round medians count):

    1. **Capacity fold ladder** — steady-state incremental refresh throughput
       (events/s across publish→caught-up cycles) per rung, where the RUNG IS
       THE SLAB CAPACITY, arms = ``surge.replay.mesh.gather`` local vs
       replicated. When the refresh scatter is undonated
       (``surge.replay.donate-refresh`` off — the regime BENCH_MESH_r01 was
       measured in; donation is on by default since ISSUE 18) every window
       copies the slab it writes: the replicated arm copies the FULL slab on
       every replica while the local arm copies one 1/n_dev shard each — the
       cost that scales with the resident set. The local arm holds flat up the
       ladder; the replicated arm collapses (that cliff is WHY multi-device
       is the first-class path for millions of resident aggregates).
    2. **Read row** — batched ``read_many`` projections per arm: device-local
       gathers + ONE collective vs gathers against the replicated slab. On
       forced host devices (shared memory, 2 vCPUs) this row sits near parity
       — the collective costs and the locality wins cancel; on silicon the
       replicated arm additionally pays n_dev× HBM for the slab.
    3. **Sharded-scan row** — QueryEngine grouped-aggregate scan events/s,
       mesh-sharded vs single-device, over the same columnar chunks.

    Knobs: SURGE_BENCH_MESH_AGGREGATES (512), _ROUNDS (3), _CAP_LADDER
    ("262144,1048576"), _FOLD_EVENTS (512 per cycle), _FOLD_CYCLES (16),
    _READ_WORKERS (16), _READ_BATCH (256), _READ_LOOPS (2),
    _SCAN_EVENTS (200000)."""
    import asyncio
    import statistics

    import jax

    from surge_tpu.codec.tensor import encode_events_columnar
    from surge_tpu.config import default_config
    from surge_tpu.log import InMemoryLog, LogRecord, TopicSpec
    from surge_tpu.models import counter
    from surge_tpu.replay.query import Aggregate, Predicate, QueryEngine, ScanQuery
    from surge_tpu.replay.resident_state import ResidentStatePlane
    from surge_tpu.serialization import SerializedMessage

    devs = jax.devices()
    assert len(devs) >= 8, (
        f"mesh bench needs 8 forced host devices, got {len(devs)} — main() "
        "must set xla_force_host_platform_device_count before jax init")
    mesh = jax.sharding.Mesh(np.array(devs[:8]), ("data",))

    n_agg = int(os.environ.get("SURGE_BENCH_MESH_AGGREGATES", 512))
    rounds = max(int(os.environ.get("SURGE_BENCH_MESH_ROUNDS", 3)), 1)
    cap_ladder = [int(x) for x in os.environ.get(
        "SURGE_BENCH_MESH_CAP_LADDER", "262144,1048576").split(",") if x]
    fold_events = int(os.environ.get("SURGE_BENCH_MESH_FOLD_EVENTS", 512))
    fold_cycles = int(os.environ.get("SURGE_BENCH_MESH_FOLD_CYCLES", 16))
    read_workers = int(os.environ.get("SURGE_BENCH_MESH_READ_WORKERS", 16))
    read_batch = int(os.environ.get("SURGE_BENCH_MESH_READ_BATCH", 256))
    read_loops = int(os.environ.get("SURGE_BENCH_MESH_READ_LOOPS", 2))
    scan_events = int(os.environ.get("SURGE_BENCH_MESH_SCAN_EVENTS", 200_000))

    evt_fmt = counter.event_formatting()
    state_fmt = counter.state_formatting()
    npart = 4
    aggs = [f"agg-{i}" for i in range(n_agg)]
    out: dict = {"mesh_devices": 8, "mesh_aggregates": n_agg,
                 "mesh_rounds": rounds}

    def make_plane_log():
        seqs = {a: 0 for a in aggs}
        log_t = InMemoryLog()
        log_t.create_topic(TopicSpec("events", npart))
        prod = log_t.transactional_producer("bench")

        def publish(n: int) -> None:
            prod.begin()
            for i in range(n):
                a = aggs[(i * 7919) % n_agg]
                seqs[a] += 1
                ev = counter.CountIncremented(a, 1, seqs[a])
                prod.send(LogRecord(topic="events", key=a,
                                    value=evt_fmt.write_event(ev).value,
                                    partition=hash(a) % npart))
                if i % 5000 == 4999:
                    prod.commit()
                    prod.begin()
            prod.commit()

        publish(n_agg * 4)  # the seed corpus
        return log_t, publish

    async def plane_arm(gather: str, cap: int, log_t, publish,
                        measure_reads: bool):
        """One arm at one capacity rung: steady-state fold cycles (+ the
        read row at the first rung). Returns (fold eps, reads/s|None,
        the arm's device-observatory ledger summary + stage columns)."""
        from surge_tpu.replay.ledger import ReplayLedger

        ledger = ReplayLedger(name=f"bench:mesh:{gather}")
        plane = ResidentStatePlane(
            log_t, "events", counter.make_replay_spec(),
            config=default_config().with_overrides({
                "surge.replay.resident.capacity": cap,
                "surge.replay.resident.refresh-interval-ms": 1,
                "surge.replay.mesh.gather": gather,
            }),
            deserialize_event=lambda b: evt_fmt.read_event(
                SerializedMessage(key="", value=b)),
            serialize_state=lambda a, s: state_fmt.write_state(s).value,
            mesh=mesh, ledger=ledger)
        await plane.start()
        try:
            publish(fold_events)  # warm the refresh program's shape bucket
            while plane.lag_records() > 0:
                await asyncio.sleep(0.002)
            t0 = time.perf_counter()
            for _ in range(fold_cycles):
                publish(fold_events)
                while plane.lag_records() > 0:
                    await asyncio.sleep(0.002)
            eps = fold_cycles * fold_events / (time.perf_counter() - t0)
            reads = None
            if measure_reads:
                async def reader(w: int) -> None:
                    for j in range(read_loops):
                        ids = [aggs[(w * read_batch + j * 137 + x) % n_agg]
                               for x in range(read_batch)]
                        got = await plane.read_many(ids)
                        if len(got) != read_batch:
                            raise RuntimeError("mesh projection missed")

                await reader(0)  # warm the gather bucket
                t0 = time.perf_counter()
                await asyncio.gather(*(reader(w)
                                       for w in range(read_workers)))
                reads = (read_workers * read_loops * read_batch
                         / (time.perf_counter() - t0))
            summ = ledger.summary()
            stages = ledger.round_stages_us()
            obs = {"waste_ratio": summ["waste_ratio"],
                   "us_per_slot": summ["us_per_slot"],
                   "stages": stages}
            return eps, reads, obs
        finally:
            await plane.stop()

    per_rung: dict = {c: {"local": [], "replicated": []} for c in cap_ladder}
    read_rows: dict = {"local": [], "replicated": []}
    obs_rows: dict = {"local": [], "replicated": []}
    for rnd in range(rounds):
        order = ("replicated", "local") if rnd % 2 else ("local", "replicated")
        for cap in cap_ladder:
            for arm in order:
                log_t, publish = make_plane_log()  # identical fresh log/arm
                eps, reads, obs = asyncio.run(plane_arm(
                    arm, cap, log_t, publish,
                    measure_reads=cap == cap_ladder[0]))
                per_rung[cap][arm].append(eps)
                if reads is not None:
                    read_rows[arm].append(reads)
                if cap == cap_ladder[0]:
                    obs_rows[arm].append(obs)
    med = statistics.median
    out["mesh_fold_ladder"] = [{
        "capacity": c,
        "events_per_cycle": fold_events,
        "local_events_per_sec": round(med(per_rung[c]["local"])),
        "replicated_events_per_sec": round(med(per_rung[c]["replicated"])),
        "local_vs_replicated": round(med(per_rung[c]["local"])
                                     / med(per_rung[c]["replicated"]), 2),
        "local_rounds": [round(x) for x in per_rung[c]["local"]],
        "replicated_rounds": [round(x) for x in per_rung[c]["replicated"]],
    } for c in cap_ladder]
    out["mesh_read_row"] = {
        "workers": read_workers, "batch": read_batch,
        "local_reads_per_sec": round(med(read_rows["local"])),
        "replicated_reads_per_sec": round(med(read_rows["replicated"])),
        "local_vs_replicated": round(med(read_rows["local"])
                                     / med(read_rows["replicated"]), 2),
    }
    for r in out["mesh_fold_ladder"]:
        log(f"capacity ladder @{r['capacity']}: local "
            f"{r['local_events_per_sec']} vs replicated "
            f"{r['replicated_events_per_sec']} ev/s "
            f"({r['local_vs_replicated']}x)")
    rr = out["mesh_read_row"]
    log(f"read row @{read_workers}x{read_batch}: local "
        f"{rr['local_reads_per_sec']} vs replicated "
        f"{rr['replicated_reads_per_sec']} reads/s "
        f"({rr['local_vs_replicated']}x)")

    # -- the device observatory's read of the first rung, per arm ----------
    out["mesh_observatory"] = {}
    for arm in ("local", "replicated"):
        waste = med(o["waste_ratio"] for o in obs_rows[arm])
        all_stages = {k: [v for o in obs_rows[arm]
                          for v in o["stages"][k]]
                      for k in ("feed_us", "encode_us", "dispatch_us")}
        out["mesh_observatory"][arm] = {
            "waste_ratio": round(waste, 2),
            "us_per_slot": round(med(o["us_per_slot"]
                                     for o in obs_rows[arm]), 2),
            "stage_medians_us": {
                k[:-3]: (round(med(v)) if v else 0)
                for k, v in all_stages.items()},
        }
        o = out["mesh_observatory"][arm]
        s = o["stage_medians_us"]
        log(f"observatory [{arm}]: waste {o['waste_ratio']}x, "
            f"{o['us_per_slot']} us/slot, round medians feed {s['feed']} / "
            f"encode {s['encode']} / dispatch {s['dispatch']} us")

    # -- sharded-scan throughput row (the query engine) ---------------------
    import random as _random

    rng = _random.Random(23)
    spec = counter.make_replay_spec()
    per_agg = max(scan_events // n_agg, 1)
    logs = []
    for i in range(n_agg):
        logs.append([counter.CountIncremented(str(i), rng.randrange(1, 4),
                                              k + 1)
                     for k in range(per_agg)])
    colev = encode_events_columnar(spec.registry, logs)
    colev.aggregate_ids = [str(i) for i in range(n_agg)]
    q = ScanQuery(aggregates=(Aggregate("count"),
                              Aggregate("sum", "increment_by"),
                              Aggregate("max", "sequence_number")),
                  predicates=(Predicate("increment_by", ">=", 2),))
    scans: dict = {"mesh": [], "single": []}
    engines = {"mesh": QueryEngine(spec, mesh=mesh),
               "single": QueryEngine(spec)}
    for arm, eng in engines.items():
        eng.scan_chunks([colev], q)  # warm/compile outside the timed rounds
    for rnd in range(rounds):
        order = ("single", "mesh") if rnd % 2 else ("mesh", "single")
        for arm in order:
            t0 = time.perf_counter()
            res = engines[arm].scan_chunks([colev], q)
            scans[arm].append(res.scanned_events
                              / (time.perf_counter() - t0))
    out["mesh_scan_row"] = {
        "events": colev.num_events,
        "mesh_events_per_sec": round(med(scans["mesh"])),
        "single_events_per_sec": round(med(scans["single"])),
        "mesh_vs_single": round(med(scans["mesh"]) / med(scans["single"]), 2),
    }
    sr = out["mesh_scan_row"]
    log(f"scan row @{sr['events']}ev: mesh {sr['mesh_events_per_sec']} vs "
        f"single {sr['single_events_per_sec']} ev/s "
        f"({sr['mesh_vs_single']}x)")
    return out


def ragged_bench() -> dict:
    """SURGE_BENCH_RAGGED=1: the bucketed ragged refresh dispatch (ISSUE 18),
    PAIRED + INTERLEAVED per the round-6 protocol — arms alternate within
    every round and only cross-round medians count.

    Two measurements:

    1. **Refresh ladder** — sustained incremental refresh throughput per
       shape × arm: per cycle the batch is published (untimed — the
       transactional publish is identical across arms), then the refresh
       DRAIN is timed over manual ``_refresh_once`` rounds; each arm-round's
       figure is the MEDIAN of its per-cycle drain rates (one 2-vCPU
       scheduler spike must not decide a round). Shapes: the
       device-observatory steady-ragged round (~10 lanes, short ragged
       tails — the ~9-10x over-dispatch regime BENCH_NOTES round 9 named)
       trickling into a PRODUCTION-sized 64Ki-row resident set, and the
       uniform dense 512-lane round. Arms: **dense** is the pre-PR refresh
       of record (the single ``[pow8(lanes), window]`` rectangle per
       window AND the copying scatter — ``donate-refresh`` off),
       **bucketed** the new defaults (one fused program per occupied pow2
       length bucket, donated scatter). Waste ratios, µs/slot and per-stage medians read off
       each arm's ReplayLedger (the PR-16 pattern: the payload and
       ``DumpReplayLedger`` cannot disagree).
    2. **Donation probe** — the 1M-row mesh-local refresh device leg,
       donate-refresh on vs off (paired, interleaved): round-10 measured
       19 ms/window (local) vs 49 ms (replicated) at this rung and named
       the undonated slab copy as the cost; the donated arm must beat the
       copying arm on the same host.

    Knobs: SURGE_BENCH_RAGGED_ROUNDS (3), _CYCLES (24 publish→drain
    cycles per arm), _DENSE_LANES (512), _CAPACITY (65536 — the steady
    shape's resident set), _PROBE_CAPACITY (1048576), _PROBE_CYCLES (4),
    _PROBE (1 — 0 skips the mesh probe)."""
    import asyncio
    import random
    import statistics

    import jax

    from surge_tpu.config import default_config
    from surge_tpu.log import InMemoryLog, LogRecord, TopicSpec
    from surge_tpu.models import counter
    from surge_tpu.replay.ledger import ReplayLedger
    from surge_tpu.replay.resident_state import ResidentStatePlane
    from surge_tpu.serialization import SerializedMessage

    rounds = max(int(os.environ.get("SURGE_BENCH_RAGGED_ROUNDS", 3)), 1)
    cycles = int(os.environ.get("SURGE_BENCH_RAGGED_CYCLES", 24))
    dense_lanes = int(os.environ.get("SURGE_BENCH_RAGGED_DENSE_LANES", 512))
    steady_cap = int(os.environ.get("SURGE_BENCH_RAGGED_CAPACITY", 65536))
    probe_cap = int(os.environ.get(
        "SURGE_BENCH_RAGGED_PROBE_CAPACITY", 1_048_576))
    probe_cycles = int(os.environ.get("SURGE_BENCH_RAGGED_PROBE_CYCLES", 4))
    run_probe = os.environ.get("SURGE_BENCH_RAGGED_PROBE", "1") == "1"

    evt_fmt = counter.event_formatting()
    state_fmt = counter.state_formatting()
    npart = 4
    med = statistics.median

    # the dense arm is the PRE-PR refresh of record — the single padded
    # rectangle per window AND the copying (undonated) scatter, exactly what
    # shipped before ISSUE 18; bucketed rides the new
    # defaults (bucketed dispatch + donated scatter). The decompositions
    # stay isolated: waste_ratio measures bucketing alone, the 1M-row probe
    # measures donation alone (both its arms bucketed).
    ARMS = {
        "dense": {"surge.replay.resident.refresh-dispatch": "dense",
                  "surge.replay.donate-refresh": False},
        "bucketed": {"surge.replay.resident.refresh-dispatch": "bucketed"},
    }
    # (lanes, tails(rng) -> per-lane event count) — every arm of a round
    # replays the IDENTICAL per-cycle workload (same seed, fresh log). The
    # steady-ragged shape is the observatory's (~10 lanes, short tails):
    # tails 5-8 land in ONE pow2 width bucket, so the bucketed arm's win is
    # pure lane-padding shed ([16,8] vs the dense [64,8] rectangle) — rounds
    # whose tails straddle several width buckets additionally pay one
    # program call per bucket, which on this 2-vCPU host is the dominant
    # cost at 10-lane sizes (see BENCH_NOTES round 11's honest-read)
    # the steady-ragged shape runs against a PRODUCTION-sized resident set
    # (_CAPACITY rows, not the observatory test's 64): trickling ragged
    # updates into a big slab is the round-9/10 roofline regime, and the
    # capacity is what the pre-PR copying scatter pays per window
    SHAPES = {
        "steady_ragged": (10, lambda rng: rng.randrange(5, 9), steady_cap),
        f"dense_{dense_lanes}": (dense_lanes, lambda rng: 4, dense_lanes),
    }

    def make_arm_log(n_lanes):
        log_t = InMemoryLog()
        log_t.create_topic(TopicSpec("events", npart))
        prod = log_t.transactional_producer("bench")
        seqs = {f"agg-{i}": 0 for i in range(n_lanes)}

        def publish(batch):
            prod.begin()
            for a, n in batch:
                for _ in range(n):
                    seqs[a] += 1
                    ev = counter.CountIncremented(a, 1, seqs[a])
                    prod.send(LogRecord(topic="events", key=a,
                                        value=evt_fmt.write_event(ev).value,
                                        partition=hash(a) % npart))
            prod.commit()
        return log_t, publish

    def make_plane(log_t, cap, ledger, overrides, mesh=None):
        return ResidentStatePlane(
            log_t, "events", counter.make_replay_spec(),
            config=default_config().with_overrides({
                "surge.replay.resident.capacity": cap,
                "surge.replay.resident.refresh-interval-ms": 1,
                "surge.replay.time-chunk": 8,
                **overrides,
            }),
            deserialize_event=lambda b: evt_fmt.read_event(
                SerializedMessage(key="", value=b)),
            serialize_state=lambda a, s: state_fmt.write_state(s).value,
            mesh=mesh, ledger=ledger)

    async def refresh_arm(arm, shape, seed):
        n_lanes, tails, cap = SHAPES[shape]
        rng = random.Random(seed)
        batches = [[(f"agg-{i}", tails(rng)) for i in range(n_lanes)]
                   for _ in range(cycles + 1)]
        log_t, publish = make_arm_log(n_lanes)
        ledger = ReplayLedger(name=f"bench:ragged:{arm}")
        plane = make_plane(log_t, cap, ledger, ARMS[arm])
        plane._ensure_device_state()
        plane.seed_from_log()
        try:
            publish(batches[0])  # warm the arm's program shapes
            while plane.lag_records() > 0:
                await plane._refresh_once()
            # the timed leg is the refresh DRAIN, driven by manual rounds
            # (no refresh timer, no catch-up poll — both would quantize a
            # sub-ms drain): the transactional publish is identical across
            # arms and ~4x the refresh at the steady-ragged size, so
            # publish-inclusive rates are flat no matter what the dispatch
            # arm does.  Per-cycle rates + median: one scheduler/GC spike
            # on the 2-vCPU host must not decide a round.
            cyc_rates = []
            for batch in batches[1:]:
                publish(batch)
                t0 = time.perf_counter()
                while plane.lag_records() > 0:
                    await plane._refresh_once()
                cyc_rates.append(sum(n for _, n in batch)
                                 / (time.perf_counter() - t0))
            eps = med(cyc_rates)
            summ = ledger.summary()
            stages = ledger.round_stages_us()
            return eps, {
                "waste_ratio": summ["waste_ratio"],
                "us_per_slot": summ["us_per_slot"],
                "bucket_programs": summ["bucket_programs"],
                "bucket_fill_ratio": (
                    round(summ["lanes"] / summ["bucket_lane_slots"], 3)
                    if summ["bucket_lane_slots"] else None),
                "dispatch_us_median": (round(med(stages["dispatch_us"]))
                                       if stages["dispatch_us"] else 0),
            }
        finally:
            await plane.stop()

    out: dict = {"ragged_rounds": rounds, "ragged_cycles": cycles,
                 "protocol": {"interleaved": True, "medians": True}}
    arm_names = list(ARMS)
    per: dict = {s: {a: {"eps": [], "obs": []} for a in ARMS} for s in SHAPES}
    for rnd in range(rounds):
        order = arm_names[::-1] if rnd % 2 else arm_names
        for shape in SHAPES:
            for arm in order:
                eps, obs = asyncio.run(refresh_arm(arm, shape, seed=rnd))
                per[shape][arm]["eps"].append(eps)
                per[shape][arm]["obs"].append(obs)
    out["ragged_ladder"] = {}
    for shape in SHAPES:
        row = {}
        for arm in ARMS:
            eps_rounds = per[shape][arm]["eps"]
            obs = per[shape][arm]["obs"]
            row[arm] = {
                "events_per_sec_median": round(med(eps_rounds)),
                "rounds": [round(x) for x in eps_rounds],
                "waste_ratio": round(med(o["waste_ratio"] for o in obs), 2),
                "us_per_slot": round(med(o["us_per_slot"] for o in obs), 2),
                "dispatch_us_median": round(
                    med(o["dispatch_us_median"] for o in obs)),
                "bucket_fill_ratio": obs[0]["bucket_fill_ratio"],
            }
        row["bucketed_vs_dense"] = round(
            row["bucketed"]["events_per_sec_median"]
            / row["dense"]["events_per_sec_median"], 2)
        row["waste_reduction"] = round(
            row["dense"]["waste_ratio"]
            / row["bucketed"]["waste_ratio"], 2)
        row["bucketed_wins_every_round"] = all(
            b > d for b, d in zip(per[shape]["bucketed"]["eps"],
                                  per[shape]["dense"]["eps"]))
        out["ragged_ladder"][shape] = row
        log(f"ragged ladder [{shape}]: dense "
            f"{row['dense']['events_per_sec_median']} vs bucketed "
            f"{row['bucketed']['events_per_sec_median']} ev/s; "
            f"waste {row['dense']['waste_ratio']}x -> "
            f"{row['bucketed']['waste_ratio']}x "
            f"({row['waste_reduction']}x less), bucketed wins every round: "
            f"{row['bucketed_wins_every_round']}")

    # -- the 1M-row donation probe (mesh-local, donate on vs off) -----------
    if run_probe:
        devs = jax.devices()
        assert len(devs) >= 8, (
            "ragged donation probe needs 8 forced host devices — main() "
            "must set xla_force_host_platform_device_count before jax init")
        mesh = jax.sharding.Mesh(np.array(devs[:8]), ("data",))
        probe_aggs = 512

        async def probe_arm(donate: bool):
            log_t, publish = make_arm_log(probe_aggs)
            ledger = ReplayLedger(name="bench:ragged:probe")
            plane = make_plane(log_t, probe_cap, ledger, {
                "surge.replay.donate-refresh": donate}, mesh=mesh)
            await plane.start()
            try:
                batch = [(f"agg-{i}", 2) for i in range(probe_aggs)]
                publish(batch)  # warm/compile outside the timed cycles
                while plane.lag_records() > 0:
                    await asyncio.sleep(0.002)
                warm_rounds = ledger.totals["rounds"]
                for _ in range(probe_cycles):
                    publish(batch)
                    while plane.lag_records() > 0:
                        await asyncio.sleep(0.002)
                # per-window device dispatch of the timed rounds only (the
                # warm cycle's rounds carry the compiles)
                per_window = [ev["dispatch_us"] / max(ev["windows"], 1)
                              for i, ev in enumerate(
                                  e for e in ledger.events()
                                  if e["type"] == "round")
                              if i >= warm_rounds]
                return {
                    "ms_per_window": round(med(per_window) / 1000.0, 2)
                    if per_window else 0.0,
                    "windows": int(ledger.totals["windows"]),
                }
            finally:
                await plane.stop()

        probe: dict = {"capacity": probe_cap, "donated": [], "copying": []}
        for rnd in range(rounds):
            order = ((False, True) if rnd % 2 else (True, False))
            for donate in order:
                r = asyncio.run(probe_arm(donate))
                probe["donated" if donate else "copying"].append(
                    r["ms_per_window"])
        out["donation_probe"] = {
            "capacity": probe_cap,
            "donated_ms_per_window": round(med(probe["donated"]), 2),
            "copying_ms_per_window": round(med(probe["copying"]), 2),
            "donated_vs_copying": round(
                med(probe["copying"]) / med(probe["donated"]), 2)
            if med(probe["donated"]) else 0.0,
            "round10_local_ms_per_window": 19.0,
            "donated_rounds": probe["donated"],
            "copying_rounds": probe["copying"],
        }
        p = out["donation_probe"]
        log(f"donation probe @{probe_cap} rows: donated "
            f"{p['donated_ms_per_window']} vs copying "
            f"{p['copying_ms_per_window']} ms/window "
            f"({p['donated_vs_copying']}x; round-10 undonated local "
            f"figure: 19 ms)")
    return out


def main() -> None:
    # every phase runs on the host CPU — pin it before any jax-importing
    # module loads
    os.environ["JAX_PLATFORMS"] = "cpu"
    if (os.environ.get("SURGE_BENCH_MESH", "0") == "1"
            or os.environ.get("SURGE_BENCH_RAGGED", "0") == "1"):
        # the mesh arms (and the ragged bench's 1M-row donation probe) need
        # the tier-1 topology: force 8 host devices BEFORE the first jax
        # backend initialization (flag changes after init are silently
        # ignored)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    payload: dict = {"metric": "commands_per_sec", "value": 0,
                     "unit": "commands/s"}

    try:
        latency_seconds = float(os.environ.get("SURGE_BENCH_LATENCY_SECONDS", 5))
    except ValueError:
        latency_seconds = 0.0
        payload["latency_error"] = "unparseable SURGE_BENCH_LATENCY_SECONDS"

    # SURGE_BENCH_FAILOVER=1: leader-kill chaos bench — unavailability
    # window + zero-loss/zero-duplicate proof
    if os.environ.get("SURGE_BENCH_FAILOVER", "0") == "1":
        payload = {"metric": "failover_unavailability_ms", "value": 0,
                   "unit": "ms"}
        stats = failover_bench()
        payload.update(stats)
        payload["value"] = stats.get("failover_unavailability_ms") or 0
        emit(payload)
        return

    # SURGE_BENCH_ANATOMY=1: traced command phase → the per-leg critical-path
    # attribution table alongside the phase's latency medians, so the next
    # perf PR starts from where-the-time-went evidence, not ladder guesses
    if os.environ.get("SURGE_BENCH_ANATOMY", "0") == "1":
        payload = {"metric": "command_p99_ms", "value": 0, "unit": "ms"}
        stats = anatomy_bench()
        payload.update(stats)
        payload["value"] = stats.get("command_p99_ms") or 0
        emit(payload)
        return

    # SURGE_BENCH_SOAK=1: sustained seeded chaos soak — a 3+-broker spread
    # cluster under rolling kills, link faults, membership churn and Zipf
    # skew, scored by the SLO engine; the verdict is 0 lost / 0 duplicated,
    # exactly one leader per partition, every burn-rate page cleared after
    # its heal, and the autobalancer's decisions on the merged timeline
    if os.environ.get("SURGE_BENCH_SOAK", "0") == "1":
        payload = {"metric": "soak_acked_commits", "value": 0, "unit": "ok"}
        stats = soak_bench()
        payload.update(stats)
        payload["value"] = stats.get("soak_acked_commits", 0)
        emit(payload)
        return

    # SURGE_BENCH_SAGA=1: the saga-storm chaos soak — hundreds of two-step
    # transfer sagas (a seeded fraction forced into the compensation walk)
    # vs rolling broker kills, link faults and a mid-storm manager restart;
    # the verdict is 0 lost / 0 duplicated / 0 half-compensated with the
    # ledger-reconciliation invariant checked per saga row
    if os.environ.get("SURGE_BENCH_SAGA", "0") == "1":
        payload = {"metric": "saga_started", "value": 0, "unit": "ok"}
        stats = saga_bench()
        payload.update(stats)
        payload["value"] = stats.get("saga_started", 0)
        emit(payload)
        return

    # SURGE_BENCH_HANDOFF=1: planned-handoff ladder — handoff vs
    # kill-failover vs full-replay cold start, paired interleaved medians
    if os.environ.get("SURGE_BENCH_HANDOFF", "0") == "1":
        payload = {"metric": "handoff_unavailability_ms", "value": 0,
                   "unit": "ms"}
        stats = handoff_bench()
        payload.update(stats)
        payload["value"] = stats.get("handoff_unavailability_ms_median") or 0
        emit(payload)
        return

    # SURGE_BENCH_MESH=1: mesh-native resident plane + sharded scans —
    # paired interleaved device-local vs replicated-slab arms (fold ladder +
    # read row) plus the query-engine sharded-scan throughput row
    if os.environ.get("SURGE_BENCH_MESH", "0") == "1":
        payload = {"metric": "mesh_fold_events_per_sec", "value": 0,
                   "unit": "events/s"}
        stats = mesh_bench()
        payload.update(stats)
        payload["value"] = max(r["local_events_per_sec"]
                               for r in stats["mesh_fold_ladder"])
        emit(payload)
        return

    # SURGE_BENCH_RAGGED=1: bucketed ragged refresh dispatch — paired
    # interleaved dense vs bucketed arms on the steady-ragged and dense
    # shapes, plus the 1M-row donation probe
    if os.environ.get("SURGE_BENCH_RAGGED", "0") == "1":
        payload = {"metric": "ragged_fold_events_per_sec", "value": 0,
                   "unit": "events/s"}
        stats = ragged_bench()
        payload.update(stats)
        payload["value"] = max(
            row["bucketed"]["events_per_sec_median"]
            for row in stats["ragged_ladder"].values())
        emit(payload)
        return

    # SURGE_BENCH_RESIDENT=1: device-resident read-plane fast path — read
    # ladder + refresh-loop folds + command guard.
    if os.environ.get("SURGE_BENCH_RESIDENT", "0") == "1":
        payload = {"metric": "resident_reads_per_sec", "value": 0,
                   "unit": "reads/s"}
        stats = resident_bench()
        payload.update(stats)
        payload["value"] = max(r["device_reads_per_sec"]
                               for r in stats["resident_read_ladder"])
        emit(payload)
        return

    # SURGE_BENCH_RESIDENT_FEED=1: paired resident sustained-fold arms —
    # native feed vs per-event Python feed over the same FileLog tail
    if os.environ.get("SURGE_BENCH_RESIDENT_FEED", "0") == "1":
        payload = {"metric": "resident_feed_events_per_sec", "value": 0,
                   "unit": "events/s"}
        stats = resident_feed_paired()
        payload["resident_feed_paired"] = stats
        payload["value"] = stats["native_feed_events_per_sec_median"]
        emit(payload)
        return

    # SURGE_BENCH_VIEWS=1: paired interleaved materialized-view-read vs
    # scan-per-read reader ladder off the resident plane's refresh feed
    if os.environ.get("SURGE_BENCH_VIEWS", "0") == "1":
        payload = {"metric": "view_reads_per_sec", "value": 0,
                   "unit": "reads/s"}
        stats = views_paired()
        payload["views_paired"] = stats
        payload["value"] = max(
            r["view_read"]["reads_per_sec_median"] for r in stats["rungs"])
        emit(payload)
        return

    # SURGE_BENCH_LADDER=1: command-path fast path — the throughput ladder
    # + producer sweep, no restore phase
    if os.environ.get("SURGE_BENCH_LADDER", "0") == "1":
        secs = latency_seconds if latency_seconds > 0 else 5.0
        # SURGE_BENCH_LANE=1 (the r08 protocol): paired interleaved
        # direct-lane vs classic-lane medians, inproc AND grpc rungs
        if os.environ.get("SURGE_BENCH_LANE", "0") == "1":
            rounds = int(os.environ.get("SURGE_BENCH_LANE_ROUNDS", 3))
            rungs = [int(t) for t in os.environ.get(
                "SURGE_BENCH_LATENCY_LADDER", "").split(",")
                if t.strip().isdigit()] or [64, 1024]
            brokers = [b.strip() for b in os.environ.get(
                "SURGE_BENCH_LANE_BROKERS", "inproc,grpc").split(",")
                if b.strip()]
            paired = lane_paired_ladder(secs, rounds=rounds, rungs=rungs,
                                        brokers=brokers)
            payload["lane_paired_ladder"] = paired
            payload["value"] = max(
                r["direct"]["commands_per_sec_median"]
                for rows in paired["ladders"].values() for r in rows)
            emit(payload)
            return
        # SURGE_BENCH_NATIVE=1 (the r07 protocol): paired interleaved
        # native-on vs native-off medians at the 64 + 1024 rungs
        if os.environ.get("SURGE_BENCH_NATIVE", "0") == "1":
            rounds = int(os.environ.get("SURGE_BENCH_NATIVE_ROUNDS", 3))
            rungs = [int(t) for t in os.environ.get(
                "SURGE_BENCH_LATENCY_LADDER", "").split(",")
                if t.strip().isdigit()] or [64, 1024]
            paired = native_paired_ladder(
                secs, rounds=rounds, rungs=rungs,
                broker=os.environ.get("SURGE_BENCH_NATIVE_BROKER", "inproc"))
            payload["native_paired_ladder"] = paired
            payload["value"] = max(
                r["native_on"]["commands_per_sec_median"]
                for r in paired["rungs"])
            emit(payload)
            return
        stats = steady_state_latency(secs)
        payload.update(stats)
        payload["value"] = stats["peak_commands_per_sec"]
        log(f"ladder fast path: p50 {stats['command_p50_ms']}ms at "
            f"{stats['latency_workers']} workers, peak "
            f"{stats['peak_commands_per_sec']} commands/s")
        if os.environ.get("SURGE_BENCH_SWEEP", "1") == "1":
            payload["producer_sweep"] = producer_sweep(secs)
        emit(payload)
        return

    if latency_seconds > 0:
        try:
            stats = steady_state_latency(latency_seconds)
            log(f"steady state: p50 {stats['command_p50_ms']}ms, "
                f"p99 {stats['command_p99_ms']}ms, "
                f"{stats['commands_per_sec']} commands/s")
            payload.update(stats)
            if os.environ.get("SURGE_BENCH_SWEEP", "1") == "1":
                try:
                    payload["producer_sweep"] = producer_sweep(latency_seconds)
                except Exception as exc:  # noqa: BLE001
                    log(f"producer sweep failed: {exc!r}")
                    payload["sweep_error"] = f"{type(exc).__name__}: {exc}"
        except Exception as exc:  # noqa: BLE001 — must not void the restore phase
            log(f"steady-state latency phase failed: {exc!r}")
            payload["latency_error"] = f"{type(exc).__name__}: {exc}"

    # -- optional restore phase: full vs checkpointed cold start ------------------
    if os.environ.get("SURGE_BENCH_RESTORE", "0") == "1":
        try:
            payload.update(restore_bench())
        except Exception as exc:  # noqa: BLE001 — must not void the latency phase
            log(f"restore bench phase failed: {exc!r}")
            payload["restore_error"] = f"{type(exc).__name__}: {exc}"

    payload["value"] = payload.get("peak_commands_per_sec", 0)
    emit(payload)


if __name__ == "__main__":
    try:
        main()
    except BaseException as err:  # terminal failure must still emit one JSON line
        import traceback

        traceback.print_exc(file=sys.stderr)
        # never clobber an already-measured result with a value-0 line: re-emit the
        # last printed payload with the error attached (last line wins)
        final = dict(_last_printed) if _last_printed else {
            "metric": "commands_per_sec", "value": 0, "unit": "commands/s"}
        final["error"] = f"{type(err).__name__}: {err}"
        print(json.dumps(final), flush=True)
        sys.exit(1)
