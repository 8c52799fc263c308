"""Driver of one served engine node under an open loop.

Set-up preloads a ``FileLog`` through the transactional producer, starts the
engine over it (restore + plane seed: ``cold_start_s``), and warms the shapes
the window will use: the gather ladder, bursts that make the refresh buckets,
and a few seconds of the cell's own traffic. The window launches every
operation of a precomputed schedule at its due time, whether or not earlier
ones have finished, on the engine's own event loop; a latency runs from the due
time. Afterwards: drain, wait for the plane's watermark to reach the log end,
read every touched aggregate back through both read paths, stop the engine,
and only then hold every ack, read and read-back to the plain reference.
"""

from __future__ import annotations

import asyncio
import os
import shutil
import tempfile
import time

import numpy as np

from benchmarks import gen, reference


class Window:
    """What one open-loop window did: per operation, seconds after its opening."""

    def __init__(self, schedule: gen.Schedule) -> None:
        n = len(schedule.due)
        self.schedule = schedule
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.refused = 0
        self.raised = 0
        self.backlog_at_close = 0
        self.close_s = 0.0
        self.drained_s = 0.0

    def latencies_ms(self, commands: bool) -> np.ndarray:
        """Done less due, every operation of the kind; one that was never
        answered counts from its due time to the end of the drain."""
        pick = self.schedule.is_command == commands
        done = np.where(np.isnan(self.done), self.drained_s, self.done)
        return (done[pick] - self.schedule.due[pick]) * 1e3

    def late_ms(self) -> np.ndarray:
        return (self.sent - self.schedule.due)[~np.isnan(self.sent)] * 1e3


class Node:
    def __init__(self, run) -> None:
        from surge_tpu import create_engine
        from surge_tpu.config import default_config
        from surge_tpu.engine.business_logic import SurgeCommandBusinessLogic
        from surge_tpu.engine.entity import CommandSuccess
        from surge_tpu.log.file import FileLog
        from surge_tpu.models import counter

        self.run = run
        self.counter, self.CommandSuccess = counter, CommandSuccess
        self.n_agg = run.sizes["aggregates"]
        self.per = run.sizes["preloaded_events_per_aggregate"]
        self.kinds = gen.preload_kinds(self.n_agg, self.per, run.seed)
        self.ids = [f"agg-{i}" for i in range(self.n_agg)]
        self.acks: dict = {}  # aggregate index -> [(delta, count, version)]
        self.reads: list = []  # (aggregate index, count, version)
        self.unanswered = 0
        self.signals: list = []
        self.workdir = tempfile.mkdtemp(prefix="surge-bench-node-")
        self.log = FileLog(os.path.join(self.workdir, "log"))
        overrides = dict(run.config["fixes"]["engine_overrides"])
        if run.rehearse:
            overrides.update(run.config.get("rehearse_overrides", {}))
        overrides["surge.replay.resident.capacity"] = (
            self.n_agg + run.sizes["capacity_headroom"])
        self.engine = create_engine(
            SurgeCommandBusinessLogic(
                aggregate_name="counter", model=counter.CounterModel(),
                state_format=counter.state_formatting(),
                event_format=counter.event_formatting()),
            log=self.log, config=default_config().with_overrides(overrides))
        self.engine.health_bus.subscribe(lambda s: self.signals.append(s.name))

    # -- set-up ---------------------------------------------------------------

    def preload(self) -> None:
        """The preloaded log, stamped as the command path would stamp it."""
        from surge_tpu.log import LogRecord

        c = self.counter
        fmt, topic = self.engine.logic.event_format, self.engine.logic.events_topic
        every = self.run.config["preload"]["commit_every_aggregates"]
        prod = self.log.transactional_producer("bench-preload")
        prod.begin()
        for i, agg in enumerate(self.ids):
            p = self.engine.router.partition_for(agg)
            version = 0
            for k in self.kinds[i].tolist():
                if k == 2:
                    ev = c.NoOpEvent(agg, version + 1)
                else:
                    version += 1
                    ev = (c.CountIncremented, c.CountDecremented)[k](
                        agg, 1, version)
                prod.send(LogRecord(topic=topic, key=agg,
                                    value=fmt.write_event(ev).value, partition=p))
            if i % every == every - 1:
                prod.commit()
                prod.begin()
        prod.commit()

    async def cold_start(self) -> tuple:
        """``engine.start()`` to the first ``project_states`` answer, and how many
        of its rows differ from the preloaded reference (compared later)."""
        sample = gen.sample_aggregates(
            self.n_agg, self.run.config["check"]["cold_start_sample"],
            self.run.seed).tolist()
        t0 = time.perf_counter()
        await self.engine.start()
        got = await self.engine.project_states([self.ids[i] for i in sample])
        cold_start_s = time.perf_counter() - t0
        for i in sample:
            self._note_read(i, got.get(self.ids[i]))
        plane = self.engine.resident_plane
        missing = self.n_agg - (plane.occupancy()
                                if plane is not None and plane.running else 0)
        return cold_start_s, missing

    # -- operations -------------------------------------------------------------

    def _note_read(self, i: int, state) -> None:
        self.reads.append((i, state.count, state.version) if state is not None
                          else (i, None, None))

    async def command(self, i: int, increment: bool) -> bool:
        c = self.counter
        agg = self.ids[i]
        with self.run.span("send_command"):
            res = await self.engine.aggregate_for(agg).send_command(
                c.Increment(agg) if increment else c.Decrement(agg))
        if not isinstance(res, self.CommandSuccess):
            return False
        self.acks.setdefault(i, []).append(
            (1 if increment else -1, res.state.count, res.state.version))
        return True

    async def read(self, i: int) -> None:
        agg = self.ids[i]
        with self.run.span("project_states"):
            got = await self.engine.project_states([agg])
        self._note_read(i, got.get(agg))

    async def settle(self) -> bool:
        """Wait for the plane's fold watermark to reach the log end."""
        plane = self.engine.resident_plane
        deadline = time.monotonic() + self.run.config["check"]["settle_seconds"]
        while plane.lag_records() > 0:
            if time.monotonic() > deadline:
                return False
            await asyncio.sleep(0.02)
        return True

    async def _train(self, i: int, width: int, increment: bool) -> int:
        """``width`` commands to one aggregate, each sent when the one before
        is acknowledged; how many were acknowledged."""
        ok = 0
        for _ in range(width):
            ok += await self.command(i, increment)
        return ok

    async def warm_up(self) -> None:
        warm = self.run.config["warmup"]
        rng = np.random.default_rng([self.run.seed, 0x3C])
        for k in warm["gather_ladder"]:
            pick = rng.choice(self.n_agg, size=min(k, self.n_agg), replace=False)
            got = await self.engine.project_states([self.ids[i] for i in pick])
            for i in pick.tolist():
                self._note_read(i, got.get(self.ids[i]))
        for lanes, width in warm["bursts"]:
            pick = rng.choice(self.n_agg, size=min(lanes, self.n_agg),
                              replace=False).tolist()
            oks = await asyncio.gather(*(
                self._train(i, width, bool(rng.integers(0, 2))) for i in pick))
            self.unanswered += sum(width - ok for ok in oks)
            await self.settle()
        if warm["traffic_seconds"] > 0 and self.run.traffic.get("loop") == "open":
            schedule = gen.open_loop_schedule(
                self.run.traffic, self.n_agg, min(warm["traffic_seconds"],
                                                  max(self.run.seconds, 1.0)),
                self.run.seed ^ 0x5EED)
            w = await self.window(schedule, trace=False)
            self.unanswered += int(np.isnan(w.done).sum()) + w.refused + w.raised
        await self.settle()

    # -- the window ---------------------------------------------------------------

    async def _op(self, w: Window, k: int, t_open: float) -> None:
        s = w.schedule
        w.sent[k] = time.perf_counter() - t_open
        try:
            if s.is_command[k]:
                if not await self.command(int(s.key[k]), bool(s.is_increment[k])):
                    w.refused += 1
                    return
            else:
                await self.read(int(s.key[k]))
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — an operation that raises has failed
            w.raised += 1
            return
        w.done[k] = time.perf_counter() - t_open

    async def window(self, schedule: gen.Schedule, trace: bool,
                     trace_seconds: float = 3.0) -> Window:
        w = Window(schedule)
        due = schedule.due
        n = len(due)
        seconds = float(due[-1]) if n else 0.0
        trace_at = max(0.0, seconds - trace_seconds) if trace else None
        loop = asyncio.get_running_loop()
        tasks = []
        k = 0
        t_open = time.perf_counter()
        while k < n:
            now = time.perf_counter() - t_open
            if trace_at is not None and now >= trace_at:
                self.run.start_trace()
                trace_at = None
                continue
            if due[k] > now:
                await asyncio.sleep(due[k] - now)
                continue
            while k < n and due[k] <= now:
                tasks.append(loop.create_task(self._op(w, k, t_open)))
                k += 1
        w.close_s = time.perf_counter() - t_open
        w.backlog_at_close = sum(1 for t in tasks if not t.done())
        if tasks:
            _done, pending = await asyncio.wait(
                tasks, timeout=self.run.config["check"]["drain_seconds"])
            for t in pending:
                t.cancel()
            if pending:
                await asyncio.wait(pending, timeout=5)
        w.drained_s = time.perf_counter() - t_open
        self.run.stop_trace()
        return w

    # -- counters -----------------------------------------------------------------

    def counters(self) -> dict:
        plane = self.engine.resident_plane
        prod = self.engine.producer_stats()
        return {**{k: int(v) for k, v in plane.stats.items()},
                "flushes": int(prod["flushes"]),
                "records_published": int(prod["records_published"])}

    # -- after the window -----------------------------------------------------------

    async def read_back(self) -> dict:
        """Every touched aggregate on the settled node through both read paths,
        and whether the slab (not the host store behind it) did the serving."""
        plane = self.engine.resident_plane
        settled = await self.settle()
        touched = sorted(self.acks)
        back = {i: [] for i in touched}
        rows0, fallbacks0 = plane.stats["gathered_rows"], plane.stats["fallbacks"]
        got = await self.engine.project_states([self.ids[i] for i in touched])
        not_off_slab = (len(touched) - (plane.stats["gathered_rows"] - rows0)
                        + plane.stats["fallbacks"] - fallbacks0)
        for i in touched:
            st = got.get(self.ids[i])
            back[i].append((st.count, st.version) if st is not None
                           else (None, None))
        for lo in range(0, len(touched), 512):
            chunk = touched[lo: lo + 512]
            states = await asyncio.gather(*(
                self.engine.aggregate_for(self.ids[i]).get_state()
                for i in chunk))
            for i, st in zip(chunk, states):
                back[i].append((st.count, st.version) if st is not None
                               else (None, None))
        lane_errors = int(self.engine.metrics_registry.get_metrics()[
            "surge.replay.resident.fallback-reads.lane-error"])
        lane_errors += int(plane.fallback_causes.get("lane-error", 0))
        bad_signals = [s for s in self.signals
                       if "refresh-error" in s or "gather-error" in s]
        short = sum(1 for p in plane.partitions if plane.partition_lag(p) > 0)
        return {"readback": back, "rows_not_off_slab": max(not_off_slab, 0),
                "lane_errors": lane_errors, "error_signals": len(bad_signals),
                "fallback_causes": dict(plane.fallback_causes),
                "watermark_short": short + (0 if settled else 1),
                "no_gather_ran": int(plane.stats["gathers"] == 0)}

    async def stop(self) -> None:
        try:
            await self.engine.stop()
        finally:
            self.log.close()
            shutil.rmtree(self.workdir, ignore_errors=True)


def p95(values: np.ndarray) -> float:
    return float(np.percentile(values, 95)) if len(values) else float("nan")


async def serve(run) -> dict:
    node = Node(run)
    try:
        t0 = time.perf_counter()
        with run.span("preload"):
            node.preload()
        preload_s = time.perf_counter() - t0
        cold_start_s, seed_missing = await node.cold_start()
        t0 = time.perf_counter()
        await node.warm_up()
        warm_up_s = time.perf_counter() - t0
        schedule = gen.open_loop_schedule(run.traffic, node.n_agg, run.seconds,
                                          run.seed)
        before = node.counters()
        reads_before = len(node.reads)
        run.window_opens()
        w = await node.window(schedule, trace=run.trace)
        after = node.counters()
        run.window_closed()
        back = await node.read_back()
    finally:
        await node.stop()

    # the program is stopped and its state freed: now the reference
    n_reads = len(node.reads) - reads_before
    run.counters = {k: after[k] - before[k] for k in after}
    run.counters["rows_asked"] = n_reads
    late = w.late_ms()
    unanswered = int(np.isnan(w.done).sum()) - w.refused - w.raised
    run.facts = {"operations": len(schedule.due),
                 "commands": int(schedule.is_command.sum()),
                 "rate_ops_per_s": len(schedule.due) / run.seconds,
                 "backlog_at_close": w.backlog_at_close,
                 "drain_s": w.drained_s - w.close_s,
                 "gen_late_p95_ms": p95(late), "cold_start_s": cold_start_s,
                 "touched": len(node.acks)}
    base_count, base_version = reference.preloaded_states(node.kinds)
    verdict = reference.judge_node(base_count, base_version, node.acks,
                                   node.reads, back["readback"])
    compared = [("acks_wrong", verdict["acks_wrong"], 0),
                ("reads_wrong", verdict["reads_wrong"], 0),
                ("readback_wrong", verdict["readback_wrong"], 0),
                ("unanswered", unanswered + node.unanswered, 0),
                ("refused", w.refused + w.raised, 0),
                ("seed_missing", seed_missing, 0),
                ("rows_not_off_slab", back["rows_not_off_slab"], 0),
                ("watermark_short", back["watermark_short"], 0),
                ("no_gather_ran", back["no_gather_ran"], 0),
                # the device path did the serving: a read that failed over to
                # the host store after a lane error is answered right by a
                # store the cell does not measure
                ("lane_errors", back["lane_errors"], 0),
                ("error_signals", back["error_signals"], 0)]
    failed = int(np.isnan(w.done).sum())
    ack_ms, read_ms = w.latencies_ms(True), w.latencies_ms(False)
    notes = [f"ops={len(schedule.due)} backlog_at_close={w.backlog_at_close} "
             f"drain_s={w.drained_s - w.close_s:.3f} "
             f"ack_p50_ms={np.median(ack_ms):.3f} read_p50_ms={np.median(read_ms):.3f} "
             f"gen_late_p95_ms={p95(late):.3f} counters={run.counters}",
             f"preload_s={preload_s:.2f} cold_start_s={cold_start_s:.2f} "
             f"warm_up_s={warm_up_s:.2f} lane_errors={back['lane_errors']} "
             f"error_signals={back['error_signals']} "
             f"fallback_causes={back['fallback_causes']}"]
    if verdict["first_bad"]:
        notes.append(f"first_bad={verdict['first_bad']}")
    return {"metrics": {"ack_p95_ms": p95(ack_ms), "read_p95_ms": p95(read_ms),
                        "cold_start_s": cold_start_s},
            "attempted": len(schedule.due), "failed": failed,
            "compared": compared, "notes": notes}


def run(run) -> dict:
    return asyncio.run(serve(run))
