"""A deterministic reproduction of the fault that keeps the served node's cell
out of ``BENCHMARK.json`` (PERF.md, Open questions, row 1).

``python -m benchmarks.repro.refresh_skips_tail``  (any backend; no chip needed)

``ResidentStatePlane._poll_batches`` reads a partition's tail and, where that
read comes back empty, asks the log for its end offset afterwards;
``_refresh_once`` takes ``end > watermark`` for a compaction hole and moves the
watermark there. Under load a command is made durable between the two calls
now and then. Here that interleaving is forced once: the plane's log is
wrapped so that the empty read of one partition is held until a command to an
aggregate of that partition, sent through the engine's own command path, has
been acknowledged. Nothing else is changed.

Prints one JSON line: what the ack carried, what ``project_states`` and
``get_state`` answer on the settled node, the plane's own ``lag_records()``,
what a fresh engine cold-started over the same log answers, and
``fault_present``. Exits 0 when it ran, whatever it found.
"""

from __future__ import annotations

import asyncio
import json
import sys
import threading


class HoldOneEmptyRead:
    """The plane's log, with one empty tail read of one partition held until
    ``release`` is set. Everything else passes through."""

    def __init__(self, log, topic: str, partition: int) -> None:
        self._log, self._topic, self._partition = log, topic, partition
        self.armed = False
        self.holding = threading.Event()
        self.release = threading.Event()

    def __getattr__(self, name):
        return getattr(self._log, name)

    def read(self, topic, partition, from_offset=0, **kw):
        recs = self._log.read(topic, partition, from_offset, **kw)
        if (self.armed and not recs and topic == self._topic
                and partition == self._partition):
            self.armed = False
            self.holding.set()
            self.release.wait(timeout=30)
        return recs


def make_engine(log):
    from surge_tpu import create_engine
    from surge_tpu.config import default_config
    from surge_tpu.engine.business_logic import SurgeCommandBusinessLogic
    from surge_tpu.models import counter

    return create_engine(
        SurgeCommandBusinessLogic(
            aggregate_name="counter", model=counter.CounterModel(),
            state_format=counter.state_formatting(),
            event_format=counter.event_formatting()),
        log=log, config=default_config().with_overrides({
            "surge.replay.resident.enabled": True,
            "surge.replay.restore-on-start": True,
            "surge.replay.resident.capacity": 1024,
            # keeps the other fault (row 2) out of this picture
            "surge.replay.donate-refresh": False}))


async def settle(plane, seconds: float = 10.0) -> None:
    for _ in range(int(seconds / 0.02)):
        if plane.lag_records() == 0:
            return
        await asyncio.sleep(0.02)


async def reproduce() -> dict:
    from surge_tpu.log.memory import InMemoryLog
    from surge_tpu.models import counter

    agg = "agg-0"
    pair = lambda st: None if st is None else [st.count, st.version]  # noqa: E731
    log = InMemoryLog()
    engine = make_engine(log)
    await engine.start()
    try:
        first = await engine.aggregate_for(agg).send_command(counter.Increment(agg))
        plane = engine.resident_plane
        await settle(plane)
        before = (await engine.project_states([agg])).get(agg)

        hold = HoldOneEmptyRead(plane.log, engine.logic.events_topic,
                                engine.router.partition_for(agg))
        plane.log = hold
        hold.armed = True
        while not hold.holding.is_set():  # the next idle poll of the partition
            await asyncio.sleep(0.005)
        # the poll has read an empty tail and has not yet asked for the end
        second = await engine.aggregate_for(agg).send_command(
            counter.Increment(agg))
        hold.release.set()

        await settle(plane)
        await asyncio.sleep(1.0)  # many more refresh rounds, should one mend it
        projected = (await engine.project_states([agg])).get(agg)
        entity = await engine.aggregate_for(agg).get_state()
        lag = plane.lag_records()
        rounds = int(plane.stats["rounds"])
        folded = int(plane.stats["folded_events"])
    finally:
        await engine.stop()

    fresh = make_engine(log)  # the second witness: a cold start over the same log
    await fresh.start()
    try:
        await settle(fresh.resident_plane)
        cold = (await fresh.project_states([agg])).get(agg)
    finally:
        await fresh.stop()

    return {"ack_1": pair(first.state), "project_states_before": pair(before),
            "ack_2": pair(second.state),
            "project_states_settled": pair(projected),
            "get_state_settled": pair(entity), "lag_records": lag,
            "refresh_rounds": rounds, "events_folded_live": folded,
            "fresh_engine_project_states": pair(cold),
            "fault_present": pair(projected) != pair(second.state)}


if __name__ == "__main__":
    import logging

    logging.disable(logging.WARNING)
    print(json.dumps(asyncio.run(reproduce())))
    sys.exit(0)
