"""The control of a served-node cell: the plain reference served in the
engine's place, with the one guarantee the configuration states broken.

``counter-node`` states that every acknowledged command is readable
afterwards. The control is a dictionary of counters that acknowledges every
command and then forgets one in five hundred: the ack carries the new state
and nothing keeps it. It is driven by the cell's own generator at its own
rate, through the driver's own window and read-back.
"""

from __future__ import annotations

import asyncio

import numpy as np

from benchmarks import gen, reference
from benchmarks.drivers import node as driver


class ForgetfulCounters:
    """A dictionary of (count, version), exact, except that it acknowledges
    one command in ``forget_every`` without keeping it."""

    def __init__(self, kinds: np.ndarray, forget_every: int) -> None:
        count, version = reference.preloaded_states(kinds)
        self.state = list(zip(count.tolist(), version.tolist()))
        self.forget_every = forget_every
        self.commands = 0

    async def command(self, i: int, increment: bool):
        await asyncio.sleep(0)
        count, version = self.state[i]
        new = (count + (1 if increment else -1), version + 1)
        self.commands += 1
        if self.commands % self.forget_every:
            self.state[i] = new
        return new

    async def read(self, i: int):
        await asyncio.sleep(0)
        return self.state[i]


class ControlNode(driver.Node):
    """The driver's node with the reference in the engine's place."""

    def __init__(self, run, forget_every: int = 500) -> None:
        self.run = run
        self.n_agg = run.sizes["aggregates"]
        self.kinds = gen.preload_kinds(
            self.n_agg, run.sizes["preloaded_events_per_aggregate"], run.seed)
        self.acks, self.reads, self.unanswered = {}, [], 0
        self.served = ForgetfulCounters(self.kinds, forget_every)

    async def command(self, i: int, increment: bool) -> bool:
        count, version = await self.served.command(i, increment)
        self.acks.setdefault(i, []).append(
            (1 if increment else -1, count, version))
        return True

    async def read(self, i: int) -> None:
        count, version = await self.served.read(i)
        self.reads.append((i, count, version))

    async def read_back(self) -> dict:
        return {i: [self.served.state[i], self.served.state[i]]
                for i in sorted(self.acks)}


async def serve(run) -> list:
    node = ControlNode(run)
    schedule = gen.open_loop_schedule(run.traffic, node.n_agg, run.seconds,
                                      run.seed)
    w = await node.window(schedule, trace=False)
    back = await node.read_back()
    base_count, base_version = reference.preloaded_states(node.kinds)
    verdict = reference.judge_node(base_count, base_version, node.acks,
                                   node.reads, back)
    return [("acks_wrong", verdict["acks_wrong"], 0),
            ("reads_wrong", verdict["reads_wrong"], 0),
            ("readback_wrong", verdict["readback_wrong"], 0),
            ("unanswered", int(np.isnan(w.done).sum()), 0)]


def control(run) -> list:
    return asyncio.run(serve(run))
