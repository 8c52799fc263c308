"""The sweep that finds a served cell's knee: builder-run, once, on the chip.

``python -m benchmarks.sweep --workload <cell> --seed <n> --rates 500,1000,...``

One set-up, then a window of ``--seconds`` at each offered rate in turn, with
the plane settled in between. Prints one JSON line per rate: offered, answered
by the window's close, backlog at the close, drain time, ack and read
percentiles, and how late the generator sent. The knee is the highest rate at
which the backlog does not grow; the cell's traffic file then fixes 0.8 of it.
Not part of a run: ``run.py`` never calls this.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

import numpy as np

from benchmarks import gen
from benchmarks import run as harness
from benchmarks.drivers import node as driver


async def sweep(run, rates: list, seconds: float) -> None:
    node = driver.Node(run)
    try:
        node.preload()
        cold_start_s, _missing = await node.cold_start()
        await node.warm_up()
        print(json.dumps({"cold_start_s": cold_start_s}), flush=True)
        for n, rate in enumerate(rates):
            schedule = gen.open_loop_schedule(run.traffic, node.n_agg, seconds,
                                              run.seed + n, rate=rate)
            compiles = run.meter.compilations
            before = node.counters()
            w = await node.window(schedule, trace=False)
            after = node.counters()
            ack, read = w.latencies_ms(True), w.latencies_ms(False)
            half = schedule.due > seconds / 2
            done = np.where(np.isnan(w.done), w.drained_s, w.done)
            lat = (done - schedule.due) * 1e3
            print(json.dumps({
                "offered_ops_per_s": rate, "operations": len(schedule.due),
                "answered_by_close": int((w.done <= w.close_s).sum()),
                "backlog_at_close": w.backlog_at_close,
                "drain_s": round(w.drained_s - w.close_s, 3),
                "never_answered": int(np.isnan(w.done).sum()),
                "ack_p50_ms": float(np.median(ack)), "ack_p95_ms": driver.p95(ack),
                "ack_p99_ms": float(np.percentile(ack, 99)),
                "read_p50_ms": float(np.median(read)),
                "read_p95_ms": driver.p95(read),
                "p95_first_half_ms": driver.p95(lat[~half]),
                "p95_second_half_ms": driver.p95(lat[half]),
                "gen_late_p95_ms": driver.p95(w.late_ms()),
                "compilations": run.meter.compilations - compiles,
                "counters": {k: after[k] - before[k] for k in after}}),
                flush=True)
            await node.settle()
    finally:
        await node.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmarks.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default=harness.MANIFEST)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    _man, cell, config, traffic = harness.load_cell(args.workload, args.manifest)
    run = harness.prepare(cell, config, traffic, args.seed, args.seconds,
                          trace=False, rehearse=args.rehearse)
    if run is None:
        return 3
    asyncio.run(sweep(run, [float(r) for r in args.rates.split(",")],
                      args.seconds))
    return 0


if __name__ == "__main__":
    sys.exit(main())
