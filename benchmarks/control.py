"""The control of a cell's comparison: it has to come out as not correct.

``python -m benchmarks.control --workload <cell> --seed <n> [--seconds s] [--rehearse]``

The system states no precision, so a control breaks one guarantee the cell's
configuration states: the plain reference is put in the program's place with
that guarantee broken, and judged by the very comparison a run uses. Each kind
of deployment has its own, ``controls/<driver>.py`` with ``control(run) ->
[(name, value, limit)]``, found by the configuration's ``driver`` as the driver
itself is.

Exits 0 when the control was judged not correct (it failed one number at
least), 1 when the comparison let it through. No benchmark run calls this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys

from benchmarks import run as harness


def control(run) -> list:
    return importlib.import_module(
        f"benchmarks.controls.{run.config['driver']}").control(run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmarks.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default=harness.MANIFEST)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    _man, cell, config, traffic = harness.load_cell(args.workload, args.manifest)
    run = harness.Run(cell, config, traffic, args.seed, args.seconds, False,
                      args.rehearse)
    compared = control(run)
    failed = [name for name, value, limit in compared if value > limit]
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "control_correct": not failed,
                      "compared": {n: {"value": v, "limit": lim}
                                   for n, v, lim in compared}}))
    return 0 if failed else 1


if __name__ == "__main__":
    sys.exit(main())
