#!/usr/bin/env python
"""roofline_record — snapshot a refresh-round ledger into the roofline JSONL.

Pulls the device observatory's ``DumpReplayLedger`` envelope from a live
engine admin endpoint (or reads one saved earlier as JSON), extracts the
roofline summary (measured fold ev/s, µs/slot, µs/event, padding-waste
ratio) and appends ONE JSON line to the trajectory file — append-only, so
the file accumulates the machine's measured history across runs and a
regression shows as a row, not a reverted doc table::

    python tools/roofline_record.py --engine 127.0.0.1:7001 \
        --out roofline.jsonl --note "post PR-16"
    python tools/roofline_record.py ledger_dump.json --out roofline.jsonl
    python tools/roofline_record.py ledger_dump.json --out roofline.jsonl \
        --compare steady-ragged-cpu

``--compare`` prints measured/published ratios against an anchor figure of
``surge_tpu.observability.roofline.REFERENCE`` (1.0 = the anchor holds). Exit code 0 on success, 2 on
bad input or an engine without the observatory.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def _engine_dump(addr: str, last):
    import asyncio

    import grpc

    from surge_tpu.admin.server import AdminClient

    async def fetch():
        async with grpc.aio.insecure_channel(addr) as channel:
            return await AdminClient(channel).replay_ledger_dump(last)

    return asyncio.run(fetch())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dump", nargs="?",
                    help="saved DumpReplayLedger JSON file")
    ap.add_argument("--engine", metavar="ADDR",
                    help="live DumpReplayLedger over the engine admin RPC")
    ap.add_argument("--last", type=int, default=None,
                    help="newest N ledger events in the pulled dump")
    ap.add_argument("--out", default="roofline.jsonl",
                    help="append-only JSONL trajectory file "
                         "(default: roofline.jsonl)")
    ap.add_argument("--source", default="",
                    help="row source label (defaults to the engine addr or "
                         "dump file name)")
    ap.add_argument("--note", default="", help="free-form row annotation")
    ap.add_argument("--compare", metavar="ANCHOR",
                    help="print measured/published ratios against a "
                         "REFERENCE anchor (e.g. steady-ragged-cpu)")
    args = ap.parse_args(argv)

    if bool(args.dump) == bool(args.engine):
        print("exactly one of a dump file or --engine is required",
              file=sys.stderr)
        return 2

    from surge_tpu.observability.roofline import (REFERENCE, RooflineRecorder,
                                                  against_reference)

    if args.engine:
        try:
            payload = _engine_dump(args.engine, args.last)
        except Exception as exc:  # noqa: BLE001 — a down engine is the finding
            print(f"engine {args.engine}: {exc}", file=sys.stderr)
            return 2
        source = args.source or args.engine
    else:
        try:
            with open(args.dump) as f:
                payload = json.load(f)
        except (OSError, ValueError) as exc:
            print(f"cannot read dump {args.dump}: {exc}", file=sys.stderr)
            return 2
        source = args.source or os.path.basename(args.dump)

    summary = payload.get("summary")
    if not isinstance(summary, dict):
        print("dump carries no ledger summary (not a DumpReplayLedger "
              "envelope?)", file=sys.stderr)
        return 2

    row = RooflineRecorder(args.out).record(summary, source=source,
                                            note=args.note)
    print(json.dumps(row))
    if args.compare:
        if args.compare not in REFERENCE:
            print(f"unknown anchor {args.compare!r} "
                  f"(have: {', '.join(sorted(REFERENCE))})", file=sys.stderr)
            return 2
        print(json.dumps({"anchor": args.compare,
                          "ratios": against_reference(row, args.compare)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
