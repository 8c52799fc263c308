#!/usr/bin/env python
"""Chaos CLI: arm a fault plan against a RUNNING log broker and watch it.

The operator entry to the fault-injection plane (surge_tpu.testing.faults)
over the broker's admin RPCs::

    python tools/chaos.py arm 127.0.0.1:16001 flaky-network --seed 7
    python tools/chaos.py arm 127.0.0.1:16001 '{"rules": [{"site": "crash.transact.post-apply", "action": "crash"}]}'
    python tools/chaos.py status 127.0.0.1:16001
    python tools/chaos.py disarm 127.0.0.1:16001
    python tools/chaos.py broker 127.0.0.1:16001     # role/epoch/leader view
    python tools/chaos.py promote 127.0.0.1:16002    # failover drill
    python tools/chaos.py flight 127.0.0.1:16001     # full flight-recorder dump
    python tools/chaos.py metrics 127.0.0.1:16001    # broker OpenMetrics text
    python tools/chaos.py plans                      # list named plans
    python tools/chaos.py cluster 127.0.0.1:16001,127.0.0.1:16002,127.0.0.1:16003
    python tools/chaos.py cluster <t1,t2,t3> --arm flaky-network --seed 7
    python tools/chaos.py cluster <t1,t2,t3> --kill 127.0.0.1:16001
    python tools/chaos.py handoff 127.0.0.1:16001 127.0.0.1:16002
    python tools/chaos.py fleet broker@127.0.0.1:16001,engine@127.0.0.1:7001
    python tools/chaos.py fleet <specs> --serve 9464
    python tools/chaos.py replay-ledger 127.0.0.1:7001 --last 32
    python tools/chaos.py views 127.0.0.1:7001           # per-view summary
    python tools/chaos.py views 127.0.0.1:7001 totals    # one view's rows
    python tools/chaos.py sagas 127.0.0.1:7001           # saga counts + verdict
    python tools/chaos.py sagas 127.0.0.1:7001 order-17  # one saga's ledger
    python tools/chaos.py audit 127.0.0.1:7001           # consistency verdict
    python tools/chaos.py audit 127.0.0.1:7001 --format=json

``cluster`` drives N brokers from ONE invocation: with no flags it prints a
per-broker summary (role, epoch, in-sync view, per-partition high-watermarks,
quorum shape, partitions led + membership epoch, armed faults) plus the
cluster verdicts — exactly one coordinator, and under leadership spread
exactly ONE leader PER PARTITION agreed by every reachable broker; a failed
verdict exits 1 so soak harnesses and CI can gate on it. ``--arm PLAN`` arms
the same seeded plan on every broker; ``--kill ADDR`` hard-stops one of them
(the reply races the socket close — unreachable IS success).
``handoff <from> <to>`` moves the leader role deliberately (bulk slice ship
-> fence -> journal-tail ship -> dedup push -> promote -> demote) and prints
the stats, fenced-span ms included; ``--partition N`` moves just that
partition index's leadership (spread clusters). A failed handoff prints the
error and exits 1.

``arm`` takes a NAMED plan (see ``plans``) or a JSON rule list / object;
after arming it reports the plane's stats, and with ``--watch`` polls the
broker until the plan's rules are exhausted (or the broker dies — which for
crash plans is the expected outcome, reported as such).

``status`` reports the fault plane's stats PLUS the broker's flight-recorder
tail (``--tail N``, default 20) and its current replication-lag gauges, so a
chaos run is debuggable from one command without attaching a scraper.

``replay-ledger`` targets an ENGINE admin endpoint (not a broker) and dumps
its device observatory — the refresh-round ledger envelope (per-round
padding-waste / per-stage timings / gather legs, plus the roofline summary)
over the ``DumpReplayLedger`` admin RPC. Pipe it to a file and feed
``tools/roofline_record.py`` to append a roofline trajectory row.

``fleet`` federates EVERY target's OpenMetrics payload (``role@addr`` specs:
``broker@host:port`` over the log-service GetMetricsText RPC,
``engine@host:port`` over the admin RPC, ``role@http://...`` plain HTTP)
into one instance/role-labelled exposition on stdout — or keeps serving it
from a scrape port with ``--serve PORT`` (0 = ephemeral; Ctrl-C stops). The
live table view over the same pass is ``tools/surgetop.py``.

Exit code 0 on success; 1 when a verdict fails (``cluster`` with a
leadership violation, ``handoff`` refused/failed); 3 when --watch ends with
the broker unreachable (crash plans: that IS the outcome); 2 on bad
arguments.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("command",
                    choices=["arm", "disarm", "status", "broker", "promote",
                             "flight", "metrics", "plans", "cluster",
                             "handoff", "fleet", "replay-ledger", "views",
                             "sagas", "audit"])
    ap.add_argument("target", nargs="?",
                    help="broker host:port (cluster: comma-separated list; "
                         "handoff: the FROM broker)")
    ap.add_argument("plan", nargs="?",
                    help="named fault plan or JSON rules (arm only); the TO "
                         "broker (handoff only)")
    ap.add_argument("--seed", type=int, default=0,
                    help="deterministic schedule seed (arm only)")
    ap.add_argument("--arm", dest="cluster_arm", default=None,
                    help="cluster: arm this plan on every broker")
    ap.add_argument("--kill", dest="cluster_kill", default=None,
                    help="cluster: hard-stop this broker (host:port)")
    ap.add_argument("--watch", action="store_true",
                    help="after arming, poll until every rule is exhausted "
                         "or the broker goes down")
    ap.add_argument("--interval", type=float, default=1.0,
                    help="--watch poll interval seconds")
    ap.add_argument("--tail", type=int, default=20,
                    help="flight-recorder events shown by status")
    ap.add_argument("--serve", type=int, default=None, metavar="PORT",
                    help="fleet: serve the merged exposition from this "
                         "scrape port (0 = ephemeral) instead of printing "
                         "one pass")
    ap.add_argument("--partition", type=int, default=None,
                    help="handoff: move only this partition index's "
                         "leadership (spread clusters)")
    ap.add_argument("--last", type=int, default=None,
                    help="replay-ledger: newest N ledger rounds")
    ap.add_argument("--format", dest="fmt", choices=["text", "json"],
                    default="text",
                    help="audit: text panel, or json with the machine-"
                         "readable verdict as the LAST stdout line")
    args = ap.parse_args(argv)

    if args.command == "plans":
        from surge_tpu.testing.faults import NAMED_PLANS

        for name, factory in sorted(NAMED_PLANS.items()):
            rules = [r.as_dict() for r in factory()]
            print(f"{name}: {json.dumps(rules)}")
        return 0

    if not args.target:
        print("a broker target is required", file=sys.stderr)
        return 2

    from surge_tpu.log import GrpcLogTransport

    if args.command == "replay-ledger":
        return _replay_ledger(args)
    if args.command == "views":
        return _views(args)
    if args.command == "sagas":
        return _sagas(args)
    if args.command == "audit":
        return _audit(args)
    if args.command == "fleet":
        return _fleet(args)
    if args.command == "cluster":
        return _cluster(args)
    if args.command == "handoff":
        if not args.plan:
            print("handoff needs <from> <to>", file=sys.stderr)
            return 2
        client = GrpcLogTransport(args.target)
        try:
            if args.partition is not None:
                stats = client.cluster_handoff(args.plan, args.partition)
            else:
                stats = client.handoff_partition(args.plan)
            print(json.dumps(stats, indent=2))
            return 0
        except Exception as exc:  # noqa: BLE001 — a failed handoff must gate
            print(json.dumps({"verdict": "FAILED",
                              "error": str(exc)[:500]}, indent=2))
            return 1
        finally:
            client.close()

    client = GrpcLogTransport(args.target)
    try:
        if args.command == "broker":
            print(json.dumps(client.broker_status(), indent=2))
            return 0
        if args.command == "promote":
            print(json.dumps(client.promote_follower(), indent=2))
            return 0
        if args.command == "flight":
            print(json.dumps(client.flight_dump(), indent=2))
            return 0
        if args.command == "metrics":
            print(client.log_metrics_text(), end="")
            return 0
        if args.command == "status":
            # one debuggable view: plane stats + the black-box tail + the
            # replication-lag gauges, no scraper required
            out = dict(client.fault_stats())
            try:
                # native-path health: a silently-degraded broker (stale .so
                # -> Python fallback) is visible at a glance
                out["native"] = client.broker_status().get(
                    "native", "unavailable")
            except Exception as exc:  # noqa: BLE001 — older broker
                out["native"] = f"unavailable: {exc!r}"
            try:
                out["flight_tail"] = client.flight_dump(
                    last=args.tail)["events"]
            except Exception as exc:  # noqa: BLE001 — older broker
                out["flight_tail"] = f"unavailable: {exc!r}"
            try:
                out["replication_lag"] = [
                    line for line in client.log_metrics_text().splitlines()
                    if line.startswith(("surge_log_replication_lag",
                                        "surge_log_replication_in_sync"))]
            except Exception as exc:  # noqa: BLE001 — older broker
                out["replication_lag"] = f"unavailable: {exc!r}"
            print(json.dumps(out, indent=2))
            return 0
        if args.command == "disarm":
            print(json.dumps(client.disarm_faults(), indent=2))
            return 0
        # arm
        if not args.plan:
            print("arm needs a named plan or JSON rules "
                  "(see `chaos.py plans`)", file=sys.stderr)
            return 2
        stats = client.arm_faults(args.plan, seed=args.seed)
        print(json.dumps(stats, indent=2))
        if not args.watch:
            return 0
        while True:
            time.sleep(args.interval)
            try:
                stats = client.fault_stats()
            except Exception as exc:  # noqa: BLE001 — broker gone
                print(json.dumps({"outcome": "broker unreachable "
                                             "(crash plans: expected)",
                                  "error": str(exc)[:200]}))
                return 3
            exhausted = all(r["times"] is not None
                            and r["fired"] >= r["times"]
                            for r in stats["rules"])
            print(json.dumps({"injected": stats["injected"],
                              "crashed": stats["crashed"],
                              "exhausted": exhausted}))
            if exhausted or stats["crashed"]:
                print(json.dumps({"outcome": "plan complete", **stats}))
                return 0
    finally:
        client.close()


def _render_bucket_anatomy(payload) -> str:
    """Per-round bucket fill + waste columns off a ledger envelope (ISSUE
    18's bucketed ragged dispatch): one line per round with buckets, then
    one line per bucket program (`w<width>×<lanes_b>` lanes dealt / lane
    slots, slot fill, waste). Empty string when no round
    in the dump carried bucket anatomy (dense or pre-bucketing engines)."""
    lines = []
    for ev in payload.get("events", []):
        if ev.get("type") != "round" or not ev.get("buckets"):
            continue
        lines.append(
            f"round events={ev['events']} lanes={ev['lanes']} "
            f"waste={ev.get('waste')} bucket_table={ev.get('bucket_table')}")
        for bk in ev["buckets"]:
            lanes, lanes_b = bk.get("lanes", 0), bk.get("lanes_b", 0)
            disp, occ = bk.get("dispatched", 0), bk.get("occupied", 0)
            lines.append(
                f"  w{bk.get('width')}×{lanes_b}: lanes {lanes}/{lanes_b}"
                f" fill={occ / disp:.2f}" if disp else
                f"  w{bk.get('width')}×{lanes_b}: lanes {lanes}/{lanes_b}"
                f" fill=-")
            if disp:
                lines[-1] += (f" waste={disp / occ:.2f}" if occ
                              else " waste=-")
    return "\n".join(lines)


def _replay_ledger(args) -> int:
    """Device-observatory dump from the CLI: one ``DumpReplayLedger``
    envelope (refresh rounds + roofline summary) off an ENGINE admin
    endpoint, printed as JSON — a down/observatory-less engine is a
    reported finding, exit 1. Rounds that carried bucket anatomy (the
    bucketed ragged dispatch) additionally render a per-bucket fill/waste
    table on STDERR, keeping stdout the parseable envelope."""
    import asyncio

    import grpc

    from surge_tpu.admin.server import AdminClient

    async def fetch():
        async with grpc.aio.insecure_channel(args.target) as channel:
            return await AdminClient(channel).replay_ledger_dump(args.last)

    try:
        payload = asyncio.run(fetch())
        print(json.dumps(payload, indent=2))
        anatomy = _render_bucket_anatomy(payload)
        if anatomy:
            print(anatomy, file=sys.stderr)
        return 0
    except Exception as exc:  # noqa: BLE001 — a down engine is the finding
        print(json.dumps({"error": str(exc)[:500]}, indent=2))
        return 1


def _views(args) -> int:
    """Materialized-view operator panel off an ENGINE admin endpoint: the
    per-view ``QueryView`` summary (active/version, fold watermarks, group
    and subscriber counts, degraded-state errors) — or, with a view name as
    the second positional, that one view's served snapshot rows."""
    import asyncio

    import grpc

    from surge_tpu.admin.server import AdminClient

    async def fetch():
        async with grpc.aio.insecure_channel(args.target) as channel:
            return await AdminClient(channel).query_view(args.plan or "")

    try:
        payload = asyncio.run(fetch())
        print(json.dumps(payload, indent=2))
        return 0
    except Exception as exc:  # noqa: BLE001 — a down engine is the finding
        print(json.dumps({"error": str(exc)[:500]}, indent=2))
        return 1


def _sagas(args) -> int:
    """Saga operator panel off an ENGINE admin endpoint: the fleet summary
    (per-status counts, in-flight/dead-letter totals, drivers) PLUS the
    ledger-reconciliation verdict — every terminal saga must be all-steps-
    committed XOR all-committed-steps-compensated. A violated invariant (or
    a summary that reports not-ok) exits 1 so chaos harnesses and CI can
    gate on it; with a saga id as the second positional the panel shows that
    one saga's ledger instead (committed/compensated steps, attempts,
    driver liveness) and exits 0 whenever the saga is known."""
    import asyncio

    import grpc

    from surge_tpu.admin.server import AdminClient

    async def fetch():
        async with grpc.aio.insecure_channel(args.target) as channel:
            return await AdminClient(channel).saga_status(args.plan or "")

    try:
        payload = asyncio.run(fetch())
    except Exception as exc:  # noqa: BLE001 — a down engine is the finding
        print(json.dumps({"error": str(exc)[:500]}, indent=2))
        return 1
    print(json.dumps(payload, indent=2))
    if args.plan:  # one saga's ledger
        return 0 if payload.get("status") != "unknown" else 1
    return 0 if payload.get("ok") else 1


def _audit(args) -> int:
    """Consistency-observatory verdict off an ENGINE admin endpoint: the
    auditor's unresolved-divergence ledger (shadow-replay mismatches name
    the aggregate + differing fields, digest mismatches the partition + each
    replica's CRC, dedup holes the probe) plus cycle stats and the last
    round's detail. ANY unresolved divergence exits 1 — the same verdict
    convention as ``cluster``/``handoff``/``sagas``, so chaos harnesses and
    CI gate on it. ``--format=json`` prints the full payload with the
    machine-readable verdict as the LAST stdout line."""
    import asyncio

    import grpc

    from surge_tpu.admin.server import AdminClient

    async def fetch():
        async with grpc.aio.insecure_channel(args.target) as channel:
            return await AdminClient(channel).audit_status()

    try:
        payload = asyncio.run(fetch())
    except Exception as exc:  # noqa: BLE001 — a down engine is the finding
        print(json.dumps({"ok": False, "error": str(exc)[:500]}))
        return 1
    if args.fmt == "json":
        # full detail first, one-line verdict LAST (machine-readable tail)
        print(json.dumps(payload, indent=2))
        print(json.dumps({"ok": payload.get("ok", False),
                          "unresolved": payload.get("unresolved", [])}))
        return 0 if payload.get("ok") else 1
    stats = payload.get("stats", {})
    print(f"consistency audit: {'OK' if payload.get('ok') else 'DIVERGED'} "
          f"(cycles={stats.get('cycles', 0)} "
          f"rows={stats.get('cohort_rows', 0)} "
          f"divergent={stats.get('divergent_rows', 0)} "
          f"digest_mismatches={stats.get('digest_mismatches', 0)} "
          f"dedup_holes={stats.get('dedup_holes', 0)})")
    for item in payload.get("unresolved", []):
        print(f"  UNRESOLVED {':'.join(item.get('key', []))}: "
              f"{json.dumps({k: v for k, v in item.items() if k != 'key'})}")
    return 0 if payload.get("ok") else 1


def _fleet(args) -> int:
    """Federated scrape from the CLI: one merged, instance/role-labelled
    OpenMetrics exposition over every ``role@addr`` target — printed once,
    or served continuously from the scraper's own scrape port."""
    from surge_tpu.observability import FederatedScraper

    specs = [t.strip() for t in args.target.split(",") if t.strip()]
    if not specs:
        print("fleet needs role@addr specs", file=sys.stderr)
        return 2
    scraper = FederatedScraper(specs)
    try:
        if args.serve is None:
            print(scraper.scrape_and_render(), end="")
            return 0
        port = scraper.serve(port=args.serve)
        print(f"serving federated scrape on http://127.0.0.1:{port}/metrics "
              f"({len(specs)} targets); Ctrl-C stops", file=sys.stderr)
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            return 0
    finally:
        scraper.stop()


def _cluster(args) -> int:
    """One invocation across N brokers: arm / kill / summarize. The summary
    is the quorum-plane debugging view — per-broker role+epoch+hwm (why a
    follower read is or is not servable) and a cluster-level verdict that
    exactly one broker is leading."""
    from surge_tpu.log import GrpcLogTransport

    targets = [t.strip() for t in args.target.split(",") if t.strip()]
    if len(targets) < 2:
        print("cluster needs a comma-separated broker list", file=sys.stderr)
        return 2
    out = {"brokers": {}, "leaders": []}
    rc = 0
    partition_claims = {}  # partition index -> [brokers claiming leadership]
    assignment_views = {}  # target -> (assign_epoch, frozen assignment map)
    for target in targets:
        client = GrpcLogTransport(target)
        try:
            if args.cluster_kill == target:
                client.kill_broker()
                out["brokers"][target] = {"killed": True}
                continue
            if args.cluster_arm:
                client.arm_faults(args.cluster_arm, seed=args.seed)
            status = client.broker_status()
            row = {
                "role": status["role"],
                "epoch": status["epoch"],
                "leader_hint": status.get("leader_hint", ""),
                "high_watermarks": status.get("high_watermarks", {}),
                "quorum": status.get("quorum", {}),
                # per-partition leadership spread (ISSUE 13): what this
                # broker leads and which membership/assignment record
                # version it is operating under
                "partitions_led": status.get("partitions_led", []),
                "membership": status.get("membership", {}),
                "assign_epoch": status.get("assign_epoch", 0),
                "handoff_fence": status.get("handoff_fence", False),
                "catch_up": status.get("catch_up", {}),
                "native": status.get("native", {}),
            }
            for p in status.get("partitions_led", []):
                partition_claims.setdefault(int(p), []).append(target)
            if status.get("assignments"):
                assignment_views[target] = (
                    status.get("assign_epoch", 0),
                    tuple(sorted(status["assignments"].items())))
            try:
                row["faults"] = client.fault_stats()
            except Exception as exc:  # noqa: BLE001 — older broker
                row["faults"] = f"unavailable: {exc!r}"
            if status["role"] == "leader":
                out["leaders"].append(target)
                try:
                    row["replication"] = client.replication_status()
                except Exception:  # noqa: BLE001
                    pass
            out["brokers"][target] = row
        except Exception as exc:  # noqa: BLE001 — broker down: report, go on
            out["brokers"][target] = {"unreachable": str(exc)[:200]}
        finally:
            client.close()
    problems = []
    if len(out["leaders"]) != 1:
        problems.append(f"{len(out['leaders'])} coordinators")
    if assignment_views:
        out["partition_leaders"] = {str(p): owners for p, owners
                                    in sorted(partition_claims.items())}
        for p, owners in sorted(partition_claims.items()):
            if len(owners) != 1:
                problems.append(
                    f"partition {p}: {len(owners)} leaders {sorted(owners)}")
        newest = max(epoch for epoch, _m in assignment_views.values())
        maps = {m for epoch, m in assignment_views.values()
                if epoch == newest}
        if len(maps) > 1:
            problems.append("brokers at the newest assign epoch disagree "
                            "on the partition map")
        all_assigned = {int(k) for _e, m in assignment_views.values()
                        for k, _v in m}
        for p in sorted(all_assigned - set(partition_claims)):
            problems.append(f"partition {p}: no live leader")
    out["verdict"] = ("ok: exactly one leader"
                      + (" per partition" if assignment_views else "")
                      if not problems else
                      "DEGRADED: " + "; ".join(problems))
    if problems:
        rc = 1  # soak harnesses / CI gate on this (ISSUE 13 satellite)
    if args.cluster_kill and args.cluster_kill not in targets:
        print(f"--kill target {args.cluster_kill} not in the cluster list",
              file=sys.stderr)
        rc = 2
    print(json.dumps(out, indent=2))
    return rc


if __name__ == "__main__":
    sys.exit(main())
