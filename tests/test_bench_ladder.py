"""Smoke test for bench.py's SURGE_BENCH_LADDER=1 fast path: the command-path
throughput ladder must be regenerable WITHOUT the 100M-event corpus build, and
its JSON payload must carry the keys the BENCH artifact (and the driver's
last-line-wins parse) depend on."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_ladder_fast_path_emits_expected_json():
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SURGE_BENCH_LADDER": "1",
        "SURGE_BENCH_LATENCY_SECONDS": "0.4",
        "SURGE_BENCH_LATENCY_LADDER": "8",
        "SURGE_BENCH_SWEEP": "0",  # the sweep has its own knobs; smoke stays fast
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")]
    assert lines, f"no JSON payload on stdout: {proc.stdout!r}"
    payload = json.loads(lines[-1])  # last line wins for the driver
    for key in ("metric", "value", "unit", "commands_per_sec",
                "command_p50_ms", "command_p99_ms", "peak_commands_per_sec",
                "throughput_ladder", "linger_ms", "max_in_flight",
                "producer_stats"):
        assert key in payload, f"{key} missing from the ladder payload"
    assert payload["metric"] == "commands_per_sec"
    assert payload["value"] == payload["peak_commands_per_sec"] > 0
    rung = payload["throughput_ladder"][0]
    assert rung["workers"] == 8
    assert rung["commands"] > 0 and rung["commands_per_txn"] >= 1
    # the corpus phases really were skipped
    assert "num_events" not in payload and "cpu_baseline_events_per_sec" not in payload


def test_bench_native_paired_ladder_smoke():
    """SURGE_BENCH_NATIVE=1: the paired interleaved native-on/native-off
    ladder (the r07 protocol) emits per-rung medians for BOTH arms plus a
    speedup ratio, tiny-sized here."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SURGE_BENCH_LADDER": "1",
        "SURGE_BENCH_NATIVE": "1",
        "SURGE_BENCH_NATIVE_ROUNDS": "1",
        "SURGE_BENCH_LATENCY_SECONDS": "0.3",
        "SURGE_BENCH_LATENCY_LADDER": "8",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON payload on stdout: {proc.stdout!r}"
    payload = json.loads(lines[-1])
    paired = payload["native_paired_ladder"]
    assert paired["protocol"]["interleaved"] and paired["protocol"]["medians"]
    (rung,) = paired["rungs"]
    assert rung["workers"] == 8
    for arm in ("native_on", "native_off"):
        assert rung[arm]["commands_per_sec_median"] > 0
        assert rung[arm]["rounds"]
    assert rung["speedup_median"] > 0
    assert payload["value"] == rung["native_on"]["commands_per_sec_median"]


def test_bench_lane_paired_ladder_smoke():
    """SURGE_BENCH_LANE=1 (the r08 protocol): the paired interleaved
    direct-vs-classic command-lane ladder emits per-rung medians for both
    arms plus a speedup ratio, tiny-sized here (inproc only for speed)."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SURGE_BENCH_LADDER": "1",
        "SURGE_BENCH_LANE": "1",
        "SURGE_BENCH_LANE_ROUNDS": "1",
        "SURGE_BENCH_LANE_BROKERS": "inproc",
        "SURGE_BENCH_LATENCY_SECONDS": "0.3",
        "SURGE_BENCH_LATENCY_LADDER": "8",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON payload on stdout: {proc.stdout!r}"
    payload = json.loads(lines[-1])
    paired = payload["lane_paired_ladder"]
    assert paired["protocol"]["interleaved"] and paired["protocol"]["medians"]
    (rung,) = paired["ladders"]["inproc"]
    assert rung["workers"] == 8
    for arm in ("direct", "classic"):
        assert rung[arm]["commands_per_sec_median"] > 0
        assert rung[arm]["rounds"]
    assert rung["speedup_median"] > 0
    assert payload["value"] == rung["direct"]["commands_per_sec_median"]


def test_bench_mesh_paired_ladder_smoke():
    """SURGE_BENCH_MESH=1: the mesh-native plane's paired interleaved ladder
    (device-local vs replicated-slab arms) plus the sharded-scan row emit
    per-arm medians, tiny-sized here."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SURGE_BENCH_MESH": "1",
        "SURGE_BENCH_MESH_AGGREGATES": "64",
        "SURGE_BENCH_MESH_ROUNDS": "1",
        "SURGE_BENCH_MESH_CAP_LADDER": "64",
        "SURGE_BENCH_MESH_FOLD_EVENTS": "200",
        "SURGE_BENCH_MESH_FOLD_CYCLES": "2",
        "SURGE_BENCH_MESH_READ_WORKERS": "4",
        "SURGE_BENCH_MESH_READ_BATCH": "32",
        "SURGE_BENCH_MESH_SCAN_EVENTS": "4000",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON payload on stdout: {proc.stdout!r}"
    payload = json.loads(lines[-1])
    assert payload["metric"] == "mesh_fold_events_per_sec"
    assert payload["mesh_devices"] == 8
    rung = payload["mesh_fold_ladder"][0]
    for key in ("capacity", "local_events_per_sec",
                "replicated_events_per_sec", "local_vs_replicated",
                "local_rounds", "replicated_rounds"):
        assert key in rung, key
    assert rung["local_events_per_sec"] > 0
    assert rung["replicated_events_per_sec"] > 0
    assert payload["value"] == max(r["local_events_per_sec"]
                                   for r in payload["mesh_fold_ladder"])
    row = payload["mesh_read_row"]
    assert row["local_reads_per_sec"] > 0 and row["replicated_reads_per_sec"] > 0
    scan = payload["mesh_scan_row"]
    assert scan["mesh_events_per_sec"] > 0 and scan["single_events_per_sec"] > 0


def test_bench_resident_feed_paired_smoke():
    """SURGE_BENCH_RESIDENT_FEED=1: the paired native-feed vs Python-feed
    sustained-fold arms over one FileLog tail emit both medians + ratio."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SURGE_BENCH_RESIDENT_FEED": "1",
        "SURGE_BENCH_FEED_EVENTS": "4000",
        "SURGE_BENCH_FEED_AGGREGATES": "512",
        "SURGE_BENCH_FEED_ROUNDS": "1",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON payload on stdout: {proc.stdout!r}"
    payload = json.loads(lines[-1])
    paired = payload["resident_feed_paired"]
    assert paired["native_feed_events_per_sec_median"] > 0
    assert paired["python_feed_events_per_sec_median"] > 0
    assert paired["speedup_median"] > 0
    assert payload["value"] == paired["native_feed_events_per_sec_median"]


def test_bench_ragged_paired_ladder_smoke():
    """SURGE_BENCH_RAGGED=1 (ISSUE 18): the paired interleaved dense vs
    bucketed refresh-dispatch ladder plus the donation
    probe emit per-arm medians and waste ratios off the ledger, tiny-sized
    here (probe capacity shrunk from 1M to 4096 rows so the smoke stays in
    tier-1 budget; the mesh topology and donate on/off arms still run)."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SURGE_BENCH_RAGGED": "1",
        "SURGE_BENCH_RAGGED_ROUNDS": "1",
        "SURGE_BENCH_RAGGED_CYCLES": "3",
        "SURGE_BENCH_RAGGED_DENSE_LANES": "32",
        "SURGE_BENCH_RAGGED_CAPACITY": "256",
        "SURGE_BENCH_RAGGED_PROBE_CAPACITY": "4096",
        "SURGE_BENCH_RAGGED_PROBE_CYCLES": "2",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON payload on stdout: {proc.stdout!r}"
    payload = json.loads(lines[-1])
    assert payload["metric"] == "ragged_fold_events_per_sec"
    assert payload["protocol"]["interleaved"] and payload["protocol"]["medians"]
    ladder = payload["ragged_ladder"]
    assert set(ladder) == {"steady_ragged", "dense_32"}
    for shape, row in ladder.items():
        for arm in ("dense", "bucketed"):
            assert row[arm]["events_per_sec_median"] > 0, (shape, arm)
            assert row[arm]["rounds"]
            assert row[arm]["waste_ratio"] >= 1.0
        assert row["waste_reduction"] > 0
        assert "bucketed_wins_every_round" in row
    # the bucketed arm sheds lane padding on the ragged shape even at
    # smoke size: its waste ratio must strictly improve on dense's
    ragged = ladder["steady_ragged"]
    assert ragged["bucketed"]["waste_ratio"] < ragged["dense"]["waste_ratio"]
    assert ragged["bucketed"]["bucket_fill_ratio"] > \
        ragged["dense"]["bucket_fill_ratio"]
    probe = payload["donation_probe"]
    assert probe["capacity"] == 4096
    assert probe["donated_ms_per_window"] > 0
    assert probe["copying_ms_per_window"] > 0
    assert probe["round10_local_ms_per_window"] == 19.0
    assert payload["value"] == max(
        row["bucketed"]["events_per_sec_median"] for row in ladder.values())


def test_bench_views_paired_smoke():
    """SURGE_BENCH_VIEWS=1 (ISSUE 17): the paired interleaved view-read vs
    scan-per-read reader ladder emits per-rung medians for both arms plus a
    speedup ratio, tiny-sized here — and even at smoke size the warm view
    must beat the from-scratch scan on medians."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SURGE_BENCH_VIEWS": "1",
        "SURGE_BENCH_VIEWS_EVENTS": "4000",
        "SURGE_BENCH_VIEWS_AGGREGATES": "256",
        "SURGE_BENCH_VIEWS_ROUNDS": "1",
        "SURGE_BENCH_VIEWS_LADDER": "8",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON payload on stdout: {proc.stdout!r}"
    payload = json.loads(lines[-1])
    paired = payload["views_paired"]
    assert paired["protocol"]["interleaved"] and paired["protocol"]["medians"]
    (rung,) = paired["rungs"]
    assert rung["readers"] == 8
    for arm in ("view_read", "scan_per_read"):
        assert rung[arm]["reads_per_sec_median"] > 0
        assert rung[arm]["rounds"]
    assert rung["speedup_median"] > 1, \
        "a materialized view must beat a scan-per-read on medians"
    assert payload["value"] == rung["view_read"]["reads_per_sec_median"]


def test_bench_saga_storm_smoke():
    """SURGE_BENCH_SAGA=1 dispatch: one tiny seeded storm through the bench
    entrypoint — the JSON payload carries the three-zeros verdict keys the
    driver's last-line-wins parse gates on."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SURGE_BENCH_SAGA": "1",
        "SURGE_BENCH_SAGA_SEEDS": "31",
        "SURGE_BENCH_SAGA_SECONDS": "5",
        "SURGE_BENCH_SAGA_COUNT": "8",
        "SURGE_BENCH_SAGA_ACCOUNTS": "6",
        "SURGE_BENCH_SAGA_PARTITIONS": "4",
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON payload on stdout: {proc.stdout!r}"
    payload = json.loads(lines[-1])
    assert payload["metric"] == "saga_started"
    for key in ("saga_rounds", "saga_seeds", "saga_started", "saga_poisoned",
                "saga_lost", "saga_duplicated", "saga_half_compensated",
                "saga_dead_letter", "saga_verdict"):
        assert key in payload, f"{key} missing from the saga payload"
    assert payload["saga_seeds"] == [31]
    assert payload["saga_started"] == 8
    assert payload["saga_verdict"] == \
        "ok: 0 lost / 0 duplicated / 0 half-compensated"
    assert payload["saga_lost"] == 0 and payload["saga_duplicated"] == 0
    assert payload["saga_half_compensated"] == 0
    round0 = payload["saga_rounds"][0]
    assert round0["reconcile"]["ok"] and round0["timeline_events"] > 0
