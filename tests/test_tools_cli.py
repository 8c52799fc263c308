"""Tier-1 CLI smokes for the repo tooling satellites (ISSUE 20):
`tools/bench_trend.py` (BENCH_*.json trajectory merge, machine-readable
last line), `tools/regen_golden_metrics.py --check` (verify-without-writing
drift gate over all three goldens), and the `chaos.py audit` down-engine
verdict."""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_trend  # noqa: E402
import chaos  # noqa: E402
import regen_golden_metrics  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- bench_trend ----------------------------------------------------------------------


def _write(path, payload):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f)


def test_bench_trend_merges_fabricated_series(tmp_path, capsys):
    """Every envelope shape the repo's BENCH files use lands as one
    trajectory row; rounds order the series; the json format's LAST stdout
    line is the machine-readable summary with per-metric first/last/delta."""
    _write(tmp_path / "BENCH_FOLD_r01.json",
           {"metric": "fold_events_per_sec", "value": 100.0,
            "unit": "events/s"})
    _write(tmp_path / "BENCH_FOLD_r02.json",
           {"metric": "fold_events_per_sec", "value": 150.0,
            "unit": "events/s"})
    _write(tmp_path / "BENCH_RUN_r03.json", {"rc": 0})  # runner envelope
    _write(tmp_path / "BENCH_LADDER_r04.json",  # nested paired-ladder notes
           {"arms": [{"baseline": {"commands_per_sec_median": 900.0}},
                     {"candidate": {"commands_per_sec_median": 1000.0}}]})
    _write(tmp_path / "BENCH_SMOKE_r05.json",  # device smoke sweep
           {"smoke": {"configs": [{"events_per_sec": 5.0},
                                  {"events_per_sec": 9.0}]}})

    rc = bench_trend.main(["--dir", str(tmp_path), "--format", "json"])
    out = capsys.readouterr().out
    assert rc == 0
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["files"] == 5
    fold = tail["series"]["fold_events_per_sec"]
    # two explicit-metric rows plus the smoke sweep's best rate
    assert fold["points"] == 3
    assert fold["first"] == 100.0
    assert fold["delta_pct"] is not None
    assert tail["series"]["bench_exit_code"]["last"] == 0
    assert tail["series"]["commands_per_sec_median"]["last"] == 1000.0
    # the human table rode stdout before the machine line
    assert "fold_events_per_sec" in out.splitlines()[0] or \
        any("fold_events_per_sec" in line for line in out.splitlines()[:-1])


def test_bench_trend_ladder_rounds_form_a_series(tmp_path, capsys):
    """A multi-round ladder series in the shape of the repo's BENCH_LADDER
    records parses end to end: every file yields a row, a record without an
    extractable number included, and the nested ladder medians form one
    series ordered by round."""
    for rnd, median in ((6, 800.0), (7, 950.0), (8, 1100.0)):
        _write(tmp_path / f"BENCH_LADDER_r{rnd:02d}.json",
               {"protocol": "paired, interleaved arms, medians only",
                "arms": [{"baseline": {"commands_per_sec_median": median / 2}},
                         {"candidate": {"commands_per_sec_median": median}}]})
    _write(tmp_path / "BENCH_HANDOFF_r01.json", {"notes": "no headline"})

    rc = bench_trend.main(["--dir", str(tmp_path), "--format", "json"])
    out = capsys.readouterr().out
    assert rc == 0
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["files"] == 4
    medians = tail["series"]["commands_per_sec_median"]
    assert medians["points"] == 3
    assert (medians["first"], medians["last"]) == (800.0, 1100.0)


def test_bench_trend_rejects_missing_dir(tmp_path, capsys):
    assert bench_trend.main(["--dir", str(tmp_path / "nope")]) == 2


def test_bench_trend_survives_unreadable_json(tmp_path, capsys):
    (tmp_path / "BENCH_BAD_r01.json").write_text("{not json", "utf-8")
    rc = bench_trend.main(["--dir", str(tmp_path), "--format", "json"])
    tail = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and tail["files"] == 1 and tail["series"] == {}


# -- regen_golden_metrics --check -----------------------------------------------------


def test_regen_check_passes_on_checked_in_goldens(capsys):
    """The CI gate: the three checked-in goldens match the canonical
    renders right now (this test IS the drift alarm for this repo)."""
    assert regen_golden_metrics.main(["--check"]) == 0
    out = capsys.readouterr().out
    assert out.count("ok ") == 3


def test_regen_check_detects_drift_without_writing(tmp_path, capsys,
                                                   monkeypatch):
    """A stale golden exits 1 naming the file and the first differing line,
    and the file on disk is NOT rewritten (verify-only); restoring the
    rendered text flips it back to 0."""
    import test_exposition

    stale = tmp_path / "metrics.om"
    stale.write_text("# stale golden\n", "utf-8")
    monkeypatch.setattr(test_exposition, "GOLDEN_PATH", str(stale))
    assert regen_golden_metrics.main(["--check"]) == 1
    out = capsys.readouterr().out
    assert f"DRIFT {stale}" in out
    assert stale.read_text("utf-8") == "# stale golden\n"  # untouched

    # a missing golden is drift too, not a crash
    monkeypatch.setattr(test_exposition, "GOLDEN_PATH",
                        str(tmp_path / "missing.om"))
    assert regen_golden_metrics.main(["--check"]) == 1
    assert "golden missing" in capsys.readouterr().out

    # write the canonical render: check goes green
    for path, text in regen_golden_metrics._renders():
        if path == str(tmp_path / "missing.om"):
            with open(path, "w", encoding="utf-8") as f:
                f.write(text)
    assert regen_golden_metrics.main(["--check"]) == 0


# -- chaos audit: down engine ---------------------------------------------------------


def test_chaos_audit_down_engine_exits_one(capsys):
    """An unreachable engine is itself the finding: exit 1 with a
    machine-readable {"ok": false, "error": ...} line."""
    rc = chaos.main(["audit", "127.0.0.1:1", "--format=json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1
    assert out["ok"] is False and "error" in out
