"""The sharded mixed rebuild's cell: the manifest, its three readers on records
and a reduced trace written by hand, a sound run at rehearsal size on four
forced host devices, the control, and a tree without the deal's span."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks import control, gen_mixed, reference_mixed
from benchmarks import run as harness
from benchmarks.tests.test_cart import assert_listed

CELL = "rebuild-mixed-mesh4"
CONFIG = "mixed-rebuild-mesh4"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NUMBERS = {"states_wrong", "events_unaccounted", "scalar_sample_wrong",
           "foreign_columns_nonzero"}
OWN = ["mesh_fold_roofline", "shard_share_pct", "shard_skew_ratio"]


def config(name=CONFIG):
    with open(os.path.join(HERE, "..", "configs", f"{name}.json"),
              encoding="utf-8") as f:
        return json.load(f)


def reader(metric):
    path = os.path.join(HERE, "..", "layers", f"{metric}.py")
    spec = importlib.util.spec_from_file_location("layer_" + metric, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# --- the manifest ---------------------------------------------------------------------

def test_the_manifest_is_clean_and_names_the_cell():
    assert harness.main(["--check"]) == 0
    man, _cell, cfg, _traffic = harness.load_cell(CELL)
    assert_listed(man, CELL, CONFIG, 4, "rebuild-loop")
    assert cfg["driver"] == "mixed_rebuild_mesh" and cfg["chips"] == 4
    assert cfg["reduced"] == ["chips"]
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    reported = {m["name"] for m in man["per_layer"]
                if harness.reports(m, CELL, man)}
    assert set(OWN) <= reported
    # one chip's bandwidth under four chips' work would read four times high
    assert "fold_roofline" not in reported
    assert {"device_idle_pct.rebuild", "scan_step_us", "union_live_pct",
            "h2d_share_pct", "fetch_wait_pct", "pull_bytes_ratio"} <= reported
    for m in man["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["layer"] == "Mesh"
            assert m["moves"] == "rebuild_events_per_s"
    moved = next(m for m in man["end_to_end"]
                 if m["name"] == "rebuild_events_per_s")
    assert CELL in moved["workloads"] and moved["bound"] == 0.085


def test_the_configuration_is_the_mixed_cells_but_for_the_chips():
    mesh, one = config(), config("mixed-rebuild")
    same = ["sizes", "source_sizes", "corpus", "assumed", "guarantees", "work",
            "check", "rehearse_factor", "reduced"]
    for key in same:
        assert mesh[key] == one[key], key
    assert mesh["fixes"]["schema"] == one["fixes"]["schema"]
    assert sorted(k for k in mesh if mesh[k] != one.get(k)) == [
        "chips", "deployment", "driver", "fixes", "name", "reduced_why",
        "source"]
    assert (mesh["chips"], one["chips"]) == (4, 1)
    assert "8 -> 4" in mesh["reduced_why"]


# --- the three readers ----------------------------------------------------------------

class FakeSpan:
    def __init__(self, name, sid, start, end, **attributes):
        self.name, self.parent_id = name, None
        self.context = types.SimpleNamespace(span_id=sid)
        self.start_mono, self.end_mono = start, end
        self.attributes = attributes


def run_over(monkeypatch, held, traced=None):
    import surge_tpu.tracing as tracing

    ring = types.SimpleNamespace(capacity=4096, spans=lambda: held)
    monkeypatch.setattr(tracing, "default_tracer", lambda: ring, raising=False)
    # the warm-up rebuild at 0, the window's two at 10 and 20
    return types.SimpleNamespace(
        spans=[("upload", 10.0 * i, 10.0 * i + 4.0) for i in range(3)],
        facts={"rebuilds": 2, "rebuild_s": 8.0, "events": 100_000_000,
               "aggregates": 1_000_000},
        config={"work": config()["work"]}, traced=traced,
        device={"kind": "TPU v5 lite"})


def deals(**attributes):
    return [FakeSpan("replay.shard", w, t, t + 0.2, **attributes)
            for w, t in (("w", 0.0), ("a", 10.0), ("b", 20.0))]


def test_shard_share_pct_is_the_deals_share_of_the_rebuilds(monkeypatch):
    read = reader("shard_share_pct")
    # two deals of 0.2 s in the window's 8 s of rebuilds; the warm-up's is out
    assert read(run_over(monkeypatch, deals())) == pytest.approx(5.0)
    # a program whose sharded rebuild opens no such span (the parent's)
    other = [FakeSpan("replay.h2d", "a", 10.0, 11.0)]
    assert read(run_over(monkeypatch, other)) is None
    assert read(run_over(monkeypatch, [])) is None


def test_shard_skew_ratio_is_the_busiest_device_over_an_even_share(monkeypatch):
    read = reader("shard_skew_ratio")
    even = deals(events=100_000_000, devices=4, events_max=25_000_000)
    assert read(run_over(monkeypatch, even)) == pytest.approx(1.0)
    skewed = deals(events=100_000_000, devices=4, events_max=30_000_000)
    assert read(run_over(monkeypatch, skewed)) == pytest.approx(1.2)
    # spans that carry no counts, and none at all
    assert read(run_over(monkeypatch, deals())) is None
    assert read(run_over(monkeypatch, [])) is None


def reduced(chips, layer_s):
    return {"chips": chips, "layer_s": {"Cold fold programs": layer_s},
            "program_s": {"jit_fold": layer_s}, "busy_s": layer_s,
            "window_s": 1.0}


def test_mesh_fold_roofline_of_a_fold_at_the_chips_bandwidth_reads_100(
        monkeypatch):
    read = reader("mesh_fold_roofline")
    one_chip = reader("fold_roofline")
    work = config()["work"]
    least = (100_000_000 * work["event_wire_bytes"]
             + 1_000_000 * work["state_row_bytes"])
    # each of four chips busy for a quarter of the log at its own bandwidth:
    # the reduction gives the layer's time as the average over the chips
    at_peak = least / 4 / 819e9
    run = run_over(monkeypatch, [], reduced(4, at_peak))
    assert read(run) == pytest.approx(100.0)
    assert one_chip(run) == pytest.approx(400.0)  # why the cell leaves it out
    assert read(run_over(monkeypatch, [], reduced(4, 10 * at_peak))) == (
        pytest.approx(10.0))
    # on one chip it is the one-chip reader
    run = run_over(monkeypatch, [], reduced(1, 0.5))
    assert read(run) == pytest.approx(one_chip(run))
    # no trace, or a trace without the layer's programs
    assert read(run_over(monkeypatch, [], None)) is None
    assert read(run_over(monkeypatch, [], {"chips": 4, "layer_s": {}})) is None


# --- a sound run, the control, a tree without the deal's span -----------------------

def test_a_rehearsal_on_four_forced_devices_is_correct():
    """In a process of its own: the cell's mesh is the first four devices, and
    the issue's rehearsal forces exactly four."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL, "--seed",
         str(2**31 + 33), "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and set(line["compared"]) == NUMBERS
    assert all(c["value"] == 0 == c["limit"] for c in line["compared"].values())
    assert line["window_compilations"] == 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                              "memory_peak_bytes": 0}
    assert set(line["metrics"]) == {"rebuild_events_per_s", "setup_s"}
    assert "devices=4" in out.stderr


def test_a_traced_rehearsal_reads_the_cells_span_metrics(capsys):
    """In this process (eight forced devices, the first four taken): every
    reader that needs no device trace gives a number."""
    rc = harness.main(["--workload", CELL, "--seed", "12", "--seconds", "1",
                       "--trace", "1", "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"]
    got = line["metrics"]
    assert {"shard_share_pct", "shard_skew_ratio", "h2d_share_pct",
            "h2d_pad_ratio", "fetch_wait_pct", "span_unaccounted_pct",
            "pull_bytes_ratio", "small_tile_slots_pct", "union_live_pct",
            "pack_share_pct", "pad_ratio"} <= set(got)
    assert 1.0 <= got["shard_skew_ratio"]["value"] < 1.05
    assert 0.0 < got["shard_share_pct"]["value"] < 25.0
    assert "mesh_fold_roofline" not in got  # the CPU backend has no trace


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 12345])
def test_the_control_is_not_correct(seed, capsys):
    assert control.main(["--workload", CELL, "--seed", str(seed),
                         "--rehearse"]) == 0  # 0: judged not correct
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not line["control_correct"] and set(line["compared"]) == NUMBERS
    failed = {n for n, c in line["compared"].items() if c["value"] > c["limit"]}
    assert {"states_wrong", "scalar_sample_wrong"} <= failed
    # the last device's share, a quarter of 2,000 aggregates, one lane off:
    # nearly all of them wrong, far over the mixed control's two
    assert line["compared"]["states_wrong"]["value"] > 400
    assert line["compared"]["events_unaccounted"]["value"] == 0


def test_one_lane_off_moves_the_last_devices_share_alone():
    from benchmarks.controls import mixed_rebuild_mesh

    corpus = gen_mixed.mixed_corpus(2000, 200_000, 5, config()["corpus"])
    sound = reference_mixed.closed_form(corpus)
    answer = mixed_rebuild_mesh.one_lane_off(sound, 4)
    moved = np.zeros(2000, dtype=bool)
    for name in reference_mixed.FIELDS:
        moved |= reference_mixed.differs(answer[name], sound[name])
        assert answer[name].dtype == np.asarray(sound[name]).dtype
    assert not moved[:1500].any() and moved[1500:].mean() > 0.9
    for name in reference_mixed.FIELDS:  # the same states, each one lane on
        assert not reference_mixed.differs(answer[name][1501:],
                                           np.asarray(sound[name])[1500:-1]).any()


def test_a_tree_without_the_deals_span_stops_at_once(monkeypatch):
    from benchmarks.drivers import mixed_rebuild_mesh

    # the parent's deal opened no span: take the new one's name away
    monkeypatch.setattr(mixed_rebuild_mesh, "SHARD_SPAN", "replay.shard.none")
    made = []
    sound = gen_mixed.mixed_corpus

    def counted(aggregates, *a, **kw):
        made.append(aggregates)
        return sound(aggregates, *a, **kw)

    monkeypatch.setattr(gen_mixed, "mixed_corpus", counted)
    run = types.SimpleNamespace(cell={"chips": 4}, seed=1, config=config(),
                                sizes=config()["sizes"])
    with pytest.raises(SystemExit, match="opens no replay.shard"):
        mixed_rebuild_mesh.run(run)
    assert made == [40]  # the asking's few hundred events, nothing of size
