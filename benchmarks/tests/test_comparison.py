"""The comparison that decides ``correct``: sound runs pass, the control and
each fault a cell can have come out as not correct. At rehearsal size, on the
CPU backend: the harness's look for a chip is skipped (``--rehearse``), the
rest of a run is driven as it stands, with the timed path broken underneath.
"""

import json

import numpy as np
import pytest

from benchmarks import control, gen, reference
from benchmarks import run as harness


#: the served node's cell waits outside BENCHMARK.json (PERF.md section 7)
STAGED = "benchmarks/staged/node-cells.json"
MANIFEST_OF = {"rebuild-1m-100m": harness.MANIFEST, "node-update-heavy": STAGED}
LAW = {"length_law": "fixed", "event_mix": [0.45, 0.35, 0.15, 0.05]}


def run_cell(capsys, cell: str, seed: int, seconds: float = 2.0) -> dict:
    rc = harness.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", "0", "--rehearse",
                       "--manifest", MANIFEST_OF[cell]])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failed_numbers(line: dict) -> set:
    return {name for name, c in line["compared"].items()
            if c["value"] > c["limit"]}


# --- the reference agrees with itself: closed form == scalar fold ---------------

@pytest.mark.parametrize("law", ["fixed", "lognormal"])
@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_closed_form_equals_scalar_fold(seed, law):
    corpus = gen.counter_corpus(300, 20_000, seed,
                                dict(LAW, length_law=law, length_sigma=0.6))
    assert corpus.num_events == 20_000 == int(corpus.lengths.sum())
    if law == "fixed":  # 66 or 67 events a log: the remainder goes to the first
        assert set(corpus.lengths.tolist()) == {66, 67}
    count, version = reference.closed_form(corpus)
    for b, want in reference.scalar_fold_sample(corpus, range(300)).items():
        assert (int(count[b]), int(version[b])) == want


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_preloaded_states_equal_the_scalar_fold(seed):
    kinds = gen.preload_kinds(200, 8, seed)
    count, version = reference.preloaded_states(kinds)
    for i in range(200):
        assert reference.preloaded_state(kinds[i].tolist()) == (count[i],
                                                                version[i])


def test_every_seed_gets_the_same_work_in_another_order():
    traffic = {"rate_ops_per_s": 500, "command_share": 0.5,
               "increment_share": 0.7, "keys": {"law": "zipf", "s": 0.99}}
    a = gen.open_loop_schedule(traffic, 4096, 4.0, 1)
    b = gen.open_loop_schedule(traffic, 4096, 4.0, 2**31 + 9)
    assert len(a.due) == len(b.due) == 2000
    assert a.is_command.sum() == b.is_command.sum() == 1000
    assert a.is_increment.sum() == b.is_increment.sum() == 700
    gaps = lambda s: np.sort(np.diff(s.due, prepend=0.0))  # noqa: E731
    assert np.allclose(gaps(a), gaps(b))
    hot = lambda s: np.sort(np.unique(s.key, return_counts=True)[1])  # noqa: E731
    assert np.array_equal(hot(a), hot(b))
    assert not np.array_equal(a.key, b.key)
    assert a.due[-1] < 4.0


# --- sound runs, the controls, the faults ------------------------------------------

def test_rebuild_sound_run_is_correct(capsys):
    line = run_cell(capsys, "rebuild-1m-100m", 11)
    assert line["correct"] and not failed_numbers(line)
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 12345])
def test_rebuild_control_is_not_correct(seed):
    _man, cell, config, traffic = harness.load_cell("rebuild-1m-100m")
    run = harness.Run(cell, config, traffic, seed, 1.0, False, True)
    failed = {name for name, value, limit in control.control(run) if value > limit}
    # two logs lose an event at this size, and a lost no-op changes no state
    assert "events_unaccounted" in failed, failed


def test_rebuild_fault_an_answer_altered_where_it_is_produced(capsys, monkeypatch):
    from surge_tpu.replay import ReplayEngine

    sound = ReplayEngine.replay_resident

    def altered(self, resident, *a, **kw):
        res = sound(self, resident, *a, **kw)
        res.states["count"] = np.array(res.states["count"])
        res.states["count"][17] += 1
        return res

    monkeypatch.setattr(ReplayEngine, "replay_resident", altered)
    line = run_cell(capsys, "rebuild-1m-100m", 12)
    assert not line["correct"]
    assert "states_wrong" in failed_numbers(line)


#: PERF.md, Open questions, row 1: under host contention the program's live
#: refresh loses events, on the CPU backend too, so a sound run can read wrong
RACE = pytest.mark.xfail(strict=False, reason="the program's refresh "
                         "fast-forward race (PERF.md section 7, row 1)")


@RACE
def test_node_sound_run_is_correct(capsys):
    line = run_cell(capsys, "node-update-heavy", 13, seconds=2.0)
    assert line["correct"], {n: line["compared"][n] for n in failed_numbers(line)}
    assert line["attempted"] == 1600 and line["failed"] == 0  # 800 a second, 2 s


@pytest.mark.parametrize("seed", [4, 2**31 + 12, 999])
def test_node_control_is_not_correct(seed):
    _man, cell, config, traffic = harness.load_cell("node-update-heavy", STAGED)
    run = harness.Run(cell, config, traffic, seed, 3.0, False, True)
    compared = control.control(run)
    assert any(value > limit for _name, value, limit in compared), compared


def test_node_fault_a_read_altered_where_it_is_produced(capsys, monkeypatch):
    from surge_tpu.engine import pipeline
    from surge_tpu.models import counter

    engine_class = pipeline.SurgeEngine
    sound = engine_class.project_states
    calls = {"n": 0}

    async def altered(self, ids, **kw):
        got = await sound(self, ids, **kw)
        calls["n"] += 1
        if calls["n"] % 50 == 0 and got:
            agg, st = next(iter(got.items()))
            got[agg] = counter.State(agg, st.count + 1, st.version)
        return got

    monkeypatch.setattr(engine_class, "project_states", altered)
    line = run_cell(capsys, "node-update-heavy", 14, seconds=2.0)
    assert not line["correct"]
    assert "reads_wrong" in failed_numbers(line)


def test_node_fault_an_ack_altered_where_it_is_produced(capsys, monkeypatch):
    from benchmarks.drivers import node as node_driver

    sound = node_driver.Node.command
    calls = {"n": 0}

    async def altered(self, i, increment):
        ok = await sound(self, i, increment)
        calls["n"] += 1
        if ok and calls["n"] % 100 == 0:
            delta, count, version = self.acks[i][-1]
            self.acks[i][-1] = (delta, count - 2, version)  # what the ack "carried"
        return ok

    monkeypatch.setattr(node_driver.Node, "command", altered)
    line = run_cell(capsys, "node-update-heavy", 15, seconds=2.0)
    assert not line["correct"]
    assert "acks_wrong" in failed_numbers(line)
