"""The cart's cell: its generator's invariants, its reference against itself,
its two readers on a span list written by hand, a sound run, the control and a
fault at rehearsal size on the CPU backend, and the manifest."""

import importlib.util
import json
import os
import types

import numpy as np
import pytest

from benchmarks import control, gen_cart, reference_cart
from benchmarks import run as harness

CELL = "rebuild-cart-ragged"
HERE = os.path.dirname(os.path.abspath(__file__))


def law():
    with open(os.path.join(HERE, "..", "configs", "cart-rebuild.json"),
              encoding="utf-8") as f:
        return json.load(f)["corpus"]


# --- the generator ------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2**31 + 19])
def test_the_generator_keeps_its_invariants(seed):
    corpus = gen_cart.cart_corpus(400, 40_000, seed, law())
    n = corpus.num_events
    assert n == 40_000 == int(corpus.lengths.sum())
    assert np.array_equal(np.bincount(corpus.agg_idx, minlength=400),
                          corpus.lengths)
    assert (np.diff(corpus.agg_idx) >= 0).all()
    assert corpus.lengths.min() < 30 and corpus.lengths.max() > 300  # ragged
    # at most one CheckedOut a cart, and only as its last event
    closed = np.flatnonzero(corpus.type_ids == gen_cart.CHECKED_OUT)
    ends = corpus.starts()[1:] - 1
    assert np.isin(closed, ends).all()
    assert 0.2 < closed.size / 400 < 0.4
    assert not corpus.quantity[closed].any()
    # the body: added 1..5 and removed 1..2, a removal at its item's price
    body = np.ones(n, dtype=bool)
    body[closed] = False
    added = corpus.type_ids == gen_cart.ADDED
    removed = corpus.type_ids == gen_cart.REMOVED
    assert (added | removed)[body].all()
    assert 0.58 < added.sum() / body.sum() < 0.66
    assert set(np.unique(corpus.quantity[added])) == {1, 2, 3, 4, 5}
    assert set(np.unique(corpus.quantity[removed])) == {1, 2}
    assert np.array_equal(corpus.unit_price_cents[body],
                          gen_cart.price_of(corpus.item_code[body], law()))
    assert 99 <= corpus.unit_price_cents[body].min()
    assert corpus.unit_price_cents[body].max() <= 49_999
    assert 0 <= corpus.item_code.min() and corpus.item_code.max() < 65_536
    # every running total inside the state's int32, and past int16 both ways
    least, greatest = gen_cart.running_total_extremes(corpus)
    assert -2**31 <= least < -32768 and 32767 < greatest < 2**31
    again = gen_cart.cart_corpus(400, 40_000, seed, law())
    assert np.array_equal(again.unit_price_cents, corpus.unit_price_cents)


def test_the_generator_refuses_a_total_that_leaves_int32():
    dear = dict(law(), price_cents=[400_000_000, 400_000_001],
                body_mix=[1.0, 0.0], checkout_share=0.0)
    with pytest.raises(AssertionError):
        gen_cart.cart_corpus(4, 40, 1, dear)


# --- the reference agrees with itself: whole-column form == scalar fold ----------

@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_closed_form_equals_scalar_fold(seed):
    corpus = gen_cart.cart_corpus(300, 600, seed, law())  # two events a cart
    assert (corpus.lengths == 0).any()  # empty carts fold to the empty state
    want = reference_cart.closed_form(corpus)
    for b, state in reference_cart.scalar_fold_sample(corpus, range(300)).items():
        assert tuple(want[name][b] for name in reference_cart.FIELDS) == state
    assert reference_cart.fold([]) == (0, 0, False, 0)


# --- the two readers, on spans written by hand ------------------------------------

def rec(name, sid, parent, start, end, **attributes):
    return {"name": name, "id": sid, "parent": parent, "start": start,
            "end": end, "attributes": attributes}


def rebuild_spans(tag, at, fetches, **fold):
    """One rebuild's fold [at, at + 3] with the given (wire, bytes) fetches."""
    out = [rec("replay.resident", "r" + tag, None, at, at + 3.0,
               aggregates=1000, **fold),
           rec("replay.fetch", "f" + tag, "r" + tag, at + 1.0, at + 3.0,
               aggregates=1000)]
    step = 2.0 / (len(fetches) + 1)
    for i, attrs in enumerate(fetches):
        out.append(rec("replay.fetch.wait", f"w{tag}{i}", "f" + tag,
                       at + 1.0 + i * step, at + 1.0 + (i + 1) * step, **attrs))
    return out


class FakeSpan:
    def __init__(self, r):
        self.name, self.parent_id = r["name"], r["parent"]
        self.context = types.SimpleNamespace(span_id=r["id"])
        self.start_mono, self.end_mono = r["start"], r["end"]
        self.attributes = r["attributes"]


def reader(metric):
    path = os.path.join(HERE, "..", "layers", f"{metric}.py")
    spec = importlib.util.spec_from_file_location("layer_" + metric, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def run_over(monkeypatch, recs, rebuilds, state_row_bytes=13):
    import surge_tpu.tracing as tracing

    ring = types.SimpleNamespace(capacity=4096,
                                 spans=lambda: [FakeSpan(r) for r in recs])
    monkeypatch.setattr(tracing, "default_tracer", lambda: ring, raising=False)
    # the warm-up rebuild at 0, the window's from 10 on, 10 s apart
    harness_spans = [("replay", 10.0 * i, 10.0 * i + 3.0)
                     for i in range(rebuilds + 1)]
    return types.SimpleNamespace(
        spans=harness_spans, facts={"rebuilds": rebuilds, "rebuild_s": 3.0},
        config={"work": {"state_row_bytes": state_row_bytes}})


def test_pull_bytes_ratio_counts_every_fetch_of_the_windows_rebuilds(monkeypatch):
    read = reader("pull_bytes_ratio")
    guess = [{"wire": "narrow", "bytes": 8008}, {"wire": "mixed", "bytes": 10008}]
    once = [{"wire": "mixed", "bytes": 10008}]
    # the warm-up pays the refetch; the window's two rebuilds fetch once
    recs = (rebuild_spans("a", 0.0, guess) + rebuild_spans("b", 10.0, once)
            + rebuild_spans("c", 20.0, once))
    run = run_over(monkeypatch, recs, rebuilds=2)
    assert read(run) == pytest.approx(2 * 10008 / (2 * 1000 * 13))
    # a program that guesses on every rebuild: both fetches count
    recs = rebuild_spans("a", 0.0, guess) + rebuild_spans("b", 10.0, [
        {"wire": "narrow", "bytes": 8008}, {"wire": "wide", "bytes": 16000}])
    run = run_over(monkeypatch, recs, rebuilds=1)
    assert read(run) == pytest.approx(24008 / 13000)
    # the counter: two int32 columns on the half-width wire
    recs = rebuild_spans("a", 10.0, [{"wire": "narrow", "bytes": 4004}])
    run = run_over(monkeypatch, recs, rebuilds=1, state_row_bytes=8)
    assert read(run) == pytest.approx(0.5005)


def test_the_new_readers_give_nothing_on_a_program_without_the_counts(monkeypatch):
    # the parent commit: the spans are there, their new attributes are not
    recs = rebuild_spans("a", 10.0, [{"wire": "narrow"}, {"wire": "wide"}],
                         padded_slots=4096, tiles=2)
    run = run_over(monkeypatch, recs, rebuilds=1)
    assert reader("pull_bytes_ratio")(run) is None
    assert reader("small_tile_slots_pct")(run) is None
    run = run_over(monkeypatch, [], rebuilds=1)  # and no ring at all
    assert reader("pull_bytes_ratio")(run) is None
    assert reader("small_tile_slots_pct")(run) is None


def test_small_tile_slots_pct_is_slots_small_over_padded_slots(monkeypatch):
    read = reader("small_tile_slots_pct")
    recs = (rebuild_spans("a", 0.0, [], padded_slots=999, slots_small=999)
            + rebuild_spans("b", 10.0, [], padded_slots=4096, slots_small=512)
            + rebuild_spans("c", 20.0, [], padded_slots=4096, slots_small=512))
    assert read(run_over(monkeypatch, recs, rebuilds=2)) == pytest.approx(12.5)
    recs = rebuild_spans("a", 10.0, [], padded_slots=4096, slots_small=0)
    assert read(run_over(monkeypatch, recs, rebuilds=1)) == 0.0


# --- a sound run, the control, a fault ---------------------------------------------

def run_cell(capsys, seed, seconds=1.0):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", "0", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failed_numbers(line):
    return {name for name, c in line["compared"].items()
            if c["value"] > c["limit"]}


def test_cart_sound_run_is_correct(capsys):
    line = run_cell(capsys, 2**31 + 21)
    assert line["correct"] and not failed_numbers(line)
    assert set(line["compared"]) == {"states_wrong", "events_unaccounted",
                                     "scalar_sample_wrong"}
    assert all(c["limit"] == 0 for c in line["compared"].values())
    assert line["window_compilations"] == 0
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so
    assert set(line["metrics"]) == {"rebuild_events_per_s", "setup_s"}


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 12345])
def test_cart_control_is_not_correct(seed, capsys):
    assert control.main(["--workload", CELL, "--seed", str(seed),
                         "--rehearse"]) == 0  # 0: judged not correct
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not line["control_correct"]
    # two carts lose their last event at this size: the count always shows
    # it, and the states do too, since every event moves the version
    failed = {n for n, c in line["compared"].items() if c["value"] > c["limit"]}
    assert {"events_unaccounted", "states_wrong"} <= failed


@pytest.mark.parametrize("column", reference_cart.FIELDS)
def test_cart_fault_an_answer_altered_where_it_is_produced(capsys, monkeypatch,
                                                          column):
    from surge_tpu.replay import ReplayEngine

    sound = ReplayEngine.replay_resident

    def altered(self, resident, *a, **kw):
        res = sound(self, resident, *a, **kw)
        col = np.array(res.states[column])
        col[17] = ~col[17] if col.dtype == bool else col[17] + 1
        res.states[column] = col
        return res

    monkeypatch.setattr(ReplayEngine, "replay_resident", altered)
    line = run_cell(capsys, 12)
    assert not line["correct"]
    assert "states_wrong" in failed_numbers(line)


# --- the manifest ---------------------------------------------------------------------

def assert_listed(man, cell, config_name, chips, traffic):
    """The manifest lists ``cell`` once, with its configuration, chips and
    traffic, found by name and not by place (later cells are appended), and
    asks for four chips in no more cells than the contract's cap."""
    entries = [w for w in man["workloads"] if w["name"] == cell]
    assert len(entries) == 1
    assert (entries[0]["config"], entries[0]["chips"], entries[0]["traffic"]) \
        == (config_name, chips, traffic)
    assert config_name in [c["name"] for c in man["configs"]]
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(man["workloads"]) // 2)


def test_the_manifest_is_clean_and_lists_the_cell():
    assert harness.main(["--check"]) == 0
    man, _cell, config, _traffic = harness.load_cell(CELL)
    assert_listed(man, CELL, "cart-rebuild", 1, "rebuild-loop")
    assert config["sizes"] == {"aggregates": 1_000_000, "events": 100_000_000}
    reported = {m["name"] for m in man["per_layer"]
                if harness.reports(m, CELL, man)}
    layers = {m["name"]: m for m in man["per_layer"]}
    # the cold fold's readers, the two this cell brought among them
    assert reported >= {"device_idle_pct.rebuild", "fold_roofline",
                        "pack_share_pct", "pad_ratio", "h2d_share_pct",
                        "fetch_wait_pct", "pull_bytes_ratio",
                        "small_tile_slots_pct"}
    for name in ("pull_bytes_ratio", "small_tile_slots_pct"):
        assert {"rebuild-1m-100m", CELL} <= set(layers[name]["workloads"])
    # none of another layer's: the mixed fold's, the mesh's, the restore's,
    # the scan's
    assert not reported & {"scan_step_us", "union_live_pct",
                           "mesh_fold_roofline", "shard_share_pct",
                           "restore_read_pct", "scan_read_pct"}
