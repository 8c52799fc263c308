"""The mixed rebuild's cell: its generator a function of the seed alone and true
to its law, its reference against itself, its two readers on spans written by
hand, a sound run, the control and a fault at rehearsal size on the CPU
backend, and the manifest."""

import importlib.util
import json
import os
import types

import numpy as np
import pytest

from benchmarks import control, gen_mixed, reference_mixed
from benchmarks import run as harness
from benchmarks.tests.test_cart import assert_listed

CELL = "rebuild-mixed-opaque"
HERE = os.path.dirname(os.path.abspath(__file__))
FAMILIES = gen_mixed.FAMILIES


def config():
    with open(os.path.join(HERE, "..", "configs", "mixed-rebuild.json"),
              encoding="utf-8") as f:
        return json.load(f)


def law():
    return config()["corpus"]


def arrays(corpus):
    """Every array of a mixed corpus, by name."""
    out = {"family": corpus.family}
    for family in FAMILIES:
        for name, value in vars(corpus.part(family)).items():
            if isinstance(value, np.ndarray):
                out[f"{family}.{name}"] = value
    return out


# --- the generator ------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2**31 + 19])
def test_the_generator_is_a_function_of_the_seed_alone(seed):
    first = arrays(gen_mixed.mixed_corpus(500, 50_000, seed, law()))
    again = arrays(gen_mixed.mixed_corpus(500, 50_000, seed, law()))
    other = arrays(gen_mixed.mixed_corpus(500, 50_000, seed + 1, law()))
    assert sorted(first) == sorted(again)
    for name, value in first.items():
        assert np.array_equal(value, again[name]), name
        assert value.dtype == again[name].dtype
    assert not np.array_equal(first["family"], other["family"])
    assert not np.array_equal(first["bank.balance"], other["bank.balance"])


@pytest.mark.parametrize("seed", [1, 3_000_000_019])
def test_the_generator_keeps_its_law(seed):
    corpus = gen_mixed.mixed_corpus(5000, 500_000, seed, law())
    assert corpus.num_aggregates == 5000 and corpus.num_events == 500_000
    sizes = {f: (corpus.part(f).num_aggregates, corpus.part(f).num_events)
             for f in FAMILIES}
    assert sizes == {"bank": (1000, 100_000), "cart": (2000, 200_000),
                     "counter": (2000, 200_000)}
    # every stretch of the union's ids holds all three families
    for lo in range(0, 5000, 250):
        assert set(corpus.family[lo:lo + 250]) == {0, 1, 2}
    for f in FAMILIES:
        assert np.array_equal(corpus.ids(f), np.flatnonzero(
            corpus.family == FAMILIES.index(f)))
    assert int(corpus.lengths().sum()) == 500_000
    assert (corpus.counter.lengths == 100).all()
    # the accounts: a first Created nearly everywhere, an Updated body, 1 % of
    # logs opening with 1 to 3 orphans and 1 % holding a second Created
    bank = corpus.bank
    assert (np.diff(bank.agg_idx) >= 0).all()
    assert bank.lengths.min() < 30 and bank.lengths.max() > 300
    created = bank.type_ids == gen_mixed.CREATED
    full = bank.lengths > 0
    first = bank.starts()[:-1][full]
    per_log = np.add.reduceat(created.astype(np.int64), first)
    assert 0 < int((~created[first]).sum()) < 30  # orphans first
    assert 0 < int((per_log == 2).sum()) < 30 and per_log.max() <= 2
    opened_late = np.flatnonzero(~created[first])
    for k in opened_late[:5]:
        log = created[first[k]: first[k] + bank.lengths[full][k]]
        assert not log.any() or 1 <= int(np.argmax(log)) <= 3
    assert set(np.unique(bank.type_ids)) == {0, 1}
    # a field is zero where the event's type has none
    assert not bank.owner_code[~created].any()
    assert not bank.security_code_code[~created].any()
    assert not bank.balance[~created].any() and not bank.new_balance[created].any()
    assert bank.balance.dtype == bank.new_balance.dtype == np.float32
    amounts = np.where(created, bank.balance, bank.new_balance)
    assert -50_000 <= amounts.min() < 0 < 900_000 < amounts.max() <= 1_000_000
    assert np.isfinite(amounts).all()
    codes = bank.owner_code[created]
    assert 0 <= codes.min() and 32767 < codes.max() < 65536


# --- the reference agrees with itself: whole-column form == scalar fold ----------

@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_closed_form_equals_scalar_fold(seed):
    small = dict(law(), bank=dict(law()["bank"], orphan_share=0.3,
                                  second_created_share=0.3))
    corpus = gen_mixed.mixed_corpus(600, 1200, seed, small)  # two events a log
    assert (corpus.bank.lengths == 0).any()  # an empty log folds to zeros
    want = reference_mixed.closed_form(corpus)
    never = 0
    for family in FAMILIES:
        ids = corpus.ids(family)
        folded = reference_mixed.scalar_fold_sample(
            corpus, family, range(corpus.part(family).num_aggregates))
        for local, state in folded.items():
            assert tuple(want[n][ids[local]]
                         for n in reference_mixed.FIELDS) == state
            never += family == "bank" and not state[3]
    assert never > 5  # accounts whose log held orphans only
    zero = tuple(reference_mixed.ZERO[n] for n in reference_mixed.FIELDS)
    assert tuple(reference_mixed.fold([])[n]
                 for n in reference_mixed.FIELDS) == zero


def test_the_scalar_fold_is_the_masked_switch():
    state = dict(reference_mixed.ZERO)
    h = reference_mixed.handle_event
    # an Updated on no account does nothing; a Created replaces the state
    assert h(state, 1, {"new_balance": 5.0}, 1) == state
    opened = h(state, 0, {"owner_code": 3, "security_code_code": 4,
                          "balance": 2.5}, 2)
    assert (opened["created"], opened["owner_code"], opened["balance"]) == (
        True, 3, np.float32(2.5))
    assert h(opened, 1, {"new_balance": 7.25}, 3)["balance"] == np.float32(7.25)
    again = h(h(opened, 1, {"new_balance": 7.25}, 3), 0,
              {"owner_code": 9, "security_code_code": 1, "balance": 1.0}, 4)
    assert (again["owner_code"], again["balance"]) == (9, np.float32(1.0))
    # a handler moves its own family's columns only; the no-op and padding none
    cart = h(opened, 2, {"quantity": 2, "unit_price_cents": 150}, 5)
    assert (cart["item_count"], cart["total_cents"], cart["version"]) == (2, 300, 5)
    assert {k for k in cart if cart[k] != opened[k]} == {
        "item_count", "total_cents", "version"}
    assert h(opened, 7, {}, 6) == opened and h(opened, -1, {}, 6) == opened
    assert h(opened, 9, {}, 6) == opened
    # what the control's shifted base does: a CheckedOut lands on the counter
    assert reference_mixed.BASES == {"bank": 0, "cart": 2, "counter": 5}


# --- the two readers, on spans written by hand ------------------------------------

def reader(metric):
    path = os.path.join(HERE, "..", "layers", f"{metric}.py")
    spec = importlib.util.spec_from_file_location("layer_" + metric, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class FakeSpan:
    def __init__(self, name, sid, start, end, **attributes):
        self.name, self.parent_id = name, None
        self.context = types.SimpleNamespace(span_id=sid)
        self.start_mono, self.end_mono = start, end
        self.attributes = attributes


def run_over(monkeypatch, held, traced):
    import surge_tpu.tracing as tracing

    ring = types.SimpleNamespace(capacity=4096, spans=lambda: held)
    monkeypatch.setattr(tracing, "default_tracer", lambda: ring, raising=False)
    # the warm-up rebuild at 0, the window's two at 10 and 20
    return types.SimpleNamespace(
        spans=[("replay", 10.0 * i, 10.0 * i + 3.0) for i in range(3)],
        facts={"rebuilds": 2, "rebuild_s": 6.0}, traced=traced)


def test_scan_step_us_is_the_folds_device_time_over_its_steps(monkeypatch):
    read = reader("scan_step_us")
    held = [FakeSpan("replay.resident", "w", 0.0, 3.0, scan_steps=999),
            FakeSpan("replay.resident", "a", 10.0, 13.0, scan_steps=64_000),
            FakeSpan("replay.resident", "b", 20.0, 23.0, scan_steps=64_000)]
    traced = {"program_s": {"jit_fold": 1.28, "jit_finalize": 0.5}}
    assert read(run_over(monkeypatch, held, traced)) == pytest.approx(20.0)
    # the assoc tree takes no steps; the parent's spans carry no count; a run
    # with no trace, or whose trace holds no fold, has no device time
    for attrs in ({"scan_steps": 0}, {}):
        held = [FakeSpan("replay.resident", "a", 10.0, 13.0, **attrs)]
        assert read(run_over(monkeypatch, held, traced)) is None
    held = [FakeSpan("replay.resident", "a", 10.0, 13.0, scan_steps=64_000)]
    assert read(run_over(monkeypatch, held, None)) is None
    assert read(run_over(monkeypatch, held, {"program_s": {}})) is None
    assert read(run_over(monkeypatch, [], traced)) is None


def test_union_live_pct_is_live_over_union_side_bytes(monkeypatch):
    read = reader("union_live_pct")
    held = [FakeSpan("replay.mixed.merge", "m", -30.0, -20.0, families=3,
                     union_side_bytes=2800, live_side_bytes=400)]
    assert read(run_over(monkeypatch, held, None)) == pytest.approx(100 / 7)
    # a program whose merge says nothing (the parent has none at all)
    held = [FakeSpan("replay.mixed.merge", "m", -30.0, -20.0, families=3)]
    assert read(run_over(monkeypatch, held, None)) is None
    assert read(run_over(monkeypatch, [], None)) is None


# --- a sound run, the control, a fault ---------------------------------------------

NUMBERS = {"states_wrong", "events_unaccounted", "scalar_sample_wrong",
           "foreign_columns_nonzero"}


def run_cell(capsys, seed, seconds=1.0):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", "0", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failed_numbers(line):
    return {name for name, c in line["compared"].items()
            if c["value"] > c["limit"]}


def test_mixed_sound_run_is_correct(capsys):
    line = run_cell(capsys, 2**31 + 21)
    assert line["correct"] and not failed_numbers(line)
    assert set(line["compared"]) == NUMBERS
    assert all(c["value"] == 0 == c["limit"] for c in line["compared"].values())
    assert line["window_compilations"] == 0
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so
    assert set(line["metrics"]) == {"rebuild_events_per_s", "setup_s"}


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 12345])
def test_mixed_control_is_not_correct(seed, capsys):
    assert control.main(["--workload", CELL, "--seed", str(seed),
                         "--rehearse"]) == 0  # 0: judged not correct
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not line["control_correct"] and set(line["compared"]) == NUMBERS
    # two aggregates fold under a base off by one at this size, and the whole
    # of every family is in the sample: both numbers show it; no event is lost
    failed = {n for n, c in line["compared"].items() if c["value"] > c["limit"]}
    assert {"states_wrong", "scalar_sample_wrong"} <= failed
    assert line["compared"]["events_unaccounted"]["value"] == 0


def test_the_shifted_base_reaches_another_familys_columns():
    from benchmarks.controls import mixed_rebuild

    corpus = gen_mixed.mixed_corpus(2000, 200_000, 5, law())
    sound = reference_mixed.closed_form(corpus)
    victims = np.arange(0, 2000, 10)
    answer = mixed_rebuild.shifted_answer(corpus, victims)
    moved = np.zeros(2000, dtype=bool)
    for name in reference_mixed.FIELDS:
        moved |= reference_mixed.differs(answer[name], sound[name])
    assert not moved[np.setdiff1d(np.arange(2000), victims)].any()
    assert moved[victims].mean() > 0.9
    # an account's Updated lands on the cart's ItemAdded, which moves version
    banks = victims[corpus.family[victims] == 0]
    assert answer["version"][banks].any() and not sound["version"][banks].any()
    assert not answer["created"][banks].any()


@pytest.mark.parametrize("column", reference_mixed.FIELDS)
def test_mixed_fault_an_answer_altered_where_it_is_produced(capsys, monkeypatch,
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


def test_a_tree_without_the_merge_stops_at_once(monkeypatch):
    from benchmarks.drivers import mixed_rebuild
    from surge_tpu.replay.mixed import MixedReplay

    monkeypatch.delattr(MixedReplay, "merge_columnar")
    made = []
    monkeypatch.setattr(gen_mixed, "mixed_corpus",
                        lambda *a, **kw: made.append(a))
    with pytest.raises(SystemExit, match="no columnar merge"):
        mixed_rebuild.run(types.SimpleNamespace())
    assert not made  # before any corpus is made


# --- the manifest ---------------------------------------------------------------------

def test_the_manifest_is_clean_and_lists_the_cell():
    assert harness.main(["--check"]) == 0
    man, _cell, cfg, _traffic = harness.load_cell(CELL)
    assert cfg["sizes"] == {"aggregates": 1_000_000, "events": 100_000_000}
    assert cfg["reduced"] == ["chips"] and cfg["driver"] == "mixed_rebuild"
    assert_listed(man, CELL, "mixed-rebuild", 1, "rebuild-loop")
    reported = {m["name"] for m in man["per_layer"]
                if harness.reports(m, CELL, man)}
    layers = {m["name"]: m for m in man["per_layer"]}
    # the cold fold's readers and the two of the sequential fold
    assert reported >= {"device_idle_pct.rebuild", "fold_roofline",
                        "pack_share_pct", "pad_ratio", "h2d_share_pct",
                        "fetch_wait_pct", "pull_bytes_ratio",
                        "small_tile_slots_pct", "scan_step_us",
                        "union_live_pct"}
    for name in ("scan_step_us", "union_live_pct"):
        assert CELL in layers[name]["workloads"]
        # a cell without the sequential fold reports neither
        for other in ("rebuild-1m-100m", "rebuild-cart-ragged"):
            assert not harness.reports(layers[name], other, man)


def test_the_configurations_work_is_its_own_arithmetic():
    work = config()["work"]
    live = (40_000_000 - 120_000) * 8 + 202_000 * 12 + 19_798_000 * 4
    assert work["event_wire_bytes"] == (100_000_000 + live) / 100_000_000
    assert work["state_row_bytes"] == (400_000 * 8 + 400_000 * 13
                                       + 200_000 * 13) / 1_000_000
    # and what a corpus of the law gives, to a part in a thousand
    corpus = gen_mixed.mixed_corpus(20_000, 2_000_000, 9, law())
    got = (8 * int((corpus.cart.type_ids != 2).sum())
           + 12 * int((corpus.bank.type_ids == 0).sum())
           + 4 * int((corpus.bank.type_ids == 1).sum())) / 2_000_000
    assert got == pytest.approx(live / 100_000_000, rel=1e-3)
