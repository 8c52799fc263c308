"""The mixed replay's cold rebuild: counters, carts and bank accounts merged
whole-column into one union log (``MixedReplay.merge_columnar``) and folded in
one batch through ``pack_resident`` -> ``upload_resident`` -> ``replay_resident``
by the sequential masked-switch tile, against the benchmark's plain reference
(``benchmarks/reference_mixed.py``), against each family's own engine folded
alone, and against the object-at-a-time bridge (``encode_logs``)."""

import json
import os
import random
import time

import numpy as np
import pytest

from benchmarks import gen_mixed, reference_mixed
from surge_tpu.codec.tensor import ColumnarEvents, encode_events_columnar
from surge_tpu.config import default_config
from surge_tpu.models import bank_account, counter, shopping_cart
from surge_tpu.replay.engine import ReplayEngine
from surge_tpu.replay.mixed import combine_replay_specs, fields_read
from surge_tpu.testing import random_bank_log, random_cart_log, random_counter_log
from surge_tpu.tracing import default_tracer

FAMILIES = gen_mixed.FAMILIES
HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "benchmarks", "configs", "mixed-rebuild.json"),
          encoding="utf-8") as f:
    LAW = json.load(f)["corpus"]
SPECS = {"bank": bank_account.make_replay_spec,
         "cart": shopping_cart.make_replay_spec,
         "counter": counter.make_replay_spec}
NUMBERED = {"bank": {}, "cart": {"sequence_number": "ordinal"},
            "counter": {"sequence_number": "ordinal"}}


def make_mixed(families=FAMILIES):
    return combine_replay_specs({f: SPECS[f]() for f in families})


def make_engine(spec, batch=256, chunk=16):
    cfg = default_config().with_overrides({
        "surge.replay.batch-size": batch, "surge.replay.time-chunk": chunk})
    return ReplayEngine(spec, config=cfg)


def parts_of(corpus):
    out = {}
    for family in FAMILIES:
        part = corpus.part(family)
        out[family] = ColumnarEvents(
            num_aggregates=part.num_aggregates, agg_idx=part.agg_idx,
            type_ids=part.type_ids, cols=corpus.columns(family),
            derived_cols=dict(NUMBERED[family]))
    return out


def named(spans, name):
    return [s for s in spans if s.name == name]


# --- the merge against the object-at-a-time bridge ---------------------------------

MAKERS = {"bank": random_bank_log, "cart": random_cart_log,
          "counter": random_counter_log}


def tagged_logs(rng, present, lanes, empty_every=0):
    vocab = bank_account.Vocab()
    out = []
    for i in range(lanes):
        family = rng.choice(present)
        log = MAKERS[family](rng, f"a{i}")
        if family == "bank":
            log = [bank_account.encode_event(vocab, e) for e in log]
        if empty_every and i % empty_every == 0:
            log = []
        out.append((family, log))
    return out


@pytest.mark.parametrize("present, lanes, empty_every", [
    (("bank", "cart", "counter"), 60, 0),
    (("bank", "cart", "counter"), 60, 3),  # every third log empty
    (("bank", "counter"), 40, 0),  # a family with no lane: an empty part
    (("cart",), 20, 4),
    (("bank", "cart", "counter"), 0, 0),  # no lane at all
], ids=["three", "three-empty-logs", "no-carts", "carts-alone", "nothing"])
def test_merge_columnar_equals_encode_logs(present, lanes, empty_every):
    mixed = make_mixed()
    tagged = tagged_logs(random.Random(len(present) * 100 + lanes), present,
                         lanes, empty_every)
    want = mixed.encode_logs(tagged)
    parts = {f: encode_events_columnar(
        mixed.parts[f].registry, [log for fam, log in tagged if fam == f])
        for f in FAMILIES}
    order = [fam for fam, _ in tagged]
    since = time.monotonic()
    got = mixed.merge_columnar(parts, order)
    assert got.num_aggregates == want.num_aggregates == lanes
    np.testing.assert_array_equal(got.agg_idx, want.agg_idx)
    np.testing.assert_array_equal(got.type_ids, want.type_ids)
    assert got.type_ids.dtype == want.type_ids.dtype
    assert sorted(got.cols) == sorted(want.cols) and not got.derived_cols
    for name, col in want.cols.items():
        assert got.cols[name].dtype == col.dtype, name
        np.testing.assert_array_equal(got.cols[name], col, err_msg=name)
    # the same by family indices, and a part left out where it has no lane
    index = mixed.families(order)
    assert index.dtype == np.int8
    assert [list(mixed.bases)[i] for i in index] == order
    some = {f: p for f, p in parts.items() if p.num_aggregates}
    again = mixed.merge_columnar(some, index)
    np.testing.assert_array_equal(again.type_ids, want.type_ids)
    (span, _) = named(default_tracer().spans(since_mono=since),
                      "replay.mixed.merge")
    assert span.attributes["events"] == want.num_events
    assert span.attributes["families"] == 3


def test_merge_refuses_what_it_cannot_lay_out():
    mixed = make_mixed()
    corpus = gen_mixed.mixed_corpus(30, 300, 1, LAW)
    parts = parts_of(corpus)
    with pytest.raises(ValueError, match="order gives 'bank' 30"):
        mixed.merge_columnar(parts, np.zeros(30, dtype=np.int8))
    with pytest.raises(KeyError, match="unknown model"):
        mixed.merge_columnar(parts, ["bank"] * 29 + ["ledger"])
    with pytest.raises(ValueError, match="family index"):
        mixed.families(np.array([0, 3]))
    # one family derives a column that another supplies
    explicit = dict(parts, counter=ColumnarEvents(
        num_aggregates=parts["counter"].num_aggregates,
        agg_idx=parts["counter"].agg_idx, type_ids=parts["counter"].type_ids,
        cols=dict(parts["counter"].cols, sequence_number=np.ones(
            parts["counter"].num_events, dtype=np.int32))))
    with pytest.raises(ValueError, match="another family derives"):
        mixed.merge_columnar(explicit, corpus.family)


def test_a_type_id_outside_its_family_reaches_no_other_family():
    mixed = make_mixed()
    corpus = gen_mixed.mixed_corpus(30, 300, 2, LAW)
    parts = parts_of(corpus)
    bad = parts["bank"].type_ids.copy()
    bad[::5] = 2  # past the bank's two types: base 0 + 2 would be a cart's
    bad[1::5] = -1
    parts["bank"] = ColumnarEvents(
        num_aggregates=parts["bank"].num_aggregates,
        agg_idx=parts["bank"].agg_idx, type_ids=bad, cols=parts["bank"].cols)
    union = mixed.merge_columnar(parts, corpus.family)
    banks = corpus.family[union.agg_idx] == FAMILIES.index("bank")
    assert set(np.unique(union.type_ids[banks])) == {-1, 0, 1}


def test_the_handlers_read_what_the_configurations_work_counts():
    reads = fields_read(make_mixed().spec)
    assert reads == {
        0: {"owner_code", "security_code_code", "balance"},
        1: {"new_balance"},
        2: {"quantity", "unit_price_cents", "sequence_number"},
        3: {"quantity", "unit_price_cents", "sequence_number"},
        4: {"sequence_number"},
        5: {"increment_by", "sequence_number"},
        6: {"decrement_by", "sequence_number"},
        8: {"sequence_number"}}  # 7, the no-op, has no handler


# --- the rebuild against the plain reference ---------------------------------------

def rebuild(engine, events):
    since = time.monotonic()
    resident = engine.upload_resident(engine.pack_resident(events))
    res = engine.replay_resident(resident)
    return res, default_tracer().spans(since_mono=since)


def hold_to_reference(corpus, res):
    want = reference_mixed.closed_form(corpus)
    assert res.num_events == corpus.num_events
    for name in reference_mixed.FIELDS:
        got = np.asarray(res.states[name])
        assert got.dtype == {"balance": np.float32, "checked_out": np.bool_,
                             "created": np.bool_}.get(name, np.int32)
        assert not reference_mixed.differs(got, want[name]).any(), name
    for family in FAMILIES:
        ids = corpus.ids(family)
        part = corpus.part(family)
        sample = sorted({0, part.num_aggregates - 1,
                         int(np.argmax(part.lengths))})
        folded = reference_mixed.scalar_fold_sample(corpus, family, sample)
        for local, state in folded.items():
            got = tuple(res.states[n][ids[local]] for n in reference_mixed.FIELDS)
            assert got == state, (family, local)
        # every column another family owns is still its zero
        for name in set(reference_mixed.FIELDS) - set(reference_mixed.OWNED[family]):
            assert not np.asarray(res.states[name])[ids].any(), (family, name)
    return want


@pytest.mark.parametrize("batch, chunk, seed, gather", [
    (256, 16, 11, "slices"), (64, 8, 2**31 + 77, "slices"),
    (1024, 64, 5, "slices"), (256, 16, 13, "rows")],
    ids=["256x16", "64x8", "1024x64", "256x16-rows"])
def test_mixed_rebuild_equals_the_plain_reference(monkeypatch, batch, chunk,
                                                  seed, gather):
    from surge_tpu.replay import engine as engine_module

    # "rows" is the chip's fetch: the two float32 side columns ride it too
    monkeypatch.setattr(engine_module, "_lane_gather", lambda: gather)
    mixed = make_mixed()
    assert mixed.spec.associative is None
    corpus = gen_mixed.mixed_corpus(3000, 120_000, seed, LAW)
    events = mixed.merge_columnar(parts_of(corpus), corpus.family)
    assert events.derived_cols == {"sequence_number": "ordinal"}
    assert (np.diff(events.agg_idx) >= 0).all()
    engine = make_engine(mixed.spec, batch=batch, chunk=chunk)
    assert engine.tile_backend == "xla"
    res, spans = rebuild(engine, events)
    want = hold_to_reference(corpus, res)
    # the accounts' rare branches are in the corpus: some never opened, some
    # opened after orphans, some opened twice; codes past the 16-bit wire
    banks = corpus.ids("bank")
    created = corpus.bank.type_ids == gen_mixed.CREATED
    first = corpus.bank.starts()[:-1][corpus.bank.lengths > 0]
    assert (~created[first]).any()
    assert (np.add.reduceat(created.astype(np.int64), first) > 1).any()
    assert want["owner_code"][banks].max() > 32767
    # several rounds, both tile sizes, and the steps they take in sequence
    (fold,) = named(spans, "replay.resident")
    a = fold.attributes
    assert a["gather"] == gather
    # the width is the plan's own choice, at most the cap
    assert a["width"] <= a["width_cap"] == chunk
    assert a["rounds"] == -(-int(corpus.lengths().max()) // a["width"]) >= 3
    assert 0 < a["tiles_small"] < a["tiles"]
    assert a["scan_steps"] == a["tiles"] * a["width"]
    assert a["padded_slots"] == res.padded_events >= corpus.num_events
    # the pull: the float always wide; the codes and the totals after one guess
    waits = [(s.attributes["wire"], s.attributes["bytes"])
             for s in named(spans, "replay.fetch.wait")]
    b = corpus.num_aggregates
    assert waits == [("mixed", (2 * 1 + 8) * 2 * b + 18),
                     ("mixed", (2 * 4 + 5) * 2 * b + 18)]
    assert engine._pull_wide == {"owner_code", "security_code_code",
                                 "total_cents"}
    res, spans = rebuild(engine, events)  # and remembered
    hold_to_reference(corpus, res)
    assert len(named(spans, "replay.fetch.wait")) == 1


@pytest.mark.parametrize("family", FAMILIES)
def test_a_familys_slice_equals_its_own_engine_folded_alone(family):
    mixed = make_mixed()
    corpus = gen_mixed.mixed_corpus(1500, 60_000, 23, LAW)
    parts = parts_of(corpus)
    res, _ = rebuild(make_engine(mixed.spec),
                     mixed.merge_columnar(parts, corpus.family))
    split = mixed.split_states(corpus.family, res.states)
    alone, _ = rebuild(make_engine(SPECS[family]()), parts[family])
    assert sorted(split[family]) == sorted(alone.states)
    for name, col in alone.states.items():
        got = split[family][name]
        assert got.dtype == col.dtype and got.shape == col.shape, name
        if col.dtype.kind == "f":  # bit for bit
            got, col = got.view(np.uint32), col.view(np.uint32)
        np.testing.assert_array_equal(got, col, err_msg=name)


def test_the_assoc_tile_takes_no_scan_steps(monkeypatch):
    # the counter alone carries an AssociativeFold: under it the umbrella says 0
    corpus = gen_mixed.mixed_corpus(600, 6000, 3, LAW)
    engine = ReplayEngine(counter.make_replay_spec(),
                          config=default_config().with_overrides({
                              "surge.replay.batch-size": 256,
                              "surge.replay.time-chunk": 16,
                              "surge.replay.tile-backend": "assoc"}))
    _, spans = rebuild(engine, parts_of(corpus)["counter"])
    (fold,) = named(spans, "replay.resident")
    assert fold.attributes["scan_steps"] == 0 < fold.attributes["tiles"]


def test_the_merge_span_carries_the_counts_the_corpus_implies():
    mixed = make_mixed()
    corpus = gen_mixed.mixed_corpus(2000, 80_000, 41, LAW)
    since = time.monotonic()
    events = mixed.merge_columnar(parts_of(corpus), corpus.family)
    (span,) = named(default_tracer().spans(since_mono=since),
                    "replay.mixed.merge")
    a = span.attributes
    n = corpus.num_events
    assert a["families"] == 3 and a["events"] == n == events.num_events
    for family in FAMILIES:
        assert a[f"events_{family}"] == corpus.part(family).num_events
    # nine event columns (the derived one left out), seven of them side
    # columns of four bytes: every event pays for all seven
    assert a["union_columns"] == 9 == len(events.cols)
    assert a["union_side_bytes"] == 28 * n
    cart, bank = corpus.cart, corpus.bank
    live = (8 * int((cart.type_ids != 2).sum())
            + 12 * int((bank.type_ids == gen_mixed.CREATED).sum())
            + 4 * int((bank.type_ids == gen_mixed.UPDATED).sum()))
    assert a["live_side_bytes"] == live
    assert 0.13 < live / (28 * n) < 0.16


def test_init_carry_is_whole_column_and_each_lanes_own():
    from surge_tpu.replay.mixed import combine_replay_specs_with_init

    specs = {f: SPECS[f]() for f in FAMILIES}
    specs["counter"].init_record = {"count": 7, "version": 0}
    mixed = combine_replay_specs_with_init(specs)
    order = ["counter", "bank", "cart", "counter"]
    carry = mixed.init_carry(order)
    assert carry["count"].tolist() == [7, 0, 0, 7]
    assert sorted(carry) == sorted(reference_mixed.FIELDS)
    again = mixed.init_carry(mixed.families(order))
    assert all(np.array_equal(carry[k], again[k]) and carry[k].dtype == again[k].dtype
               for k in carry)
