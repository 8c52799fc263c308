"""The shopping cart's cold rebuild (``pack_resident`` -> ``upload_resident`` ->
``replay_resident``) against the benchmark's plain reference
(``benchmarks/reference_cart.py``): ragged logs, three side columns, a
four-field state with a bool, a corpus folded once and again, both tile
granularities over several rounds, and the state pull that remembers which
columns went wide."""

import time

import numpy as np
import pytest

from benchmarks import gen_cart, reference_cart
from surge_tpu.codec.tensor import ColumnarEvents
from surge_tpu.config import default_config
from surge_tpu.models import shopping_cart
from surge_tpu.replay.engine import ReplayEngine
from surge_tpu.tracing import default_tracer

LAW = {"length_law": "lognormal", "length_sigma": 0.6,
       "body_mix": [0.62, 0.38], "added_quantity": [1, 5],
       "removed_quantity": [1, 2], "item_codes": 65536,
       "price_cents": [99, 49999], "checkout_share": 0.3}
#: prices and logs so small that every column of every cart fits 16 bits
SMALL = dict(LAW, price_cents=[1, 3], length_sigma=0.2)


def make_engine(batch=256, chunk=64):
    cfg = default_config().with_overrides({
        "surge.replay.batch-size": batch, "surge.replay.time-chunk": chunk})
    return ReplayEngine(shopping_cart.make_replay_spec(), config=cfg)


def make_corpus(carts, events, seed, law=LAW):
    corpus = gen_cart.cart_corpus(carts, events, seed, law)
    columns = ColumnarEvents(
        num_aggregates=corpus.num_aggregates, agg_idx=corpus.agg_idx,
        type_ids=corpus.type_ids,
        cols={"item_code": corpus.item_code, "quantity": corpus.quantity,
              "unit_price_cents": corpus.unit_price_cents},
        derived_cols={"sequence_number": "ordinal"})
    return corpus, columns


def rebuild(engine, corpus, columns, folds=1):
    """One whole rebuild, all four columns held to the whole-column reference
    and a sample (the longest log in it) to the scalar fold; ``folds`` > 1
    folds the uploaded corpus again and holds the last fold's states. Returns
    the spans it left in the ring."""
    since = time.monotonic()
    resident = engine.upload_resident(engine.pack_resident(columns))
    for _ in range(folds):
        res = engine.replay_resident(resident)
    spans = default_tracer().spans(since_mono=since)
    want = reference_cart.closed_form(corpus)
    assert res.num_events == corpus.num_events
    for name in reference_cart.FIELDS:
        got = np.asarray(res.states[name])
        assert got.dtype == (np.bool_ if name == "checked_out" else np.int32)
        np.testing.assert_array_equal(got.astype(want[name].dtype), want[name],
                                      err_msg=name)
    sample = [int(np.argmax(corpus.lengths)), 0, corpus.num_aggregates - 1]
    for j, state in reference_cart.scalar_fold_sample(corpus, sample).items():
        assert tuple(res.states[n][j] for n in reference_cart.FIELDS) == state
    return spans, want


def named(spans, name):
    return [s for s in spans if s.name == name]


def fetches(spans):
    return [(s.attributes["wire"], s.attributes["bytes"])
            for s in named(spans, "replay.fetch.wait")]


def case_fold(folds):
    def body():
        engine = make_engine()
        spans, _ = rebuild(engine, *make_corpus(3000, 90_000, 11), folds=folds)
        resident = named(spans, "replay.resident")
        assert len(resident) == folds
        # every fold of a corpus asks its buffers for the same rows, and only
        # the first compiles; the second pulls what the first learned
        assert len({s.attributes["rows_fetched"] for s in resident}) == 1
        assert len(named(spans, "replay.compile")) == 2
        assert len(named(spans, "replay.dispatch")) == 2 * (folds - 1)
        assert [w for w, _ in fetches(spans)] == (
            ["narrow", "mixed"] + ["mixed"] * (folds - 1))
    return body


def case_three_rounds_two_granularities():
    # a width of at most 16 (the plan's own choice under that cap) over logs
    # of up to a few hundred events: the shrinking prefix is covered by
    # 256-lane tiles and, for its remainder, 32-lane tiles
    engine = make_engine(batch=256, chunk=16)
    corpus, columns = make_corpus(3000, 120_000, 2**31 + 77)
    spans, _ = rebuild(engine, corpus, columns)
    (fold,) = named(spans, "replay.resident")
    a = fold.attributes
    assert a["width"] in (8, 16) and a["width_cap"] == 16
    assert a["rounds"] == -(-int(corpus.lengths.max()) // a["width"]) >= 3
    assert int((corpus.lengths > 32).sum()) > 0  # lanes that take three rounds
    assert 0 < a["tiles_small"] < a["tiles"]
    assert a["slots_small"] == a["tiles_small"] * 32 * a["width"]
    assert 0 < a["slots_small"] < a["padded_slots"]
    batches = sorted(s.attributes["batch"] for s in spans
                     if s.name in ("replay.compile", "replay.dispatch"))
    assert batches == [32, 256]


def case_empty_carts():
    # a mean of two events a cart: the floor leaves many carts with none
    engine = make_engine()
    corpus, columns = make_corpus(2000, 4000, 5)
    assert int((corpus.lengths == 0).sum()) > 100
    _, want = rebuild(engine, corpus, columns)
    empty = corpus.lengths == 0
    assert not want["version"][empty].any()
    assert not want["checked_out"][empty].any()


def case_totals_leave_int16_on_both_sides():
    # as much removed as added: the totals walk away from zero both ways
    law = dict(LAW, body_mix=[0.5, 0.5], removed_quantity=[1, 5])
    engine = make_engine()
    _, want = rebuild(engine, *make_corpus(2000, 60_000, 23, law))
    assert want["total_cents"].min() < -32768 < 32767 < want["total_cents"].max()
    assert -32768 <= want["item_count"].min() < 0 < want["item_count"].max()


def case_the_pull_remembers_wide_columns():
    engine = make_engine()
    b = 2000
    wide_corpus = make_corpus(b, 60_000, 31)
    mixed = 10 * b + 8  # total_cents in four bytes, three columns in two, four flags
    narrow = 8 * b + 8
    spans, want = rebuild(engine, *wide_corpus)
    assert want["total_cents"].max() > 32767
    # the first pull guesses narrow, learns that total_cents overflowed, and
    # fetches again with that one column wide
    assert fetches(spans) == [("narrow", narrow), ("mixed", mixed)]
    assert engine._pull_wide == {"total_cents"}
    # from the second rebuild on: one fetch
    for _ in range(2):
        spans, _ = rebuild(engine, *wide_corpus)
        assert fetches(spans) == [("mixed", mixed)]
    assert len(engine._finalize_programs) == 2
    # a corpus whose columns all fit: pulled as remembered (exact either way),
    # and the flags send total_cents back to two bytes for the pull after it
    small_corpus = make_corpus(b, 20_000, 37, SMALL)
    spans, want = rebuild(engine, *small_corpus)
    assert abs(want["total_cents"]).max() <= 32767
    assert fetches(spans) == [("mixed", mixed)]
    assert engine._pull_wide == frozenset()
    spans, _ = rebuild(engine, *small_corpus)
    assert fetches(spans) == [("narrow", narrow)]
    # and a wide corpus after that contradicts the memory: the refetch again
    spans, _ = rebuild(engine, *wide_corpus)
    assert fetches(spans) == [("narrow", narrow), ("mixed", mixed)]
    assert len(engine._finalize_programs) == 2


def case_the_ring_carries_the_counts():
    engine = make_engine(batch=256, chunk=32)
    corpus, columns = make_corpus(1500, 45_000, 41)
    rebuild(engine, corpus, columns)
    spans, _ = rebuild(engine, corpus, columns)  # the memory is warm
    (fold,) = named(spans, "replay.resident")
    (wait,) = named(spans, "replay.fetch.wait")
    (encode,) = named(spans, "replay.encode")
    (h2d,) = named(spans, "replay.h2d")
    b, n = corpus.num_aggregates, corpus.num_events
    assert wait.attributes == {"wire": "mixed", "bytes": 10 * b + 8}
    a = fold.attributes
    assert a["gather"] == "slices"  # the CPU backend's
    assert a["width"] <= a["width_cap"] == 32
    assert a["rounds"] == -(-int(corpus.lengths.max()) // a["width"])
    assert a["slots_small"] == a["tiles_small"] * 32 * a["width"]
    assert a["padded_slots"] >= n and a["aggregates"] == b
    # three int32 side columns of the events' rows, the caller's own arrays,
    # beside a one-byte word that alone carries guard rows
    side = encode.attributes["side_bytes"]
    assert side == h2d.attributes["side_bytes"] == 12 * n
    assert encode.attributes["wire_bytes"] - side > n
    assert encode.attributes["side_aliased"] == 3
    assert encode.attributes["side_copied_bytes"] == 0


CASES = {
    "first": case_fold(1),
    "again": case_fold(2),
    "three-rounds-two-granularities": case_three_rounds_two_granularities,
    "empty-carts": case_empty_carts,
    "totals-beyond-int16-both-sides": case_totals_leave_int16_on_both_sides,
    "pull-remembers-wide-columns": case_the_pull_remembers_wide_columns,
    "ring-carries-the-counts": case_the_ring_carries_the_counts,
}


@pytest.mark.parametrize("case", list(CASES))
def test_cart_rebuild_equals_the_plain_reference(case):
    CASES[case]()
