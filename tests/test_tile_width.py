"""The resident plan's tile width, chosen from the corpus's own lengths
(``engine._tile_width``): the rule as a pure function of the lengths, and every
width it may choose folding every fixture model, and the mixed spec, to the
plain reference's states (``benchmarks/reference_mixed.py``: whole-column, and
the scalar fold over a sample), floats bit for bit, over logs that cross
several rounds, from a wire saved under one engine and loaded under another."""

import functools
import time

import numpy as np
import pytest

from benchmarks import gen_mixed, reference_mixed
from surge_tpu.config import default_config
from surge_tpu.replay import engine as engine_module
from surge_tpu.replay.engine import (ReplayEngine, ResidentWire, _tile_sizes,
                                     _tile_width)
from surge_tpu.tracing import default_tracer
from tests.test_mixed_rebuild import (LAW, SPECS, hold_to_reference, make_mixed,
                                      parts_of)

WIDTHS = [8, 16, 32, 64, 128, 256, 512]  # the default ladder: 8 up to 512
BIG, SMALL = 8192, 1024  # the default tile sizes


def lognormal_lengths(n, mean, sigma, seed):
    rng = np.random.default_rng(seed)
    raw = rng.lognormal(np.log(mean) - sigma * sigma / 2, sigma, n)
    return np.sort(np.maximum(raw.astype(np.int32), 1))[::-1]


def choose(lengths, gather="rows", widths=WIDTHS, big=BIG, small=SMALL):
    return _tile_width(np.asarray(lengths, dtype=np.int32), big, small, widths,
                       gather)


def tiled_lanes(lengths, w, small):
    """The lanes the plan's tiles of width ``w`` cover, round by round, counted
    the slow way (``small`` divides the big tile, so it alone rounds)."""
    asc = np.sort(lengths)
    return sum(-(-(len(asc) - int(np.searchsorted(asc, t, side="right")))
                 // small) * small for t in range(0, int(asc[-1]), w))


# --- the rule, as a pure function of the lengths -----------------------------------

@pytest.mark.parametrize("gather", ["rows", "slices"])
@pytest.mark.parametrize("lengths, want", [
    # the counter cell's law: where the fetch reads aligned rows, one tile
    # of 128; where it reads a slice a lane, the fewest slots, 13 tiles of 8
    (np.full(100_000, 100), {"rows": 128, "slices": 8}),
    (np.full(100_000, 5), 8),  # every log under the min window: the least
    (np.full(100_000, 8), 8),
    (np.full(100_000, 96), {"rows": 128, "slices": 32}),  # of equals the wider
    (np.full(100_000, 512), 512),  # every log at the cap: the cap
    (np.full(100_000, 2048), 512),  # and at multiples of it
    (np.zeros(100_000), 512),  # nothing to fold: the plan is empty at any
    (np.zeros(0), 512),  # an empty corpus
    (np.full(3, 100), {"rows": 128, "slices": 8}),  # fewer lanes than a tile
], ids=["all-100", "all-5", "all-8", "all-96", "all-at-cap", "all-4x-cap",
        "all-empty", "no-lanes", "three-lanes"])
def test_the_width_of_equal_logs(lengths, want, gather):
    if isinstance(want, dict):
        want = want[gather]
    assert choose(lengths, gather) == want


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 77])
@pytest.mark.parametrize("law, mean, want", [
    ("cart", 100, 128), ("mixed", 100, 128), ("cart", 300, 256),
    ("mixed", 300, 128), ("cart", 30, 64), ("mixed", 30, 64)])
def test_the_rule_picks_what_measured_best_on_the_chip(law, mean, want, seed):
    """The cart cell's lognormal logs and the mixed cell's 40 / 40 / 20 of
    fixed, lognormal and lognormal logs: 100M events in logs of a mean of 100
    (the cells' own), of 300 and of 30, under the chip's gather. The width is
    the one whose fold measured fastest on the v5e in that shape (PERF.md, PR
    34; at a mean of 30 the cart's 64 and 128 measured level)."""
    n = 100_000_000 // mean
    lengths = lognormal_lengths(n if law == "cart" else n - 4 * n // 10, mean,
                                0.6, seed)
    if law == "mixed":
        lengths = np.sort(np.concatenate([
            np.full(4 * n // 10, mean, dtype=np.int32), lengths]))[::-1]
    assert int(lengths.max()) > 4 * mean  # a long tail
    assert choose(lengths, "rows") == want
    if mean == 100:  # what the cells then report: under two slots an event
        assert (tiled_lanes(lengths, want, SMALL) * want
                / int(lengths.sum(dtype=np.int64))) < 1.8


@pytest.mark.parametrize("seed", range(6))
def test_any_corpus_gets_a_width_of_the_ladder_whatever_its_order(seed):
    """Never over the cap, always one of the ladder (a power of two), and a
    function of the lengths as a multiset: sorted either way or shuffled."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 5000))
    mean = float(rng.choice([3, 20, 100, 700, 3000]))
    lengths = lognormal_lengths(n, mean, float(rng.uniform(0.1, 1.2)), seed)
    lengths[rng.integers(0, n, size=n // 7)] = 0  # some empty logs
    cap = int(rng.choice([8, 64, 512]))
    widths = [w for w in WIDTHS if w <= cap]
    big, small = _tile_sizes(256, 8, n)
    gather = str(rng.choice(["rows", "slices"]))
    desc = np.sort(lengths)[::-1]
    got = choose(desc, gather, widths, big, small)
    assert got in widths and got <= cap and got & (got - 1) == 0
    for other in (desc[::-1], rng.permutation(desc)):
        assert choose(other, gather, widths, big, small) == got
    # it is the cheapest by the counts the plan reports
    def cost(w):
        moved = w if gather == "slices" else ((w + 126) // 128 + 1) * 128
        return tiled_lanes(desc, w, small) * (w + moved)
    assert cost(got) == min(cost(w) for w in widths)
    assert got == max(w for w in widths if cost(w) == cost(got))


def test_the_engine_chooses_among_the_ladder_up_to_its_cap(monkeypatch):
    def engine(**keys):
        return ReplayEngine(SPECS["counter"](), config=default_config(
        ).with_overrides({f"surge.replay.{k.replace('_', '-')}": v
                          for k, v in keys.items()}))

    assert engine()._tile_widths() == WIDTHS
    assert engine().resident_tile_width() == 512
    assert engine(time_chunk=100)._tile_widths() == [8, 16, 32, 64, 128]
    assert engine(time_chunk=4)._tile_widths() == [8]
    assert engine(min_time_window=0, time_chunk=4)._tile_widths() == [1, 2, 4]
    # the HBM cap binds before the time-chunk does
    capped = engine(time_chunk=1 << 20, resident_slab_cap_mb=1)
    assert capped._tile_widths()[-1] == capped.resident_cap_width() == 8
    # the CPU backend's gather is a slice a lane: the fewest slots
    hundred = np.full(3000, 100, dtype=np.int32)
    assert engine().lane_gather == "slices"
    assert engine()._chosen_width(hundred) == 8
    assert engine(min_time_window=32)._chosen_width(hundred) == 128
    monkeypatch.setattr(engine_module, "_lane_gather", lambda: "rows")
    assert engine()._chosen_width(hundred) == 128
    assert engine(time_chunk=64)._chosen_width(hundred) == 64
    assert engine(time_chunk=16)._chosen_width(hundred) == 16


# --- every width of the ladder folds every model to the reference ------------------

CAP = 32  # the ladder 8, 16, 32 over logs of 50 events and more


@functools.lru_cache(maxsize=None)
def the_corpus():
    corpus = gen_mixed.mixed_corpus(700, 35_000, 2**31 + 11, LAW)
    assert int(corpus.lengths().max()) > 3 * CAP
    return corpus, reference_mixed.closed_form(corpus)


def make_engine(spec, tile, chunk):
    return ReplayEngine(spec, config=default_config().with_overrides({
        "surge.replay.batch-size": 128, "surge.replay.time-chunk": chunk,
        "surge.replay.tile-backend": tile}))


MODELS = [("counter", "xla"), ("counter", "assoc"), ("cart", "xla"),
          ("cart", "assoc"), ("bank", "xla"), ("mixed", "xla")]


@pytest.mark.parametrize("width", [8, 16, 32, None],
                         ids=["w8", "w16", "w32", "chosen"])
@pytest.mark.parametrize("model, tile", MODELS,
                         ids=[f"{m}-{t}" for m, t in MODELS])
def test_every_width_folds_to_the_reference(monkeypatch, tmp_path, model, tile,
                                            width):
    """``chosen``: the width the rule takes for the corpus, unforced."""
    corpus, want = the_corpus()
    if model == "mixed":
        mixed = make_mixed()
        spec = mixed.spec
        events = mixed.merge_columnar(parts_of(corpus), corpus.family)
        ids, fields = np.arange(corpus.num_aggregates), reference_mixed.FIELDS
    else:
        spec, events = SPECS[model](), parts_of(corpus)[model]
        ids, fields = corpus.ids(model), reference_mixed.OWNED[model]
    # packed and saved under an engine with another cap, loaded under this one
    packer = make_engine(spec, tile, 4 * CAP)
    packer.pack_resident(events).save(str(tmp_path))
    engine = make_engine(spec, tile, CAP)
    assert engine._tile_widths() == [8, 16, CAP] != packer._tile_widths()
    if width is not None:
        monkeypatch.setattr(engine_module, "_tile_width", lambda *a: width)
    wire = ResidentWire.load(str(tmp_path))
    assert wire.guard >= packer.resident_tile_width() > CAP
    resident = engine.upload_resident(wire)
    since = time.monotonic()
    res = engine.replay_resident(resident)
    (fold,) = [s for s in default_tracer().spans(since_mono=since)
               if s.name == "replay.resident"]
    a = fold.attributes
    chosen = engine._plan_for(resident).width
    assert a["width"] == chosen == (width or chosen) and a["width_cap"] == CAP
    assert chosen in (8, 16, CAP)
    longest = int(wire.lengths.max())
    assert a["rounds"] == -(-longest // chosen) >= 2
    assert a["scan_steps"] == (0 if tile == "assoc" else a["tiles"] * chosen)
    assert a["padded_slots"] == res.padded_events
    assert engine.tile_backend == tile
    assert sorted(res.states) == sorted(fields)
    for name in fields:
        got = np.asarray(res.states[name])
        assert not reference_mixed.differs(got, want[name][ids]).any(), name
    if model == "mixed":
        hold_to_reference(corpus, res)  # the scalar fold, the foreign columns
    else:
        part = corpus.part(model)
        sample = sorted({0, part.num_aggregates - 1,
                         int(np.argmax(part.lengths))})
        for local, state in reference_mixed.scalar_fold_sample(
                corpus, model, sample).items():
            owned = dict(zip(reference_mixed.FIELDS, state))
            assert all(res.states[n][local] == owned[n] for n in fields), local
    # a second fold of the corpus takes the cached plan and gives the same
    again = engine.replay_resident(resident)
    for name in fields:
        assert not reference_mixed.differs(
            np.asarray(again.states[name]), want[name][ids]).any(), name
