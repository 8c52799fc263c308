"""Driver of the mixed replay's cold rebuild: counters, carts and bank
accounts, interleaved by aggregate id, folded in one batch to every
aggregate's union state.

The cart's driver (``cart_rebuild.py``) with three corpora and the program's
columnar merge in front. Set-up makes the corpus from the seed, merges the
three families' columns into the union's (``MixedReplay.merge_columnar``, once:
the merged log is what a node holds) and runs one whole rebuild, which compiles
every program the window will use; the window runs whole rebuilds back to back
(``pack_resident`` -> ``upload_resident`` -> ``replay_resident``, every state
pulled to the host) under the same three harness spans until ``--seconds`` have
passed, and ends with the last whole one. The engine is built over the
combined spec with every key at its default, and the combined spec carries no
associative fold: the tile is the sequential masked switch. Afterwards every
union column of every state of every rebuild of the window is held to the
whole-column reference, every column a family does not own to zero, and a
sample of each family in the last rebuild to the scalar fold.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import gen, gen_mixed, reference_mixed


def make_mixed():
    """The program's combined spec. Its columnar merge is asked for before
    anything is made: a tree without it stops here, at once."""
    from surge_tpu.models import bank_account, counter, shopping_cart
    from surge_tpu.replay.mixed import combine_replay_specs

    mixed = combine_replay_specs({"bank": bank_account.make_replay_spec(),
                                  "cart": shopping_cart.make_replay_spec(),
                                  "counter": counter.make_replay_spec()})
    if not hasattr(mixed, "merge_columnar"):
        raise SystemExit("this tree's MixedReplay has no columnar merge "
                         "(merge_columnar): the cell cannot run on it")
    assert mixed.bases == reference_mixed.BASES, mixed.bases
    assert mixed.spec.associative is None
    return mixed


def build_inputs(run, mixed):
    """The corpus from the seed, and the union's columns in the program's own
    input type, merged by the program from each family's."""
    from surge_tpu.codec.tensor import ColumnarEvents

    corpus = gen_mixed.mixed_corpus(run.sizes["aggregates"], run.sizes["events"],
                                    run.seed, run.config["corpus"])
    parts = {}
    for family in gen_mixed.FAMILIES:
        part = corpus.part(family)
        numbered = any(f.name == "sequence_number" for f in
                       mixed.parts[family].registry.union_columns())
        parts[family] = ColumnarEvents(
            num_aggregates=part.num_aggregates, agg_idx=part.agg_idx,
            type_ids=part.type_ids, cols=corpus.columns(family),
            derived_cols={"sequence_number": "ordinal"} if numbered else {})
    return corpus, mixed.merge_columnar(parts, corpus.family)


def make_rebuild(run, mixed, events):
    from surge_tpu.replay import ReplayEngine

    engine = ReplayEngine(mixed.spec)  # engine defaults

    def rebuild():
        with run.span("pack"):
            wire = engine.pack_resident(events)
        with run.span("upload"):
            resident = engine.upload_resident(wire)
        del wire
        with run.span("replay"):
            return engine.replay_resident(resident)

    return engine, rebuild


def judge(corpus, results: list, sample_size: int, seed: int) -> list:
    """[(name, value, limit)]: exact comparisons, so every limit is 0."""
    want = reference_mixed.closed_form(corpus)
    ids = {family: corpus.ids(family) for family in gen_mixed.FAMILIES}
    states_wrong = events_unaccounted = foreign = 0
    for res in results:
        events_unaccounted += abs(int(res.num_events) - corpus.num_events)
        wrong = np.zeros(corpus.num_aggregates, dtype=bool)
        for name in reference_mixed.FIELDS:
            wrong |= reference_mixed.differs(res.states[name], want[name])
        states_wrong += int(np.count_nonzero(wrong))
        # what the masked switch promises, read off the answer alone: a
        # family's lanes hold the zero of every column another family owns
        for family, own in reference_mixed.OWNED.items():
            for name in reference_mixed.FIELDS:
                if name not in own:
                    col = np.asarray(res.states[name])[ids[family]]
                    if col.dtype.kind == "f":
                        col = col.astype(np.float32).view(np.uint32)
                    foreign += int(np.count_nonzero(col))
    # the scalar fold over a sample of each family drawn from the seed, the
    # family's longest log in it
    last = results[-1]
    scalar_wrong = 0
    for family in gen_mixed.FAMILIES:
        part = corpus.part(family)
        sample = gen.sample_aggregates(
            part.num_aggregates, sample_size, seed,
            always=[int(np.argmax(part.lengths))] if part.num_aggregates else [])
        folded = reference_mixed.scalar_fold_sample(corpus, family, sample)
        for local, state in folded.items():
            j = ids[family][local]
            got = tuple(last.states[name][j] for name in reference_mixed.FIELDS)
            scalar_wrong += got != state
    return [("states_wrong", states_wrong, 0),
            ("events_unaccounted", events_unaccounted, 0),
            ("scalar_sample_wrong", scalar_wrong, 0),
            ("foreign_columns_nonzero", foreign, 0)]


def run(run) -> dict:
    mixed = make_mixed()
    corpus, events = build_inputs(run, mixed)
    engine, rebuild = make_rebuild(run, mixed, events)
    rebuild()  # compiles and warms every shape: the window folds the same corpus

    t_open = run.window_opens()
    results = []
    rebuild_s = 0.0  # wall time inside whole rebuilds (the profiler's own time left out)
    while True:
        tracing = run.trace and not results  # the first rebuild of the window
        if tracing:
            run.start_trace()
        t0 = time.perf_counter()
        results.append(rebuild())
        rebuild_s += time.perf_counter() - t0
        if tracing:
            run.stop_trace()
        if time.perf_counter() - t_open >= run.seconds:
            break
    t_close = time.perf_counter()
    run.window_closed()

    n = len(results)
    last = results[-1]
    run.facts = {"rebuilds": n, "window_s": t_close - t_open,
                 "rebuild_s": rebuild_s,
                 "aggregates": corpus.num_aggregates,
                 "events": corpus.num_events,
                 "padded_events": int(last.padded_events),
                 "tile_backend": engine.tile_backend,
                 "pack_s": run.span_seconds("pack", since=t_open),
                 "upload_s": run.span_seconds("upload", since=t_open),
                 "replay_s": run.span_seconds("replay", since=t_open)}
    del engine, rebuild, events  # the program's state goes before the reference runs
    compared = judge(corpus, results, run.config["check"]["scalar_fold_sample"],
                     run.seed)
    return {"metrics": {"rebuild_events_per_s":
                        n * corpus.num_events / (t_close - t_open)},
            "attempted": n, "failed": 0, "compared": compared,
            "notes": [f"rebuilds={n} window_s={t_close - t_open:.3f} "
                      f"pack_s={run.facts['pack_s']:.3f} "
                      f"upload_s={run.facts['upload_s']:.3f} "
                      f"replay_s={run.facts['replay_s']:.3f} "
                      f"tile_backend={run.facts['tile_backend']} "
                      f"longest_log={int(corpus.lengths().max(initial=0))}",
                      "each rebuild's pack/upload/replay seconds: " + " ".join(
                          f"{e - s:.3f}" for _n, s, e in run.spans
                          if s >= t_open)]}
