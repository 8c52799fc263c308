"""Driver of the shopping cart's cold rebuild: the whole ragged log folded to
every cart's four-field state.

The counter's driver (``rebuild.py``) with the cart's corpus, columns and
reference in its place: set-up makes the corpus from the seed and runs one
whole rebuild, which compiles every program the window will use; the window
runs whole rebuilds back to back (``pack_resident`` -> ``upload_resident`` ->
``replay_resident``, every state pulled to the host) under the same three
harness spans until ``--seconds`` have passed, and ends with the last whole
one. Afterwards all four columns of every state of every rebuild of the window
are held to the whole-column reference, and a sample of the last rebuild to the
scalar fold.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import gen, gen_cart, reference_cart


def build_inputs(run):
    """The corpus from the seed, and the same columns in the program's own
    input type."""
    from surge_tpu.codec.tensor import ColumnarEvents

    corpus = gen_cart.cart_corpus(run.sizes["aggregates"], run.sizes["events"],
                                  run.seed, run.config["corpus"])
    events = ColumnarEvents(
        num_aggregates=corpus.num_aggregates, agg_idx=corpus.agg_idx,
        type_ids=corpus.type_ids,
        cols={"item_code": corpus.item_code, "quantity": corpus.quantity,
              "unit_price_cents": corpus.unit_price_cents},
        derived_cols={"sequence_number": "ordinal"})
    return corpus, events


def make_rebuild(run, events):
    from surge_tpu.models import shopping_cart
    from surge_tpu.replay import ReplayEngine

    engine = ReplayEngine(shopping_cart.make_replay_spec())  # engine defaults

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
    want = reference_cart.closed_form(corpus)
    states_wrong = events_unaccounted = 0
    for res in results:
        events_unaccounted += abs(int(res.num_events) - corpus.num_events)
        wrong = np.zeros(corpus.num_aggregates, dtype=bool)
        for name in reference_cart.FIELDS:
            got = np.asarray(res.states[name])
            wrong |= (got if got.dtype == bool
                      else got.astype(np.int64)) != want[name]
        states_wrong += int(np.count_nonzero(wrong))
    # the scalar fold over a sample drawn from the seed, the longest log in it
    last = results[-1]
    sample = gen.sample_aggregates(corpus.num_aggregates, sample_size, seed,
                                   always=[int(np.argmax(corpus.lengths))])
    scalar_wrong = 0
    for j, state in reference_cart.scalar_fold_sample(corpus, sample).items():
        got = (int(last.states["item_count"][j]),
               int(last.states["total_cents"][j]),
               bool(last.states["checked_out"][j]),
               int(last.states["version"][j]))
        scalar_wrong += got != state
    return [("states_wrong", states_wrong, 0),
            ("events_unaccounted", events_unaccounted, 0),
            ("scalar_sample_wrong", scalar_wrong, 0)]


def run(run) -> dict:
    corpus, events = build_inputs(run)
    engine, rebuild = make_rebuild(run, events)
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
                      f"longest_log={int(corpus.lengths.max(initial=0))}",
                      "each rebuild's pack/upload/replay seconds: " + " ".join(
                          f"{e - s:.3f}" for _n, s, e in run.spans
                          if s >= t_open)]}
