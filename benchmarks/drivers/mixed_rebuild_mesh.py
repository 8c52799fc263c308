"""Driver of the mixed replay's cold rebuild over a mesh: the mixed cell's
log (``mixed_rebuild.py``: counters, carts and bank accounts interleaved by
aggregate id) dealt over the cell's chips and folded by one ``shard_map``
program, every union state pulled to the host in the original order.

The mixed driver with the sharded entry in the single-device one's place: the
engine is ``ReplayEngine(mixed.spec, mesh=...)`` with every key at its default,
the mesh a 1-D ``data`` mesh over the cell's chips, built as
``engine/pipeline.py:_resolve_mesh`` builds it. Set-up makes the corpus from
the seed, merges it once and runs one whole rebuild, which compiles every
program the window will use; the window runs whole rebuilds back to back
(``pack_resident`` -> ``prepare_resident_sharded`` ->
``replay_resident_sharded``) under the mixed driver's three harness spans,
``pack``, ``upload`` and ``replay``: the deal (the program's ``replay.shard``
span) lies inside ``upload``, since ``prepare_resident_sharded`` is one call,
and its seconds are the fact ``shard_s``. The comparison is the mixed cell's
own ``judge``, on what the timed rebuilds returned.

A tree whose sharded path opens no ``replay.shard`` span (the parent of the
PR that brought this cell: its deal copies every lane in a Python loop, 25 s a
rebuild at this size by that PR's reckoning) stops at once, before any corpus
is made.
"""

from __future__ import annotations

import time
import types

import numpy as np

from benchmarks.drivers.mixed_rebuild import build_inputs, judge, make_mixed

SHARD_SPAN = "replay.shard"


def shard_spans(since: float) -> list:
    from surge_tpu.tracing import default_tracer

    return [s for s in default_tracer().spans(since_mono=since)
            if s.name == SHARD_SPAN and s.end_mono is not None]


def make_engine(run, mixed, devices):
    """The engine over a 1-D ``data`` mesh of ``devices``. A deal of a few
    hundred events is asked for its ``replay.shard`` span before anything of
    size is made: a tree without it stops here, at once."""
    import jax

    from surge_tpu.replay import ReplayEngine

    mesh = jax.sharding.Mesh(np.asarray(devices), ("data",))
    engine = ReplayEngine(mixed.spec, mesh=mesh)  # engine defaults
    tiny = types.SimpleNamespace(sizes={"aggregates": 40, "events": 400},
                                 seed=run.seed, config=run.config)
    since = time.monotonic()
    engine.prepare_resident_sharded(build_inputs(tiny, mixed)[1])
    if not shard_spans(since):
        raise SystemExit(f"this tree's sharded rebuild opens no {SHARD_SPAN} "
                         "span (its deal is the per-lane copy): the cell "
                         "cannot run on it")
    return engine


def make_rebuild(run, engine, events):
    def rebuild():
        with run.span("pack"):
            wire = engine.pack_resident(events)
        with run.span("upload"):
            sharded = engine.prepare_resident_sharded(wire)
        del wire
        with run.span("replay"):
            return engine.replay_resident_sharded(sharded)

    return rebuild


def run(run) -> dict:
    import jax

    mixed = make_mixed()
    devices = jax.devices()[: run.cell["chips"]]
    engine = make_engine(run, mixed, devices)
    corpus, events = build_inputs(run, mixed)
    rebuild = make_rebuild(run, engine, events)
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
    deals = shard_spans(t_open)
    run.facts = {"rebuilds": n, "window_s": t_close - t_open,
                 "rebuild_s": rebuild_s,
                 "aggregates": corpus.num_aggregates,
                 "events": corpus.num_events,
                 "padded_events": int(last.padded_events),
                 "tile_backend": engine.tile_backend,
                 "devices": len(devices),
                 "pack_s": run.span_seconds("pack", since=t_open),
                 "upload_s": run.span_seconds("upload", since=t_open),
                 "shard_s": sum(s.end_mono - s.start_mono for s in deals),
                 "replay_s": run.span_seconds("replay", since=t_open)}
    del engine, rebuild, events  # the program's state goes before the reference runs
    compared = judge(corpus, results, run.config["check"]["scalar_fold_sample"],
                     run.seed)
    return {"metrics": {"rebuild_events_per_s":
                        n * corpus.num_events / (t_close - t_open)},
            "attempted": n, "failed": 0, "compared": compared,
            "notes": [f"rebuilds={n} window_s={t_close - t_open:.3f} "
                      f"devices={len(devices)} "
                      f"pack_s={run.facts['pack_s']:.3f} "
                      f"upload_s={run.facts['upload_s']:.3f} "
                      f"(shard_s={run.facts['shard_s']:.3f}) "
                      f"replay_s={run.facts['replay_s']:.3f} "
                      f"tile_backend={run.facts['tile_backend']} "
                      f"longest_log={int(corpus.lengths().max(initial=0))}",
                      "each rebuild's pack/upload/replay seconds: " + " ".join(
                          f"{e - s:.3f}" for _n, s, e in run.spans
                          if s >= t_open)]}
