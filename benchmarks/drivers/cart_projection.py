"""Driver of the cart's read-side projection rebuild: the item-demand rollup
rebuilt from the whole committed log, as ``SurgeEngine.query()`` runs it.

Set-up makes the corpus from the seed, writes it as the committed columnar
segment the restore cell restores from (the restore driver's own
``write_segment``; a temporary directory, removed at exit) and runs one whole
rebuild, which compiles every program. The window runs whole rebuilds back to
back, each ``QueryEngine.scan_segment(path, query)`` through the one engine a
process ``engine/pipeline.py:query_engine`` keeps, under one harness span a
rebuild, until ``--seconds`` have passed, and ends with the last whole one.
Afterwards what every timed rebuild returned is held to the plain reference:
every output of every group, the groups' keys, the counts, and a sample of
codes against a scalar loop.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from benchmarks import reference_cart_projection, spans
from benchmarks.drivers import cart_restore


def make_rebuild(run, path: str):
    """``rebuild() -> QueryResult``: one whole scan of the segment, through
    the engine the pipeline builds for the cart's business logic."""
    from surge_tpu.config import default_config
    from surge_tpu.engine.business_logic import SurgeCommandBusinessLogic
    from surge_tpu.models import shopping_cart
    from surge_tpu.replay.query import QueryEngine, ScanQuery

    logic = SurgeCommandBusinessLogic(
        aggregate_name="cart", model=shopping_cart.CartModel(),
        state_format=shopping_cart.state_formatting(),
        event_format=shopping_cart.event_formatting())
    engine = QueryEngine(logic.replay_spec(), config=default_config(),
                         mesh=None)
    query = ScanQuery.from_json(run.config["projection"])

    def rebuild():
        with run.span("rebuild"):
            return engine.scan_segment(path, query)

    return rebuild


def rows_of(result) -> dict:
    """A ``QueryResult`` as ``{key: (count, sum_quantity,
    max_unit_price_cents)}``."""
    columns = [np.asarray(result.columns[name]).tolist()
               for name in reference_cart_projection.OUTPUTS]
    return dict(zip(result.aggregate_ids, zip(*columns)))


def sample_codes(codes: int, k: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 0x5C])
    return rng.choice(codes, size=min(k, codes), replace=False)


def judge(corpus, codes: int, rebuilds: list, sample_size: int,
          seed: int) -> list:
    """[(name, value, limit)], every limit 0. ``rebuilds``: what each rebuild
    returned, ``(rows {key: (three outputs)}, scanned_events,
    matched_events)``."""
    want = reference_cart_projection.expected_rows(corpus, codes)
    matched = reference_cart_projection.matched_events(corpus)
    rows_wrong = groups_missing = groups_extra = events_unaccounted = 0
    for rows, scanned_events, matched_events in rebuilds:
        events_unaccounted += (abs(int(scanned_events) - corpus.num_events)
                               + abs(int(matched_events) - matched))
        groups_extra += sum(1 for key in rows if key not in want)
        for key, row in want.items():
            got = rows.get(key)
            if got is None:
                groups_missing += 1
            elif got != row:
                rows_wrong += 1
    # the scalar loop over a sample of codes drawn from the seed, against the
    # last rebuild's rows
    last = rebuilds[-1][0]
    scalar = reference_cart_projection.scalar_rows(
        corpus, sample_codes(codes, sample_size, seed))
    scalar_wrong = sum(1 for key, row in scalar.items()
                       if last.get(key) != row)
    return [("rows_wrong", rows_wrong, 0),
            ("groups_missing", groups_missing, 0),
            ("groups_extra", groups_extra, 0),
            ("events_unaccounted", events_unaccounted, 0),
            ("scalar_sample_wrong", scalar_wrong, 0)]


def require_scan_account() -> None:
    """The configuration is the scan engine with its own account: the program
    pinned as ``query.SCAN_JIT_NAMES`` (the layer "Scan programs" of
    ``programs/scan.json``) and the ``replay.scan*`` spans, which this
    driver's ``padded_events`` and every per-layer metric of the cell read. A
    program that has neither cannot run it: say so and leave, before any
    set-up."""
    from surge_tpu.replay import query

    if not getattr(query, "SCAN_JIT_NAMES", None):
        raise SystemExit(
            "cart-projection-rebuild: surge_tpu.replay.query pins no scan "
            "program (SCAN_JIT_NAMES) and opens no replay.scan span: this "
            "program cannot run the configuration")


def padded_events(run) -> int:
    """Event slots the scan programs of one rebuild ran over: the
    ``padded_events`` of the window's ``replay.scan.h2d`` spans (counted by
    the program), a rebuild."""
    slots = [r["attributes"]["padded_events"]
             for r in spans.program_spans(run)[0]
             if r["name"] == "replay.scan.h2d"]
    return sum(slots) // run.facts["rebuilds"]


def stage_seconds(run) -> list:
    """A rebuild's own account, for the run's notes: the seconds of each
    stage under each ``replay.scan`` root of the window, oldest first."""
    found = spans.program_spans(run)
    if found is None:
        return []
    out = []
    for root in (r for r in found[0] if r["name"] == "replay.scan"):
        split: dict = {}
        for r in found[0]:
            if r["parent"] == root["id"]:
                name = r["name"].rsplit(".", 1)[-1]
                split[name] = split.get(name, 0.0) + r["end"] - r["start"]
        out.append(" ".join(f"{k}={v:.2f}" for k, v in split.items()))
    return out


def run(run) -> dict:
    require_scan_account()
    corpus, ids = cart_restore.build_inputs(run)
    tmp = tempfile.mkdtemp(prefix="surge-cart-projection-")
    try:
        return measure(run, corpus, ids, os.path.join(tmp, "cart-events.scol"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure(run, corpus, ids: list, path: str) -> dict:
    from surge_tpu.log import segment

    chunk = run.sizes["chunk_aggregates"]
    t0 = time.perf_counter()
    info = cart_restore.write_segment(path, corpus, ids, chunk)
    write_s = time.perf_counter() - t0
    del ids
    rebuild = make_rebuild(run, path)
    t0 = time.perf_counter()
    rebuild()  # compiles every program
    warm_s = time.perf_counter() - t0
    warm_compilations = run.meter.compilations

    t_open = run.window_opens()
    done = []
    rebuild_s = 0.0  # wall time inside whole rebuilds (the profiler's own left out)
    while True:
        tracing = run.trace and not done  # the first rebuild of the window
        if tracing:
            run.start_trace()
        t0 = time.perf_counter()
        done.append(rebuild())
        rebuild_s += time.perf_counter() - t0
        if tracing:
            run.stop_trace()
        if time.perf_counter() - t_open >= run.seconds:
            break
    t_close = time.perf_counter()
    run.window_closed()

    n = len(done)
    codes = int(run.config["corpus"]["item_codes"])
    run.facts = {"rebuilds": n, "window_s": t_close - t_open,
                 "rebuild_s": rebuild_s,
                 "aggregates": corpus.num_aggregates,
                 "events": corpus.num_events,
                 "chunks": info["num_chunks"],
                 "groups": done[-1].num_aggregates}
    run.facts["padded_events"] = padded_events(run)
    splits = stage_seconds(run)
    segment_bytes = os.path.getsize(path)
    rebuilds = [(rows_of(result), result.scanned_events,
                 result.matched_events) for result in done]
    del done, rebuild  # the program's state goes before the reference runs
    compared = judge(corpus, codes, rebuilds,
                     run.config["check"]["scalar_sample_codes"], run.seed)
    return {"metrics": {"rebuild_events_per_s":
                        n * corpus.num_events / (t_close - t_open)},
            "attempted": n, "failed": 0, "compared": compared,
            "notes": [f"rebuilds={n} window_s={t_close - t_open:.3f} "
                      f"chunks={info['num_chunks']} "
                      f"groups={run.facts['groups']}",
                      "each rebuild's seconds: " + " ".join(
                          f"{e - s:.3f}" for _n, s, e in run.spans
                          if s >= t_open),
                      f"set-up: segment written in {write_s:.3f} s "
                      f"({segment_bytes} B, codec "
                      f"{'slz' if segment.native_codec_available() else 'raw'}"
                      f"), warm-up rebuild {warm_s:.3f} s "
                      f"({warm_compilations} compilations so far)",
                      *(f"rebuild {i}: {split}"
                        for i, split in enumerate(splits[:8]))]}
