"""Driver of the shopping cart's segment restore: a node's cold start as the
engine runs it with ``surge.replay.segment-path`` set.

Set-up makes the corpus from the seed, writes it as a committed columnar
segment into a temporary directory (removed at exit, the ``.wires`` sidecar
with it) and runs one whole restore, which compiles every program and fills
the wire cache. The window runs whole restores back to back, each
``restore_from_segment`` into a fresh empty store with the hooks
``engine/pipeline.py:_rebuild_from_segment`` passes for the cart's logic,
under one harness span a restore, until ``--seconds`` have passed, and ends
with the last whole one. Afterwards what every timed restore left in its
store is held to the plain reference: every cart's bytes parsed against the
closed form's dictionary, the ids, the counts, and a sample's bytes against
the scalar fold's, byte for byte.
"""

from __future__ import annotations

import inspect
import json
import os
import shutil
import tempfile
import time

import numpy as np

from benchmarks import gen, gen_cart, reference_cart_restore, spans


def build_inputs(run):
    """The corpus from the seed and its carts' ids. A cart with no event has
    no key in an events topic, so the segment of a topic could not hold it.
    The law's ``lengths_seed`` keeps a chunk's lengths together: every seed's
    segment has chunks of the same events, in its own order."""
    corpus = gen_cart.cart_corpus(run.sizes["aggregates"], run.sizes["events"],
                                  run.seed, run.config["corpus"],
                                  block=run.sizes["chunk_aggregates"])
    if int(corpus.lengths.min(initial=1)) < 1:
        raise ValueError("the corpus holds a cart with no event: a topic's "
                         "segment cannot (choose sizes with longer logs)")
    return corpus, reference_cart_restore.cart_ids(corpus.num_aggregates)


def write_segment(path: str, corpus, ids: list, chunk_aggregates: int) -> dict:
    """The corpus as the segment ``build_segment_from_topic`` writes for a
    one-partition events topic of these carts: chunks of ``chunk_aggregates``
    carts in key order, aggregate-sorted, ``sequence_number`` derived, every
    chunk with its ids, partition 0. Returns ``segment_info``.

    A committed segment is durable before anyone reads it: the writer flushes
    a fresh file but does not sync it, so the file and its directory are
    synced here, before the warm-up, and the kernel's write-back of the
    gigabyte cannot fall inside a timed window. The pages stay cached."""
    from surge_tpu.codec.tensor import ColumnarEvents
    from surge_tpu.log.columnar import ColumnarSegmentWriter, segment_info

    starts = corpus.starts()
    with ColumnarSegmentWriter(path) as writer:
        for lo in range(0, corpus.num_aggregates, chunk_aggregates):
            hi = min(lo + chunk_aggregates, corpus.num_aggregates)
            a, b = int(starts[lo]), int(starts[hi])
            writer.append(ColumnarEvents(
                num_aggregates=hi - lo,
                agg_idx=corpus.agg_idx[a:b] - np.int32(lo),
                type_ids=corpus.type_ids[a:b],
                cols={"item_code": corpus.item_code[a:b],
                      "quantity": corpus.quantity[a:b],
                      "unit_price_cents": corpus.unit_price_cents[a:b]},
                derived_cols={"sequence_number": "ordinal"},
                aggregate_ids=ids[lo:hi]), partition=0)
    for synced in (path, os.path.dirname(os.path.abspath(path))):
        fd = os.open(synced, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    return segment_info(path)


def make_restore(run, path: str):
    """``restore() -> (store, RestoreResult)``: one whole cold start into a
    fresh store, as the pipeline runs it for the cart's business logic."""
    from surge_tpu.engine.business_logic import SurgeCommandBusinessLogic
    from surge_tpu.models import shopping_cart
    from surge_tpu.replay import ReplayEngine
    from surge_tpu.store import InMemoryKeyValueStore, restore_from_segment

    logic = SurgeCommandBusinessLogic(
        aggregate_name="cart", model=shopping_cart.CartModel(),
        state_format=shopping_cart.state_formatting(),
        event_format=shopping_cart.event_formatting())
    spec = logic.replay_spec()
    state_fmt = logic.state_format
    hooks = dict(
        replay_spec=spec,
        serialize_state=lambda agg_id, st: state_fmt.write_state(st).value,
        decode_state=getattr(logic, "decode_state", None))
    # one engine a process, as the pipeline keeps one: a program without the
    # parameter (an older commit) builds an engine, and compiles, a restore
    if "engine" in inspect.signature(restore_from_segment).parameters:
        hooks["engine"] = ReplayEngine(spec)  # engine defaults

    def restore():
        store = InMemoryKeyValueStore()
        with run.span("restore"):
            result = restore_from_segment(path, store, **hooks)
        return store, result

    return restore


def judge(corpus, ids: list, restores: list, sample_size: int,
          seed: int) -> list:
    """[(name, value, limit)], every limit 0. ``restores``: what each restore
    left, ``(items {id: bytes}, num_events, num_aggregates)``."""
    want = reference_cart_restore.expected_store(corpus, ids)
    states_wrong = store_missing = store_extra = events_unaccounted = 0
    for items, num_events, num_aggregates in restores:
        events_unaccounted += (abs(int(num_events) - corpus.num_events)
                               + abs(int(num_aggregates)
                                     - corpus.num_aggregates))
        store_extra += sum(1 for key in items if key not in want)
        for key, state in want.items():
            raw = items.get(key)
            if raw is None:
                store_missing += 1
            elif json.loads(raw) != state:
                states_wrong += 1
    # the scalar fold's bytes over a sample drawn from the seed, the longest
    # log in it, against the last restore's
    last = restores[-1][0]
    sample = gen.sample_aggregates(corpus.num_aggregates, sample_size, seed,
                                   always=[int(np.argmax(corpus.lengths))])
    scalar = reference_cart_restore.scalar_fold_bytes(corpus, ids, sample)
    scalar_wrong = sum(1 for key, raw in scalar.items()
                       if last.get(key) != raw)
    return [("states_wrong", states_wrong, 0),
            ("store_missing", store_missing, 0),
            ("store_extra", store_extra, 0),
            ("events_unaccounted", events_unaccounted, 0),
            ("scalar_sample_wrong", scalar_wrong, 0)]


def tree_bytes(root: str) -> int:
    return sum(os.path.getsize(os.path.join(base, name))
               for base, _dirs, names in os.walk(root) for name in names)


def padded_events(run) -> int | None:
    """Event slots the chunk folds of one restore scanned: ``padded_slots``
    of the ``replay.resident`` spans inside the window's restores (counted by
    the program), a restore. None where the program keeps no such spans."""
    found = spans.program_spans(run)
    if found is None:
        return None
    slots = [r["attributes"]["padded_slots"] for r in found[0]
             if r["name"] == "replay.resident"
             and "padded_slots" in r["attributes"]]
    return sum(slots) // run.facts["rebuilds"] if slots else None


def stage_seconds(run) -> list:
    """A restore's own account, for the run's notes: the seconds of each
    stage under each ``replay.restore`` root of the window, oldest first."""
    found = spans.program_spans(run)
    if found is None:
        return []
    out = []
    for root in (r for r in found[0] if r["name"] == "replay.restore"):
        split: dict = {}
        for r in found[0]:
            if r["parent"] == root["id"]:
                name = r["name"].rsplit(".", 1)[-1]
                split[name] = split.get(name, 0.0) + r["end"] - r["start"]
        out.append(" ".join(f"{k}={v:.2f}" for k, v in split.items()))
    return out


def run(run) -> dict:
    corpus, ids = build_inputs(run)
    tmp = tempfile.mkdtemp(prefix="surge-cart-restore-")
    try:
        return measure(run, corpus, ids, os.path.join(tmp, "cart-events.scol"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure(run, corpus, ids: list, path: str) -> dict:
    from surge_tpu.log import segment

    t0 = time.perf_counter()
    info = write_segment(path, corpus, ids, run.sizes["chunk_aggregates"])
    write_s = time.perf_counter() - t0
    restore = make_restore(run, path)
    t0 = time.perf_counter()
    restore()  # compiles every program, fills the wire cache
    warm_s = time.perf_counter() - t0
    warm_compilations = run.meter.compilations

    t_open = run.window_opens()
    done = []
    restore_s = 0.0  # wall time inside whole restores (the profiler's own left out)
    while True:
        tracing = run.trace and not done  # the first restore of the window
        if tracing:
            run.start_trace()
        t0 = time.perf_counter()
        done.append(restore())
        restore_s += time.perf_counter() - t0
        if tracing:
            run.stop_trace()
        if time.perf_counter() - t_open >= run.seconds:
            break
    t_close = time.perf_counter()
    run.window_closed()

    n = len(done)
    run.facts = {"rebuilds": n, "window_s": t_close - t_open,
                 "rebuild_s": restore_s,
                 "aggregates": corpus.num_aggregates,
                 "events": corpus.num_events,
                 "chunks": info["num_chunks"]}
    slots = padded_events(run)
    if slots is not None:
        run.facts["padded_events"] = slots
    splits = stage_seconds(run)
    segment_bytes = os.path.getsize(path)
    wires_bytes = tree_bytes(f"{path}.wires")
    restores = [(dict(store.all_items()), result.num_events,
                 result.num_aggregates) for store, result in done]
    del done, restore  # the program's state goes before the reference runs
    compared = judge(corpus, ids, restores,
                     run.config["check"]["scalar_fold_sample"], run.seed)
    return {"metrics": {"rebuild_events_per_s":
                        n * corpus.num_events / (t_close - t_open)},
            "attempted": n, "failed": 0, "compared": compared,
            "notes": [f"restores={n} window_s={t_close - t_open:.3f} "
                      f"chunks={info['num_chunks']} "
                      f"longest_log={int(corpus.lengths.max(initial=0))}",
                      "each restore's seconds: " + " ".join(
                          f"{e - s:.3f}" for _n, s, e in run.spans
                          if s >= t_open),
                      f"set-up: segment written in {write_s:.3f} s "
                      f"({segment_bytes} B, codec "
                      f"{'slz' if segment.native_codec_available() else 'raw'}"
                      f"), warm-up restore {warm_s:.3f} s "
                      f"({warm_compilations} compilations so far), wire "
                      f"cache {wires_bytes} B",
                      *(f"restore {i}: {split}"
                        for i, split in enumerate(splits[:8]))]}
