#!/usr/bin/env python3
"""Chip smoke: the cold fold and the served resident path, once, on the attached TPU.

One process, two legs through the public entry points with engine defaults
(``tile-backend=auto``, ``refresh-dispatch=bucketed``,
donation on), every answer checked against a plain reference outside any timing:

- **cold**: counter, 1,000,000 aggregates / 100,000,000 events (BASELINE.json)
  from ``synth_counter_corpus(seed=--seed)`` through ``ReplayEngine.pack_resident``
  -> ``upload_resident`` -> ``replay_resident``; all states against the corpus's
  closed form, a sample against the scalar ``fold_events``. Then bank_account,
  shopping_cart and the mixed batch at a few thousand aggregates against the
  scalar fold.
- **served**: ``create_engine(logic, log=FileLog(dir))`` with the resident plane,
  restore-on-start and ``surge.replay.backend=tpu`` over a log preloaded through
  the transactional producer (262,144 aggregates x 8 events); cold start, command
  waves over resident and new aggregates with reads racing the refresh,
  ``get_state`` / ``project_states`` read-back of every acknowledged command
  against the scalar fold, ``engine.stop()``. Fails unless the plane (not the
  host store behind it) answered the reads.

Exits non-zero, printing no result line, unless ``jax.devices()[0].platform`` is
``tpu``. ``--cpu-tiny`` runs the same code at a tiny size on the CPU backend, to
debug before chip time is spent. ``--mesh`` runs the sharded paths over every
visible device instead (a four-chip host). The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import time
from importlib import metadata

REPO = os.path.dirname(os.path.abspath(__file__))

#: the scale the legs are stated at; a smaller run lists each cut under `reduced`
STATED = {"cold_aggregates": 1_000_000, "cold_events": 100_000_000,
          "family_aggregates": 3_000, "served_aggregates": 262_144,
          "served_events_per_aggregate": 8, "served_commands": 4_096,
          "mesh_aggregates": 65_536, "mesh_events": 4_000_000}
TINY = {"cold_aggregates": 2_000, "cold_events": 60_000,
        "family_aggregates": 60, "served_aggregates": 512,
        "served_events_per_aggregate": 8, "served_commands": 192,
        "mesh_aggregates": 512, "mesh_events": 12_000}


class SmokeFailure(Exception):
    """A leg's answer or one of its device-path assertions was wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(key: str, value) -> None:
    print(f"{key}: {value if isinstance(value, str) else json.dumps(value)}",
          flush=True)


class CompileMeter:
    """XLA compilations and the seconds spent in them, from jax's own monitoring
    events. A persistent-cache hit counts as a compilation, with its retrieval
    time, so a cache-warm run shows the same count and fewer seconds; jax writes
    an entry only for a program that took over its threshold (1 s) to compile."""

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    CACHE_WRITE = "/jax/compilation_cache/cache_misses"  # recorded on write

    def __init__(self) -> None:
        import jax

        self.compilations = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == self.BACKEND_COMPILE:
            self.compilations += 1
            self.compile_s += seconds

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.CACHE_HIT:
            self.cache_hits += 1
        elif event == self.CACHE_WRITE:
            self.cache_writes += 1

    def snapshot(self) -> tuple:
        return (self.compilations, self.compile_s, self.cache_hits,
                self.cache_writes)

    def since(self, snap: tuple) -> dict:
        return {"compilations": self.compilations - snap[0],
                "compile_s": round(self.compile_s - snap[1], 3),
                "cache_hits": self.cache_hits - snap[2],
                "cache_writes": self.cache_writes - snap[3]}


def peak_bytes(device):
    stats = device.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return int(stats["peak_bytes_in_use"])


# --------------------------------------------------------------------------------------
# native libraries: built here, never silently absent
# --------------------------------------------------------------------------------------

def build_native() -> dict:
    """Build csrc/ from the committed sources into a clean csrc/build/ and report
    which native paths are live. With g++ present, a library that does not load
    is a failure (the loaders would otherwise degrade to Python without a word)."""
    shutil.rmtree(os.path.join(REPO, "csrc", "build"), ignore_errors=True)
    if shutil.which("g++") is None:
        return {"g++": False, "store": False, "segment": False, "txn": False}
    subprocess.run(["sh", os.path.join(REPO, "csrc", "build.sh")], check=True,
                   timeout=600, stdout=subprocess.DEVNULL)
    from surge_tpu.log import native_gate, segment
    from surge_tpu.store import native as store_native

    active = {"g++": True, "store": store_native.native_available(),
              "segment": segment.native_codec_available(),
              "txn": native_gate.available()}
    check(all(active.values()), f"native library failed to load: {active}")
    return active


# --------------------------------------------------------------------------------------
# leg 1: cold fold
# --------------------------------------------------------------------------------------

def leg_cold(sizes: dict, seed: int) -> dict:
    import numpy as np

    from surge_tpu.engine.model import fold_events
    from surge_tpu.models import counter
    from surge_tpu.replay import ReplayEngine
    from surge_tpu.replay.corpus import (decode_sample, sample_indices,
                                         synth_counter_corpus)

    t0 = time.perf_counter()
    corpus = synth_counter_corpus(sizes["cold_aggregates"], sizes["cold_events"],
                                  seed=seed)
    build_s = time.perf_counter() - t0
    engine = ReplayEngine(counter.make_replay_spec())  # engine defaults
    t0 = time.perf_counter()
    wire = engine.pack_resident(corpus.events)
    pack_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    resident = engine.upload_resident(wire)
    upload_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    result = engine.replay_resident(resident)
    first_replay_s = time.perf_counter() - t0

    # all states against the closed form, then a sample against the scalar fold
    check(result.num_events == corpus.num_events, "cold: event accounting")
    check(np.array_equal(result.states["count"], corpus.expected_count),
          "cold: count differs from the closed form")
    check(np.array_equal(result.states["version"], corpus.expected_version),
          "cold: version differs from the closed form")
    idx = sample_indices(corpus, min(200_000, corpus.num_events))
    model = counter.CounterModel()
    for j, events in zip(idx, decode_sample(corpus, idx)):
        st = fold_events(model, None, events)
        want = (st.count, st.version) if st is not None else (0, 0)
        got = (int(result.states["count"][j]), int(result.states["version"][j]))
        check(got == want, f"cold: aggregate {j} folded to {got}, scalar {want}")
    facts = {"aggregates": corpus.num_aggregates, "events": corpus.num_events,
             "states_equal_closed_form": corpus.num_aggregates,
             "scalar_sample_aggregates": int(len(idx)),
             "tile_backend": engine.tile_backend,
             "pad_ratio": round(result.padded_events / corpus.num_events, 3),
             "corpus_build_s": round(build_s, 2), "pack_s": round(pack_s, 2),
             "upload_s": round(upload_s, 2),
             "first_replay_s": round(first_replay_s, 2)}
    del corpus, wire, resident, result
    facts["families"] = verify_families(sizes["family_aggregates"], seed)
    return facts


def verify_families(n: int, seed: int) -> list:
    """bank_account (f32 + vocab side columns, wide pull), shopping_cart (bool
    state) and the three-family mixed batch through the same resident path with
    engine defaults, each against the scalar fold."""
    from surge_tpu.codec.tensor import decode_states, encode_events_columnar
    from surge_tpu.engine.model import fold_events
    from surge_tpu.models import bank_account, counter, shopping_cart
    from surge_tpu.replay import ReplayEngine
    from surge_tpu.replay.mixed import combine_replay_specs
    from surge_tpu.testing import (random_bank_log, random_cart_log,
                                   random_counter_log)

    rng = random.Random(seed)
    rows = []

    def row(family: str, engine, res, t0: float) -> None:
        rows.append({"family": family, "aggregates": res.num_aggregates,
                     "events": res.num_events, "tile": engine.tile_backend,
                     "s": round(time.perf_counter() - t0, 2)})

    vocab = bank_account.Vocab()
    t0 = time.perf_counter()
    ids = [f"b{i}" for i in range(n)]
    logs = [random_bank_log(rng, a) for a in ids]
    model = bank_account.BankAccountModel()
    truth = [fold_events(model, None, log) for log in logs]
    spec = bank_account.make_replay_spec()
    engine = ReplayEngine(spec)
    res = engine.replay_resident(engine.prepare_resident(encode_events_columnar(
        spec.registry,
        [[bank_account.encode_event(vocab, e) for e in log] for log in logs])))
    for a, want, rec in zip(ids, truth,
                            decode_states(spec.registry.state, res.states)):
        got = bank_account.decode_state(vocab, a, rec)
        check(got == want, f"bank_account {a}: {got} != scalar {want}")
    row("bank_account", engine, res, t0)

    t0 = time.perf_counter()
    logs = [random_cart_log(rng, f"c{i}") for i in range(n)]
    model = shopping_cart.CartModel()
    truth = [fold_events(model, None, log) for log in logs]
    spec = shopping_cart.make_replay_spec()
    engine = ReplayEngine(spec)
    res = engine.replay_resident(engine.prepare_resident(
        encode_events_columnar(spec.registry, logs)))
    fields = ("item_count", "total_cents", "checked_out", "version")
    for i, want in enumerate(truth):
        got = tuple(res.states[f][i].item() for f in fields)
        exp = (tuple(getattr(want, f) for f in fields) if want is not None
               else (0, 0, False, 0))
        check(got == exp, f"shopping_cart c{i}: {got} != scalar {exp}")
    row("shopping_cart", engine, res, t0)

    # three families in ONE batch (tagged-union columns, masked dispatch)
    t0 = time.perf_counter()
    mixed = combine_replay_specs({
        "counter": counter.make_replay_spec(),
        "cart": shopping_cart.make_replay_spec(),
        "bank": bank_account.make_replay_spec()})
    models = {"counter": counter.CounterModel(),
              "cart": shopping_cart.CartModel(),
              "bank": bank_account.BankAccountModel()}
    makers = {"counter": random_counter_log, "cart": random_cart_log,
              "bank": random_bank_log}
    tagged, truths = [], []
    for i in range(n):
        kind = ("counter", "cart", "bank")[i % 3]
        log = makers[kind](rng, f"m{i}")
        truths.append(fold_events(models[kind], None, log))
        if kind == "bank":
            log = [bank_account.encode_event(vocab, e) for e in log]
        tagged.append((kind, log))
    tags = [kind for kind, _ in tagged]
    engine = ReplayEngine(mixed.spec)
    res = engine.replay_resident(
        engine.prepare_resident(mixed.encode_logs(tagged)),
        init_carry=mixed.init_carry(tags))
    compared = {"counter": ("count", "version"),
                "cart": ("item_count", "total_cents", "checked_out"),
                "bank": ("balance",)}
    for i, (kind, want, got) in enumerate(zip(
            tags, truths, mixed.decode_states(tags, res.states))):
        if want is not None:
            check(all(getattr(got, f) == getattr(want, f) for f in compared[kind]),
                  f"mixed m{i} ({kind}): {got} != scalar {want}")
    row("mixed(counter+cart+bank)", engine, res, t0)
    return rows


# --------------------------------------------------------------------------------------
# leg 2: served path
# --------------------------------------------------------------------------------------

class Served:
    """The served deployment both the default and the ``--mesh`` run drive: a
    counter engine over a ``FileLog`` preloaded through the transactional
    producer, resident plane on, restore on start, ``surge.replay.backend=tpu``;
    plus the scalar reference of what it must answer."""

    def __init__(self, workdir: str, n_agg: int, per: int, seed: int,
                 capacity: int, overrides: dict | None = None) -> None:
        import numpy as np

        from surge_tpu import create_engine
        from surge_tpu.config import default_config
        from surge_tpu.engine.business_logic import SurgeCommandBusinessLogic
        from surge_tpu.log.file import FileLog
        from surge_tpu.models import counter

        self.counter = counter
        self.model = counter.CounterModel()
        self.n_agg = n_agg
        # per aggregate `per` events: 60% increment (0), 30% decrement (1),
        # 10% no-op (2), stamped as the command path would (sequence = version + 1)
        draw = np.random.default_rng(seed).integers(0, 100, size=(n_agg, per))
        self.kinds = (draw >= 60).astype(np.int8) + (draw >= 90)
        self.acked: dict = {}  # aggregate -> events of its acknowledged commands
        self.signals: list = []
        self.log = FileLog(workdir)
        self.engine = create_engine(
            SurgeCommandBusinessLogic(
                aggregate_name="counter", model=self.model,
                state_format=counter.state_formatting(),
                event_format=counter.event_formatting()),
            log=self.log, config=default_config().with_overrides({
                "surge.replay.resident.enabled": True,
                "surge.replay.restore-on-start": True,
                "surge.replay.backend": "tpu",
                "surge.replay.resident.capacity": capacity,
                **(overrides or {})}))
        self.engine.health_bus.subscribe(lambda s: self.signals.append(s.name))

    def preloaded(self, agg: str) -> list:
        if not agg.startswith("agg-"):
            return []  # an aggregate the log had never seen
        c = self.counter
        events, version = [], 0
        for k in self.kinds[int(agg[4:])].tolist():
            if k == 2:  # a no-op leaves the version where it was
                events.append(c.NoOpEvent(agg, version + 1))
            else:
                version += 1
                events.append((c.CountIncremented, c.CountDecremented)[k](
                    agg, 1, version))
        return events

    def preload(self) -> None:
        from surge_tpu.log import LogRecord

        fmt, topic = self.engine.logic.event_format, self.engine.logic.events_topic
        prod = self.log.transactional_producer("chip-smoke-preload")
        prod.begin()
        for i in range(self.n_agg):
            agg = f"agg-{i}"
            p = self.engine.router.partition_for(agg)
            for ev in self.preloaded(agg):
                prod.send(LogRecord(topic=topic, key=agg,
                                    value=fmt.write_event(ev).value, partition=p))
            if i % 8192 == 8191:
                prod.commit()
                prod.begin()
        prod.commit()

    def reference(self, agg: str):
        """Scalar fold of the aggregate's preloaded + acknowledged events."""
        from surge_tpu.engine.model import fold_events

        return fold_events(self.model, None,
                           self.preloaded(agg) + self.acked.get(agg, []))

    def is_reference(self, agg: str, state) -> bool:
        want = self.reference(agg)
        if want is None or state is None:
            return state is None and want is None
        return (state.count, state.version) == (want.count, want.version)

    async def command_wave(self, cmds: list, what: str) -> None:
        """Send one concurrent wave (distinct aggregates) and hold every ack to
        the scalar fold of exactly the acknowledged commands."""
        from surge_tpu.engine.entity import CommandSuccess

        prior = {c.aggregate_id: self.reference(c.aggregate_id) for c in cmds}
        results = await asyncio.gather(*(
            self.engine.aggregate_for(c.aggregate_id).send_command(c)
            for c in cmds))
        for cmd, res in zip(cmds, results):
            agg = cmd.aggregate_id
            check(isinstance(res, CommandSuccess),
                  f"{what}: {cmd} was not acknowledged: {res}")
            self.acked.setdefault(agg, []).extend(
                self.model.process_command(prior[agg], cmd))
            check(self.is_reference(agg, res.state),
                  f"{what}: ack of {cmd} carried {res.state}")
        await self.settle(what)

    async def settle(self, what: str) -> None:
        """Wait for the plane's fold watermark to reach the log end."""
        plane = self.engine.resident_plane
        deadline = time.monotonic() + 120
        while plane.lag_records() > 0:
            check(time.monotonic() < deadline, f"{what}: refresh never caught up")
            await asyncio.sleep(0.02)

    async def projection_is_reference(self, ids: list, what: str) -> None:
        """One ``project_states`` over tracked aggregates on a settled plane:
        every row equals the scalar fold AND came off the device slab."""
        plane = self.engine.resident_plane
        rows0, fallbacks0 = plane.stats["gathered_rows"], plane.stats["fallbacks"]
        got = await self.engine.project_states(ids)
        for agg in ids:
            check(self.is_reference(agg, got.get(agg)),
                  f"{what}: project_states({agg}) is {got.get(agg)}, scalar "
                  f"fold {self.reference(agg)}")
        check(plane.stats["gathered_rows"] - rows0 == len(ids)
              and plane.stats["fallbacks"] == fallbacks0,
              f"{what}: {plane.stats['gathered_rows'] - rows0} rows gathered "
              f"for {len(ids)} ids, fallbacks {plane.fallback_causes}")

    def device_path_served(self, what: str) -> dict:
        """The plane is "an optimization" with the host store behind it, so a
        program the chip refused would still answer correctly — from the host.
        Fail unless the device path did the serving."""
        plane = self.engine.resident_plane
        lane_errors = self.engine.metrics_registry.get_metrics()[
            "surge.replay.resident.fallback-reads.lane-error"]
        check(plane.stats["gathers"] > 0, f"{what}: no gather ran on the device")
        check(lane_errors == 0 and not plane.fallback_causes.get("lane-error"),
              f"{what}: {lane_errors} reads failed on the gather lane")
        bad = [s for s in self.signals
               if "refresh-error" in s or "gather-error" in s]
        check(not bad, f"{what}: health bus carried {bad}")
        for p in plane.partitions:
            check(plane.partition_lag(p) == 0,
                  f"{what}: partition {p} fold watermark short of the log end")
        return {"refresh_rounds": plane.stats["rounds"],
                "folded_events": plane.stats["folded_events"],
                "gathers": plane.stats["gathers"],
                "gathered_rows": plane.stats["gathered_rows"],
                "fallbacks": dict(plane.fallback_causes),
                "lane_errors": lane_errors, "error_signals": bad}

    async def stop(self) -> None:
        await self.engine.stop()
        self.log.close()


async def leg_served(sizes: dict, seed: int, workdir: str) -> dict:
    n_agg = sizes["served_aggregates"]
    n_cmd = sizes["served_commands"]
    n_new = max(n_cmd // 8, 8)  # aggregates the log has never seen
    served = Served(os.path.join(workdir, "log"), n_agg,
                    sizes["served_events_per_aggregate"], seed,
                    capacity=n_agg + 4 * n_new)
    counter, engine = served.counter, served.engine
    t0 = time.perf_counter()
    served.preload()
    preload_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    await engine.start()  # restore + plane seed
    cold_start_s = time.perf_counter() - t0
    try:
        plane = engine.resident_plane
        check(plane is not None and plane.running, "served: no running plane")
        check(plane.occupancy() == n_agg,
              f"served: seed left {plane.occupancy()} of {n_agg} resident")
        check(engine.indexer.store.approximate_num_entries() == n_agg,
              "served: restore did not fill the host store")

        # reads before any command: every one of them is a plane gather
        rng = random.Random(seed)
        cold_ids = [f"agg-{i}" for i in rng.sample(range(n_agg), min(2048, n_agg))]
        await served.projection_is_reference(cold_ids, "served (after seed)")
        first = cold_ids[:256]
        rows0 = plane.stats["gathered_rows"]
        states = await asyncio.gather(*(engine.aggregate_for(a).get_state()
                                        for a in first))
        for agg, st in zip(first, states):
            check(served.is_reference(agg, st),
                  f"served: get_state({agg}) after seed is {st}")
        check(plane.stats["gathered_rows"] - rows0 == len(first)
              and plane.stats["fallbacks"] == 0,
              f"served: entity inits after the seed missed the plane "
              f"({plane.stats}, {plane.fallback_causes})")

        # command waves over resident and new aggregates, reads racing the refresh
        resident_ids = [f"agg-{i}" for i in rng.sample(range(n_agg),
                                                       min(n_cmd // 2, n_agg))]
        targets = resident_ids + [f"new-{i}" for i in range(n_new)]
        untouched = set(cold_ids) - set(resident_ids)
        watchers = [a for a in cold_ids if a in untouched][:512]
        sent = waves = 0
        rounds0 = plane.stats["rounds"]
        while sent < n_cmd:
            wave = rng.sample(targets, min(512, len(targets), n_cmd - sent))
            _, raced = await asyncio.gather(
                served.command_wave([(counter.Increment(a) if rng.random() < 0.7
                                      else counter.Decrement(a)) for a in wave],
                                    "served"),
                engine.project_states(watchers))
            for agg in watchers:
                check(served.is_reference(agg, raced.get(agg)),
                      f"served: read of {agg} racing wave {waves} is "
                      f"{raced.get(agg)}")
            sent += len(wave)
            waves += 1
        check(plane.stats["rounds"] - rounds0 >= min(waves, 3),
              f"served: {plane.stats['rounds'] - rounds0} refresh rounds for "
              f"{waves} command waves")

        # read back every acknowledged command: off the slab, and from the entity
        touched = sorted(served.acked)
        await served.projection_is_reference(touched, "served (read-back)")
        for lo in range(0, len(touched), 512):
            chunk = touched[lo: lo + 512]
            states = await asyncio.gather(*(engine.aggregate_for(a).get_state()
                                            for a in chunk))
            for agg, st in zip(chunk, states):
                check(served.is_reference(agg, st),
                      f"served: get_state({agg}) is {st}")
        facts = {"aggregates": n_agg,
                 "preloaded_events": n_agg * sizes["served_events_per_aggregate"],
                 "commands_acked": sent, "aggregates_touched": len(touched),
                 "new_aggregates": sum(a.startswith("new-") for a in touched),
                 "waves": waves, **served.device_path_served("served"),
                 "preload_s": round(preload_s, 2),
                 "cold_start_s": round(cold_start_s, 2)}
    finally:
        await served.stop()
    return facts


# --------------------------------------------------------------------------------------
# --mesh: the sharded paths over every visible device
# --------------------------------------------------------------------------------------

async def leg_mesh(sizes: dict, seed: int, workdir: str) -> dict:
    import jax
    import numpy as np

    from surge_tpu.codec.tensor import encode_events
    from surge_tpu.engine.model import fold_events
    from surge_tpu.models import counter
    from surge_tpu.replay import ReplayEngine, replay_time_sharded
    from surge_tpu.replay.corpus import synth_counter_corpus
    from surge_tpu.replay.resident_mesh import fold_resident_sharded

    devices = jax.devices()
    n_dev = len(devices)
    check(n_dev >= 2, f"--mesh needs several devices, found {n_dev}")
    mesh = jax.sharding.Mesh(np.asarray(devices), ("data",))
    spec = counter.make_replay_spec()

    def on_every_device(column, what: str) -> int:
        where = {s.device for s in column.addressable_shards}
        check(len(where) == n_dev,
              f"mesh: {what} lies on {len(where)} of {n_dev} devices")
        return len(where)

    # cold sharded fold: lanes dealt across the devices, one shard_map dispatch
    corpus = synth_counter_corpus(sizes["mesh_aggregates"], sizes["mesh_events"],
                                  seed=seed)
    engine = ReplayEngine(spec, mesh=mesh)
    sharded = engine.prepare_resident_sharded(corpus.events)
    slab = fold_resident_sharded(engine, sharded)
    slab_devices = on_every_device(next(iter(slab.values())), "cold slab")
    res = engine.replay_resident_sharded(sharded)
    check(np.array_equal(res.states["count"], corpus.expected_count)
          and np.array_equal(res.states["version"], corpus.expected_version),
          "mesh: sharded resident fold differs from the closed form")

    # sequence-parallel fold of a few long logs: one ordered all_gather
    rng = random.Random(seed)
    model = counter.CounterModel()
    logs = []
    for i in range(3):
        state, log = None, []
        for _ in range(40 * n_dev + 7 + i):  # ragged, not divisible by the mesh
            cmd = (counter.Increment(f"sp{i}") if rng.random() < 0.7
                   else counter.Decrement(f"sp{i}"))
            for e in model.process_command(state, cmd):
                state = model.handle_event(state, e)
                log.append(e)
        logs.append(log)
    enc = encode_events(spec.registry, logs)
    events = {"type_id": enc.type_ids.T.astype(np.int32)}
    for name, col in enc.cols.items():
        events[name] = col.T
    out = replay_time_sharded(counter.make_associative_fold(), spec, events, mesh)
    for i, log in enumerate(logs):
        want = fold_events(model, None, log)
        got = (int(out["count"][i]), int(out["version"][i]))
        check(got == (want.count, want.version),
              f"mesh: time-sharded lane {i} folded to {got}, scalar {want}")

    # the served path over the mesh plane: sharded slab, per-shard refresh deals,
    # one-collective gathers
    n_agg = sizes["served_aggregates"] // 8
    served = Served(
        os.path.join(workdir, "mesh-log"), n_agg,
        sizes["served_events_per_aggregate"], seed, capacity=2 * n_agg,
        overrides={"surge.feature-flags.experimental.enable-mesh-sharding": True})
    served.preload()
    await served.engine.start()
    try:
        plane = served.engine.resident_plane
        check(plane is not None and plane._meshp is not None,
              "mesh: the engine did not build a mesh plane")
        ids = [f"agg-{i}" for i in rng.sample(range(n_agg), min(256, n_agg))]
        for _ in range(3):
            await served.command_wave([counter.Increment(a) for a in ids], "mesh")
        await served.projection_is_reference(ids, "mesh")
        plane_devices = on_every_device(next(iter(plane._slab.values())),
                                        "plane slab")
        facts = {"devices": n_dev, "cold_aggregates": corpus.num_aggregates,
                 "cold_events": corpus.num_events, "slab_devices": slab_devices,
                 "plane_slab_devices": plane_devices, "plane_aggregates": n_agg,
                 **served.device_path_served("mesh")}
    finally:
        await served.stop()
    return facts


# --------------------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="debug run: tiny sizes on the CPU backend")
    ap.add_argument("--mesh", action="store_true",
                    help="run the sharded paths over every visible device")
    for key in ("cold_aggregates", "cold_events", "served_aggregates"):
        ap.add_argument("--" + key.replace("_", "-"), type=int, default=None,
                        help=f"cut of scale (stated: {STATED[key]:,})")
    args = ap.parse_args()

    want = "cpu" if args.cpu_tiny else "tpu"
    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.mesh:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:  # JAX_PLATFORMS names a backend that is not there
        print(f"no usable JAX backend (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}): {exc}", file=sys.stderr)
        return 2
    if devices[0].platform != want:
        print(f"need platform {want!r}, JAX found {devices[0].platform!r} "
              f"({len(devices)} x {devices[0].device_kind}; JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}). A chip run takes no flag; "
              "--cpu-tiny is the debug run on the CPU backend.", file=sys.stderr)
        return 2

    from surge_tpu.replay.engine import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    meter = CompileMeter()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    say("mode", "cpu-tiny (--cpu-tiny): a debug run, no device number in it"
        if args.cpu_tiny else "chip")
    say("platform", device["platform"])
    say("device_kind", device["kind"])
    say("device_count", device["count"])
    say("versions", {pkg: metadata.version(pkg)
                     for pkg in ("jax", "jaxlib", "libtpu")})
    say("seed", args.seed)
    say("compile_cache", cache_dir
        or f"{os.environ['JAX_COMPILATION_CACHE_DIR']} (JAX_COMPILATION_CACHE_DIR)")

    sizes = dict(TINY if args.cpu_tiny else STATED)
    for key in ("cold_aggregates", "cold_events", "served_aggregates"):
        if getattr(args, key) is not None:
            sizes[key] = getattr(args, key)
    say("reduced", {k: {"stated": STATED[k], "run": v}
                    for k, v in sizes.items() if v != STATED[k]})
    say("native", build_native())

    workdir = tempfile.mkdtemp(prefix="surge-chip-smoke-")
    if args.mesh:
        legs = [("mesh", lambda: asyncio.run(leg_mesh(sizes, args.seed, workdir)))]
    else:
        legs = [("cold", lambda: leg_cold(sizes, args.seed)),
                ("served",
                 lambda: asyncio.run(leg_served(sizes, args.seed, workdir)))]
    try:
        for name, run in legs:
            t0, snap = time.perf_counter(), meter.snapshot()
            facts = run()
            say(f"leg {name}", {"wall_s": round(time.perf_counter() - t0, 2),
                                **meter.since(snap),
                                "peak_bytes_in_use": peak_bytes(devices[0]),
                                **facts})
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
