"""Per-stage replay profiler: stage coverage on the streaming and resident
paths, span emission, DEBUG gating of the histograms (an engine handed no
profiler builds a counter-only one over the process-wide ring)."""

import time

import numpy as np

from surge_tpu.codec.tensor import ColumnarEvents
from surge_tpu.config import default_config
from surge_tpu.metrics import Metrics, RecordingLevel, engine_metrics
from surge_tpu.models.counter import make_replay_spec
from surge_tpu.replay.engine import ReplayEngine
from surge_tpu.replay.profiler import ReplayProfiler
from surge_tpu.tracing import InMemoryTracer, default_tracer

CFG = default_config().with_overrides({
    "surge.replay.batch-size": 64,
    "surge.replay.time-chunk": 16,
})


def make_events(n_agg=32, n_per=20):
    n = n_agg * n_per
    return ColumnarEvents(
        num_aggregates=n_agg,
        agg_idx=np.repeat(np.arange(n_agg, dtype=np.int32), n_per),
        type_ids=np.zeros(n, dtype=np.int32),
        cols={"increment_by": np.ones(n, dtype=np.int64),
              "decrement_by": np.zeros(n, dtype=np.int64)},
        derived_cols={"sequence_number": "ordinal"})


def make_profiled_engine(tracer=None):
    registry = Metrics(recording_level=RecordingLevel.DEBUG)
    prof = ReplayProfiler.if_enabled(registry, engine_metrics(registry),
                                     tracer=tracer)
    assert prof is not None
    return ReplayEngine(make_replay_spec(), config=CFG, profiler=prof), prof, registry


def test_if_enabled_gates_on_recording_level():
    assert ReplayProfiler.if_enabled(Metrics()) is None  # INFO: hot path free
    assert ReplayProfiler.if_enabled(
        Metrics(recording_level=RecordingLevel.DEBUG)) is not None
    assert ReplayProfiler.if_enabled(
        Metrics(recording_level=RecordingLevel.TRACE)) is not None


def test_streaming_path_stage_breakdown():
    engine, prof, registry = make_profiled_engine()
    ev = make_events()
    res = engine.replay_columnar(ev)
    assert (res.states["count"] == 20).all()
    s = prof.summary()
    # windowed path: pack + transfer + (first-dispatch) compile + fetch
    assert s["encode"]["count"] > 0
    assert s["h2d"]["count"] > 0
    assert s["compile"]["count"] > 0  # first window paid the XLA compile
    assert s["fetch"]["count"] > 0
    assert s["total_accounted_s"] > 0
    # windows counts DISPATCHED windows (engine-reported), not record() calls
    assert s["windows"] == engine.stats["windows"]
    # the per-stage timings also landed in the DEBUG registry instruments
    snap = registry.get_metrics()
    assert snap["surge.replay.profile.windows"] == engine.stats["windows"]
    assert snap["surge.replay.profile.compile-timer.max"] > 0
    # a re-fold of the same shapes is steady: dispatch, not compile
    before = s["compile"]["count"]
    engine.replay_columnar(ev)
    s2 = prof.summary()
    assert s2["compile"]["count"] == before
    assert s2["dispatch"]["count"] > 0


def test_resident_path_emits_pass_and_stage_spans():
    tracer = InMemoryTracer()
    engine, prof, _ = make_profiled_engine(tracer=tracer)
    ev = make_events()
    resident = engine.prepare_resident(ev)
    res = engine.replay_resident(resident)
    assert (res.states["count"] == 20).all()
    s = prof.summary()
    assert s["encode"]["count"] > 0  # pack_resident
    assert s["h2d"]["count"] > 0  # upload_resident
    assert s["fetch"]["count"] > 0  # the single state pull
    names = [sp.name for sp in tracer.finished]
    assert "replay.resident" in names
    assert "replay.fetch" in names
    # stage spans parent under the pass span, one trace per pass
    pass_span = tracer.spans_named("replay.resident")[0]
    fetch = tracer.spans_named("replay.fetch")[0]
    assert fetch.context.trace_id == pass_span.context.trace_id
    assert fetch.parent_id == pass_span.context.span_id
    assert pass_span.attributes["events"] == ev.num_events


def test_unprofiled_engine_holds_none_and_matches_results():
    """An engine handed no profiler builds its own counter-only one over the
    default ring (it held None before every stage became a real span), and
    folds to the same states as a DEBUG-profiled one."""
    plain = ReplayEngine(make_replay_spec(), config=CFG)
    assert isinstance(plain.profiler, ReplayProfiler)
    assert plain.profiler.metrics is None
    assert plain.profiler.tracer is default_tracer()
    engine, _, _ = make_profiled_engine()
    ev = make_events()
    since = time.monotonic()
    a = plain.replay_columnar(ev)
    b = engine.replay_columnar(ev)
    assert (a.states["count"] == b.states["count"]).all()
    assert (a.states["version"] == b.states["version"]).all()
    # the plain engine's stages are real intervals in the ring: open while
    # the work ran, both clocks read at its edges (never dated back)
    ring = default_tracer().spans(since_mono=since)
    assert {"replay.encode", "replay.h2d", "replay.fetch"} <= {
        s.name for s in ring}
    for s in ring:
        assert since <= s.start_mono <= s.end_mono
        assert abs((s.end_time - s.start_time) - s.seconds) < 0.05
    assert plain.stats["pack_s"] == sum(
        s.seconds for s in ring if s.name == "replay.encode")


def test_summary_reset():
    engine, prof, _ = make_profiled_engine()
    engine.replay_columnar(make_events(8, 4))
    assert prof.summary()["total_accounted_s"] > 0
    prof.reset()
    s = prof.summary()
    assert s["total_accounted_s"] == 0
    assert all(s[k]["count"] == 0 for k in
               ("encode", "h2d", "compile", "dispatch", "fetch"))


def test_refresh_stage_covers_incremental_folds():
    """The resident plane's incremental folds land in the per-stage profile
    like cold-start passes: `refresh` is the per-round umbrella, its host
    pack shows under `encode`, the first window under `compile` and repeats
    under `dispatch`."""
    import asyncio

    from surge_tpu.log import InMemoryLog, LogRecord, TopicSpec
    from surge_tpu.models.counter import (CountIncremented, event_formatting,
                                          state_formatting)
    from surge_tpu.replay.profiler import ReplayProfiler
    from surge_tpu.replay.resident_state import ResidentStatePlane
    from surge_tpu.serialization import SerializedMessage

    registry = Metrics(recording_level=RecordingLevel.DEBUG)
    prof = ReplayProfiler.if_enabled(registry, engine_metrics(registry))
    evt, st = event_formatting(), state_formatting()
    log = InMemoryLog()
    log.create_topic(TopicSpec("events", 1))

    def append(events):
        prod = log.transactional_producer("t")
        prod.begin()
        for ev in events:
            msg = evt.write_event(ev)
            prod.send(LogRecord(topic="events", partition=0,
                                key=msg.key, value=msg.value))
        prod.commit()

    async def scenario():
        plane = ResidentStatePlane(
            log, "events", make_replay_spec(),
            config=default_config().with_overrides({
                "surge.replay.batch-size": 16, "surge.replay.time-chunk": 8,
                "surge.replay.resident.refresh-interval-ms": 5}),
            deserialize_event=lambda raw: evt.read_event(
                SerializedMessage(key="", value=raw)),
            serialize_state=lambda a, s: st.write_state(s).value,
            profiler=prof)
        await plane.start()
        try:
            append([CountIncremented(f"a{i}", 1, 1) for i in range(8)])
            for _ in range(200):
                if plane.lag_records() == 0 and plane.stats["rounds"] > 0:
                    break
                await asyncio.sleep(0.01)
            append([CountIncremented(f"a{i}", 1, 2) for i in range(8)])
            for _ in range(200):
                if plane.lag_records() == 0 and plane.stats["rounds"] > 1:
                    break
                await asyncio.sleep(0.01)
        finally:
            await plane.stop()
        return plane

    plane = asyncio.run(scenario())
    s = prof.summary()
    assert s["refresh"]["count"] == plane.stats["rounds"] >= 2
    assert s["encode"]["count"] >= s["refresh"]["count"]
    assert s["compile"]["count"] > 0   # first refresh window paid the compile
    assert s["dispatch"]["count"] > 0  # the repeat round reused the program
    snap = registry.get_metrics()
    assert snap["surge.replay.profile.refresh-timer.max"] > 0
