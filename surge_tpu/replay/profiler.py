"""Per-stage profiler for the chunked TPU replay fold.

The replay is the headline workload, yet the bench trajectory only carried
one end-to-end timer: a regression in encode, H2D transfer, compile behavior,
device fold, or the state fetch was indistinguishable. This profiler splits a
replay pass into five host-clock stages:

- ``encode``  — host-side wire packing / bucketing (CPU-bound);
- ``h2d``     — host→device transfer of windows / the resident corpus;
- ``compile`` — fold dispatches that triggered a fresh XLA compilation
  (detected from the engine's static-shape signature set, never a private
  JAX API);
- ``dispatch``— steady fold dispatches (host-side async cost only — the
  device keeps executing after dispatch returns);
- ``fetch``   — dispatch → results on host. The stage is closed by the repo's
  **fetch-barrier discipline**: a real device→host fetch whose data dependency
  forces the chained programs to finish (bench.py).
- ``refresh`` — one incremental fold round of the resident state plane
  (surge_tpu.replay.resident_state): encode + h2d + dispatch of a committed
  batch into the on-device slab. The plane also reports its pack time under
  ``encode`` and its window dispatches under ``compile``/``dispatch``, so
  incremental folds break down in the per-stage profile exactly like
  cold-start passes; ``refresh`` is the per-round umbrella.

Each stage occurrence feeds the DEBUG-level ``surge.replay.profile.*`` timers
in :class:`~surge_tpu.metrics.EngineMetrics`, emits a span when a tracer is
attached, and — when ``jax.profiler`` is importable — wraps
device-dispatching stages in ``jax.profiler.TraceAnnotation`` so the stages
line up with XLA ops in a captured device profile.

Two modes, same names (docs/observability.md):

- **counter-only** (:meth:`ReplayProfiler.counters`) — always on; the
  resident plane's per-round "refresh" umbrella runs through it. Stage
  seconds/counts accumulate as plain float/int bumps and the histogram
  ``record_ms`` calls no-op because the timers' sensors are disabled below
  DEBUG — the device observatory's per-stage accounting without histogram
  cost.
- **full histograms** (:meth:`ReplayProfiler.if_enabled`, or the same
  counters profiler under a DEBUG registry) — the cold-start replay path's
  opt-in: every stage occurrence also lands in the
  ``surge.replay.profile.*`` timer distributions.

Usage::

    registry = Metrics(recording_level=RecordingLevel.DEBUG)
    metrics = engine_metrics(registry)
    prof = ReplayProfiler.if_enabled(registry, metrics, tracer=tracer)
    engine = ReplayEngine(spec, config=cfg, profiler=prof)
    engine.replay_columnar(events)
    print(prof.summary())
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, Optional

from surge_tpu.metrics import EngineMetrics, Metrics, RecordingLevel, Timer

__all__ = ["ReplayProfiler"]

#: stage name -> EngineMetrics timer attribute
_STAGE_TIMERS = {
    "encode": "replay_encode_timer",
    "h2d": "replay_h2d_timer",
    "compile": "replay_compile_timer",
    "dispatch": "replay_dispatch_timer",
    "fetch": "replay_fetch_timer",
    "refresh": "replay_refresh_timer",
}

#: stages that dispatch device work — annotated into XLA profiles
_DEVICE_STAGES = frozenset({"compile", "dispatch", "fetch"})


def _trace_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for device-visible stages, or None
    when jax (or its profiler) is unavailable — profiling must never create a
    jax dependency for host-only callers."""
    try:
        import jax.profiler as jp

        return jp.TraceAnnotation(name)
    except Exception:  # noqa: BLE001 — optional integration only
        return None


class ReplayProfiler:
    """Accumulates per-stage wall time and occurrence counts for replay passes.

    Thread-compatible with the engine's single-dispatcher model (replay runs
    on one thread); the summary dict is plain data, safe to ship in a bench
    payload or log line.
    """

    def __init__(self, metrics: Optional[EngineMetrics] = None,
                 tracer=None, annotate: bool = True) -> None:
        self.metrics = metrics
        self.tracer = tracer
        self.annotate = annotate
        self.stage_s: Dict[str, float] = {s: 0.0 for s in _STAGE_TIMERS}
        self.stage_n: Dict[str, int] = {s: 0 for s in _STAGE_TIMERS}
        self.windows = 0  # windows/tiles dispatched (engine-reported)
        self._pass_span = None  # current pass-level span (parent of stages)

    @classmethod
    def if_enabled(cls, registry: Metrics,
                   metrics: Optional[EngineMetrics] = None,
                   tracer=None, annotate: bool = True
                   ) -> Optional["ReplayProfiler"]:
        """A profiler iff the registry records at DEBUG or finer — the gate
        that keeps the INFO hot path paying nothing (the engine then holds
        ``profiler=None`` and every hook short-circuits on one ``is None``)."""
        if registry.recording_level < RecordingLevel.DEBUG:
            return None
        return cls(metrics=metrics, tracer=tracer, annotate=annotate)

    @classmethod
    def counters(cls, metrics: Optional[EngineMetrics] = None,
                 tracer=None, annotate: bool = True) -> "ReplayProfiler":
        """Counter-only mode: ALWAYS returns a profiler (no recording-level
        gate). The resident plane's per-round "refresh" umbrella runs through
        this — cheap always-on accounting (``stage_s``/``stage_n`` float/int
        bumps, the device observatory's per-stage wall µs) with the histogram
        cost still opt-in: the ``surge.replay.profile.*`` timers are
        registered at DEBUG, so at the default INFO recording level their
        sensors are disabled and ``record_ms`` is a no-op. Raising the
        registry to DEBUG upgrades the SAME profiler to full-histogram mode
        with zero call-site changes — the names stay stable across both
        modes (docs/observability.md, "Two profiler modes")."""
        return cls(metrics=metrics, tracer=tracer, annotate=annotate)

    # -- recording ----------------------------------------------------------------------

    def record(self, stage: str, seconds: float, **attrs) -> None:
        """Attribute ``seconds`` of wall time to ``stage`` (already measured by
        the caller — the engine's hot loops keep their own perf_counter reads)."""
        self.stage_s[stage] = self.stage_s.get(stage, 0.0) + seconds
        self.stage_n[stage] = self.stage_n.get(stage, 0) + 1
        if self.metrics is not None:
            timer: Timer = getattr(self.metrics, _STAGE_TIMERS[stage])
            timer.record_ms(seconds * 1000.0)
        if self.tracer is not None:
            span = self.tracer.start_span(f"replay.{stage}",
                                          parent=self._pass_span)
            # retro-dated to the measured interval so the trace timeline
            # matches the perf_counter numbers the engine recorded — BOTH
            # clocks: the tail sampler's keep decision and the anatomy
            # placement read the mono pair first, so a wall-only retro-date
            # would make a 2s stage look like a 0ms span
            span.start_time = time.time() - seconds
            span.start_mono = time.monotonic() - seconds
            try:
                for k, v in attrs.items():
                    span.set_attribute(k, v)
            finally:
                # finish unconditionally (span-leak rule): a raising
                # attribute value must not leak the span — under tail
                # sampling a leaked span pins its whole trace in the buffer
                span.finish()

    def count_windows(self, n: int = 1) -> None:
        """Engine-reported window/tile dispatch count (one bump per window the
        fold actually dispatched — record() calls must not inflate it)."""
        self.windows += n
        if self.metrics is not None:
            self.metrics.replay_profile_windows.record(n)

    @contextmanager
    def stage(self, name: str, **attrs):
        """Time a stage inline (used where the engine has no existing timer),
        wrapping device stages in a TraceAnnotation for XLA profiles. The
        record lands even when the block raises — a failing compile/fetch is
        exactly the pass an operator profiles."""
        ann = (_trace_annotation(f"surge.replay.{name}")
               if self.annotate and name in _DEVICE_STAGES else None)
        t0 = time.perf_counter()
        try:
            if ann is not None:
                with ann:
                    yield
            else:
                yield
        finally:
            self.record(name, time.perf_counter() - t0, **attrs)

    @contextmanager
    def replay_pass(self, name: str = "replay.pass", **attrs):
        """Span + timing for one whole replay pass; stage spans emitted inside
        become its children so a trace shows the breakdown under one parent."""
        span = None
        if self.tracer is not None:
            span = self.tracer.start_span(name)
            for k, v in attrs.items():
                span.set_attribute(k, v)
            self._pass_span = span
        try:
            yield span
        finally:
            self._pass_span = None
            if span is not None:
                span.finish()

    # -- reporting ----------------------------------------------------------------------

    def summary(self) -> dict:
        """``{stage: {"seconds": s, "count": n}}`` plus the covered total."""
        out = {s: {"seconds": round(self.stage_s[s], 4),
                   "count": self.stage_n[s]}
               for s in _STAGE_TIMERS}
        out["windows"] = self.windows
        out["total_accounted_s"] = round(sum(self.stage_s.values()), 4)
        return out

    def reset(self) -> None:
        for s in self.stage_s:
            self.stage_s[s] = 0.0
            self.stage_n[s] = 0
        self.windows = 0
