"""Per-stage profiler for the chunked TPU replay fold.

The replay is the headline workload, yet the bench trajectory only carried
one end-to-end timer: a regression in encode, H2D transfer, compile behavior,
device fold, or the state fetch was indistinguishable. This profiler splits a
replay pass into stages, each a span that is open while the work runs:

- ``encode``  — host-side wire packing / bucketing (CPU-bound); the cold
  rebuild's ``pack_resident``, with children ``encode.lanes`` (length count
  and sort, grouped check), ``encode.words`` (the word build, or the
  hand-over of its source columns where the upload's ``mk_word`` builds it on
  the device: ``encode`` says which as ``words_from``),
  ``encode.bytes`` (the side columns: one already in its wire dtype is
  handed over as the caller's array, any other cast into a fresh ``[N]``
  buffer; ``encode`` counts them as ``side_aliased`` and
  ``side_copied_bytes``) and ``encode.guard`` (lane starts; the guard rows
  are the word buffer's alone);
- ``h2d``     — host→device transfer of windows / the resident corpus
  (``upload_resident``), with children ``h2d.bucket`` (what the host still
  copies for the bucket: lane starts and lengths, each array's last partial
  piece) and ``h2d.put`` (the puts, and each piece's placement into the
  bucket-shaped device buffer, whose zeros are every row past an array's
  last, through ``block_until_ready`` of them all);
- ``resident`` — the umbrella of one resident fold (``replay_resident`` /
  ``fold_resident_slab``): ``plan`` (lane order, tile plan, work lists),
  then ``compile``/``dispatch`` and ``fetch``;
- ``compile`` — fold dispatches that triggered a fresh XLA compilation
  (detected from the engine's static-shape signature set, never a private
  JAX API);
- ``dispatch``— steady fold dispatches (host-side async cost only — the
  device keeps executing after dispatch returns);
- ``fetch``   — dispatch → results on host. The stage is closed by the repo's
  **fetch-barrier discipline**: a real device→host fetch whose data dependency
  forces the chained programs to finish (bench.py). On the resident path its
  children are ``fetch.wait`` (finalize dispatch to bytes on the host: where
  the host waits for the chip) and ``fetch.decode``;
- ``refresh`` — one incremental fold round of the resident state plane
  (surge_tpu.replay.resident_state): encode + h2d + dispatch of a committed
  batch into the on-device slab. The plane also reports its pack time under
  ``encode`` and its window dispatches under ``compile``/``dispatch``, so
  incremental folds break down in the per-stage profile exactly like
  cold-start passes; ``refresh`` is the per-round umbrella.

:meth:`ReplayProfiler.stage` is the one way the replay path times anything:
it opens a real span ``replay.<stage>`` (child of the span open in this
context), enters a ``jax.profiler.TraceAnnotation`` of the same name — every
stage, so a captured device profile shows the stages on its own clock beside
the XLA programs — and on exit adds the span's one measured interval to the
stage's seconds/count and, for the top-level stages, to the DEBUG-level
``surge.replay.profile.*`` timers in :class:`~surge_tpu.metrics.EngineMetrics`.
Each stage span also says what it cost the host, in ``Span.usage``: the
operating system's own counters read as the span opens and as it closes
(:func:`_os_counters`; the keys are in :func:`_spent`): the calling thread's
on every stage, the whole process's on the outermost (``encode``, ``shard``,
``h2d``, ``resident``).
:meth:`ReplayProfiler.record` (an interval measured beforehand, a span dated
back to it) remains for the resident plane's per-round callers only.

Two modes, same names (docs/observability.md):

- **counter-only** (:meth:`ReplayProfiler.counters`) — always on; what a
  ``ReplayEngine`` builds for itself (over
  :func:`surge_tpu.tracing.default_tracer`, a bounded in-memory ring) and what
  the resident plane's per-round "refresh" umbrella runs through. Stage
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
from contextlib import contextmanager, nullcontext
from typing import Dict, Optional

from surge_tpu.metrics import EngineMetrics, Metrics, RecordingLevel, Timer
from surge_tpu.tracing import NoopTracer, SpanContext, active_span

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

#: where a profiler without a tracer opens its stage spans: real spans
#: (nesting and the measured interval need them), exported nowhere
_UNEXPORTED = NoopTracer()


def _os_counters(process: bool):
    """What the operating system has charged the calling thread so far and,
    with ``process``, the whole process: ``(getrusage(RUSAGE_THREAD),
    getrusage(RUSAGE_SELF) or None)``, a system call each and nothing else.
    None on a host without ``resource`` or without ``RUSAGE_THREAD`` (one
    that is not Linux)."""
    try:
        from resource import RUSAGE_SELF, RUSAGE_THREAD, getrusage
    except ImportError:
        return None
    return getrusage(RUSAGE_THREAD), getrusage(RUSAGE_SELF) if process else None


def _spent(before, after) -> dict:
    """A stage's ``Span.usage``: the differences of two :func:`_os_counters`
    readings. Of the calling thread: ``user_s`` and ``sys_s``, its CPU seconds
    in user code and in the kernel (a first-touch page fault is kernel time);
    ``minflt`` and ``majflt``, its minor and major page faults; ``nivcsw``,
    the times it was taken off its core against its will. Where the readings
    hold the whole process's too (the runtime's transfer threads, a mesh's
    upload threads): ``proc_cpu_s``, user and kernel CPU seconds together,
    and ``proc_minflt``. An umbrella's figures include its children's."""
    (t0, p0), (t1, p1) = before, after
    usage = {"user_s": t1.ru_utime - t0.ru_utime,
             "sys_s": t1.ru_stime - t0.ru_stime,
             "minflt": t1.ru_minflt - t0.ru_minflt,
             "majflt": t1.ru_majflt - t0.ru_majflt,
             "nivcsw": t1.ru_nivcsw - t0.ru_nivcsw}
    if p0 is not None:
        usage["proc_cpu_s"] = ((p1.ru_utime + p1.ru_stime)
                               - (p0.ru_utime + p0.ru_stime))
        usage["proc_minflt"] = p1.ru_minflt - p0.ru_minflt
    return usage


def _trace_annotation(name: str, **counts):
    """A ``jax.profiler.TraceAnnotation`` carrying the stage's counts, or a
    null context when jax (or its profiler) is unavailable — profiling must
    never create a jax dependency for host-only callers."""
    try:
        import jax.profiler as jp

        return jp.TraceAnnotation(name, **counts)
    except Exception:  # noqa: BLE001 — optional integration only
        return nullcontext()


class ReplayProfiler:
    """Accumulates per-stage wall time and occurrence counts for replay passes.

    Thread-compatible with the engine's single-dispatcher model (replay runs
    on one thread); the summary dict is plain data, safe to ship in a bench
    payload or log line.
    """

    def __init__(self, metrics: Optional[EngineMetrics] = None,
                 tracer=None) -> None:
        self.metrics = metrics
        self.tracer = tracer
        self.stage_s: Dict[str, float] = {s: 0.0 for s in _STAGE_TIMERS}
        self.stage_n: Dict[str, int] = {s: 0 for s in _STAGE_TIMERS}
        self.windows = 0  # windows/tiles dispatched (engine-reported)

    @classmethod
    def if_enabled(cls, registry: Metrics,
                   metrics: Optional[EngineMetrics] = None,
                   tracer=None) -> Optional["ReplayProfiler"]:
        """A profiler iff the registry records at DEBUG or finer: the gate of
        the full-histogram mode. An engine handed ``None`` builds its own
        counter-only profiler."""
        if registry.recording_level < RecordingLevel.DEBUG:
            return None
        return cls(metrics=metrics, tracer=tracer)

    @classmethod
    def counters(cls, metrics: Optional[EngineMetrics] = None,
                 tracer=None) -> "ReplayProfiler":
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
        return cls(metrics=metrics, tracer=tracer)

    # -- recording ----------------------------------------------------------------------

    def _account(self, stage: str, seconds: float) -> None:
        self.stage_s[stage] = self.stage_s.get(stage, 0.0) + seconds
        self.stage_n[stage] = self.stage_n.get(stage, 0) + 1
        timer_attr = _STAGE_TIMERS.get(stage)  # child stages have no timer
        if self.metrics is not None and timer_attr is not None:
            timer: Timer = getattr(self.metrics, timer_attr)
            timer.record_ms(seconds * 1000.0)

    def record(self, stage: str, seconds: float, **attrs) -> None:
        """Attribute ``seconds`` of wall time, measured beforehand by the
        caller, to ``stage``: the resident plane's per-round callers only
        (the replay path itself times through :meth:`stage`)."""
        self._account(stage, seconds)
        if self.tracer is not None:
            span = self.tracer.start_span(f"replay.{stage}")
            # retro-dated to the measured interval so the trace timeline
            # matches the perf_counter numbers the plane recorded — BOTH
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
        fold actually dispatched — stage occurrences must not inflate it)."""
        self.windows += n
        if self.metrics is not None:
            self.metrics.replay_profile_windows.record(n)

    @contextmanager
    def stage(self, name: str, follows: Optional[SpanContext] = None,
              **counts):
        """Time a stage as a span that is open while the work runs.

        The span ``replay.<name>`` is a child of the span open in this context
        (an enclosing stage, or the caller's own), else of ``follows`` (the
        context a wire or corpus carries from the stage that made it), else a
        new root. A ``jax.profiler.TraceAnnotation`` of the same name with the
        same ``counts`` puts it on a captured device profile's clock. The
        span is yielded, for counts known only later (``set_attribute``) and,
        once closed, its ``seconds`` and its ``usage`` (what the interval
        cost the host: :func:`_spent`; the process's figures on a stage that
        no other stage encloses; empty where the host has no such
        counters). It finishes, with its usage, and is accounted even when
        the block raises — a failing compile/fetch is exactly the pass an
        operator profiles."""
        tracer = self.tracer if self.tracer is not None else _UNEXPORTED
        enclosing = active_span()
        span = tracer.start_span(f"replay.{name}",
                                 parent=enclosing or follows)
        span.attributes.update(counts)
        # a stage inside another reads the thread's counters alone: the
        # process's are on the outermost stage (a reading costs 6 us on the
        # chip's host: PERF.md, PR 35)
        outermost = (enclosing is None
                     or not enclosing.name.startswith("replay."))
        try:
            with span:
                before = _os_counters(outermost)
                try:
                    with _trace_annotation(span.name, **counts):
                        yield span
                finally:
                    # while the span is open: an exporter writes it as it ends
                    if before is not None:
                        span.usage.update(
                            _spent(before, _os_counters(outermost)))
        finally:
            self._account(name, span.seconds)

    # -- reporting ----------------------------------------------------------------------

    def summary(self) -> dict:
        """``{stage: {"seconds": s, "count": n}}`` for every stage seen, plus
        the windows dispatched and the covered total."""
        out = {s: {"seconds": round(self.stage_s[s], 4),
                   "count": self.stage_n[s]}
               for s in self.stage_s}
        out["windows"] = self.windows
        # the flat stages only: a child's or an umbrella's seconds lie inside
        # another stage's
        out["total_accounted_s"] = round(
            sum(self.stage_s[s] for s in _STAGE_TIMERS), 4)
        return out

    def reset(self) -> None:
        for s in self.stage_s:
            self.stage_s[s] = 0.0
            self.stage_n[s] = 0
        self.windows = 0
