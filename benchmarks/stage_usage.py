"""What the host's stages cost, and each rebuild's own account, for the
per-layer readers that say why a stage takes what it takes.

``ReplayProfiler.stage`` leaves on every ``replay.*`` span what the operating
system charged for it (``Span.usage``: the calling thread's ``user_s``,
``sys_s``, ``minflt``, ``majflt``, ``nivcsw``; on a stage no other encloses
also the whole process's ``proc_cpu_s``, ``proc_minflt``), and one rebuild is
one trace id. This module
joins the records of ``benchmarks.spans.program_spans`` (the program's spans
inside the window's rebuilds) to the ring's spans by span id, for ``usage`` and
the trace id, and groups them by trace id into rebuilds.

A program whose spans carry no ``usage`` (an older commit) gives ``None``
everywhere, and the result line leaves the metric out.

The arithmetic works on plain records, ``benchmarks.spans``'s with ``"usage"``
and ``"trace"`` added, so that a test can hand it a span list written by hand.
"""

from __future__ import annotations

import mmap
import statistics

from benchmarks import spans

PAGE_BYTES = mmap.PAGESIZE

#: the stages that follow one another through one rebuild (``shard`` on a
#: mesh only): their seconds are the rebuild's
ROOTS = ("replay.encode", "replay.shard", "replay.h2d", "replay.resident")
#: no rebuild is whole without these
WHOLE = {"replay.encode", "replay.h2d", "replay.resident"}


def seconds(rec: dict) -> float:
    return rec["end"] - rec["start"]


def attribute(key: str):
    """``record -> its attribute key``, None where the span has no such count."""
    return lambda rec: rec["attributes"].get(key)


def usage(key: str):
    """``record -> its usage key``, None where the span carries none."""
    return lambda rec: rec["usage"].get(key)


def stage_records(run) -> tuple | None:
    """``(records, seconds)`` as ``spans.program_spans`` gives them, each
    record with the ``usage`` and the ``trace`` id of the ring's span of the
    same id. None where the program's spans carry no usage."""
    found = spans.program_spans(run)
    if found is None:
        return None
    from surge_tpu.tracing import default_tracer

    held = {s.context.span_id: s for s in default_tracer().spans()}
    recs = []
    for r in found[0]:
        span = held.get(r["id"])
        recs.append({**r, "usage": dict(getattr(span, "usage", None) or {}),
                     "trace": span.context.trace_id if span else None})
    if not any(r["usage"] for r in recs):
        return None
    return recs, found[1]


def ratio(run, names, top, bottom, scale: float = 1.0):
    """``scale * sum(top) / sum(bottom)`` over the window's spans called one
    of ``names``. None where there is no such span, where one of them lacks
    what ``top`` or ``bottom`` reads, or where the bottoms sum to 0."""
    found = stage_records(run)
    if found is None:
        return None
    pairs = [(top(r), bottom(r)) for r in found[0] if r["name"] in names]
    if not pairs or any(t is None or b is None for t, b in pairs):
        return None
    below = sum(b for _t, b in pairs)
    return scale * sum(t for t, _b in pairs) / below if below else None


def share_pct(run, names):
    """The spans called one of ``names`` as a share of the rebuilds' wall
    time (``spans.share_pct``'s denominator), in per cent."""
    found = stage_records(run)
    if found is None:
        return None
    named = [seconds(r) for r in found[0] if r["name"] in names]
    return 100.0 * sum(named) / found[1] if named else None


def put_cores(run):
    """Cores the process kept busy while the put ran. The process's CPU
    seconds are on the upload's outermost stage, ``replay.h2d``: less the
    thread's own CPU seconds in ``replay.h2d.bucket`` (the host's copy, one
    thread), over the seconds of ``replay.h2d.put``."""
    found = stage_records(run)
    if found is None:
        return None
    kids = spans.children(found[0])
    busy = put_s = 0.0
    for upload in found[0]:
        if upload["name"] != "replay.h2d":
            continue
        if "proc_cpu_s" not in upload["usage"]:
            return None
        busy += upload["usage"]["proc_cpu_s"]
        for kid in kids[upload["id"]]:
            if kid["name"] == "replay.h2d.put":
                put_s += seconds(kid)
            else:
                busy -= kid["usage"].get("user_s", 0.0)
                busy -= kid["usage"].get("sys_s", 0.0)
    return busy / put_s if put_s else None


def rebuilds(run) -> list | None:
    """The window's whole rebuilds but the first, oldest first: ``{"trace",
    "start", "seconds", "nivcsw", "stages": {root stage: seconds}}`` each, a
    rebuild's seconds those of its root stages. The first rebuild of a window is the
    one the profiler traced (and stopped after): it is left out. None where
    fewer than two are left."""
    found = stage_records(run)
    if found is None:
        return None
    traced_until = spans.window_intervals(run)[0][1]
    by_trace: dict = {}
    for r in found[0]:
        if r["name"] in ROOTS and r["trace"] is not None:
            by_trace.setdefault(r["trace"], []).append(r)
    out = []
    for trace, roots in by_trace.items():
        first = min(r["start"] for r in roots)
        if first < traced_until or not WHOLE <= {r["name"] for r in roots}:
            continue
        stages: dict = {}
        for r in roots:
            stages[r["name"]] = stages.get(r["name"], 0.0) + seconds(r)
        out.append({"trace": trace, "start": first, "stages": stages,
                    "seconds": sum(stages.values()),
                    "nivcsw": sum(r["usage"].get("nivcsw", 0) for r in roots)})
    if len(out) < 2:
        return None
    return sorted(out, key=lambda b: b["start"])


def slowest_ratio(run):
    """The slowest rebuild's seconds over the median rebuild's."""
    counted = rebuilds(run)
    if counted is None:
        return None
    return (max(b["seconds"] for b in counted)
            / statistics.median(b["seconds"] for b in counted))


def preempts_per_rebuild(run):
    """Times the rebuilds' calling thread was taken off its core against its
    will, a rebuild."""
    counted = rebuilds(run)
    if counted is None:
        return None
    return sum(b["nivcsw"] for b in counted) / len(counted)
