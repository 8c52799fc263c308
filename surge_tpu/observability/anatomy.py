"""Command anatomy: cross-process trace assembly + the critical-path latency
attributor (ISSUE 14 — the analysis half of the command-anatomy plane).

**Assembly.** Brokers and engines each retain their tail-kept spans in a
bounded :class:`~surge_tpu.tracing.tail.TraceRing`; :func:`assemble_traces`
merges several rings' ``DumpTraces`` envelopes into whole traces. Spans are
placed on one timeline by the SAME mono↔wall offset estimation the flight
merge uses (:func:`~surge_tpu.observability.flight.host_wall_offset`): each
dump's header pairs the host's two clocks at one instant, so every span of
that dump is positioned at ``offset + start_mono`` — an NTP step or a
deliberately skewed wall clock during the incident cannot scramble the order
of a trace's legs (tests/test_anatomy.py proves a 3-host dump set whose raw
wall order inverts the fsync leg still assembles correctly). Dumps without
the header pair (hand-built) fall back to raw wall stamps.

**Attribution.** For each assembled COMMAND trace (one that reaches a broker
``log.server.transact`` span), :func:`attribute_trace` decomposes the root
span's wall time into named legs along the ack critical path:

- ``mailbox-wait`` — ask boundary → entity receive (routing + mailbox);
- ``command-handling`` — entity receive → publish enqueue (handler + fold +
  serialize);
- ``publisher-linger`` — publish enqueue → flush dispatch (the group-commit
  linger actually paid);
- ``lane-dispatch`` — flush dispatch → the broker call leaving the client;
- ``router-resolve`` — PartitionRouter resolve/redirect/retry time around
  the broker calls (router span self-time);
- ``gate-wait`` — the broker's in-order/dedup apply gate hold
  (``leg.gate-wait-ms`` span attribute);
- ``journal-fsync`` — local apply + the WAL group-commit fsync round
  (``leg.fsync-ms``);
- ``replication-ack`` — the quorum/in-sync replication ack wait
  (``leg.repl-ms``);
- ``reply-decode`` — client-observed broker-call time not accounted on the
  broker (wire + reply decode);
- ``gather-coalesce`` / ``device-dispatch`` / ``fetch-barrier`` /
  ``decode`` — the DEVICE legs (the fold anatomy, ISSUE 16): resident-plane
  ``resident.gather`` and engine ``query.scan`` spans carry measured
  ``leg.{coalesce,dispatch,fetch,decode}-ms`` attributes, and the replay
  profiler's ``replay.dispatch``/``replay.compile``/``replay.fetch`` stage
  spans map by name — so a stalled refresh dispatch names
  ``device-dispatch`` dominant the same way a slow WAL names
  ``journal-fsync``;
- ``other`` — root residue none of the above claims (reply fan-out, event
  loop scheduling).

Legs are *self-times on the critical path*: they sum to (at most) the root
duration, so a leg's share IS its share of the command's wall time.
:func:`attribution_table` aggregates kept traces into per-leg
p50/p99/total/share rows and names the dominant leg — the evidence the next
perf PR starts from, instead of paired-ladder medians that can only say THAT
time was lost, not where.
"""

from __future__ import annotations

import time
from typing import Dict, Iterable, List, Optional, Sequence

from surge_tpu.observability.flight import host_wall_offset

__all__ = ["LEGS", "assemble_traces", "attribute_trace", "attribution_table",
           "dominant_leg"]

#: attribution legs in critical-path order (the table's row order)
LEGS = ("mailbox-wait", "command-handling", "publisher-linger",
        "lane-dispatch", "router-resolve", "gate-wait", "journal-fsync",
        "replication-ack", "reply-decode", "gather-coalesce",
        "device-dispatch", "fetch-barrier", "decode", "other")

#: broker span attributes carrying measured waits (surge_tpu/log/server.py
#: stamps them on the active ``log.server.transact`` span)
_BROKER_ATTR_LEGS = (("leg.gate-wait-ms", "gate-wait"),
                     ("leg.fsync-ms", "journal-fsync"),
                     ("leg.repl-ms", "replication-ack"))

#: span names marking a COMMAND-shaped trace: the attribution table skips
#: traces with none of these (an indexer's kept read-poll trace is one bare
#: ``log.Read`` span — aggregating it would dilute every command leg)
_COMMAND_MARKERS = ("aggregate-ref.", "entity.", "publisher.",
                    "router.commit", "log.server.transact", "log.Transact")

#: span-name prefixes of the device planes (resident gather lane, query
#: engine, replay profiler stages) — accepted alongside the command markers
#: so a kept device trace attributes instead of being skipped as noise
_DEVICE_MARKERS = ("resident.", "query.", "replay.")

#: device span attributes carrying measured leg times (resident_state's
#: gather spans, pipeline's query spans — measured, not inferred)
_DEVICE_ATTR_LEGS = (("leg.coalesce-ms", "gather-coalesce"),
                     ("leg.dispatch-ms", "device-dispatch"),
                     ("leg.fetch-ms", "fetch-barrier"),
                     ("leg.decode-ms", "decode"))

#: replay-profiler stage spans carry no leg attributes — their whole
#: duration IS the leg, mapped by their exact name: a stage's children
#: (``replay.fetch.wait``, ``replay.fetch.decode``) lie inside it and claim
#: nothing of their own (host stages encode/h2d/plan stay in ``other``: they
#: are not device legs)
_DEVICE_NAME_LEGS = {"replay.dispatch": "device-dispatch",
                     "replay.compile": "device-dispatch",
                     "replay.fetch": "fetch-barrier"}


def _place(span: dict, offset: Optional[float]) -> dict:
    """Copy a span with estimated-wall ``start``/``end`` stamps."""
    s = dict(span)
    if offset is not None and s.get("start_mono") is not None:
        s["start"] = offset + s["start_mono"]
        end_mono = s.get("end_mono")
        s["end"] = (offset + end_mono) if end_mono is not None \
            else s["start"]
    else:
        s["start"] = s.get("start_wall", 0.0)
        s["end"] = s.get("end_wall") or s["start"]
    return s


def assemble_traces(dumps: Sequence[dict]) -> Dict[str, List[dict]]:
    """Merge several ``DumpTraces`` envelopes into whole traces.

    Returns ``{trace_id: [span, ...]}`` with spans ordered by estimated wall
    start time; each span gains ``recorder``/``lane`` (who recorded it) and
    ``start``/``end`` (estimated-wall placement, module doc). ``keep_reason``
    carries the recorder's tail-keep verdict."""
    traces: Dict[str, List[dict]] = {}
    for d in dumps:
        who = d.get("recorder") or d.get("node") or "?"
        lane = d.get("role") or "broker"
        offset = host_wall_offset(d)
        for entry in d.get("traces", ()):
            tid = entry.get("trace_id", "")
            for span in entry.get("spans", ()):
                s = _place(span, offset)
                s["recorder"] = who
                s["lane"] = lane
                s["keep_reason"] = entry.get("reason", "")
                traces.setdefault(tid, []).append(s)
    for spans in traces.values():
        spans.sort(key=lambda s: (s["start"], s.get("span_id", "")))
    return traces


def _first_named(spans: Sequence[dict], *prefixes: str) -> Optional[dict]:
    for s in spans:
        name = s.get("name", "")
        if any(name.startswith(p) for p in prefixes):
            return s
    return None


def _dur(span: Optional[dict]) -> float:
    if span is None:
        return 0.0
    return max((span["end"] - span["start"]) * 1000.0, 0.0)


def attribute_trace(spans: Sequence[dict]) -> Optional[dict]:
    """Decompose one assembled trace into the critical-path legs.

    Returns ``{"trace_id", "duration_ms", "legs": {leg: ms}, "dominant"}``,
    or None for a trace with no recognizable command shape (no root span).
    Partial traces attribute the legs their spans cover; the residue stays
    in ``other`` rather than being guessed."""
    spans = list(spans)
    if not spans:
        return None
    root = next((s for s in spans if not s.get("parent_id")), None)
    if root is None:
        # every span is a child of something remote/unkept: use the earliest
        # as the envelope — partial anatomy beats none mid-incident
        root = spans[0]
    total_ms = _dur(root)
    legs = {leg: 0.0 for leg in LEGS}

    entity = _first_named(spans, "entity.")
    publish = _first_named(spans, "publisher.publish")
    flush = _first_named(spans, "publisher.flush")
    client_calls = [s for s in spans if s.get("name", "").startswith("log.")
                    and not s.get("name", "").startswith("log.server.")]
    broker_spans = [s for s in spans
                    if s.get("name", "") == "log.server.transact"]
    router_spans = [s for s in spans
                    if s.get("name", "").startswith("router.")]
    first_call = (router_spans[0] if router_spans
                  else (client_calls[0] if client_calls else None))

    if entity is not None:
        legs["mailbox-wait"] = max(
            (entity["start"] - root["start"]) * 1000.0, 0.0)
    if publish is not None and entity is not None:
        legs["command-handling"] = max(
            (publish["start"] - entity["start"]) * 1000.0, 0.0)
    if flush is not None and publish is not None:
        legs["publisher-linger"] = max(
            (flush["start"] - publish["start"]) * 1000.0, 0.0)
    if flush is not None and first_call is not None:
        legs["lane-dispatch"] = max(
            (first_call["start"] - flush["start"]) * 1000.0, 0.0)
    # router self-time: resolve/redirect/backoff around the broker calls.
    # Subtract only children NESTED UNDER a router span (router.resolve is a
    # child of router.commit, client calls are children of either) — summing
    # all router durations minus all client calls would double-count the
    # overlapped commit/resolve interval on redirect-heavy traces
    if router_spans:
        router_ids = {r.get("span_id") for r in router_spans}
        nested_client = sum(_dur(c) for c in client_calls
                            if c.get("parent_id") in router_ids)
        nested_router = sum(_dur(r) for r in router_spans
                            if r.get("parent_id") in router_ids)
        legs["router-resolve"] = max(
            sum(_dur(r) for r in router_spans)
            - nested_client - nested_router, 0.0)
    # broker-measured waits ride span attributes (measured, not inferred)
    for attr, leg in _BROKER_ATTR_LEGS:
        for b in broker_spans:
            try:
                legs[leg] += float((b.get("attributes") or {}).get(attr, 0.0))
            except (TypeError, ValueError):
                pass
    # device legs (the fold anatomy): gather/query spans claim their
    # measured leg attributes; attribute-less profiler stage spans map by
    # name — a span claims via attributes OR name, never both (the
    # attributes already decompose the span's own duration)
    for s in spans:
        name = s.get("name", "")
        if not name.startswith(_DEVICE_MARKERS):
            continue
        attrs = s.get("attributes") or {}
        claimed = False
        for attr, leg in _DEVICE_ATTR_LEGS:
            if attr in attrs:
                try:
                    legs[leg] += float(attrs[attr])
                    claimed = True
                except (TypeError, ValueError):
                    pass
        if not claimed and name in _DEVICE_NAME_LEGS:
            legs[_DEVICE_NAME_LEGS[name]] += _dur(s)
    # client-observed broker time the broker itself does not account for:
    # wire + request encode + reply decode
    if client_calls and broker_spans:
        client_ms = sum(_dur(c) for c in client_calls)
        broker_ms = sum(_dur(b) for b in broker_spans)
        legs["reply-decode"] = max(client_ms - broker_ms, 0.0)
    elif client_calls and flush is not None:
        # no broker dump for this trace: the whole call is unattributed wire
        legs["reply-decode"] = sum(_dur(c) for c in client_calls)

    accounted = sum(v for k, v in legs.items() if k != "other")
    if total_ms > 0.0:
        legs["other"] = max(total_ms - accounted, 0.0)
    dominant = max(legs, key=lambda leg: legs[leg]) if any(
        v > 0.0 for v in legs.values()) else None
    return {"trace_id": spans[0].get("trace_id", ""),
            "duration_ms": round(total_ms, 3),
            "legs": {k: round(v, 3) for k, v in legs.items()},
            "dominant": dominant}


def _percentile(values: List[float], q: float) -> float:
    if not values:
        return 0.0
    ordered = sorted(values)
    idx = min(int(q * (len(ordered) - 1) + 0.5), len(ordered) - 1)
    return ordered[idx]


def attribution_table(traces: Dict[str, List[dict]], metrics=None,
                      command_only: bool = True) -> dict:
    """Aggregate assembled traces into the per-leg attribution table.

    Returns ``{"traces": N, "legs": {leg: {"p50", "p99", "total_ms",
    "share"}}, "dominant", "dominant_share", "slowest": [...]}`` — shares
    are of the summed critical-path time across all attributed traces.
    ``command_only`` (default) restricts to command-shaped traces
    (``_COMMAND_MARKERS``) so kept read-poll traces cannot dilute the legs.
    ``metrics`` (a FleetMetrics quiver) records the assembly+attribution
    duration into ``surge.trace.assembly-timer``."""
    t0 = time.perf_counter()
    rows: List[dict] = []
    for tid, spans in traces.items():
        if command_only and not any(
                s.get("name", "").startswith(_COMMAND_MARKERS)
                or s.get("name", "").startswith(_DEVICE_MARKERS)
                for s in spans):
            continue
        row = attribute_trace(spans)
        if row is not None:
            row["trace_id"] = tid
            rows.append(row)
    per_leg: Dict[str, List[float]] = {leg: [] for leg in LEGS}
    for row in rows:
        for leg in LEGS:
            per_leg[leg].append(row["legs"].get(leg, 0.0))
    totals = {leg: sum(vals) for leg, vals in per_leg.items()}
    grand = sum(totals.values())
    legs = {leg: {"p50": round(_percentile(per_leg[leg], 0.50), 3),
                  "p99": round(_percentile(per_leg[leg], 0.99), 3),
                  "total_ms": round(totals[leg], 3),
                  "share": round(totals[leg] / grand, 4) if grand else 0.0}
            for leg in LEGS}
    dominant = max(totals, key=lambda leg: totals[leg]) if grand else None
    slowest = sorted(rows, key=lambda r: r["duration_ms"], reverse=True)[:5]
    out = {"traces": len(rows), "legs": legs, "dominant": dominant,
           "dominant_share": (round(totals[dominant] / grand, 4)
                              if dominant else 0.0),
           "slowest": [{"trace_id": r["trace_id"],
                        "duration_ms": r["duration_ms"],
                        "dominant": r["dominant"]} for r in slowest]}
    if metrics is not None:
        metrics.trace_assembly_timer.record_ms(
            (time.perf_counter() - t0) * 1000.0)
    return out


def dominant_leg(dumps: Iterable[dict], metrics=None) -> Optional[dict]:
    """One-call convenience for the SLO wiring: assemble + attribute and
    return ``{"dominant", "dominant_share", "traces"}`` (None when the dumps
    hold no attributable trace)."""
    table = attribution_table(assemble_traces(list(dumps)), metrics=metrics)
    if not table["traces"] or table["dominant"] is None:
        return None
    return {"dominant": table["dominant"],
            "dominant_share": table["dominant_share"],
            "traces": table["traces"]}
