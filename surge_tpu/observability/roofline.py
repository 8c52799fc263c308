"""Roofline recorder: measured device-fold figures as append-only JSONL.

Fold figures used to be hand-carried out of bench runs into a doc table;
this module makes the measurement continuous: a :class:`RooflineRecorder`
snapshots a refresh-round ledger's
:meth:`~surge_tpu.replay.ledger.ReplayLedger.summary` (measured ev/s,
µs/slot, µs/event, padding-waste ratio) into one JSON line per snapshot —
append-only, so a file accumulates the machine's trajectory across runs
and regressions show as rows, not as a reverted doc table.

``tools/roofline_record.py`` is the operator CLI (pulls ``DumpReplayLedger``
from a live engine, or reads a saved dump file); :data:`REFERENCE` carries
the anchor figures a row can be compared against in one call
(:func:`against_reference`).
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Iterator, Optional

__all__ = ["REFERENCE", "RooflineRecorder", "against_reference",
           "roofline_row"]

#: anchor figures new rows are read against. Keys name the measured regime.
REFERENCE: Dict[str, Dict[str, float]] = {
    # steady ragged incremental folds on the CPU backend of the sandbox
    # (host clock, not a device figure) — ~8 µs of host-observed dispatch per padded event slot,
    # ~9× padding over-dispatch (pow8 lane bucket × pow2 window tail)
    "steady-ragged-cpu": {"us_per_slot": 8.0, "waste_ratio": 9.0},
}

#: the summary keys a roofline row carries (the derived ratios first — the
#: compared figures — then the raw totals they came from)
_ROW_KEYS = ("fold_events_per_sec", "us_per_slot", "us_per_event",
             "waste_ratio", "rounds", "events", "dispatched_slots",
             "occupied_slots", "dispatch_us", "encode_us", "feed_us",
             "gathers", "gathered_rows", "gather_wait_us")


def roofline_row(summary: Dict[str, object], *, source: str = "",
                 note: str = "", wall: Optional[float] = None) -> dict:
    """One JSONL row from a ledger summary (``ReplayLedger.summary()`` or
    the ``summary`` key of a ``DumpReplayLedger`` payload)."""
    row = {"wall": round(wall if wall is not None else time.time(), 3),
           "source": source, "note": note}
    for k in _ROW_KEYS:
        if k in summary:
            row[k] = summary[k]
    return row


def against_reference(row: Dict[str, object], name: str = "steady-ragged-cpu"
                      ) -> Dict[str, float]:
    """Measured/published ratios against a :data:`REFERENCE` anchor
    (``{figure: measured/reference}`` — 1.0 means the wall holds; missing
    figures are omitted, an unknown anchor raises KeyError)."""
    ref = REFERENCE[name]
    out: Dict[str, float] = {}
    for k, published in ref.items():
        v = row.get(k)
        if isinstance(v, (int, float)) and published:
            out[k] = round(float(v) / published, 3)
    return out


class RooflineRecorder:
    """Append-only JSONL sink for roofline rows.

    Each :meth:`record` call appends one line and returns the row it wrote;
    the file is opened per append (the recorder holds no handle — several
    bench processes may share one trajectory file, and a crashed run can
    never leave a torn writer)."""

    def __init__(self, path: str) -> None:
        self.path = path

    def record(self, summary: Dict[str, object], *, source: str = "",
               note: str = "", wall: Optional[float] = None) -> dict:
        row = roofline_row(summary, source=source, note=note, wall=wall)
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(self.path, "a") as f:
            f.write(json.dumps(row) + "\n")
        return row

    def rows(self) -> Iterator[dict]:
        """Every recorded row, oldest first (missing file → no rows;
        torn/blank lines are skipped — append-only files on crashed hosts
        end mid-line)."""
        try:
            f = open(self.path)
        except OSError:
            return
        with f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    yield json.loads(line)
                except ValueError:
                    continue

    def latest(self) -> Optional[dict]:
        row = None
        for row in self.rows():  # noqa: B007 — want the last one
            pass
        return row
