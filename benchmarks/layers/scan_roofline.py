"""The scan programs' share of their roofline, from the device trace.

Memory-bound: a scan does a compare and three integer reduces an event, so the
least time the chip could take is the bytes it must move over its HBM
bandwidth. The bytes are the configuration's (``config.work``), whatever
implements the scan: every event's type id, group column and reduced columns
at their stored widths read once, and every group's output values written
once a chunk. That least time is divided by the summed device time, within
the traced window (one whole rebuild), of every program ``programs/*.json``
maps to the layer.
"""

import json
import os

LAYER = "Scan programs"
HERE = os.path.dirname(os.path.abspath(__file__))


def scan_bytes(events: int, groups: int, chunks: int, work: dict) -> int:
    return (events * work["event_bytes"]
            + groups * chunks * work["group_row_bytes"])


def read(run):
    t = run.traced
    if t is None:
        return None
    device_s = t["layer_s"].get(LAYER, 0.0)
    if device_s <= 0:
        return None
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        peaks = json.load(f)
    kind = run.device["kind"]
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    f = run.facts
    least_s = scan_bytes(f["events"], f["groups"], f["chunks"],
                         run.config["work"]) / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
