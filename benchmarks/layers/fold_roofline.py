"""The cold fold's share of its roofline, from the device trace.

Memory-bound: the fold does a few integer operations an event, so the least
time the chip could take is the bytes it must move over its HBM bandwidth. The
bytes are the corpus's, unpadded, whatever implements the fold: every event's
wire bytes read once, and every aggregate's state row written once for the
pull. That least time is divided by the summed device time, within the traced
window, of every program ``programs/*.json`` maps to the layer: densify, fold
and finalize together, since all of them are the fold's cost.
"""

import json
import os

LAYER = "Cold fold programs"
HERE = os.path.dirname(os.path.abspath(__file__))


def fold_bytes(events: int, aggregates: int, work: dict) -> int:
    return (events * work["event_wire_bytes"]
            + aggregates * work["state_row_bytes"])


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
    # one whole rebuild is traced
    least_s = fold_bytes(run.facts["events"], run.facts["aggregates"],
                         run.config["work"]) / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
