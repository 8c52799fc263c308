"""From the profiler's ``.xplane.pb`` to device busy time, time per program and
idle gaps by what the host was doing.

What a trace of this system on one v5e chip holds (read by hand, PR 24): each
chip is a plane ``/device:TPU:<n>`` whose line ``XLA Modules`` has one event per
execution of a jitted program, named ``jit_<function>(<fingerprint>)``; its line
``XLA Ops`` has one event per HLO operation executed (millions for one rebuild,
whose densify program loops over rows), which this reduction never walks. The
harness's ``TraceAnnotation`` spans are events of the plane ``/host:CPU`` under
their own names, on the same clock. Busy time is the union of the module
events' intervals; a program's time is the sum of its events' durations.
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = "/device:TPU:"
MODULE_LINE = "XLA Modules"
WINDOW_SPAN = "bench_traced_window"
_FINGERPRINT = re.compile(r"\(\d+\)\Z")


def load_programs(directory: str) -> list:
    """[(prefix, layer)] from every ``programs/*.json``, longest prefix first."""
    table = []
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path, encoding="utf-8") as f:
            group = json.load(f)
        table.extend((prefix, group["layer"]) for prefix in group["prefixes"])
    return sorted(table, key=lambda row: -len(row[0]))


def read_events(pb_path: str, span_names) -> dict:
    """The few events the reduction needs, as plain lists (times in ns)."""
    from jax.profiler import ProfileData

    wanted = set(span_names) | {WINDOW_SPAN}
    modules, spans = [], []
    for plane in ProfileData.from_file(pb_path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules.extend([plane.name, e.name, float(e.start_ns),
                                    float(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in line.events if e.name in wanted)
    return {"modules": modules, "spans": spans}


def _union(intervals: list) -> list:
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def reduce(events: dict, programs: list) -> dict | None:
    """Busy and window seconds, seconds per program and per layer, programs no
    prefix maps, and idle seconds by the harness span that covered them. None
    when no program ran on a device inside the traced window."""
    spans = [s for s in events["spans"] if s[0] != WINDOW_SPAN]
    window = [s for s in events["spans"] if s[0] == WINDOW_SPAN]
    modules = events["modules"]
    if window:
        w_lo, w_hi = window[0][1], window[0][1] + window[0][2]
    elif modules:
        starts = [m[2] for m in modules] + [s[1] for s in spans]
        ends = [m[2] + m[3] for m in modules] + [s[1] + s[2] for s in spans]
        w_lo, w_hi = min(starts), max(ends)
    else:
        return None
    by_plane: dict = {}
    per_program: dict = {}
    for plane, name, start, dur in modules:
        lo, hi = max(start, w_lo), min(start + dur, w_hi)
        if hi <= lo:
            continue
        by_plane.setdefault(plane, []).append((lo, hi))
        program = _FINGERPRINT.sub("", name)
        per_program[program] = per_program.get(program, 0.0) + (hi - lo)
    if not by_plane:
        return None
    unions = {plane: _union(iv) for plane, iv in by_plane.items()}
    busy_ns = sum(sum(hi - lo for lo, hi in u) for u in unions.values())
    chips = len(unions)
    per_layer: dict = {}
    unmapped = []
    for program, ns in per_program.items():
        layer = next((lay for prefix, lay in programs
                      if program.startswith(prefix)), None)
        if layer is None:
            unmapped.append([program, ns / 1e9 / chips])
        else:
            per_layer[layer] = per_layer.get(layer, 0.0) + ns / 1e9 / chips

    # idle gaps of the first chip, shared among the harness spans that cover them
    first = unions[sorted(unions)[0]]
    edges = [w_lo] + [t for iv in first for t in iv] + [w_hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    idle: dict = {}
    spans.sort(key=lambda s: s[1])
    for lo, hi in gaps:
        shares: dict = {}
        for name, start, dur in spans:
            if start >= hi:
                break
            overlap = min(hi, start + dur) - max(lo, start)
            if overlap > 0:
                shares[name] = shares.get(name, 0.0) + overlap
        covered = sum(shares.values())
        # spans one after the other share the gap by what each covers; spans
        # that run side by side (a served node's operations) share it in
        # proportion, so that the shares of a gap never add up to more than it
        scale = min(1.0, (hi - lo) / covered) if covered else 0.0
        for name, overlap in shares.items():
            idle[name] = idle.get(name, 0.0) + overlap * scale / 1e9
        rest = (hi - lo) - covered * scale
        if rest > 1e-3:
            idle["(no harness span)"] = idle.get("(no harness span)",
                                                 0.0) + rest / 1e9
    unmapped_names = {name for name, _s in unmapped}
    return {
        "busy_s": busy_ns / 1e9 / chips,
        "window_s": (w_hi - w_lo) / 1e9,
        "chips": chips,
        "program_s": {p: ns / 1e9 / chips for p, ns in per_program.items()},
        "layer_s": per_layer,
        # a program no prefix maps stays in the list, marked, never dropped
        "device_ops": sorted(([p if p not in unmapped_names else f"{p} [unmapped]",
                               ns / 1e9 / chips]
                              for p, ns in per_program.items()),
                             key=lambda r: -r[1]),
        "unmapped": sorted(unmapped, key=lambda r: -r[1]),
        "idle_gaps": sorted(([n, s] for n, s in idle.items()),
                            key=lambda r: -r[1]),
    }


def find_pb(trace_dir: str) -> str | None:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None

