"""Check ``BENCHMARK.json`` and the files it names before anything runs.

``python -m benchmarks.run --check`` runs this alone; every run runs it first.
It holds the manifest to the character and shape rules a manifest was once
refused for (a ``source`` with a character outside printable ASCII), and to the
harness's own layout: every name finds its file.
"""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state_size", "proj",
               "head_dim", "head_size", "expansion", "experts_per_tok")


def _line(text, what: str, errors: list, limit: int = 200) -> None:
    """1..limit printable ASCII characters, one line, no tab."""
    if (not isinstance(text, str) or not 1 <= len(text) <= limit
            or any(not 32 <= ord(c) <= 126 for c in text)):
        errors.append(f"{what}: must be 1 to {limit} printable ASCII characters "
                      f"on one line, not {text!r}")


def _name(text, what: str, errors: list) -> None:
    if not isinstance(text, str) or not NAME.match(text):
        errors.append(f"{what}: {text!r} is not a name "
                      "([A-Za-z0-9_][A-Za-z0-9_.-]{0,63})")


def _keys(entry: dict, need: set, may: set, what: str, errors: list) -> None:
    have = set(entry)
    if have - need - may or need - have:
        errors.append(f"{what}: keys must be {sorted(need)} (optional "
                      f"{sorted(may)}), not {sorted(have)}")


def _metric(m: dict, what: str, errors: list) -> None:
    _name(m.get("name"), f"{what} name", errors)
    if not isinstance(m.get("unit"), str) or not UNIT.match(m["unit"]):
        errors.append(f"{what}: unit {m.get('unit')!r} must be 1 to 16 of "
                      "letters, digits, _ / % . -")
    if m.get("better") not in ("lower", "higher"):
        errors.append(f"{what}: better must be lower or higher")
    if m.get("source") not in SOURCES:
        errors.append(f"{what}: source must be one of {sorted(SOURCES)}")


def check(root: str, manifest: str = "BENCHMARK.json") -> list:
    """Every fault found, as text; empty when the manifest may be used.
    ``manifest`` is its path from ``root``."""
    errors: list = []
    path = os.path.join(root, manifest)
    if os.path.getsize(path) > 64 * 1024:
        errors.append(f"{manifest} is over 64 KiB")
    with open(path, encoding="utf-8") as f:
        man = json.load(f)
    if set(man) != TOP_KEYS:
        return [f"top-level keys must be exactly {sorted(TOP_KEYS)}, "
                f"not {sorted(man)}"]

    paths = man["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        errors.append("paths: 1 to 16 directories")
    for p in paths:
        if (not isinstance(p, str) or not PATH.match(p) or p.startswith("/")
                or ".." in p.split("/")):
            errors.append(f"paths: {p!r} is not a relative path inside the repo")
        elif not os.path.isdir(os.path.join(root, p)):
            errors.append(f"paths: {p} is not a directory")

    def under_paths(rel: str) -> bool:
        return any(rel == p or rel.startswith(p.rstrip("/") + "/") for p in paths)

    cmd = man["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        errors.append("command: a list of 1 to 32 strings")
    for word in cmd:
        _line(word, "command word", errors)
        if isinstance(word, str) and (word.startswith("/")
                                      or ".." in word.split("/")):
            errors.append(f"command: {word!r} leads out of the repo")
        elif (isinstance(word, str) and os.path.exists(os.path.join(root, word))
              and not under_paths(word)):
            errors.append(f"command: {word!r} names a file outside paths")
    rs = man["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool) and 1 <= rs <= 51):
        errors.append("run_seconds: a whole number from 1 to 51")

    # configurations
    configs = {}
    files = set()
    if not 1 <= len(man["configs"]) <= 24:
        errors.append("configs: 1 to 24")
    for c in man["configs"]:
        what = f"config {c.get('name')}"
        _keys(c, {"name", "source", "file", "reduced", "why"}, set(), what, errors)
        _name(c.get("name"), what, errors)
        _line(c.get("source"), f"{what}: source", errors)
        _line(c.get("why"), f"{what}: why", errors)
        if c.get("name") in configs:
            errors.append(f"{what}: named twice")
        configs[c.get("name")] = c
        rel = c.get("file", "")
        if not isinstance(rel, str) or not PATH.match(rel) or not under_paths(rel):
            errors.append(f"{what}: file {rel!r} must lie under paths")
        elif not os.path.isfile(os.path.join(root, rel)):
            errors.append(f"{what}: file {rel} does not exist")
        else:
            if rel in files:
                errors.append(f"{what}: file {rel} is another configuration's")
            files.add(rel)
            with open(os.path.join(root, rel), encoding="utf-8") as f:
                body = json.load(f)
            if "driver" in body and not os.path.isfile(os.path.join(
                    root, paths[0], "drivers", f"{body['driver']}.py")):
                errors.append(f"{what}: no driver {body['driver']}.py")
            for key in ("source", "reduced"):
                if key in body and body[key] != c.get(key):
                    errors.append(f"{what}: {key} differs from the one in {rel}")
        reduced = c.get("reduced", [])
        if not isinstance(reduced, list) or len(reduced) > 16:
            errors.append(f"{what}: reduced is a list of at most 16 keys")
        for key in reduced if isinstance(reduced, list) else []:
            _name(key, f"{what}: reduced key", errors)
            if isinstance(key, str) and (key.endswith(("_dim", "_rank")) or any(
                    w in key for w in WIDTH_WORDS)):
                errors.append(f"{what}: reduced may not name a width ({key})")

    # cells
    cells = {}
    pairs = set()
    if not 1 <= len(man["workloads"]) <= 24:
        errors.append("workloads: 1 to 24 cells")
    for w in man["workloads"]:
        what = f"cell {w.get('name')}"
        _keys(w, {"name", "config", "traffic", "chips", "why"}, set(), what, errors)
        for key in ("name", "config", "traffic"):
            _name(w.get(key), f"{what}: {key}", errors)
        _line(w.get("why"), f"{what}: why", errors)
        if w.get("chips") not in (1, 4):
            errors.append(f"{what}: chips is 1 or 4")
        if w.get("name") in cells:
            errors.append(f"{what}: named twice")
        cells[w.get("name")] = w
        if w.get("config") not in configs:
            errors.append(f"{what}: no configuration {w.get('config')!r}")
        if (w.get("config"), w.get("traffic")) in pairs:
            errors.append(f"{what}: configuration and traffic appear twice")
        pairs.add((w.get("config"), w.get("traffic")))
        if not any(os.path.isfile(os.path.join(root, p, "traffic",
                                               f"{w.get('traffic')}{s}"))
                   for p in paths for s in TRAFFIC_SUFFIXES):
            errors.append(f"{what}: no traffic file traffic/{w.get('traffic')}.*")
    four = sum(1 for w in man["workloads"] if w.get("chips") == 4)
    if four > max(1, len(man["workloads"]) // 2):
        errors.append(f"{four} cells ask for 4 chips: more than half")
    for name in configs:
        if not any(w.get("config") == name for w in man["workloads"]):
            errors.append(f"config {name}: used by no cell")

    # metrics
    seen = set()
    e2e = {}
    if not 1 <= len(man["end_to_end"]) <= 16:
        errors.append("end_to_end: 1 to 16 metrics")
    for m in man["end_to_end"]:
        what = f"end-to-end metric {m.get('name')}"
        _keys(m, {"name", "unit", "better", "bound", "source"}, {"workloads"},
              what, errors)
        _metric(m, what, errors)
        if m.get("source") not in ("host_clock", "device_trace"):
            errors.append(f"{what}: source is host_clock or device_trace")
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and 0.01 <= b <= 0.25):
            errors.append(f"{what}: bound must lie in 0.01..0.25")
        if m.get("name") in seen:
            errors.append(f"{what}: named twice")
        seen.add(m.get("name"))
        e2e[m.get("name")] = set(m.get("workloads", cells))
        for c in m.get("workloads", []):
            if c not in cells:
                errors.append(f"{what}: no cell {c!r}")
    if "setup_s" not in e2e:
        errors.append("end_to_end: setup_s is missing")
    elif e2e["setup_s"] != set(cells):
        errors.append("setup_s: every cell reports it")
    if not 1 <= len(man["per_layer"]) <= 128:
        errors.append("per_layer: 1 to 128 metrics")
    layered = set()
    for m in man["per_layer"]:
        what = f"per-layer metric {m.get('name')}"
        _keys(m, {"name", "unit", "better", "source", "layer", "moves"},
              {"workloads"}, what, errors)
        _metric(m, what, errors)
        _line(m.get("layer"), f"{what}: layer", errors)
        if m.get("name") in seen:
            errors.append(f"{what}: named twice")
        seen.add(m.get("name"))
        name = m.get("name", "")
        if name.endswith("_roofline") or "mfu" in name:
            if m.get("unit") != "%":
                errors.append(f"{what}: a roofline or mfu share has the unit %")
        if not os.path.isfile(os.path.join(root, paths[0], "layers",
                                           f"{name}.py")):
            errors.append(f"{what}: no reader layers/{name}.py")
        moved = m.get("moves")
        if moved not in e2e:
            errors.append(f"{what}: moves {moved!r}, which is no end-to-end metric")
            continue
        for c in m.get("workloads", e2e[moved]):
            if c not in cells:
                errors.append(f"{what}: no cell {c!r}")
            elif c not in e2e[moved]:
                errors.append(f"{what}: cell {c} does not report {moved}")
            layered.add(c)
    for c in cells:
        if sum(1 for ws in e2e.values() if c in ws) < 2:
            errors.append(f"cell {c}: reports no end-to-end metric but setup_s")
        if c not in layered:
            errors.append(f"cell {c}: reports no per-layer metric")

    # whatever lies under paths is named from the characters of a name and /
    for p in paths:
        for base, dirs, names in os.walk(os.path.join(root, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for n in names:
                rel = os.path.relpath(os.path.join(base, n), root)
                if not PATH.match(rel):
                    errors.append(f"file name {rel!r} has a character outside "
                                  "letters, digits, _ . - /")
    return errors
