"""The manifest checker: the committed manifest passes, and the faults that
have refused a manifest before are caught here, before any chip call."""

import json
import os
import shutil

import pytest

from benchmarks import manifest_check

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_committed_manifest_passes():
    assert manifest_check.check(ROOT) == []


def test_staged_manifest_passes():
    assert manifest_check.check(ROOT, "benchmarks/staged/node-cells.json") == []


@pytest.fixture
def copy(tmp_path):
    """A copy of the manifest and its files, free to break."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    (tmp_path / "bench.py").write_text("")  # a file of the repo outside paths
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))

    def edit(change):
        path = tmp_path / "BENCHMARK.json"
        man = json.loads(path.read_text())
        change(man)
        path.write_text(json.dumps(man))
        return manifest_check.check(str(tmp_path))

    return edit


def _set(path, value):
    def change(man):
        node = man
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = value
    return change


@pytest.mark.parametrize("path, value, word", [
    (("configs", 0, "source"), "1M entities → 100M events", "printable ASCII"),
    (("configs", 0, "source"), "x" * 201, "printable ASCII"),
    (("configs", 0, "source"), "two\nlines", "printable ASCII"),
    (("workloads", 0, "why"), "tab\there", "printable ASCII"),
    (("workloads", 0, "name"), "has space", "not a name"),
    (("workloads", 0, "config"), "no-such-config", "no configuration"),
    (("workloads", 0, "traffic"), "no-such-mix", "no traffic file"),
    (("workloads", 0, "chips"), 2, "chips is 1 or 4"),
    (("end_to_end", 0, "unit"), "events per second", "unit"),
    (("end_to_end", 0, "bound"), 0.3, "bound"),
    (("end_to_end", 0, "source"), "program_counter", "host_clock or device_trace"),
    (("per_layer", 0, "moves"), "no_such_metric", "no end-to-end metric"),
    (("per_layer", 0, "name"), "no_reader_for_this", "no reader"),
    (("per_layer", 0, "workloads"), ["no-such-cell"], "no cell"),
    (("per_layer", 0, "why"), "an extra key", "keys must be"),
    (("run_seconds",), 52, "run_seconds"),
    (("command",), ["python3", "../elsewhere.py"], "leads out"),
    (("command",), ["python3", "bench.py"], "outside paths"),
    (("configs", 0, "reduced"), ["hidden_dim"], "width"),
    (("configs", 0, "reduced"), [], "reduced differs"),
    (("configs", 0, "source"), "another source", "source differs"),
])
def test_faults_are_caught(copy, path, value, word):
    errors = copy(_set(path, value))
    assert any(word in e for e in errors), errors


def test_more_than_half_on_four_chips_is_refused(copy):
    def change(man):
        first = man["workloads"][0]
        man["workloads"] = [dict(first, name=f"cell-{i}", traffic=first["traffic"],
                                 chips=4) for i in range(3)]
    assert any("more than half" in e for e in copy(change))


def test_a_missing_key_and_an_extra_top_level_key(copy):
    assert copy(lambda man: man.pop("per_layer"))
    assert copy(lambda man: man.__setitem__("notes", "x"))
