"""The trace reduction on a small recorded trace: the events the reduction read
from one traced rebuild on a v5e chip (``run.py --keep-trace-events``, PR 24),
kept under ``fixtures/``."""

import json
import os

import pytest

from benchmarks import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(os.path.dirname(HERE), "fixtures",
                       "rebuild-trace-events.json")
# the recorded rebuild also ran ``jit_densify``, the dense layout's program,
# which no program bears any more: the table the trace was reduced with then
PROGRAMS = trace_reduce.load_programs(os.path.join(
    os.path.dirname(HERE), "programs")) + [("jit_densify", "Cold fold programs")]


@pytest.fixture(scope="module")
def events():
    with open(FIXTURE, encoding="utf-8") as f:
        return json.load(f)


def test_busy_is_the_union_and_the_window_is_the_span(events):
    t = trace_reduce.reduce(events, PROGRAMS)
    window = next(s for s in events["spans"]
                  if s[0] == trace_reduce.WINDOW_SPAN)
    assert t["window_s"] == pytest.approx(window[2] / 1e9)
    total = sum(m[3] for m in events["modules"]) / 1e9
    # the rebuild's programs run one after the other: the union is their sum
    assert t["busy_s"] == pytest.approx(total, rel=1e-6)
    assert 0 < t["busy_s"] < t["window_s"]
    assert t["chips"] == 1


def test_programs_map_to_layers_by_prefix(events):
    t = trace_reduce.reduce(events, PROGRAMS)
    assert set(t["program_s"]) >= {"jit_densify", "jit_fold"}
    assert t["unmapped"] == []
    assert t["layer_s"]["Cold fold programs"] == pytest.approx(t["busy_s"])
    assert t["device_ops"][0][0] == "jit_densify"  # the longest first


def test_a_program_no_prefix_maps_is_listed_never_dropped(events):
    without_fold = [row for row in PROGRAMS if row[0] != "jit_fold"]
    t = trace_reduce.reduce(events, without_fold)
    assert [name for name, _s in t["unmapped"]] == ["jit_fold"]
    assert "jit_fold [unmapped]" in [name for name, _s in t["device_ops"]]
    assert t["busy_s"] == pytest.approx(
        trace_reduce.reduce(events, PROGRAMS)["busy_s"])


def test_idle_gaps_go_to_the_span_that_covers_them(events):
    t = trace_reduce.reduce(events, PROGRAMS)
    gaps = dict(t["idle_gaps"])
    # the device waits while the host packs: the longest gap is the pack's
    assert t["idle_gaps"][0][0] == "pack"
    pack = next(s for s in events["spans"] if s[0] == "pack")
    assert gaps["pack"] == pytest.approx(pack[2] / 1e9, rel=1e-3)
    upload = next(s for s in events["spans"] if s[0] == "upload")
    assert gaps["upload"] == pytest.approx(upload[2] / 1e9, rel=1e-3)
    assert sum(gaps.values()) == pytest.approx(t["window_s"] - t["busy_s"])


def test_side_by_side_spans_share_a_gap_and_never_exceed_it():
    """A slice of a traced served-node window (v5e, PR 24): hundreds of
    ``send_command`` and ``project_states`` spans overlap every idle gap."""
    with open(os.path.join(os.path.dirname(FIXTURE),
                           "node-trace-events.json"), encoding="utf-8") as f:
        events = json.load(f)
    t = trace_reduce.reduce(events, PROGRAMS)
    assert t["unmapped"] == []
    assert set(t["layer_s"]) == {"Gather lane", "Refresh dispatch"}
    gaps = dict(t["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(t["window_s"] - t["busy_s"])
    assert gaps["send_command"] > gaps["project_states"] > 0


def test_overlapping_programs_are_not_counted_twice():
    events = {"spans": [[trace_reduce.WINDOW_SPAN, 0.0, 100.0]],
              "modules": [["/device:TPU:0", "jit_a(1)", 10.0, 30.0],
                          ["/device:TPU:0", "jit_b(2)", 20.0, 30.0],
                          ["/device:TPU:0", "jit_a(1)", 90.0, 30.0]]}
    t = trace_reduce.reduce(events, [("jit_a", "A")])
    assert t["busy_s"] == pytest.approx((40.0 + 10.0) / 1e9)  # clipped at the window
    assert t["layer_s"] == {"A": pytest.approx(40.0 / 1e9)}
    assert [n for n, _s in t["unmapped"]] == ["jit_b"]


def test_no_device_program_gives_nothing():
    assert trace_reduce.reduce({"spans": [["pack", 0.0, 5.0]], "modules": []},
                               PROGRAMS) is None
