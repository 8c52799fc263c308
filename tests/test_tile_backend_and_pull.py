"""The resident fold's tile backend and its single-round-trip state pull.

1. ``surge.replay.tile-backend = auto`` resolves by the backend and the spec,
   and a value other than ``auto | xla | assoc`` raises;
2. ``replay_resident`` pulls states in ONE device→host fetch — a u16 matrix
   with device-computed fit flags when every column is integer/bool, falling
   back to a wide u32 refetch when a value overflows 16 bits (the pull grows
   with the aggregate count); float columns always ride full width.
"""

from __future__ import annotations

import numpy as np
import pytest

from surge_tpu.codec.tensor import encode_events_columnar
from surge_tpu.config import Config
from surge_tpu.models import bank_account as ba
from surge_tpu.models import counter
from surge_tpu.replay.engine import ReplayEngine


def _replay(tile: str, events, **cfg):
    eng = ReplayEngine(counter.make_replay_spec(), config=Config({
        "surge.replay.tile-backend": tile,
        "surge.replay.batch-size": 256,
        "surge.replay.time-chunk": 16,
        **cfg,
    }))
    return eng.replay_resident(eng.prepare_resident(events))


def test_auto_tile_backend_resolves_per_backend():
    """``auto`` must resolve to the scan on CPU hosts (the tree measured ~2×
    slower there) even though counter ships an AssociativeFold; explicit
    ``assoc`` is always honored."""
    eng = ReplayEngine(counter.make_replay_spec())
    assert eng.tile_backend == "xla"  # conftest pins the cpu backend
    eng2 = ReplayEngine(counter.make_replay_spec(), config=Config({
        "surge.replay.tile-backend": "assoc"}))
    assert eng2.tile_backend == "assoc"


def test_narrow_pull_overflow_falls_back_wide():
    """A version past 32767 must trip the device fit flag and refetch wide —
    the u16 fast path can never silently truncate."""
    n = 40_000  # > 2^15 events on one lane -> version overflows int16
    logs = [[counter.CountIncremented("big", 1, k + 1) for k in range(n)],
            [counter.CountIncremented("small", 1, 1)]]
    ev = encode_events_columnar(counter.make_registry(), logs)
    res = _replay("assoc", ev, **{"surge.replay.time-chunk": 64})
    assert int(res.states["count"][0]) == n
    assert int(res.states["version"][0]) == n  # exact despite the u16 fast path
    assert int(res.states["count"][1]) == 1


def test_float_state_pulls_wide():
    """bank_account's f32 balance forces the wide (bitcast u32) pull; the
    tiles must carry its side column correctly."""
    rng = np.random.default_rng(11)
    vocab = ba.Vocab()
    logs, finals = [], []
    for j in range(37):
        evs = [ba.BankAccountCreated(f"acct-{j}", f"o{j}", "s", 4.25)]
        bal = 4.25
        for _ in range(int(rng.integers(0, 24))):
            bal += 0.25
            evs.append(ba.BankAccountUpdated(f"acct-{j}", bal))
        finals.append(bal)
        logs.append([ba.encode_event(vocab, e) for e in evs])
    ev = encode_events_columnar(ba.make_registry(), logs)
    eng = ReplayEngine(ba.make_replay_spec(), config=Config({
        "surge.replay.batch-size": 64,
        "surge.replay.time-chunk": 8,
    }))
    res = eng.replay_resident(eng.prepare_resident(ev))
    for j, want in enumerate(finals):
        assert res.states["created"][j]
        np.testing.assert_allclose(res.states["balance"][j], want, rtol=1e-6)


@pytest.mark.parametrize("tile", ["pallas", "select", "XLA", ""])
def test_an_unknown_tile_backend_raises(tile):
    """The tile has two lowerings, the xla scan and the assoc tree: any other
    ``tile-backend`` (the Pallas kernel's old name too) raises at
    construction, naming the values accepted."""
    with pytest.raises(ValueError, match=r"\(auto\|xla\|assoc\)"):
        ReplayEngine(counter.make_replay_spec(), config=Config({
            "surge.replay.tile-backend": tile}))
