"""The cart's restore cell: its reference against itself, its five readers on
a span list written by hand, a sound run and the control at rehearsal size on
the CPU backend, a fault, and the manifest's new entries."""

import json
import os

import numpy as np
import pytest

from benchmarks import control, gen_cart, reference_cart, reference_cart_restore
from benchmarks import run as harness
from benchmarks.tests.test_cart import (assert_listed, law, reader, rec,
                                        run_over)

CELL = "restore-cart-segment"
LIMITS = {"states_wrong", "store_missing", "store_extra", "events_unaccounted",
          "scalar_sample_wrong"}
NEW = ["restore_read_pct", "restore_wire_pct", "restore_decode_pct",
       "restore_writeback_pct", "restore_host_us_per_aggregate"]
# the cells of the cold fold that were there before this one
FOLD_CELLS = ["rebuild-1m-100m", "rebuild-cart-ragged", "rebuild-mixed-opaque",
              "rebuild-mixed-mesh4"]


# --- the reference agrees with itself: the dictionary == the scalar fold's bytes --

@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_the_expected_store_is_the_scalar_folds(seed):
    corpus = gen_cart.cart_corpus(200, 4000, seed, law())
    ids = reference_cart_restore.cart_ids(200)
    assert ids[0] == "cart-0000000" and ids == sorted(ids)
    store = reference_cart_restore.expected_store(corpus, ids)
    scalar = reference_cart_restore.scalar_fold_bytes(corpus, ids, range(200))
    assert list(store) == ids == list(scalar)
    for cart_id in ids:
        assert list(store[cart_id]) == list(reference_cart_restore.STATE_FIELDS)
        assert json.loads(scalar[cart_id]) == store[cart_id]
    # the events are the corpus's own, one object an event
    events = reference_cart_restore.cart_events(corpus, ids, 3)
    assert len(events) == corpus.lengths[3]
    assert [e.sequence_number for e in events] == list(range(1, len(events) + 1))
    assert {e.cart_id for e in events} == {ids[3]}
    plain = reference_cart.scalar_fold_sample(corpus, [3])[3]
    assert tuple(store[ids[3]][k] for k in reference_cart.FIELDS) == plain


# --- the five readers, on spans written by hand ------------------------------------

def restore_spans(tag, at, aggregates=1000):
    """One restore [at, at + 3] of two chunks: read 0.1, wire 0.05, fold 0.35,
    decode 0.6 and write-back 0.4 s a chunk."""
    out = [rec("replay.restore", "R" + tag, None, at, at + 3.0, chunks=2)]
    for c in range(2):
        t = at + 1.5 * c
        for name, lo, hi, attrs in [
                ("replay.restore.read", 0.0, 0.1, {}),
                ("replay.restore.wire", 0.1, 0.15, {"hit": True}),
                ("replay.resident", 0.15, 0.5, {}),
                ("replay.restore.decode", 0.5, 1.1,
                 {"aggregates": aggregates // 2}),
                ("replay.restore.writeback", 1.1, 1.5,
                 {"aggregates": aggregates // 2})]:
            out.append(rec(name, f"{name}{tag}{c}", "R" + tag, t + lo, t + hi,
                           **attrs))
    return out


def test_the_stage_shares_are_of_the_windows_restores(monkeypatch):
    # the warm-up restore at 0 is left out; the window's two count
    recs = (restore_spans("a", 0.0) + restore_spans("b", 10.0)
            + restore_spans("c", 20.0))
    run = run_over(monkeypatch, recs, rebuilds=2)
    run.facts["rebuild_s"] = 6.0
    want = {"restore_read_pct": 100 * 0.4 / 6, "restore_wire_pct": 100 * 0.2 / 6,
            "restore_decode_pct": 100 * 2.4 / 6,
            "restore_writeback_pct": 100 * 1.6 / 6,
            "restore_host_us_per_aggregate": 1e6 * 4.0 / 2000}
    for name in NEW:
        assert reader(name)(run) == pytest.approx(want[name]), name


def test_the_readers_give_nothing_on_a_program_without_the_spans(monkeypatch):
    # the parent commit: the fold's spans are there, the restore's are not
    recs = [rec("replay.resident", "r", None, 10.0, 13.0, aggregates=1000)]
    for run in (run_over(monkeypatch, recs, rebuilds=1),
                run_over(monkeypatch, [], rebuilds=1)):  # and no ring at all
        for name in NEW:
            assert reader(name)(run) is None, name


# --- the committed segment is on disk before the set-up reads it -------------------

def test_the_segment_is_synced_before_write_segment_returns(tmp_path,
                                                            monkeypatch):
    """A committed segment is durable before anyone reads it: the writer
    flushes a fresh file but does not sync it, so ``write_segment`` syncs the
    whole closed file and then its directory before it returns."""
    from benchmarks.drivers import cart_restore as driver

    synced = []
    real_fsync = os.fsync

    def fsync(fd):
        name = os.path.realpath(os.readlink(f"/proc/self/fd/{fd}"))
        synced.append((name, os.fstat(fd).st_size))
        real_fsync(fd)

    monkeypatch.setattr(os, "fsync", fsync)
    corpus = gen_cart.cart_corpus(300, 6000, 5, law())
    path = os.path.join(os.path.realpath(tmp_path), "cart-events.scol")
    info = driver.write_segment(path, corpus,
                                reference_cart_restore.cart_ids(300), 128)
    assert info["num_chunks"] == 3
    assert [name for name, _size in synced] == [path, os.path.dirname(path)]
    assert synced[0][1] == os.path.getsize(path) > 0  # the whole file


@pytest.mark.parametrize("seeds", [(3, 2**31 + 11), (3_300_000_033,
                                                  3_300_000_035)])
def test_every_seed_gets_a_chunk_of_the_same_events(seeds):
    """Under the segment cells' law the seed orders a chunk's lengths and does
    not draw them: every chunk holds the same lengths, so the same events,
    whatever the seed; the events themselves are the seed's."""
    from benchmarks.drivers import cart_restore as driver

    _man, cell, config, traffic = harness.load_cell(CELL)
    made = []
    for seed in seeds:
        run = harness.Run(cell, config, traffic, seed, 1.0, False, True)
        made.append(driver.build_inputs(run)[0])
    block = run.sizes["chunk_aggregates"]
    assert 0 < block < run.sizes["aggregates"] // 4  # several chunks
    one, other = made
    assert one.num_events == other.num_events == run.sizes["events"]
    for lo in range(0, run.sizes["aggregates"], block):
        a, b = one.lengths[lo:lo + block], other.lengths[lo:lo + block]
        assert np.array_equal(np.sort(a), np.sort(b))
        assert not np.array_equal(a, b)  # in the seed's own order
    assert not np.array_equal(one.item_code, other.item_code)
    # without the key the seed draws the lengths, as in rebuild-cart-ragged
    drawn = [gen_cart.cart_corpus(run.sizes["aggregates"], run.sizes["events"],
                                  seed, law(), block=block) for seed in seeds]
    assert "lengths_seed" not in law()
    assert not np.array_equal(np.sort(drawn[0].lengths),
                              np.sort(drawn[1].lengths))


# --- a sound run, the control, a fault ---------------------------------------------

def run_cell(capsys, seed, trace=0, seconds=1.0):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace), "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failed_numbers(line):
    return {name for name, c in line["compared"].items()
            if c["value"] > c["limit"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_restore_sound_run_is_correct(capsys, trace):
    before = set(os.listdir(os.environ.get("TMPDIR", "/tmp")))
    line = run_cell(capsys, 2**31 + 21, trace)
    assert line["correct"] and not failed_numbers(line)
    assert set(line["compared"]) == LIMITS
    assert all(c["limit"] == 0 for c in line["compared"].values())
    assert line["window_compilations"] == 0
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so
    if trace:
        assert set(NEW) <= set(line["metrics"])
        assert "span_unaccounted_pct" in line["metrics"]
        assert line["metrics"]["restore_host_us_per_aggregate"]["value"] > 0
    else:
        assert set(line["metrics"]) == {"rebuild_events_per_s", "setup_s"}
    # the segment and its wire cache went with the run
    left = set(os.listdir(os.environ.get("TMPDIR", "/tmp"))) - before
    assert not [n for n in left if n.startswith("surge-cart-restore-")]


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 12345])
def test_restore_control_is_not_correct(seed, capsys):
    assert control.main(["--workload", CELL, "--seed", str(seed),
                         "--rehearse"]) == 0  # 0: judged not correct
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not line["control_correct"]
    # two carts lost their last event and two their id at this size: each is
    # a state that differs; the lost events are counted; no id is missing
    compared = {n: c["value"] for n, c in line["compared"].items()}
    assert compared["states_wrong"] == 4
    assert compared["events_unaccounted"] == 2
    assert compared["store_missing"] == compared["store_extra"] == 0


def test_restore_fault_a_cart_stored_without_its_id(capsys, monkeypatch):
    """What the restore did before the model's hook: every cart differs."""
    from surge_tpu.models import shopping_cart

    monkeypatch.delattr(shopping_cart.CartModel, "decode_state")
    line = run_cell(capsys, 12)
    assert not line["correct"]
    assert {"states_wrong", "scalar_sample_wrong"} <= failed_numbers(line)
    assert line["compared"]["states_wrong"]["value"] % 2000 == 0


@pytest.mark.parametrize("victim", ["value", "key"])
def test_restore_fault_a_put_altered_where_it_is_made(capsys, monkeypatch,
                                                     victim):
    from surge_tpu.store import InMemoryKeyValueStore

    sound = InMemoryKeyValueStore.put

    def altered(self, key, value):
        if key == "cart-0000017":
            if victim == "key":
                key = "cart-9999999"
            else:
                value = value.replace(b'"version": ', b'"version": 1')
        sound(self, key, value)

    monkeypatch.setattr(InMemoryKeyValueStore, "put", altered)
    line = run_cell(capsys, 12)
    assert not line["correct"]
    assert failed_numbers(line) >= ({"states_wrong"} if victim == "value"
                                    else {"store_missing", "store_extra"})


# --- the manifest ---------------------------------------------------------------------

def test_the_manifest_is_clean_and_lists_the_cell():
    assert harness.main(["--check"]) == 0
    man, cell, config, _traffic = harness.load_cell(CELL)
    # four chips for steadiness alone: the restore itself folds on one
    assert_listed(man, CELL, "cart-segment-restore", 4, "rebuild-loop")
    assert config["chips"] == 1
    assert config["sizes"] == {"aggregates": 1_000_000, "events": 100_000_000,
                               "chunk_aggregates": 65536}
    assert config["reduced"] == ["chips"] and config["driver"] == "cart_restore"
    cart = harness.load_cell("rebuild-cart-ragged")[2]
    # cart-rebuild's laws, and one key more: the lengths drawn from a seed of
    # their own, the run's seed ordering them within a chunk
    assert config["corpus"] == dict(cart["corpus"], lengths_seed=0)
    assert config["work"] == cart["work"]
    assert len(config["source"]) <= 200
    # found by name, not by place: a later PR appends after these
    assert cell["config"] in [c["name"] for c in man["configs"]]
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert CELL in e2e["rebuild_events_per_s"]["workloads"]
    layers = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        m = layers[name]
        assert m["workloads"][0] == CELL and m["layer"] == "Segment restore"
        assert m["moves"] == "rebuild_events_per_s"
    reported = [m["name"] for m in man["per_layer"]
                if harness.reports(m, CELL, man)]
    assert set(reported) >= {
        "device_idle_pct.rebuild", "fold_roofline", "pad_ratio",
        "h2d_share_pct", "fetch_wait_pct", "span_unaccounted_pct",
        "h2d_pad_ratio", "pull_bytes_ratio", "small_tile_slots_pct",
        "h2d_put_gbps", "replay_host_pct", "fetch_ratio"} | set(NEW)
    # readers with nothing to read in a restore (no pack, one root stage)
    assert not set(reported) & {
        "pack_share_pct", "encode_words_pct", "pack_sys_pct", "h2d_put_cores",
        "rebuild_slowest_ratio", "host_preempts_per_rebuild"}
    # additions only: the cell joined each list after the fold's cells
    for name in reported:
        listed = layers[name]["workloads"]
        older = [c for c in listed if c in FOLD_CELLS]
        assert listed[:len(older)] == older
