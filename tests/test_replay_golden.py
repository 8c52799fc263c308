"""Golden-value replay tests: TPU batched fold ≡ scalar CPU fold (SURVEY.md §4
implication: "golden-value replay tests comparing TPU batched fold vs. scalar CPU fold").
"""

import random

import jax
import numpy as np
import pytest

from surge_tpu.codec import decode_states, encode_events
from surge_tpu.config import Config
from surge_tpu.engine.model import fold_events
from surge_tpu.models import bank_account, counter, shopping_cart
from surge_tpu.replay import ReplayEngine


def scalar_fold_states(model, logs, agg_ids=None):
    out = []
    for i, log in enumerate(logs):
        state = model.initial_state(agg_ids[i] if agg_ids else str(i))
        out.append(fold_events(model, state, log))
    return out


def random_counter_logs(n, max_len, seed=0):
    rng = random.Random(seed)
    logs = []
    for i in range(n):
        seq = 0
        log = []
        for _ in range(rng.randrange(max_len + 1)):
            seq += 1
            kind = rng.randrange(3)
            if kind == 0:
                log.append(counter.CountIncremented(str(i), rng.randrange(1, 4), seq))
            elif kind == 1:
                log.append(counter.CountDecremented(str(i), rng.randrange(1, 4), seq))
            else:
                log.append(counter.NoOpEvent(str(i), seq))
        logs.append(log)
    return logs


def test_counter_dense_golden():
    model = counter.CounterModel()
    logs = random_counter_logs(37, 19, seed=1)
    expected = scalar_fold_states(model, logs)

    eng = ReplayEngine(model.replay_spec())
    enc = encode_events(model.replay_spec().registry, logs)
    res = eng.replay_encoded(enc)

    for i, exp in enumerate(expected):
        exp_count = exp.count if exp else 0
        exp_version = exp.version if exp else 0
        assert int(res.states["count"][i]) == exp_count, f"aggregate {i}"
        assert int(res.states["version"][i]) == exp_version, f"aggregate {i}"


def test_counter_time_chunked_golden():
    """Chunked streaming scan must agree with single-scan results."""
    model = counter.CounterModel()
    logs = random_counter_logs(16, 50, seed=2)
    expected = scalar_fold_states(model, logs)

    cfg = Config(overrides={"surge.replay.time-chunk": 7})
    eng = ReplayEngine(model.replay_spec(), config=cfg)
    enc = encode_events(model.replay_spec().registry, logs)
    res = eng.replay_encoded(enc)
    for i, exp in enumerate(expected):
        assert int(res.states["count"][i]) == (exp.count if exp else 0)


def test_bank_account_golden_with_vocab():
    model = bank_account.BankAccountModel()
    vocab = bank_account.Vocab()
    rng = random.Random(3)
    logs, enc_logs = [], []
    for i in range(25):
        log = []
        if rng.random() < 0.8:
            log.append(bank_account.BankAccountCreated(str(i), f"owner{i}", f"sec{i}", 100.0))
            bal = 100.0
            for _ in range(rng.randrange(6)):
                # quarters only: exactly representable in f32
                delta = rng.randrange(1, 40) * 0.25
                if rng.random() < 0.5 or bal < delta:
                    bal += delta
                    log.append(bank_account.BankAccountUpdated(str(i), bal))
                else:
                    bal -= delta
                    log.append(bank_account.BankAccountUpdated(str(i), bal))
        else:
            # orphan update on a never-created account: must stay None
            log.append(bank_account.BankAccountUpdated(str(i), 42.0))
        logs.append(log)
        enc_logs.append([bank_account.encode_event(vocab, e) for e in log])

    expected = scalar_fold_states(model, logs)
    spec = model.replay_spec()
    eng = ReplayEngine(spec)
    enc = encode_events(spec.registry, enc_logs)
    res = eng.replay_encoded(enc)

    for i, exp in enumerate(expected):
        rec = bank_account.EncodedAccountState(
            created=bool(res.states["created"][i]),
            owner_code=int(res.states["owner_code"][i]),
            security_code_code=int(res.states["security_code_code"][i]),
            balance=float(res.states["balance"][i]))
        got = bank_account.decode_state(vocab, str(i), rec)
        if exp is None:
            assert got is None, f"aggregate {i}"
        else:
            assert got is not None
            assert got.account_owner == exp.account_owner
            assert got.security_code == exp.security_code
            assert got.balance == pytest.approx(exp.balance)


def random_cart_logs(n, seed=0, max_len=30):
    rng = random.Random(seed)
    model = shopping_cart.CartModel()
    logs = []
    for i in range(n):
        # generate through the command path so logs are semantically valid
        state = None
        log = []
        for _ in range(rng.randrange(max_len)):
            if state is not None and state.checked_out:
                break
            kind = rng.random()
            try:
                if kind < 0.6:
                    cmd = shopping_cart.AddItem(str(i), rng.randrange(1, 100),
                                                rng.randrange(1, 4), rng.randrange(100, 5000))
                elif kind < 0.9:
                    cmd = shopping_cart.RemoveItem(str(i), rng.randrange(1, 100),
                                                   rng.randrange(1, 3), rng.randrange(100, 5000))
                else:
                    cmd = shopping_cart.Checkout(str(i))
                events = model.process_command(state, cmd)
            except Exception:
                continue
            for ev in events:
                state = model.handle_event(state, ev)
                log.append(ev)
        logs.append(log)
    return logs


def test_shopping_cart_ragged_golden():
    model = shopping_cart.CartModel()
    logs = random_cart_logs(53, seed=5)
    expected = scalar_fold_states(model, logs)

    cfg = Config(overrides={"surge.replay.length-buckets": "4,8,16,32"})
    eng = ReplayEngine(model.replay_spec(), config=cfg)
    res = eng.replay_ragged(logs)

    assert res.num_aggregates == len(logs)
    assert res.num_events == sum(len(l) for l in logs)
    for i, exp in enumerate(expected):
        assert int(res.states["item_count"][i]) == (exp.item_count if exp else 0)
        assert int(res.states["total_cents"][i]) == (exp.total_cents if exp else 0)
        assert bool(res.states["checked_out"][i]) == (exp.checked_out if exp else False)


def test_replay_stream_carries_state_across_chunks():
    model = counter.CounterModel()
    logs = random_counter_logs(8, 40, seed=7)
    expected = scalar_fold_states(model, logs)
    spec = model.replay_spec()

    # split each log into time windows of 10 and encode each window separately
    def chunks():
        t = max(len(l) for l in logs)
        for start in range(0, t, 10):
            window = [l[start:start + 10] for l in logs]
            yield encode_events(spec.registry, window, pad_to=10)

    eng = ReplayEngine(spec)
    res = eng.replay_stream(chunks(), batch=len(logs))
    for i, exp in enumerate(expected):
        assert int(res.states["count"][i]) == (exp.count if exp else 0)
    assert res.num_events == sum(len(l) for l in logs)


# mesh tests take the tests/conftest.py `mesh8` fixture instead of the old
# `skipif device_count < 8` marker: a broken device forcing must FAIL the
# multi-device proofs loudly, never silently skip them out of tier-1


def test_mesh_sharded_replay_golden(mesh8):
    """B sharded over an 8-device CPU mesh must give identical results."""
    mesh = mesh8
    model = counter.CounterModel()
    logs = random_counter_logs(100, 12, seed=9)
    expected = scalar_fold_states(model, logs)

    eng = ReplayEngine(model.replay_spec(), mesh=mesh)
    enc = encode_events(model.replay_spec().registry, logs)
    res = eng.replay_encoded(enc)
    for i, exp in enumerate(expected):
        assert int(res.states["count"][i]) == (exp.count if exp else 0)
        assert int(res.states["version"][i]) == (exp.version if exp else 0)


def test_mesh_sharded_resident_replay_golden(mesh8):
    """The resident tile-loop design across an 8-device CPU mesh: identical
    states to the scalar fold, in original order, via one shard_map dispatch
    per granularity (no collectives — lanes are independent)."""
    from surge_tpu.codec.tensor import encode_events_columnar

    mesh = mesh8
    model = counter.CounterModel()
    logs = random_counter_logs(517, 40, seed=13)  # ragged, not device-aligned
    expected = scalar_fold_states(model, logs)

    cfg = Config(overrides={"surge.replay.batch-size": 128,
                            "surge.replay.time-chunk": 16})
    eng = ReplayEngine(model.replay_spec(), config=cfg, mesh=mesh)
    colev = encode_events_columnar(model.replay_spec().registry, logs)
    sharded = eng.prepare_resident_sharded(colev)
    res = eng.replay_resident_sharded(sharded)
    assert res.num_events == sum(len(l) for l in logs)
    for i, exp in enumerate(expected):
        assert int(res.states["count"][i]) == (exp.count if exp else 0), i
        assert int(res.states["version"][i]) == (exp.version if exp else 0), i

    # resume: fold the first half, carry into the second half
    cut = [len(l) // 2 for l in logs]
    first = encode_events_columnar(model.replay_spec().registry,
                                   [l[:c] for l, c in zip(logs, cut)])
    second = encode_events_columnar(model.replay_spec().registry,
                                    [l[c:] for l, c in zip(logs, cut)])
    r1 = eng.replay_resident_sharded(eng.prepare_resident_sharded(first))
    r2 = eng.replay_resident_sharded(eng.prepare_resident_sharded(second),
                                     init_carry=r1.states,
                                     ordinal_base=np.asarray(cut, np.int32))
    for i, exp in enumerate(expected):
        assert int(r2.states["count"][i]) == (exp.count if exp else 0), i


def test_mesh_sharded_resident_bank_account_side_columns(mesh8):
    """bank_account on the sharded resident path: float side columns ride the
    per-device slabs, and handlers returning literal columns (created=True)
    must compile under shard_map (VMA divergence across switch branches)."""
    from surge_tpu.codec.tensor import encode_events_columnar

    mesh = mesh8
    model = bank_account.BankAccountModel()
    vocab = bank_account.Vocab()
    rng = random.Random(4)
    logs, enc_logs = [], []
    for i in range(85):
        log = [bank_account.BankAccountCreated(str(i), f"o{i}", "s", 100.0)]
        bal = 100.0
        for _ in range(rng.randrange(0, 8)):
            bal += rng.randrange(1, 20) * 0.25
            log.append(bank_account.BankAccountUpdated(str(i), bal))
        logs.append(log)
        enc_logs.append([bank_account.encode_event(vocab, e) for e in log])
    expected = scalar_fold_states(model, logs)

    eng = ReplayEngine(model.replay_spec(), config=Config(overrides={
        "surge.replay.batch-size": 64, "surge.replay.time-chunk": 8}),
        mesh=mesh)
    colev = encode_events_columnar(model.replay_spec().registry, enc_logs)
    res = eng.replay_resident_sharded(eng.prepare_resident_sharded(colev))
    for i, exp in enumerate(expected):
        assert float(res.states["balance"][i]) == pytest.approx(exp.balance), i
        assert bool(res.states["created"][i]), i


def test_mesh_sharded_resident_small_tiles_fold_once(mesh8):
    """800 single-event lanes on 8 devices: per device 100 active lanes with
    bs=128/bs_small=64 ⇒ every window needs TWO small tiles. Each event must
    fold exactly once (a small tile dispatched through the big-bs program
    would overlap/clamp its lane slices and double-fold)."""
    from surge_tpu.codec.tensor import encode_events_columnar

    mesh = mesh8
    model = counter.CounterModel()
    logs = [[counter.CountIncremented(f"a{i}", 1, 1)] for i in range(800)]

    cfg = Config(overrides={"surge.replay.batch-size": 128,
                            "surge.replay.time-chunk": 16})
    eng = ReplayEngine(model.replay_spec(), config=cfg, mesh=mesh)
    colev = encode_events_columnar(model.replay_spec().registry, logs)
    res = eng.replay_resident_sharded(eng.prepare_resident_sharded(colev))
    assert all(int(c) == 1 for c in res.states["count"]), \
        np.unique(np.asarray(res.states["count"]))


def test_mesh_sharded_resident_assoc_golden(mesh8):
    """The assoc tree fold under shard_map (``tile-backend = assoc`` inside
    the sharded fold's per-device tile loop): byte-identical states to the
    scalar fold, including a resumed fold with ordinal bases."""
    from surge_tpu.codec.tensor import encode_events_columnar

    model = counter.CounterModel()
    logs = random_counter_logs(233, 37, seed=17)  # ragged, not device-aligned
    expected = scalar_fold_states(model, logs)

    cfg = Config(overrides={"surge.replay.batch-size": 128,
                            "surge.replay.time-chunk": 16,
                            "surge.replay.tile-backend": "assoc"})
    eng = ReplayEngine(model.replay_spec(), config=cfg, mesh=mesh8)
    assert eng.tile_backend == "assoc"
    spec = model.replay_spec()
    colev = encode_events_columnar(spec.registry, logs)
    res = eng.replay_resident_sharded(eng.prepare_resident_sharded(colev))
    for i, exp in enumerate(expected):
        assert int(res.states["count"][i]) == (exp.count if exp else 0), i
        assert int(res.states["version"][i]) == (exp.version if exp else 0), i

    # resume: the tile's ordinal bases must continue derived ordinals
    cut = [len(l) // 2 for l in logs]
    first = encode_events_columnar(spec.registry,
                                   [l[:c] for l, c in zip(logs, cut)])
    second = encode_events_columnar(spec.registry,
                                    [l[c:] for l, c in zip(logs, cut)])
    r1 = eng.replay_resident_sharded(eng.prepare_resident_sharded(first))
    r2 = eng.replay_resident_sharded(eng.prepare_resident_sharded(second),
                                     init_carry=r1.states,
                                     ordinal_base=np.asarray(cut, np.int32))
    for i, exp in enumerate(expected):
        assert int(r2.states["count"][i]) == (exp.count if exp else 0), i
        assert int(r2.states["version"][i]) == (exp.version if exp else 0), i


def test_resume_from_snapshot_carry():
    """Replay can resume from checkpointed states (watermark semantics, SURVEY §5.4)."""
    model = counter.CounterModel()
    logs = random_counter_logs(10, 20, seed=11)
    spec = model.replay_spec()
    eng = ReplayEngine(spec)

    # fold first half, decode states, re-encode as carry, fold second half
    half = [l[:len(l) // 2] for l in logs]
    rest = [l[len(l) // 2:] for l in logs]
    res1 = eng.replay_encoded(encode_events(spec.registry, half))
    mid_states = decode_states(spec.registry.state, res1.states)
    carry = eng.carry_from_states(mid_states)
    res2 = eng.replay_encoded(encode_events(spec.registry, rest), init_carry=carry)

    expected = scalar_fold_states(model, logs)
    for i, exp in enumerate(expected):
        assert int(res2.states["count"][i]) == (exp.count if exp else 0)
