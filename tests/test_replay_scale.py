"""Scale-discipline tests: B-chunking, fixed-width streaming, donation safety,
columnar encode — the VERDICT r1 "weak" items around HBM budget and compile count."""

import numpy as np
import pytest

from surge_tpu.codec import encode_events
from surge_tpu.codec.tensor import (
    ColumnarEvents,
    columnar_to_batch,
    encode_events_columnar,
)
from surge_tpu.config import Config, default_config
from surge_tpu.engine.model import fold_events
from surge_tpu.models import counter
from surge_tpu.replay import ReplayEngine

from tests.test_replay_golden import random_counter_logs, scalar_fold_states


def test_columnar_encode_matches_object_encode():
    logs = random_counter_logs(23, 17, seed=21)
    reg = counter.make_registry()
    enc_obj = encode_events(reg, logs)
    colev = encode_events_columnar(reg, logs)
    enc_col = columnar_to_batch(colev)
    np.testing.assert_array_equal(enc_obj.type_ids, enc_col.type_ids)
    np.testing.assert_array_equal(enc_obj.lengths, enc_col.lengths)
    for name in enc_obj.cols:
        np.testing.assert_array_equal(enc_obj.cols[name], enc_col.cols[name])


def test_columnar_scatter_pure_numpy_path():
    """Synthetic columnar log (no Python objects at all) folds correctly."""
    rng = np.random.default_rng(0)
    b, n = 50, 4000
    agg_idx = np.sort(rng.integers(0, b, size=n).astype(np.int32))
    type_ids = rng.integers(0, 2, size=n).astype(np.int32)  # inc / dec
    inc = np.where(type_ids == 0, rng.integers(1, 4, size=n), 0).astype(np.int32)
    dec = np.where(type_ids == 1, rng.integers(1, 4, size=n), 0).astype(np.int32)
    seq = np.ones(n, dtype=np.int32)
    colev = ColumnarEvents(num_aggregates=b, agg_idx=agg_idx, type_ids=type_ids,
                           cols={"increment_by": inc, "decrement_by": dec,
                                 "sequence_number": seq})
    eng = ReplayEngine(counter.make_replay_spec())
    res = eng.replay_columnar(colev)
    # ground truth via numpy segment sums
    expected = (np.bincount(agg_idx, weights=inc, minlength=b)
                - np.bincount(agg_idx, weights=dec, minlength=b))
    np.testing.assert_array_equal(res.states["count"], expected.astype(np.int32))
    assert res.num_events == n


def test_b_chunking_bounds_device_batch():
    """batch-size smaller than B: replay must chunk and still be exact."""
    model = counter.CounterModel()
    logs = random_counter_logs(100, 15, seed=22)
    expected = scalar_fold_states(model, logs)
    cfg = Config(overrides={"surge.replay.batch-size": 16, "surge.replay.time-chunk": 8})
    eng = ReplayEngine(model.replay_spec(), config=cfg)
    assert eng.batch_size == 16  # lane multiple of 8 on single device
    res = eng.replay_encoded(encode_events(model.replay_spec().registry, logs))
    for i, exp in enumerate(expected):
        assert int(res.states["count"][i]) == (exp.count if exp else 0)
        assert int(res.states["version"][i]) == (exp.version if exp else 0)
    # one compiled program serves all (B-chunk, T-chunk) windows
    assert eng.num_compiles() == 1


def test_stream_compiled_programs_bounded_by_ladder():
    """Varying-width stream chunks must not compile per input width: padded widths
    come from the fixed time-chunk + power-of-two tail ladder, so the program
    count is bounded by ``1 + log2(chunk/min-time-window)`` no matter how many
    distinct widths arrive."""
    model = counter.CounterModel()
    logs = random_counter_logs(8, 33, seed=23)
    spec = model.replay_spec()
    cfg = Config(overrides={"surge.replay.time-chunk": 16})
    eng = ReplayEngine(spec, config=cfg)

    def chunks():
        t = max(len(l) for l in logs)
        # deliberately ragged window widths: 13, then 7s
        bounds = [0, 13]
        while bounds[-1] < t:
            bounds.append(min(bounds[-1] + 7, t))
        for s, e in zip(bounds, bounds[1:]):
            yield encode_events(spec.registry, [l[s:e] for l in logs], pad_to=e - s)

    res = eng.replay_stream(chunks(), batch=len(logs))
    expected = scalar_fold_states(model, logs)
    for i, exp in enumerate(expected):
        assert int(res.states["count"][i]) == (exp.count if exp else 0)
    # widths 13 and 7 map onto ladder programs {16, 8}, never one per width
    assert eng.num_compiles() <= 2

    # with the ladder disabled every window pads to the full time-chunk: exactly
    # one program regardless of input widths (the round-3 contract)
    eng2 = ReplayEngine(spec, config=Config(overrides={
        "surge.replay.time-chunk": 16, "surge.replay.min-time-window": 0}))
    res2 = eng2.replay_stream(chunks(), batch=len(logs))
    for i, exp in enumerate(expected):
        assert int(res2.states["count"][i]) == (exp.count if exp else 0)
    assert eng2.num_compiles() == 1


def test_external_carry_not_donated():
    """ADVICE r1 (medium): caller-supplied init_carry must survive the fold, even when
    batch is exactly lane-aligned (no padding copy)."""
    model = counter.CounterModel()
    spec = model.replay_spec()
    eng = ReplayEngine(spec)
    b = 8  # exactly the lane multiple: the r1 bug path
    logs = random_counter_logs(b, 10, seed=24)
    enc = encode_events(spec.registry, logs)
    carry = {"count": np.full(b, 5, dtype=np.int32),
             "version": np.zeros(b, dtype=np.int32)}
    res1 = eng.replay_encoded(enc, init_carry=carry)
    # reuse the same carry — r1 raised "Buffer has been deleted or donated" here
    res2 = eng.replay_encoded(enc, init_carry=carry)
    np.testing.assert_array_equal(res1.states["count"], res2.states["count"])
    np.testing.assert_array_equal(np.asarray(carry["count"]), np.full(b, 5))


def test_out_of_range_type_id_is_padding():
    """ADVICE r1: corrupt positive type_ids must carry state through, not dispatch."""
    spec = counter.make_replay_spec()
    eng = ReplayEngine(spec)
    b = 8
    colev = ColumnarEvents(
        num_aggregates=b,
        agg_idx=np.repeat(np.arange(b, dtype=np.int32), 2),
        type_ids=np.tile(np.array([0, 99], dtype=np.int32), b),  # inc, then corrupt
        cols={"increment_by": np.ones(2 * b, dtype=np.int32),
              "decrement_by": np.zeros(2 * b, dtype=np.int32),
              "sequence_number": np.ones(2 * b, dtype=np.int32)})
    res = eng.replay_columnar(colev)
    np.testing.assert_array_equal(res.states["count"], np.ones(b, dtype=np.int32))


def test_unserializable_event_tensor_parity():
    """ADVICE r1: UnserializableEvent folds on the tensor path (version bump)."""
    model = counter.CounterModel()
    logs = [[counter.CountIncremented("0", 2, 1),
             counter.UnserializableEvent("0", 2, "boom")]]
    expected = scalar_fold_states(model, logs)[0]
    eng = ReplayEngine(model.replay_spec())
    res = eng.replay_encoded(encode_events(model.replay_spec().registry, logs))
    assert int(res.states["count"][0]) == expected.count == 2
    assert int(res.states["version"][0]) == expected.version == 2


def test_config_with_overrides_kwargs():
    """ADVICE r1: kwarg overrides must canonicalize to dotted/dashed keys."""
    cfg = default_config().with_overrides(surge_replay_time_chunk=99)
    assert cfg.get_int("surge.replay.time-chunk") == 99
    cfg2 = default_config().with_overrides({"surge.replay.batch-size": 7})
    assert cfg2.get_int("surge.replay.batch-size") == 7


def test_columnar_chunked_skewed_lengths():
    """replay_columnar densifies per B-chunk: one huge log must not blow up padding
    for other chunks (bounded host memory)."""
    rng = np.random.default_rng(3)
    b = 40
    parts = []
    for i in range(b):
        ln = 500 if i == 0 else int(rng.integers(1, 10))
        parts.append(np.full(ln, i, dtype=np.int32))
    agg_idx = np.concatenate(parts)
    n = agg_idx.size
    type_ids = rng.integers(0, 2, size=n).astype(np.int32)
    inc = np.where(type_ids == 0, 1, 0).astype(np.int32)
    dec = np.where(type_ids == 1, 1, 0).astype(np.int32)
    colev = ColumnarEvents(b, agg_idx, type_ids,
                           {"increment_by": inc, "decrement_by": dec,
                            "sequence_number": np.ones(n, dtype=np.int32)})
    cfg = Config(overrides={"surge.replay.batch-size": 8, "surge.replay.time-chunk": 32})
    eng = ReplayEngine(counter.make_replay_spec(), config=cfg)
    res = eng.replay_columnar(colev)
    expected = (np.bincount(agg_idx, weights=inc, minlength=b)
                - np.bincount(agg_idx, weights=dec, minlength=b)).astype(np.int32)
    np.testing.assert_array_equal(res.states["count"], expected)
    # the 500-long log only inflates its own chunk: padding ≤ chunk0(512*8) + others(32*8 each)
    assert res.padded_events <= 8 * 512 + (b // 8 - 1) * 8 * 32 + 8 * 32


def test_length_sorted_chunking_cuts_padding_and_stays_exact():
    """VERDICT r3 next #2: with a skewed length distribution, length-sorted
    B-chunking plus the tail-window ladder must bring pad_ratio near 1 while
    producing byte-identical states in the caller's original aggregate order."""
    rng = np.random.default_rng(7)
    b = 256
    # heavy skew: most logs short, a few long — the distribution that produced
    # pad_ratio 6.29 unsorted at bench scale
    lens = np.where(rng.random(b) < 0.9,
                    rng.integers(1, 12, size=b),
                    rng.integers(200, 400, size=b)).astype(np.int64)
    order = rng.permutation(b)  # lengths deliberately interleaved
    lens = lens[order]
    parts = [np.full(lens[i], i, dtype=np.int32) for i in range(b)]
    agg_idx = np.concatenate(parts)
    n = agg_idx.size
    type_ids = rng.integers(0, 2, size=n).astype(np.int32)
    inc = np.where(type_ids == 0, rng.integers(1, 4, size=n), 0).astype(np.int32)
    dec = np.where(type_ids == 1, 1, 0).astype(np.int32)
    cols = {"increment_by": inc, "decrement_by": dec,
            "sequence_number": np.ones(n, dtype=np.int32)}
    expected = (np.bincount(agg_idx, weights=inc, minlength=b)
                - np.bincount(agg_idx, weights=dec, minlength=b)).astype(np.int32)

    cfg = Config(overrides={"surge.replay.batch-size": 32,
                            "surge.replay.time-chunk": 64})
    eng = ReplayEngine(counter.make_replay_spec(), config=cfg)
    res = eng.replay_columnar(ColumnarEvents(b, agg_idx, type_ids, dict(cols)))
    np.testing.assert_array_equal(res.states["count"], expected)
    ratio_sorted = res.padded_events / n

    off = Config(overrides={"surge.replay.batch-size": 32,
                            "surge.replay.time-chunk": 64,
                            "surge.replay.sort-by-length": False,
                            "surge.replay.min-time-window": 0})
    eng_off = ReplayEngine(counter.make_replay_spec(), config=off)
    res_off = eng_off.replay_columnar(ColumnarEvents(b, agg_idx, type_ids, dict(cols)))
    np.testing.assert_array_equal(res_off.states["count"], expected)
    ratio_unsorted = res_off.padded_events / n

    assert ratio_sorted < ratio_unsorted / 2  # the lever actually levers
    assert ratio_sorted < 2.0


def test_resident_corpus_replay_matches_streaming_and_scalar():
    """Resident-corpus replay (one flat upload + on-device gather) must
    produce byte-identical states to the streaming window path and the scalar
    fold, in the caller's original aggregate order, while packing exactly
    wire_bytes_per_event() per event."""
    from surge_tpu.replay.corpus import synth_counter_corpus

    corpus = synth_counter_corpus(3000, 120_000, seed=17)  # unsorted order
    cfg = Config(overrides={"surge.replay.batch-size": 256,
                            "surge.replay.time-chunk": 32})
    eng = ReplayEngine(counter.make_replay_spec(), config=cfg)
    wire = eng.pack_resident(corpus.events)
    # 1 byte/event in the packed word + the guard tail (slice safety); the
    # device buffer is those rows bucketed to a power of two
    from surge_tpu.replay.engine import _WIRE_GUARD_MIN, _bucket_len
    guard = max(eng.resident_tile_width(), _WIRE_GUARD_MIN)
    assert wire.packed_shape == (corpus.num_events + guard, 1)
    resident = eng.upload_resident(wire)
    assert resident.flat_wire.shape == (
        _bucket_len(corpus.num_events + guard), 1)
    res = eng.replay_resident(resident)
    np.testing.assert_array_equal(res.states["count"], corpus.expected_count)
    np.testing.assert_array_equal(res.states["version"], corpus.expected_version)
    assert res.num_events == corpus.num_events

    # streaming path agreement (same engine, same config)
    res2 = eng.replay_columnar(corpus.events)
    for name in res.states:
        np.testing.assert_array_equal(res.states[name], res2.states[name])


def test_resident_plan_small_tile_divides_big():
    """bs_small must divide bs_big whatever the batch-size knob says: the
    narrow-tile walk steps in bs_small over a buffer padded only to a bs_big
    multiple, so a non-divisor's clamped last tile would silently re-apply a
    round's events to already-covered lanes (ADVICE r4). The awkward
    batch-sizes here exercise the guard AND the replay must stay exact."""
    from surge_tpu.replay.corpus import synth_counter_corpus

    corpus = synth_counter_corpus(1500, 60_000, seed=23)
    for batch in (1007, 72):
        cfg = Config(overrides={"surge.replay.batch-size": batch,
                                "surge.replay.time-chunk": 32})
        eng = ReplayEngine(counter.make_replay_spec(), config=cfg)
        resident = eng.prepare_resident(corpus.events)
        plan = eng._resident_plan(resident)
        assert plan.bs_big % plan.bs_small == 0, (batch, plan)
        if plan.small_i0.size:
            # every narrow tile stays inside the padded lane buffer unclamped
            assert int(plan.small_i0.max()) + plan.bs_small <= resident.b_pad
        res = eng.replay_resident(resident)
        np.testing.assert_array_equal(res.states["count"], corpus.expected_count)
        np.testing.assert_array_equal(res.states["version"],
                                      corpus.expected_version)


def test_resident_wire_save_load_roundtrip(tmp_path):
    """pack_resident -> save -> mmap load -> upload must replay identically to
    the direct prepare_resident path (the cold-start-from-segment flow)."""
    from surge_tpu.replay.corpus import synth_counter_corpus
    from surge_tpu.replay.engine import ResidentWire

    corpus = synth_counter_corpus(800, 40_000, seed=9)
    cfg = Config(overrides={"surge.replay.batch-size": 128,
                            "surge.replay.time-chunk": 32})
    eng = ReplayEngine(counter.make_replay_spec(), config=cfg)
    wire = eng.pack_resident(corpus.events)
    wire.save(str(tmp_path / "wire"))
    loaded = ResidentWire.load(str(tmp_path / "wire"))
    res = eng.replay_resident(eng.upload_resident(loaded))
    np.testing.assert_array_equal(res.states["count"], corpus.expected_count)
    np.testing.assert_array_equal(res.states["version"], corpus.expected_version)

    # an engine whose tile width exceeds the packed guard must refuse the wire
    # (its slab slices could read past the buffer)
    big = ReplayEngine(counter.make_replay_spec(), config=Config(overrides={
        "surge.replay.batch-size": 256,
        "surge.replay.time-chunk": 32768,
        "surge.replay.resident-slab-cap-mb": 100000}))
    assert big.resident_tile_width() > loaded.guard
    with pytest.raises(ValueError):
        big.upload_resident(loaded)


def test_streamed_resident_replay_matches_plain():
    """replay_resident_streamed (piecewise upload+dispatch, one sync pass)
    must equal the plain resident replay and the closed form, including
    resume, across awkward segment counts."""
    from surge_tpu.replay.corpus import synth_counter_corpus

    corpus = synth_counter_corpus(3100, 130_000, seed=19)
    eng = ReplayEngine(counter.make_replay_spec(), config=Config(overrides={
        "surge.replay.batch-size": 256, "surge.replay.time-chunk": 32}))
    wire = eng.pack_resident(corpus.events)
    plain = eng.replay_resident(eng.upload_resident(wire))
    for segments in (2, 3, 7):
        streamed = eng.replay_resident_streamed(wire, segments=segments)
        for name in plain.states:
            np.testing.assert_array_equal(streamed.states[name],
                                          plain.states[name],
                                          err_msg=f"segments={segments}")
    np.testing.assert_array_equal(plain.states["count"], corpus.expected_count)

    # resume mid-log through the streamed path
    ev = corpus.events
    n = ev.num_events
    half_mask = np.arange(n) < n // 2
    import dataclasses

    def subset(mask):
        return dataclasses.replace(
            ev, agg_idx=ev.agg_idx[mask], type_ids=ev.type_ids[mask],
            cols={k: v[mask] for k, v in ev.cols.items()})

    first = eng.pack_resident(subset(half_mask))
    second = eng.pack_resident(subset(~half_mask))
    r1 = eng.replay_resident_streamed(first, segments=3)
    counts1 = np.bincount(ev.agg_idx[half_mask], minlength=ev.num_aggregates)
    r2 = eng.replay_resident_streamed(second, segments=3,
                                      init_carry=r1.states,
                                      ordinal_base=counts1.astype(np.int32))
    np.testing.assert_array_equal(r2.states["count"], corpus.expected_count)
    np.testing.assert_array_equal(r2.states["version"], corpus.expected_version)

    # segments=1 degrades to the plain path
    one = eng.replay_resident_streamed(wire, segments=1)
    for name in plain.states:
        np.testing.assert_array_equal(one.states[name], plain.states[name])


def test_assoc_tile_matches_switch_scan():
    """The assoc tree tile (``tile-backend = assoc``) must be state-identical
    to the streaming window path's ``lax.switch`` scan and to the closed
    form."""
    from surge_tpu.replay.corpus import synth_counter_corpus

    corpus = synth_counter_corpus(1200, 60_000, seed=23)
    eng = ReplayEngine(counter.make_replay_spec(), config=Config(overrides={
        "surge.replay.batch-size": 256, "surge.replay.time-chunk": 32,
        "surge.replay.tile-backend": "assoc"}))
    assert eng.tile_backend == "assoc"
    r1 = eng.replay_resident(eng.prepare_resident(corpus.events))
    r2 = eng.replay_columnar(corpus.events)
    for name in r1.states:
        np.testing.assert_array_equal(r1.states[name], r2.states[name])
    np.testing.assert_array_equal(r1.states["count"], corpus.expected_count)


def test_resident_len_bucketing_reuses_programs_across_sizes():
    """With the default pow2 length bucketing, replaying two different-sized
    corpora (e.g. consecutive restore chunks) whose buffers land in the same
    bucket must not add a second compiled-program signature."""
    from surge_tpu.replay.corpus import synth_counter_corpus

    eng = ReplayEngine(counter.make_replay_spec(), config=Config(overrides={
        "surge.replay.batch-size": 128, "surge.replay.time-chunk": 32}))
    c1 = synth_counter_corpus(500, 20_000, seed=1)
    c2 = synth_counter_corpus(470, 23_000, seed=2)
    r1 = eng.replay_resident(eng.prepare_resident(c1.events))
    n_after_first = eng.num_compiles()
    r2 = eng.replay_resident(eng.prepare_resident(c2.events))
    assert eng.num_compiles() == n_after_first, "same bucket must reuse programs"
    np.testing.assert_array_equal(r1.states["count"], c1.expected_count)
    np.testing.assert_array_equal(r2.states["count"], c2.expected_count)


def test_resident_wire_layout_mismatch_refused(tmp_path):
    """A wire packed under a different schema layout must be refused at upload
    (silent misaligned decode would fold wrong states)."""
    import dataclasses

    from surge_tpu.models import bank_account as ba
    from surge_tpu.replay.corpus import synth_counter_corpus
    from surge_tpu.replay.engine import ResidentWire

    corpus = synth_counter_corpus(100, 2_000, seed=4)
    eng = ReplayEngine(counter.make_replay_spec(), config=Config(overrides={
        "surge.replay.batch-size": 64}))
    wire = eng.pack_resident(corpus.events)
    # forge a layout drift: pretend the wire was packed with 2 bytes/event
    forged = dataclasses.replace(
        wire, packed=np.repeat(wire.packed, 2, axis=1))
    with pytest.raises(ValueError, match="layout mismatch"):
        eng.upload_resident(forged)
    # same byte count but different BIT layout (field shifts moved) must also
    # be refused — the fingerprint pins positions, not just widths
    drifted_layout = dict(wire.layout)
    drifted_layout["packed"] = [[n, d, b, s + 1]
                                for n, d, b, s in drifted_layout["packed"]]
    with pytest.raises(ValueError, match="layout mismatch"):
        eng.upload_resident(dataclasses.replace(wire, layout=drifted_layout))
    # and a different model's engine must refuse this wire's side columns
    beng = ReplayEngine(ba.BankAccountModel().replay_spec(),
                        config=Config(overrides={"surge.replay.batch-size": 64}))
    with pytest.raises(ValueError):
        beng.upload_resident(wire)


def test_resident_unsorted_skewed_plan_stays_chunk_local():
    """With sort-by-length disabled and one lane's log dwarfing the rest, the
    tile plan must stay bounded by each lane range's LOCAL max (the streaming
    path's bound), not schedule every range out to the global max."""
    from surge_tpu.codec.tensor import ColumnarEvents
    from surge_tpu.replay.corpus import synth_counter_corpus

    corpus = synth_counter_corpus(600, 6_000, seed=3)
    # graft a long tail onto ONE aggregate: 4000 extra increments on agg 7
    ev = corpus.events
    extra = 4000
    agg_idx = np.concatenate([ev.agg_idx, np.full(extra, 7, dtype=ev.agg_idx.dtype)])
    type_ids = np.concatenate([ev.type_ids, np.zeros(extra, dtype=ev.type_ids.dtype)])
    cols = {k: np.concatenate([v, np.ones(extra, dtype=v.dtype) if k == "increment_by"
                               else np.zeros(extra, dtype=v.dtype)])
            for k, v in ev.cols.items()}
    colev = ColumnarEvents(num_aggregates=600, agg_idx=agg_idx, type_ids=type_ids,
                           cols=cols, derived_cols=dict(ev.derived_cols))
    cfg = Config(overrides={"surge.replay.batch-size": 128,
                            "surge.replay.time-chunk": 32,
                            "surge.replay.sort-by-length": False})
    eng = ReplayEngine(counter.make_replay_spec(), config=cfg)
    resident = eng.prepare_resident(colev)
    plan = eng._resident_plan(resident)
    # only aggregate 7's range pays for the long log; the others stop at their
    # local max (~tens of events), so the slot bound is far below b×max_len
    assert plan.padded_slots < 600 * 4000 // 2
    res = eng.replay_resident(resident)
    scalar = eng.replay_columnar(colev)
    for name in res.states:
        np.testing.assert_array_equal(res.states[name], scalar.states[name])


def test_resident_replay_with_side_columns_and_resume():
    """bank_account has float side columns (they ride the flat side arrays);
    resume through init_carry/ordinal_base must continue derived ordinals."""
    from surge_tpu.models import bank_account as ba

    rng = np.random.default_rng(3)
    reg = ba.make_registry()
    logs = []
    for i in range(60):
        n = int(rng.integers(1, 12))
        evs = [ba.EncodedCreated(owner_code=i % 5, security_code_code=1,
                                 balance=np.float32(100.0))]
        for k in range(n):
            evs.append(ba.EncodedUpdated(new_balance=np.float32(
                100.0 + (k + 1) * 0.25)))
        logs.append(evs)
    colev = encode_events_columnar(reg, logs)
    cfg = Config(overrides={"surge.replay.batch-size": 16,
                            "surge.replay.time-chunk": 8})
    eng = ReplayEngine(ba.make_replay_spec(), config=cfg)
    resident = eng.prepare_resident(colev)
    res = eng.replay_resident(resident)
    ref = eng.replay_columnar(colev)
    for name in res.states:
        np.testing.assert_array_equal(res.states[name], ref.states[name])

    # split replay: fold first half of every log, then resume on the second
    from surge_tpu.replay.corpus import synth_counter_corpus

    corpus = synth_counter_corpus(64, 4000, seed=11)
    ev = corpus.events
    starts = np.zeros(corpus.num_aggregates + 1, dtype=np.int64)
    np.cumsum(corpus.lengths, out=starts[1:])
    first_len = corpus.lengths // 2
    keep = np.zeros(corpus.num_events, dtype=bool)
    for b in range(corpus.num_aggregates):
        keep[starts[b]: starts[b] + first_len[b]] = True

    def subset(mask):
        return ColumnarEvents(
            num_aggregates=corpus.num_aggregates, agg_idx=ev.agg_idx[mask],
            type_ids=ev.type_ids[mask],
            cols={k: v[mask] for k, v in ev.cols.items()},
            derived_cols=dict(ev.derived_cols))

    ceng = ReplayEngine(counter.make_replay_spec(), config=Config(overrides={
        "surge.replay.batch-size": 32, "surge.replay.time-chunk": 16}))
    r1 = ceng.replay_resident(ceng.prepare_resident(subset(keep)))
    r2 = ceng.replay_resident(
        ceng.prepare_resident(subset(~keep)),
        init_carry=r1.states,
        ordinal_base=first_len.astype(np.int32))
    np.testing.assert_array_equal(r2.states["count"], corpus.expected_count)
    np.testing.assert_array_equal(r2.states["version"], corpus.expected_version)


def test_resume_with_derived_ordinals_continues_sequence():
    """Checkpoint-resume over a derived-ordinal corpus: the second half's derived
    sequence numbers must continue from each aggregate's already-folded count
    (ordinal_base), not restart at 1 (which would corrupt version)."""
    import numpy as np

    from surge_tpu.models.counter import make_replay_spec
    from surge_tpu.replay.corpus import synth_counter_corpus
    from surge_tpu.replay.engine import ReplayEngine

    corpus = synth_counter_corpus(64, 4000, seed=11)
    ev = corpus.events  # aggregate-sorted flat columnar stream
    engine = ReplayEngine(make_replay_spec())

    # split each aggregate's log in half at the event level
    starts = np.zeros(corpus.num_aggregates + 1, dtype=np.int64)
    np.cumsum(corpus.lengths, out=starts[1:])
    first_len = corpus.lengths // 2
    keep_first = np.zeros(corpus.num_events, dtype=bool)
    for b in range(corpus.num_aggregates):
        keep_first[starts[b]: starts[b] + first_len[b]] = True

    from surge_tpu.codec.tensor import ColumnarEvents

    def subset(mask):
        return ColumnarEvents(
            num_aggregates=corpus.num_aggregates, agg_idx=ev.agg_idx[mask],
            type_ids=ev.type_ids[mask],
            cols={k: v[mask] for k, v in ev.cols.items()},
            derived_cols=dict(ev.derived_cols))

    r1 = engine.replay_columnar(subset(keep_first))
    r2 = engine.replay_columnar(subset(~keep_first), init_carry=r1.states,
                                ordinal_base=first_len.astype(np.int32))
    assert np.array_equal(r2.states["count"], corpus.expected_count)
    assert np.array_equal(r2.states["version"], corpus.expected_version)


def test_grouped_pack_is_indirect_and_exact_everywhere(mesh8):
    # mesh8 (not a skipif): the sharded-deal leg MUST run on every tier-1
    # pass — the fixture fails loudly if the 8 forced host devices are gone
    """A grouped-input corpus (every encode path produces one) packs WITHOUT
    the 100M-event sort: the buffer keeps input order and lanes point at
    their segments by indirection. Every consumer of the wire — plain
    resident, streamed pieces, save/load round-trip, sharded mesh deal —
    must agree with the closed form on such a wire."""
    from surge_tpu.replay.corpus import synth_counter_corpus
    from surge_tpu.replay.engine import ResidentWire

    corpus = synth_counter_corpus(900, 40_000, seed=77)
    cfg = Config(overrides={"surge.replay.batch-size": 128,
                            "surge.replay.time-chunk": 32})
    eng = ReplayEngine(counter.make_replay_spec(), config=cfg)
    wire = eng.pack_resident(corpus.events)
    # the fast path really triggered: lanes are length-sorted but the buffer
    # is not lane-ordered
    assert wire.perm is not None
    cum = np.zeros(wire.lengths.shape[0], dtype=np.int64)
    np.cumsum(wire.lengths[:-1].astype(np.int64), out=cum[1:])
    assert not np.array_equal(wire.starts.astype(np.int64), cum)

    plain = eng.replay_resident(eng.upload_resident(wire))
    np.testing.assert_array_equal(plain.states["count"], corpus.expected_count)
    np.testing.assert_array_equal(plain.states["version"],
                                  corpus.expected_version)

    for segments in (2, 5):
        st = eng.replay_resident_streamed(wire, segments=segments)
        for name in plain.states:
            np.testing.assert_array_equal(st.states[name], plain.states[name],
                                          err_msg=f"segments={segments}")

    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        wire.save(f"{tmp}/w")
        loaded = ResidentWire.load(f"{tmp}/w")
        res = eng.replay_resident(eng.upload_resident(loaded))
        np.testing.assert_array_equal(res.states["count"],
                                      corpus.expected_count)

    # the sharded mesh deal gathers per-lane slabs straight from the indirect
    # starts (resident_mesh host-side re-pack)
    meng = ReplayEngine(counter.make_replay_spec(), config=cfg, mesh=mesh8)
    sharded = meng.prepare_resident_sharded(wire)
    sres = meng.replay_resident_sharded(sharded)
    np.testing.assert_array_equal(sres.states["count"], corpus.expected_count)
    np.testing.assert_array_equal(sres.states["version"],
                                  corpus.expected_version)


def test_streamed_indirect_wire_with_empty_aggregates():
    """Zero-length lanes occupy no buffer rows: the indirect streamed path
    must still stream (not silently fall back) and return their init state."""
    rng = np.random.default_rng(5)
    b, n = 60, 6000
    # aggregate 7, 23, 40 have NO events; others grouped ascending
    live = np.array([a for a in range(b) if a not in (7, 23, 40)])
    agg_idx = np.sort(rng.choice(live, size=n)).astype(np.int32)
    type_ids = rng.integers(0, 2, size=n).astype(np.int32)
    inc = np.where(type_ids == 0, 1, 0).astype(np.int32)
    dec = np.where(type_ids == 1, 1, 0).astype(np.int32)
    colev = ColumnarEvents(
        num_aggregates=b, agg_idx=agg_idx, type_ids=type_ids,
        cols={"increment_by": inc, "decrement_by": dec},
        derived_cols={"sequence_number": "ordinal"})
    eng = ReplayEngine(counter.make_replay_spec(), config=Config(overrides={
        "surge.replay.batch-size": 16, "surge.replay.time-chunk": 16}))
    wire = eng.pack_resident(colev)
    assert int((wire.lengths == 0).sum()) == 3
    plain = eng.replay_resident(eng.upload_resident(wire))
    expected = (np.bincount(agg_idx, weights=inc, minlength=b)
                - np.bincount(agg_idx, weights=dec, minlength=b)).astype(np.int32)
    np.testing.assert_array_equal(plain.states["count"], expected)
    import unittest.mock as mock

    for segments in (2, 4):
        # count piece uploads to prove the path really streamed instead of
        # silently falling back to one plain upload
        real_upload = ReplayEngine.upload_resident
        with mock.patch.object(ReplayEngine, "upload_resident",
                               autospec=True, side_effect=real_upload) as up:
            st = eng.replay_resident_streamed(wire, segments=segments)
        assert up.call_count == segments
        np.testing.assert_array_equal(st.states["count"], expected,
                                      err_msg=f"segments={segments}")
        np.testing.assert_array_equal(st.states["version"],
                                      plain.states["version"])
