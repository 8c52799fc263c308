"""The plain reference: what the counter's logs fold to, and what a served
counter may answer. Imports nothing of the program and takes nothing it made.

Two forms of the same semantics: the closed form over whole columns (numpy), and
the counter's four event handlers written out and folded one event at a time.
"""

from __future__ import annotations

import numpy as np

from benchmarks.gen import (DECREMENTED, INCREMENTED, NOOP, UNSERIALIZABLE,
                            Corpus)


# --- the scalar fold: state is (count, version), None before the first event ---

def handle_event(state, kind: int, amount: int, seq: int):
    count, version = state if state is not None else (0, 0)
    if kind == INCREMENTED:
        return (count + amount, seq)
    if kind == DECREMENTED:
        return (count - amount, seq)
    if kind == NOOP:
        return (count, version)
    if kind == UNSERIALIZABLE:
        return (count, seq)
    raise ValueError(f"unknown event type {kind}")


def fold(events) -> tuple:
    """``events``: (kind, amount, sequence_number) in log order."""
    state = None
    for kind, amount, seq in events:
        state = handle_event(state, kind, amount, seq)
    return state if state is not None else (0, 0)


def scalar_fold_sample(corpus: Corpus, indices) -> dict:
    """{aggregate index: (count, version)} by the scalar fold; the sequence
    number of an event is its 1-based position in its aggregate's log."""
    starts = corpus.starts()
    out = {}
    for b in np.asarray(indices).tolist():
        lo, hi = int(starts[b]), int(starts[b + 1])
        kinds = corpus.type_ids[lo:hi].tolist()
        amounts = (corpus.inc[lo:hi] + corpus.dec[lo:hi]).tolist()
        out[b] = fold(zip(kinds, amounts, range(1, hi - lo + 1)))
    return out


def closed_form(corpus: Corpus):
    """(count [B] int64, version [B] int64) of every aggregate: count is the sum
    of increments less the sum of decrements, version the position of the last
    event that is not a no-op."""
    b = corpus.num_aggregates
    lengths = corpus.lengths
    starts = corpus.starts()
    n = corpus.num_events
    delta = corpus.inc.astype(np.int64) - corpus.dec
    seq = np.arange(n, dtype=np.int64) - starts[corpus.agg_idx] + 1
    seq[corpus.type_ids == NOOP] = 0
    count = np.zeros(b, dtype=np.int64)
    version = np.zeros(b, dtype=np.int64)
    nonempty = lengths > 0
    if n and nonempty.any():
        idx = starts[:-1][nonempty]
        count[nonempty] = np.add.reduceat(delta, idx)
        version[nonempty] = np.maximum.reduceat(seq, idx)
    return count, version


# --- the served node: preloaded state, and the history an aggregate may show ---

def preloaded_state(kinds_row) -> tuple:
    """Fold of one aggregate's preloaded events as the command path stamps them:
    increments and decrements are by 1 and take sequence version + 1; a no-op
    carries sequence version + 1 and leaves the version where it was."""
    state, version = None, 0
    for k in kinds_row:
        if k == 2:
            state = handle_event(state, NOOP, 0, version + 1)
        else:
            version += 1
            state = handle_event(state, INCREMENTED if k == 0 else DECREMENTED,
                                 1, version)
    return state if state is not None else (0, 0)


def preloaded_states(kinds: np.ndarray):
    """The same for every aggregate at once: (count [B], version [B])."""
    inc = (kinds == 0).sum(axis=1).astype(np.int64)
    dec = (kinds == 1).sum(axis=1).astype(np.int64)
    return inc - dec, inc + dec


def judge_node(base_count, base_version, acks: dict, reads: list,
               readback: dict) -> dict:
    """Hold a served window to the counter's semantics.

    ``acks``: {aggregate: [(delta, count, version)]} of every acknowledged
    command (delta +1/-1, then the state its ack carried). ``reads``:
    [(aggregate, count, version)] of every read answered in the window (None
    for no state). ``readback``: {aggregate: [(count, version), ...]} of every
    touched aggregate on the settled node, once per read path (path 0 is
    ``project_states``, path 1 ``get_state``).

    An aggregate's acknowledged commands, in the order of the versions their
    acks carry, must step the version by exactly one from the preloaded state
    and the count by each command's own delta. A read must show a state the
    aggregate had: the preloaded one or one an ack carried. The read-back must
    show the last of them."""
    bad_acks = bad_reads = bad_readback = 0
    first_bad = []
    history = {}
    for agg, rows in acks.items():
        count, version = int(base_count[agg]), int(base_version[agg])
        seen = {(count, version)}
        for delta, got_count, got_version in sorted(rows, key=lambda r: r[2]):
            count, version = count + delta, version + 1
            if (got_count, got_version) != (count, version):
                bad_acks += 1
                if len(first_bad) < 5:
                    first_bad.append(("ack", agg, (got_count, got_version),
                                      (count, version)))
                count, version = got_count, got_version  # judge the rest on its own
            seen.add((count, version))
        history[agg] = (seen, (count, version))
    for agg, got_count, got_version in reads:
        if agg in history:
            ok = (got_count, got_version) in history[agg][0]
        else:
            ok = (got_count, got_version) == (int(base_count[agg]),
                                              int(base_version[agg]))
        if not ok:
            bad_reads += 1
            if len(first_bad) < 5:
                first_bad.append(("read", agg, (got_count, got_version),
                                  sorted(history[agg][0], key=lambda r: r[1])
                                  if agg in history else "untouched"))
    for agg, answers in readback.items():
        want = (history[agg][1] if agg in history
                else (int(base_count[agg]), int(base_version[agg])))
        for path, got in enumerate(answers):
            if tuple(got) != want:
                bad_readback += 1
                if len(first_bad) < 5:
                    first_bad.append((f"readback path {path}", agg, tuple(got),
                                      want))
    return {"acks_wrong": bad_acks, "reads_wrong": bad_reads,
            "readback_wrong": bad_readback, "first_bad": first_bad}
