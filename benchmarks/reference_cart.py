"""The plain reference of the shopping cart: what its logs fold to. Imports
nothing of the program and takes nothing it made.

Two forms of the same semantics: the three handlers of
``CartModel.handle_event`` written out and folded one event at a time, and the
whole-column form in int64 integer arithmetic (sums over segments, no float
weights), which is exact at any size.
"""

from __future__ import annotations

import numpy as np

from benchmarks.gen_cart import ADDED, CHECKED_OUT, REMOVED, CartCorpus

FIELDS = ("item_count", "total_cents", "checked_out", "version")


# --- the scalar fold: state is (item_count, total_cents, checked_out, version),
# --- None before the first event

def handle_event(state, kind: int, quantity: int, unit_price_cents: int,
                 seq: int):
    count, total, closed, _version = (state if state is not None
                                      else (0, 0, False, 0))
    if kind == ADDED:
        return (count + quantity, total + quantity * unit_price_cents, closed,
                seq)
    if kind == REMOVED:
        return (count - quantity, total - quantity * unit_price_cents, closed,
                seq)
    if kind == CHECKED_OUT:
        return (count, total, True, seq)
    raise ValueError(f"unknown event type {kind}")


def fold(events) -> tuple:
    """``events``: (kind, quantity, unit_price_cents, sequence_number) in log
    order."""
    state = None
    for kind, quantity, price, seq in events:
        state = handle_event(state, kind, quantity, price, seq)
    return state if state is not None else (0, 0, False, 0)


def scalar_fold_sample(corpus: CartCorpus, indices) -> dict:
    """{cart index: (item_count, total_cents, checked_out, version)} by the
    scalar fold; the sequence number of an event is its 1-based position in
    its cart's log."""
    starts = corpus.starts()
    out = {}
    for b in np.asarray(indices).tolist():
        lo, hi = int(starts[b]), int(starts[b + 1])
        out[b] = fold(zip(corpus.type_ids[lo:hi].tolist(),
                          corpus.quantity[lo:hi].tolist(),
                          corpus.unit_price_cents[lo:hi].tolist(),
                          range(1, hi - lo + 1)))
    return out


# --- the whole-column form ---------------------------------------------------------

def closed_form(corpus: CartCorpus) -> dict:
    """{field: [B]} of every cart (int64, ``checked_out`` bool): ``item_count``
    is the sum of the signed quantities, ``total_cents`` the sum of signed
    quantity times price, ``checked_out`` whether any event is a
    ``CheckedOut``, ``version`` the log's length (every event carries its
    position, and the last one's stays)."""
    b = corpus.num_aggregates
    signed = np.where(corpus.type_ids == ADDED, 1,
                      np.where(corpus.type_ids == REMOVED, -1, 0)
                      ).astype(np.int64)
    signed *= corpus.quantity
    out = {"item_count": np.zeros(b, dtype=np.int64),
           "total_cents": np.zeros(b, dtype=np.int64),
           "checked_out": np.zeros(b, dtype=bool),
           "version": corpus.lengths.astype(np.int64)}
    nonempty = corpus.lengths > 0
    if corpus.num_events and nonempty.any():
        idx = corpus.starts()[:-1][nonempty]
        out["item_count"][nonempty] = np.add.reduceat(signed, idx)
        signed *= corpus.unit_price_cents
        out["total_cents"][nonempty] = np.add.reduceat(signed, idx)
        out["checked_out"][nonempty] = np.add.reduceat(
            (corpus.type_ids == CHECKED_OUT).astype(np.int64), idx) > 0
    return out
