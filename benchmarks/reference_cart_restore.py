"""The plain reference of the cart's segment restore: what the state store
holds after a cold start, from the corpus's columns and the carts' ids alone.
Takes nothing the restore made.

Two forms of the same semantics. For every cart, the store as a dictionary
``{id: {"cart_id", "item_count", "total_cents", "checked_out", "version"}}``
from the whole-column int64 closed form of ``reference_cart.py``. For a
sample, the scalar way, which is what the configuration's guarantee names:
the cart's events as objects, ``fold_events(CartModel(), None, events)`` (the
model's own ``handle_event``, one event at a time), and the bytes
``state_formatting().write_state`` gives for the state it ends in.
"""

from __future__ import annotations

import numpy as np

from benchmarks import reference_cart
from benchmarks.gen_cart import ADDED, CHECKED_OUT, REMOVED, CartCorpus

STATE_FIELDS = ("cart_id",) + reference_cart.FIELDS


def cart_ids(num_aggregates: int) -> list:
    """The carts' keys in the corpus's order: zero-padded, so that a topic's
    sorted key order is the corpus's own."""
    return [f"cart-{i:07d}" for i in range(num_aggregates)]


def expected_store(corpus: CartCorpus, ids: list) -> dict:
    """{id: the five fields of the cart's state} for every cart of the
    corpus: a cart of a topic has an event at least, and so a state."""
    want = reference_cart.closed_form(corpus)
    columns = [want[name].tolist() for name in reference_cart.FIELDS]
    return {cart_id: dict(zip(STATE_FIELDS, (cart_id, *row)))
            for cart_id, *row in zip(ids, *columns)}


def cart_events(corpus: CartCorpus, ids: list, b: int) -> list:
    """Cart ``b``'s log as the model's own event objects; an event's sequence
    number is its 1-based position in its cart's log."""
    from surge_tpu.models import shopping_cart as sc

    starts = corpus.starts()
    lo, hi = int(starts[b]), int(starts[b + 1])
    out = []
    for seq, (kind, code, quantity, price) in enumerate(zip(
            corpus.type_ids[lo:hi].tolist(), corpus.item_code[lo:hi].tolist(),
            corpus.quantity[lo:hi].tolist(),
            corpus.unit_price_cents[lo:hi].tolist()), start=1):
        if kind == ADDED:
            out.append(sc.ItemAdded(ids[b], code, quantity, price, seq))
        elif kind == REMOVED:
            out.append(sc.ItemRemoved(ids[b], code, quantity, price, seq))
        elif kind == CHECKED_OUT:
            out.append(sc.CheckedOut(ids[b], seq))
        else:
            raise ValueError(f"unknown event type {kind}")
    return out


def scalar_fold_bytes(corpus: CartCorpus, ids: list, indices) -> dict:
    """{id: the bytes the scalar fold of the cart's whole log serializes to}
    for the carts at ``indices``."""
    from surge_tpu.engine.model import fold_events
    from surge_tpu.models import shopping_cart as sc

    model, fmt = sc.CartModel(), sc.state_formatting()
    return {ids[b]: fmt.write_state(fold_events(
        model, None, cart_events(corpus, ids, b))).value
        for b in np.asarray(indices).tolist()}
