"""Inputs from ``--seed``: the shopping cart's ragged log.

Plain numpy; imports nothing of the program. The lengths follow the
configuration's ``corpus`` law through ``gen.log_lengths`` (the counter's
generator, reused); the events are drawn one by one from the configuration's
mix, so a cart's running count may dip below zero where the command side would
have clamped a removal. The fold is total, and ``reference_cart.py`` folds the
same log.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from benchmarks.gen import log_lengths

# the cart's event types, as its schema registers them (0..2)
ADDED, REMOVED, CHECKED_OUT = 0, 1, 2

INT32_MAX = 2**31 - 1


@dataclass
class CartCorpus:
    """A cart log, aggregate-sorted, time-ordered within an aggregate."""

    num_aggregates: int
    lengths: np.ndarray  # [B] int64 events per cart
    agg_idx: np.ndarray  # [N] int32
    type_ids: np.ndarray  # [N] int32
    item_code: np.ndarray  # [N] int32 (0 where the type has none)
    quantity: np.ndarray  # [N] int32
    unit_price_cents: np.ndarray  # [N] int32

    @property
    def num_events(self) -> int:
        return int(self.type_ids.shape[0])

    def starts(self) -> np.ndarray:
        out = np.zeros(self.num_aggregates + 1, dtype=np.int64)
        np.cumsum(self.lengths, out=out[1:])
        return out


def price_of(item_code: np.ndarray, law: dict) -> np.ndarray:
    """An item's price in cents: a fixed function of its code that spreads the
    codes over ``price_cents`` (both ends included), so that a removal carries
    the price its item was added at."""
    lo, hi = law["price_cents"]
    return (lo + (item_code.astype(np.int64) * 40_503) % (hi - lo + 1)
            ).astype(np.int32)


def running_total_extremes(corpus: CartCorpus) -> tuple:
    """(least, greatest) running ``total_cents`` any cart shows at any point
    of its log, in int64."""
    if not corpus.num_events:
        return 0, 0
    signed = np.where(corpus.type_ids == REMOVED, -1, 1).astype(np.int64)
    signed *= corpus.quantity
    signed *= corpus.unit_price_cents
    running = np.cumsum(signed)
    # less what the carts before this one had added up to
    before = np.concatenate([[0], running])[corpus.starts()[:-1]]
    running -= np.repeat(before, corpus.lengths)
    return int(running.min()), int(running.max())


def cart_corpus(num_aggregates: int, num_events: int, seed: int,
                law: dict, block: int | None = None) -> CartCorpus:
    """The cart's log under ``law`` (a configuration's ``corpus`` group): its
    ``length_law``; ``body_mix``, the shares of ``ItemAdded`` and
    ``ItemRemoved`` among the events of a log's body; ``added_quantity`` and
    ``removed_quantity``, the ends of their uniform quantities; ``item_codes``,
    how many codes an item is drawn from; ``price_cents``, the ends of the
    prices; ``checkout_share``, the share of carts whose last event is their
    one ``CheckedOut``. ``block``: the aggregates a law's ``lengths_seed``
    keeps the lengths of together (``gen.log_lengths``)."""
    rng = np.random.default_rng(seed)
    lengths = log_lengths(law, num_aggregates, num_events, rng, block)
    n = int(lengths.sum())
    agg_idx = np.repeat(np.arange(num_aggregates, dtype=np.int32), lengths)
    cut = int(round(law["body_mix"][0] * 10_000))
    type_ids = (rng.integers(0, 10_000, size=n, dtype=np.uint16)
                >= cut).astype(np.int32)
    a_lo, a_hi = law["added_quantity"]
    r_lo, r_hi = law["removed_quantity"]
    quantity = np.where(
        type_ids == ADDED,
        rng.integers(a_lo, a_hi + 1, size=n, dtype=np.int32),
        rng.integers(r_lo, r_hi + 1, size=n, dtype=np.int32)).astype(np.int32)
    item_code = rng.integers(0, int(law["item_codes"]), size=n, dtype=np.int32)
    unit_price = price_of(item_code, law)
    # a checked-out cart's last event is its CheckedOut, which carries no item
    ends = np.cumsum(lengths) - 1
    closed = ends[(lengths > 0)
                  & (rng.random(num_aggregates) < law["checkout_share"])]
    type_ids[closed] = CHECKED_OUT
    for col in (item_code, quantity, unit_price):
        col[closed] = 0
    corpus = CartCorpus(num_aggregates, lengths, agg_idx, type_ids, item_code,
                        quantity, unit_price)
    # every running total_cents must stay inside the state's int32. No log can
    # leave it while its length times the dearest event stays inside; only
    # where that cheap bound fails are the running sums walked
    dearest = max(a_hi, r_hi) * int(law["price_cents"][1])
    if int(lengths.max(initial=0)) * dearest > INT32_MAX:
        least, greatest = running_total_extremes(corpus)
        assert -INT32_MAX - 1 <= least and greatest <= INT32_MAX, (least,
                                                                   greatest)
    return corpus
