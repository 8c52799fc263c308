"""Inputs from ``--seed``: three aggregate families in one log.

Plain numpy; imports nothing of the program. The counters and the carts are
the accepted cells' own corpora (``gen.counter_corpus``, ``gen_cart.cart_corpus``)
at this deployment's shares; the bank accounts are made here. Which family an
aggregate id belongs to is drawn from the seed, so every stretch of the merged
log holds all three. The answers a corpus must fold to are in
``reference_mixed.py``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from benchmarks import gen, gen_cart

#: the families in the order the program's combined registry lays them out
#: (sorted by name), and the bank account's event types (0..1)
FAMILIES = ("bank", "cart", "counter")
CREATED, UPDATED = 0, 1

#: a family's event columns: the schema's field name -> its corpus's attribute
COLUMNS = {
    "bank": {"owner_code": "owner_code",
             "security_code_code": "security_code_code",
             "balance": "balance", "new_balance": "new_balance"},
    "cart": {"item_code": "item_code", "quantity": "quantity",
             "unit_price_cents": "unit_price_cents"},
    "counter": {"increment_by": "inc", "decrement_by": "dec"}}


@dataclass
class BankCorpus:
    """A bank-account log, aggregate-sorted, time-ordered within an account."""

    num_aggregates: int
    lengths: np.ndarray  # [B] int64 events per account
    agg_idx: np.ndarray  # [N] int32
    type_ids: np.ndarray  # [N] int32
    owner_code: np.ndarray  # [N] int32 (0 where the type has none)
    security_code_code: np.ndarray  # [N] int32
    balance: np.ndarray  # [N] float32, a Created's opening balance
    new_balance: np.ndarray  # [N] float32, an Updated's balance

    @property
    def num_events(self) -> int:
        return int(self.type_ids.shape[0])

    def starts(self) -> np.ndarray:
        out = np.zeros(self.num_aggregates + 1, dtype=np.int64)
        np.cumsum(self.lengths, out=out[1:])
        return out


def amounts(rng: np.random.Generator, n: int, law: dict) -> np.ndarray:
    """``n`` float32 balances, uniform over ``balance_range``: sums and
    differences of earlier commands as a log holds them, with every bit of the
    mantissa in use."""
    lo, hi = law["balance_range"]
    return (np.float32(lo) + rng.random(n, dtype=np.float32)
            * np.float32(hi - lo)).astype(np.float32)


def bank_corpus(num_aggregates: int, num_events: int, seed,
                law: dict) -> BankCorpus:
    """The bank account's log under ``law`` (the ``bank`` group of a
    configuration's ``corpus``): its ``length_law``; ``codes``, how many codes
    an owner and a security code are drawn from; ``balance_range``;
    ``orphan_share``, the share of accounts whose log opens with
    ``orphan_updates`` (both ends included) ``Updated`` events before its
    ``Created``; ``second_created_share``, the share that hold a second
    ``Created`` somewhere after the first. An account's first event is
    otherwise its ``Created``, and the rest of its log is ``Updated``."""
    rng = np.random.default_rng(seed)
    lengths = gen.log_lengths(law, num_aggregates, num_events, rng)
    n = int(lengths.sum())
    starts = np.zeros(num_aggregates + 1, dtype=np.int64)
    np.cumsum(lengths, out=starts[1:])
    agg_idx = np.repeat(np.arange(num_aggregates, dtype=np.int32), lengths)
    type_ids = np.full(n, UPDATED, dtype=np.int32)
    # where the account opens: after its orphans, if the log is that long
    o_lo, o_hi = law["orphan_updates"]
    opens_at = np.where(rng.random(num_aggregates) < law["orphan_share"],
                        rng.integers(o_lo, o_hi + 1, size=num_aggregates), 0)
    opened = opens_at < lengths
    type_ids[(starts[:-1] + opens_at)[opened]] = CREATED
    # a second Created, anywhere after the first
    room = lengths - opens_at - 1
    again = (rng.random(num_aggregates) < law["second_created_share"]) & (room > 0)
    at = opens_at + 1 + (rng.random(num_aggregates) * room).astype(np.int64)
    type_ids[(starts[:-1] + at)[again]] = CREATED
    created = type_ids == CREATED
    codes = int(law["codes"])
    zero = np.int32(0)
    owner = np.where(created, rng.integers(0, codes, size=n, dtype=np.int32), zero)
    security = np.where(created, rng.integers(0, codes, size=n, dtype=np.int32),
                        zero)
    amount = amounts(rng, n, law)
    nothing = np.float32(0)
    return BankCorpus(num_aggregates, lengths, agg_idx, type_ids, owner,
                      security, np.where(created, amount, nothing),
                      np.where(created, nothing, amount))


@dataclass
class MixedCorpus:
    """The three families' corpora, and which union aggregate is whose."""

    bank: BankCorpus
    cart: gen_cart.CartCorpus
    counter: gen.Corpus
    family: np.ndarray  # [B] int8, an index into FAMILIES

    @property
    def num_aggregates(self) -> int:
        return int(self.family.shape[0])

    @property
    def num_events(self) -> int:
        return sum(self.part(f).num_events for f in FAMILIES)

    def part(self, family: str):
        return getattr(self, family)

    def columns(self, family: str) -> dict:
        """{field name: [N_family]} of ``family``'s event columns."""
        part = self.part(family)
        return {name: getattr(part, attr)
                for name, attr in COLUMNS[family].items()}

    def ids(self, family: str) -> np.ndarray:
        """The union ids of ``family``'s aggregates, in its own order: its
        k-th aggregate is the k-th union aggregate of that family."""
        return np.flatnonzero(self.family == FAMILIES.index(family))

    def lengths(self) -> np.ndarray:
        """[B] int64 events per union aggregate."""
        out = np.zeros(self.num_aggregates, dtype=np.int64)
        for f in FAMILIES:
            out[self.ids(f)] = self.part(f).lengths
        return out


def shares(total: int, law: dict) -> dict:
    """``total`` split over the families by ``law['shares']``, whole numbers
    that add up to it (what rounding leaves goes to the last family)."""
    out = {f: int(total * law["shares"][f]) for f in FAMILIES}
    out[FAMILIES[-1]] += total - sum(out.values())
    return out


def mixed_corpus(num_aggregates: int, num_events: int, seed: int,
                 law: dict) -> MixedCorpus:
    """The deployment's log under ``law`` (a configuration's ``corpus``):
    ``shares`` of aggregates and of events a family (the same for both, so
    every family's mean log is the deployment's), and one group a family with
    that family's own law."""
    aggregates = shares(num_aggregates, law)
    events = shares(num_events, law)
    made = {
        "bank": bank_corpus(aggregates["bank"], events["bank"], [seed, 0xBA],
                            law["bank"]),
        "cart": gen_cart.cart_corpus(aggregates["cart"], events["cart"],
                                     [seed, 0xCA], law["cart"]),
        "counter": gen.counter_corpus(aggregates["counter"], events["counter"],
                                      [seed, 0xC0], law["counter"])}
    family = np.random.default_rng([seed, 0xFA]).permutation(np.repeat(
        np.arange(len(FAMILIES), dtype=np.int8),
        [aggregates[f] for f in FAMILIES]))
    return MixedCorpus(family=family, **made)
