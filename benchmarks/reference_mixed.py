"""The plain reference of the mixed replay: what three families' logs, folded
side by side in one batch, come to. Imports nothing of the program and takes
nothing it made.

Two forms of the same semantics. The whole-column form: the counter's closed
form and the cart's int64 segment sums (``reference.py``, ``reference_cart.py``)
and, here, the bank account's, each laid into the union's columns at its own
aggregates, zero elsewhere. The scalar form: all nine handlers written out and
folded one event at a time over a union state, dispatched on the union's type
id as the program's masked switch is. The bank account's follow
``BankAccountCommandModel.scala:53-88`` as ``surge_tpu/models/bank_account.py``
cites it: ``BankAccountCreated`` replaces the state, ``BankAccountUpdated``
sets the balance only where an account exists (``aggregate.map(_.copy(...))``).
"""

from __future__ import annotations

import numpy as np

from benchmarks import reference, reference_cart
from benchmarks.gen_mixed import CREATED, FAMILIES, BankCorpus, MixedCorpus

#: the union state's columns (sorted, as the combined registry lays them out),
#: the ones each family's handlers move, and each family's first union type id
FIELDS = ("balance", "checked_out", "count", "created", "item_count",
          "owner_code", "security_code_code", "total_cents", "version")
OWNED = {"bank": ("balance", "created", "owner_code", "security_code_code"),
         "cart": ("checked_out", "item_count", "total_cents", "version"),
         "counter": ("count", "version")}
BASES = {"bank": 0, "cart": 2, "counter": 5}
TYPES = 9
(BANK_CREATED, BANK_UPDATED, CART_ADDED, CART_REMOVED, CART_CHECKED_OUT,
 COUNT_INCREMENTED, COUNT_DECREMENTED, COUNT_NOOP,
 COUNT_UNSERIALIZABLE) = range(TYPES)

ZERO = {"balance": np.float32(0), "checked_out": False, "count": 0,
        "created": False, "item_count": 0, "owner_code": 0,
        "security_code_code": 0, "total_cents": 0, "version": 0}


# --- the scalar fold: a union state, one event at a time -------------------------

def handle_event(state: dict, kind: int, ev: dict, seq: int) -> dict:
    """``state`` after one event of union type ``kind``. ``ev`` holds the
    event's own fields; a field it lacks reads 0, as the union's columns do. A
    handler moves its own family's columns and no other; a type id outside the
    nine is padding and moves nothing."""
    get = lambda name: ev.get(name, 0)  # noqa: E731
    if kind == BANK_CREATED:
        return dict(state, created=True, owner_code=get("owner_code"),
                    security_code_code=get("security_code_code"),
                    balance=np.float32(get("balance")))
    if kind == BANK_UPDATED:
        if not state["created"]:
            return state
        return dict(state, balance=np.float32(get("new_balance")))
    if kind in (CART_ADDED, CART_REMOVED):
        sign = 1 if kind == CART_ADDED else -1
        return dict(state, version=seq,
                    item_count=state["item_count"] + sign * get("quantity"),
                    total_cents=state["total_cents"]
                    + sign * get("quantity") * get("unit_price_cents"))
    if kind == CART_CHECKED_OUT:
        return dict(state, checked_out=True, version=seq)
    if kind == COUNT_INCREMENTED:
        return dict(state, count=state["count"] + get("increment_by"),
                    version=seq)
    if kind == COUNT_DECREMENTED:
        return dict(state, count=state["count"] - get("decrement_by"),
                    version=seq)
    if kind == COUNT_UNSERIALIZABLE:
        return dict(state, version=seq)
    return state  # COUNT_NOOP, and padding


def fold(events) -> dict:
    """``events``: (union type id, fields) in log order; the sequence number
    of an event is its 1-based position."""
    state = dict(ZERO)
    for seq, (kind, ev) in enumerate(events, start=1):
        state = handle_event(state, kind, ev, seq)
    return state


def log_of(corpus: MixedCorpus, family: str, local: int) -> list:
    """[(union type id, fields)] of ``family``'s aggregate ``local``."""
    part = corpus.part(family)
    starts = part.starts()
    lo, hi = int(starts[local]), int(starts[local + 1])
    cols = {name: col[lo:hi].tolist()
            for name, col in corpus.columns(family).items()}
    kinds = (part.type_ids[lo:hi] + BASES[family]).tolist()
    return [(kind, {n: col[i] for n, col in cols.items()})
            for i, kind in enumerate(kinds)]


def scalar_fold_sample(corpus: MixedCorpus, family: str, locals_,
                       shift: int = 0) -> dict:
    """{local index: the union state (a tuple in ``FIELDS`` order)} of the
    given aggregates of ``family`` by the scalar fold. ``shift`` moves every
    type id by that much, as a wrong base would (the control's fault): an id
    it pushes past the last type is padding."""
    out = {}
    for b in np.asarray(locals_).tolist():
        events = [(kind + shift if 0 <= kind + shift < TYPES else -1, ev)
                  for kind, ev in log_of(corpus, family, b)]
        state = fold(events)
        out[b] = tuple(state[name] for name in FIELDS)
    return out


# --- the whole-column form -----------------------------------------------------------

def bank_closed_form(corpus: BankCorpus) -> dict:
    """{field: [B]} of every account. The last ``Created`` of a log decides
    whether there is an account, its owner and its security code; its balance
    is the log's last event's, since whatever follows the last ``Created`` is
    an ``Updated`` on an account that exists. An ``Updated`` before the first
    ``Created`` found no account and did nothing; a later ``Created`` wipes
    what came before it."""
    b = corpus.num_aggregates
    out = {"created": np.zeros(b, dtype=bool),
           "owner_code": np.zeros(b, dtype=np.int64),
           "security_code_code": np.zeros(b, dtype=np.int64),
           "balance": np.zeros(b, dtype=np.float32)}
    nonempty = corpus.lengths > 0
    if not (corpus.num_events and nonempty.any()):
        return out
    starts = corpus.starts()
    at = np.where(corpus.type_ids == CREATED,
                  np.arange(corpus.num_events, dtype=np.int64), -1)
    last_created = np.full(b, -1, dtype=np.int64)
    last_created[nonempty] = np.maximum.reduceat(at, starts[:-1][nonempty])
    has = last_created >= 0
    c = last_created[has]
    end = starts[1:][has] - 1
    out["created"][has] = True
    out["owner_code"][has] = corpus.owner_code[c]
    out["security_code_code"][has] = corpus.security_code_code[c]
    out["balance"][has] = np.where(end > c, corpus.new_balance[end],
                                   corpus.balance[c])
    return out


def closed_form(corpus: MixedCorpus) -> dict:
    """{field: [B]} over the union's aggregates: every family's own form at
    its own aggregates, and the zero of a column at every aggregate of a
    family that does not own it. Integers int64, ``balance`` float32."""
    b = corpus.num_aggregates
    out = {name: np.zeros(b, dtype=np.asarray(ZERO[name]).dtype
                          if name in ("balance", "checked_out", "created")
                          else np.int64) for name in FIELDS}
    count, version = reference.closed_form(corpus.counter)
    own = {"bank": bank_closed_form(corpus.bank),
           "cart": reference_cart.closed_form(corpus.cart),
           "counter": {"count": count, "version": version}}
    for family in FAMILIES:
        ids = corpus.ids(family)
        for name in OWNED[family]:
            out[name][ids] = own[family][name]
    return out


def differs(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """[B] bool: where a pulled column is not the reference's, exactly. A
    float column is compared bit for bit: the fold assigns and never adds."""
    got = np.asarray(got)
    if want.dtype == np.float32:
        return got.astype(np.float32).view(np.uint32) != want.view(np.uint32)
    if want.dtype == bool:
        return got.astype(bool) != want
    return got.astype(np.int64) != want
