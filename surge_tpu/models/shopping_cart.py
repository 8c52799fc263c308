"""ShoppingCart — variable-length-log fixture for ragged/segmented replay
(BASELINE.json config: "ShoppingCart aggregate, variable-length event logs").

The reference has no shopping-cart sample; this family exists to exercise the ragged
batching path (length buckets + masked scan) and a richer heterogeneous event set.
Prices are integer cents so scalar↔tensor golden comparisons are exact.
"""

from __future__ import annotations

import functools as _functools
from dataclasses import dataclass
from typing import Optional, Sequence

from surge_tpu.codec.schema import SchemaRegistry
from surge_tpu.engine.model import RejectedCommand, ReplayHandlers, ReplaySpec
from surge_tpu.serialization import JsonEventFormatting, JsonFormatting


@dataclass(frozen=True)
class Cart:
    cart_id: str
    item_count: int
    total_cents: int
    checked_out: bool
    version: int


# commands
@dataclass(frozen=True)
class AddItem:
    cart_id: str
    item_code: int
    quantity: int
    unit_price_cents: int


@dataclass(frozen=True)
class RemoveItem:
    cart_id: str
    item_code: int
    quantity: int
    unit_price_cents: int


@dataclass(frozen=True)
class Checkout:
    cart_id: str


# events
@dataclass(frozen=True)
class ItemAdded:
    cart_id: str
    item_code: int
    quantity: int
    unit_price_cents: int
    sequence_number: int


@dataclass(frozen=True)
class ItemRemoved:
    cart_id: str
    item_code: int
    quantity: int
    unit_price_cents: int
    sequence_number: int


@dataclass(frozen=True)
class CheckedOut:
    cart_id: str
    sequence_number: int


class CartAlreadyCheckedOut(RejectedCommand):
    pass


class CartModel:
    def initial_state(self, aggregate_id: str) -> Optional[Cart]:
        return None

    def process_command(self, state: Optional[Cart], command) -> Sequence[object]:
        if state is not None and state.checked_out:
            raise CartAlreadyCheckedOut(command.cart_id)
        seq = (state.version if state else 0) + 1
        if isinstance(command, AddItem):
            return [ItemAdded(command.cart_id, command.item_code, command.quantity,
                              command.unit_price_cents, seq)]
        if isinstance(command, RemoveItem):
            have = state.item_count if state else 0
            qty = min(command.quantity, have)
            if qty <= 0:
                return []
            return [ItemRemoved(command.cart_id, command.item_code, qty,
                                command.unit_price_cents, seq)]
        if isinstance(command, Checkout):
            return [CheckedOut(command.cart_id, seq)]
        raise RejectedCommand(f"unknown command {command!r}")

    def handle_event(self, state: Optional[Cart], event) -> Optional[Cart]:
        cur = state if state is not None else Cart(event.cart_id, 0, 0, False, 0)
        if isinstance(event, ItemAdded):
            return Cart(cur.cart_id, cur.item_count + event.quantity,
                        cur.total_cents + event.quantity * event.unit_price_cents,
                        cur.checked_out, event.sequence_number)
        if isinstance(event, ItemRemoved):
            return Cart(cur.cart_id, cur.item_count - event.quantity,
                        cur.total_cents - event.quantity * event.unit_price_cents,
                        cur.checked_out, event.sequence_number)
        if isinstance(event, CheckedOut):
            return Cart(cur.cart_id, cur.item_count, cur.total_cents, True, event.sequence_number)
        return cur

    def replay_spec(self) -> ReplaySpec:
        return make_replay_spec()

    def decode_state(self, aggregate_id: str, state: Cart) -> Cart:
        """A cart decoded from its tensor columns, with the id the columns
        cannot carry (``cart_id`` is a string, outside the tensor schema):
        the restore hook of ``SurgeCommandBusinessLogic.decode_state``."""
        return Cart(aggregate_id, state.item_count, state.total_cents,
                    state.checked_out, state.version)


ADDED, REMOVED, CHECKED_OUT = 0, 1, 2


def make_registry() -> SchemaRegistry:
    reg = SchemaRegistry()
    reg.register_event(ItemAdded, type_id=ADDED, exclude=("cart_id",))
    reg.register_event(ItemRemoved, type_id=REMOVED, exclude=("cart_id",))
    reg.register_event(CheckedOut, type_id=CHECKED_OUT, exclude=("cart_id",))
    reg.register_state(Cart, exclude=("cart_id",))
    return reg


def make_replay_spec() -> ReplaySpec:
    def added(s, f):
        return {"item_count": s["item_count"] + f["quantity"],
                "total_cents": s["total_cents"] + f["quantity"] * f["unit_price_cents"],
                "checked_out": s["checked_out"],
                "version": f["sequence_number"]}

    def removed(s, f):
        return {"item_count": s["item_count"] - f["quantity"],
                "total_cents": s["total_cents"] - f["quantity"] * f["unit_price_cents"],
                "checked_out": s["checked_out"],
                "version": f["sequence_number"]}

    def checked_out(s, f):
        import jax.numpy as jnp
        return {"item_count": s["item_count"], "total_cents": s["total_cents"],
                "checked_out": jnp.asarray(True), "version": f["sequence_number"]}

    return ReplaySpec(
        registry=make_registry(),
        handlers=ReplayHandlers({ADDED: added, REMOVED: removed, CHECKED_OUT: checked_out}),
        init_record={"item_count": 0, "total_cents": 0, "checked_out": False, "version": 0},
        associative=make_associative_fold(),
    )


@_functools.cache
def make_associative_fold():
    """The cart fold as an associative transform monoid for sequence-parallel
    replay (surge_tpu.replay.seqpar): item/total deltas are additive,
    checked_out is OR-monotone, version is right-biased on any real event.
    Repeated factory calls are structurally equal, sharing seqpar's compiled
    programs and one-time conformance check."""
    import jax.numpy as jnp
    import numpy as np

    from surge_tpu.replay.seqpar import AssociativeFold

    def lift(ev):
        tid = ev["type_id"]
        add = tid == ADDED
        rem = tid == REMOVED
        real = add | rem | (tid == CHECKED_OUT)
        signed_qty = (jnp.where(add, ev["quantity"], 0)
                      - jnp.where(rem, ev["quantity"], 0))
        return {
            "d_items": signed_qty.astype(jnp.int32),
            "d_cents": (signed_qty * ev["unit_price_cents"]).astype(jnp.int32),
            "checked": tid == CHECKED_OUT,
            "has": real,
            "last_seq": jnp.where(real, ev["sequence_number"],
                                  0).astype(jnp.int32),
        }

    def combine(a, b):
        return {
            "d_items": a["d_items"] + b["d_items"],
            "d_cents": a["d_cents"] + b["d_cents"],
            "checked": a["checked"] | b["checked"],
            "has": a["has"] | b["has"],
            "last_seq": jnp.where(b["has"], b["last_seq"], a["last_seq"]),
        }

    def apply(state, s):
        return {
            "item_count": (state["item_count"] + s["d_items"]).astype(jnp.int32),
            "total_cents": (state["total_cents"] + s["d_cents"]).astype(jnp.int32),
            "checked_out": state["checked_out"] | s["checked"],
            "version": jnp.where(s["has"], s["last_seq"],
                                 state["version"]).astype(jnp.int32),
        }

    return AssociativeFold(
        lift=lift, combine=combine, apply=apply,
        identity={"d_items": np.int32(0), "d_cents": np.int32(0),
                  "checked": np.bool_(False), "has": np.bool_(False),
                  "last_seq": np.int32(0)})


_EVENTS = {c.__name__: c for c in (ItemAdded, ItemRemoved, CheckedOut)}


def state_formatting() -> JsonFormatting:
    return JsonFormatting(
        to_dict=lambda s: {k: getattr(s, k) for k in s.__dataclass_fields__},
        from_dict=lambda d: Cart(**d))


def event_formatting() -> JsonEventFormatting:
    def to_dict(e):
        d = {k: getattr(e, k) for k in e.__dataclass_fields__}
        d["_type"] = type(e).__name__
        return d

    def from_dict(d):
        d = dict(d)
        return _EVENTS[d.pop("_type")](**d)

    return JsonEventFormatting(to_dict=to_dict, from_dict=from_dict, key_of=lambda e: e.cart_id)
