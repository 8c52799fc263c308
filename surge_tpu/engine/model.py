"""User-facing processing-model API + the TPU replay contract.

Scalar side mirrors the reference's model family (scaladsl/command/CommandModels.scala:12-74):
``AggregateCommandModel`` (sync ``process_command``/``handle_event``),
``AsyncAggregateCommandModel``, and the event-engine-only ``AggregateEventModel``
(scaladsl/event/AggregateEventModel.scala:10-38). Rejections are exceptions
(``RejectedCommand``) rather than Try/Failure.

TPU side (**new — the point of this framework**): a model may attach a :class:`ReplaySpec`
declaring its tensor schemas and a per-event-type JAX step function. The replay engine
(surge_tpu.replay) lifts those steps into ``lax.switch`` inside a ``lax.scan`` over
time-major event columns, ``vmap``-ed across aggregates — the batched form of the
per-aggregate ``handleEvent`` fold at CommandModels.scala:20-27 / SURVEY.md §3.3.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Generic, Mapping, Optional, Protocol, Sequence, TypeVar

from surge_tpu.codec.schema import SchemaRegistry

S = TypeVar("S")
C = TypeVar("C")
E = TypeVar("E")

# A state "record" on the tensor path: dict of scalar jnp values, one per state column.
StateTree = Dict[str, Any]
# Event fields at one timestep: dict of scalar jnp values, one per union column.
EventFields = Mapping[str, Any]
# One event type's JAX step: (state, fields) -> state. Pure, traceable, scalar (the
# engine vmaps it across the aggregate batch).
JaxEventHandler = Callable[[StateTree, EventFields], StateTree]


class RejectedCommand(Exception):
    """Domain rejection of a command (reference: Failure(...) from processCommand,
    surfaced as CommandFailure — scaladsl/common/AggregateRefResult.scala:5-11)."""


class AggregateCommandModel(Protocol[S, C, E]):
    """Sync command model — scaladsl AggregateCommandModel (CommandModels.scala:12-31).

    ``process_command`` returns the events to persist (raise :class:`RejectedCommand` to
    reject); ``handle_event`` is the pure fold the engine applies — and the function the
    TPU replay path batches.
    """

    def initial_state(self, aggregate_id: str) -> Optional[S]:
        return None

    def process_command(self, state: Optional[S], command: C) -> Sequence[E]: ...

    def handle_event(self, state: Optional[S], event: E) -> Optional[S]: ...


class AsyncAggregateCommandModel(Protocol[S, C, E]):
    """Async variant — scaladsl AsyncAggregateCommandModel (CommandModels.scala:33-52).
    Used by the multilanguage bridge where handlers are RPCs to another process
    (GenericAsyncAggregateCommandModel.scala:14-104)."""

    def initial_state(self, aggregate_id: str) -> Optional[S]:
        return None

    async def process_command(self, state: Optional[S], command: C) -> Sequence[E]: ...

    async def handle_events(self, state: Optional[S], events: Sequence[E]) -> Optional[S]: ...


class AggregateEventModel(Protocol[S, E]):
    """Event-engine-only model — scaladsl/event/AggregateEventModel.scala:10-38.
    ``apply_events`` folds externally-produced events; there is no command side."""

    def initial_state(self, aggregate_id: str) -> Optional[S]:
        return None

    def apply_events(self, state: Optional[S], events: Sequence[E]) -> Optional[S]: ...


def fold_events(model: AggregateCommandModel, state: Optional[S], events: Sequence[E]) -> Optional[S]:
    """The scalar fold (reference: events.foldLeft at CommandModels.scala:20-21).

    Prefers per-event ``handle_event``; falls back to a synchronous batch
    ``handle_events``. Async-only models (e.g. the multilanguage gRPC model) cannot
    fold offline — bulk restore must go through a scalar-capable model."""
    import inspect

    handle_event = getattr(model, "handle_event", None)
    if handle_event is not None:
        for ev in events:
            state = handle_event(state, ev)
        return state
    batch = getattr(model, "handle_events", None)
    if batch is not None and not inspect.iscoroutinefunction(batch):
        return batch(state, list(events))
    raise TypeError(
        f"{type(model).__name__} has no synchronous fold (handle_event or "
        f"non-async handle_events) — offline replay/restore is unavailable for "
        f"async-only models")


# --------------------------------------------------------------------------------------
# TPU replay contract
# --------------------------------------------------------------------------------------


@dataclass(frozen=True)
class ReplayHandlers:
    """Per-event-type JAX step functions keyed by the registry's type_ids."""

    by_type_id: Mapping[int, JaxEventHandler]

    def ordered(self, num_types: int) -> list[JaxEventHandler]:
        """Dense handler table for ``lax.switch``; missing ids get identity."""
        identity: JaxEventHandler = lambda state, fields: state
        return [self.by_type_id.get(tid, identity) for tid in range(num_types)]


@dataclass
class ReplaySpec:
    """Everything the TPU replay engine needs to batch-fold one model family.

    - ``registry``: event/state tensor schemas (surge_tpu.codec.schema).
    - ``handlers``: the JAX form of ``handle_event``, split per event type.
    - ``init_record``: column values of the "empty" state (the ``None`` aggregate).
      Replay starts every aggregate here unless a snapshot carry is supplied.
    """

    registry: SchemaRegistry
    handlers: ReplayHandlers
    init_record: Dict[str, Any] = field(default_factory=dict)
    #: optional AssociativeFold (surge_tpu.replay.seqpar) — when present, the
    #: replay engine's ``auto`` tile backend folds each tile by lift +
    #: order-preserving tree reduction instead of a sequential time scan
    #: (12.5 µs a step of 8192 lanes on the v5e: PERF.md, PR 31), and the
    #: time axis can shard across a mesh. Law-checked on first use.
    associative: Any = None

    def init_state_tree(self) -> StateTree:
        """Scalar init record with schema-complete columns (missing fields → 0)."""
        import numpy as np

        out: StateTree = {}
        for f in self.registry.state.fields:
            v = self.init_record.get(f.name, 0)
            out[f.name] = np.asarray(v, dtype=f.dtype)
        return out


class ReplayableModel(Protocol):
    """A model that supports the TPU replay backend (``replay_backend = "tpu"``,
    BASELINE.json north star). ``replay_spec`` is consulted by the state-store bulk
    restore and by surge_tpu.replay directly."""

    def replay_spec(self) -> ReplaySpec: ...
