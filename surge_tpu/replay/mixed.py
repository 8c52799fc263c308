"""Mixed aggregate-type replay: heterogeneous models folded in ONE batch.

The reference runs one engine per aggregate type; each type's KTable restores
independently (SURVEY.md §2.6). On TPU that leaves the chip idle while small
families restore serially — so this module combines several models'
:class:`~surge_tpu.engine.model.ReplaySpec`\\ s into one: event type_ids get
disjoint ranges, event/state columns merge into one union layout (tagged-union
columns — each lane only ever reads its own model's fields, SURVEY.md §5.7
"masked vmap for heterogeneous aggregate types"), and the per-type
``lax.switch`` dispatch already built into the fold does the rest. One
``ReplayEngine`` over the combined spec then folds counters, carts and bank
accounts side by side in the same ``[B]`` batch.

Two ways in. The columnar one is the bulk path: :meth:`MixedReplay.merge_columnar`
takes each family's ``ColumnarEvents`` and gives the union's, whole-column, and
:meth:`MixedReplay.split_states` gives each family back its own columns of the
pulled union state. The scalar-world bridges (`encode_logs`, `decode_states`)
take Python objects a log at a time, for tests and small batches; both keep each
lane's model identity, as ``order`` / ``models``: the family of every lane.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Mapping, Sequence

import numpy as np

from surge_tpu.codec.schema import FieldSpec, SchemaRegistry
from surge_tpu.codec.tensor import ColumnarEvents
from surge_tpu.codec.wire import WireFormat
from surge_tpu.engine.model import ReplayHandlers, ReplaySpec
from surge_tpu.replay.profiler import ReplayProfiler
from surge_tpu.tracing import default_tracer


def fields_read(spec: ReplaySpec) -> dict[int, frozenset]:
    """``{type id: the event fields its handler reads}``, found by running
    each handler once on abstract values with a mapping that notes what is
    looked up in it (``fields[name]``). A type with no handler reads none."""
    import jax

    state = {f.name: jax.ShapeDtypeStruct((), f.dtype)
             for f in spec.registry.state.fields}
    cols = {f.name: jax.ShapeDtypeStruct((), f.dtype)
            for f in spec.registry.union_columns()}
    out = {}
    for tid, handler in spec.handlers.by_type_id.items():
        seen: set = set()

        class Noting(dict):
            def __getitem__(self, key):
                seen.add(key)
                return dict.__getitem__(self, key)

        jax.eval_shape(lambda s, f, h=handler: h(s, Noting(f)), state, cols)
        out[tid] = frozenset(seen)
    return out


@dataclass
class MixedReplay:
    """A combined spec plus the per-model bookkeeping to use it."""

    spec: ReplaySpec
    #: model name -> type_id offset of its events in the combined registry
    bases: dict[str, int]
    #: model name -> its original ReplaySpec
    parts: dict[str, ReplaySpec]

    def type_id(self, model: str, local_type_id: int) -> int:
        return self.bases[model] + local_type_id

    def families(self, order) -> np.ndarray:
        """``order`` (the family of every lane: model names, or indices into
        the sorted names as :attr:`bases` lists them) as int8 indices ``[B]``."""
        names = np.array(list(self.bases))
        got = np.asarray(order)
        if not got.size:
            return np.zeros(0, dtype=np.int8)
        if got.dtype.kind in "iu":
            if not 0 <= got.min() <= got.max() < len(names):
                raise ValueError(f"family index outside 0..{len(names) - 1}")
            return got.astype(np.int8)
        fam = np.searchsorted(names, got).clip(max=len(names) - 1)
        if not np.array_equal(names[fam], got):
            raise KeyError(f"unknown model among {sorted(set(got.tolist()))}; "
                           f"the spec combines {list(self.bases)}")
        return fam.astype(np.int8)

    def merge_columnar(self, parts: Mapping[str, ColumnarEvents], order,
                       profiler: ReplayProfiler | None = None
                       ) -> ColumnarEvents:
        """One union ``ColumnarEvents`` from each family's own, whole-column.

        ``parts[model]`` holds that family's aggregates ``0..B_m - 1`` under
        its own type ids and columns; ``order`` says which family every union
        aggregate belongs to, and the k-th lane of a family is its aggregate
        ``k``. The union keeps logs grouped by aggregate in the order of the
        union's ids (so families interleave as ``order`` does), offsets type
        ids by :attr:`bases`, holds every union column with zeros where a
        family has no such field, and carries the parts' ``derived_cols``
        (which must agree: a column one family derives, no family supplies).
        A family that is absent from ``parts`` has no lane in ``order``.

        The span ``replay.mixed.merge`` counts what the union costs: ``events``
        (and ``events_<model>``), ``union_columns``, ``union_side_bytes`` (the
        side columns' wire bytes, every event paying for every column) and
        ``live_side_bytes`` (the part of them some handler of the row's own
        type reads, :func:`fields_read`)."""
        fam = self.families(order)
        b = fam.shape[0]
        derived: dict[str, str] = {}
        for part in parts.values():
            derived.update(part.derived_cols)
        wire = WireFormat(self.spec.registry, derived)
        profiler = profiler or ReplayProfiler.counters(tracer=default_tracer())
        with profiler.stage("mixed.merge", families=len(parts)) as span:
            # model -> (its lanes' union ids, its grouped log, the columns it
            # supplies)
            logs, lengths = {}, np.zeros(b, dtype=np.int64)
            for i, model in enumerate(self.bases):
                ids = np.flatnonzero(fam == i)
                part = parts.get(model)
                held = part.num_aggregates if part is not None else 0
                if held != ids.size:
                    raise ValueError(
                        f"order gives {model!r} {ids.size} aggregates, "
                        f"its part holds {held}")
                if part is None:
                    continue
                own = {f.name for f in
                       self.parts[model].registry.union_columns()}
                if set(part.derived_cols) != own & set(derived):
                    raise ValueError(
                        f"{model!r} supplies a column another family derives: "
                        f"{dict(part.derived_cols)} against {derived}")
                logs[model] = (ids, part.sorted_by_aggregate(),
                               own - set(derived))
                lengths[ids] = np.bincount(part.agg_idx,
                                           minlength=part.num_aggregates)
            starts = np.zeros(b + 1, dtype=np.int64)
            np.cumsum(lengths, out=starts[1:])
            n = int(starts[-1])
            type_ids = np.empty(n, dtype=np.int32)
            cols = {f.name: np.zeros(n, dtype=f.dtype)
                    for f in self.spec.registry.union_columns()
                    if f.name not in derived}
            reads = fields_read(self.spec)
            side = {f.name: f.dtype.itemsize for f in wire.side_fields}
            live = 0
            for model, (ids, part, supplied) in logs.items():
                own = lengths[ids]
                first = np.zeros(own.shape[0] + 1, dtype=np.int64)
                np.cumsum(own, out=first[1:])
                # where each of the family's events lies in the union: its
                # log's start there, less its log's start here, plus its place
                dest = np.repeat(starts[ids] - first[:-1], own)
                dest += np.arange(part.num_events, dtype=np.int64)
                # an id outside the family's own types (padding, corrupt)
                # stays outside the union's: it must reach no other family
                kinds = self.parts[model].registry.num_event_types
                real = (part.type_ids >= 0) & (part.type_ids < kinds)
                type_ids[dest] = np.where(
                    real, part.type_ids + np.int32(self.bases[model]), -1)
                for name in supplied:
                    cols[name][dest] = part.cols[name]
                span.set_attribute(f"events_{model}", part.num_events)
                per_type = np.bincount(part.type_ids[real], minlength=kinds)
                live += sum(
                    int(count) * sum(side.get(name, 0) for name in reads.get(
                        self.bases[model] + tid, ()))
                    for tid, count in enumerate(per_type.tolist()))
            span.set_attribute("events", n)
            span.set_attribute("union_columns", len(cols))
            span.set_attribute("union_side_bytes", n * sum(side.values()))
            span.set_attribute("live_side_bytes", live)
            return ColumnarEvents(
                num_aggregates=b,
                agg_idx=np.repeat(np.arange(b, dtype=np.int32), lengths),
                type_ids=type_ids, cols=cols, derived_cols=derived)

    def split_states(self, order, states: Mapping[str, np.ndarray]
                     ) -> dict[str, dict[str, np.ndarray]]:
        """The inverse of :meth:`merge_columnar` on the pulled union state:
        ``{model: {field: [B_m]}}``, each family's own fields of its own lanes,
        in the family's own dtypes and the order of its aggregates."""
        fam = self.families(order)
        out = {}
        for i, model in enumerate(self.bases):
            lanes = fam == i
            out[model] = {f.name: np.asarray(states[f.name])[lanes]
                          .astype(f.dtype, copy=False)
                          for f in self.parts[model].registry.state.fields}
        return out

    def encode_logs(self, tagged_logs: Sequence[tuple[str, Sequence[Any]]]
                    ) -> ColumnarEvents:
        """Columnar-encode per-aggregate logs tagged with their model name.

        Events must already be in their tensor form (e.g. bank_account's
        vocab-encoded ``EncodedCreated``). The merged registry maps each event
        class to its offset type_id, so this delegates to the codec's grouped
        ``encode_events_columnar`` (one comprehension per (type, field), not a
        per-event Python loop); the model tags are only needed later, by
        :meth:`init_carry` and :meth:`decode_states`."""
        from surge_tpu.codec.tensor import encode_events_columnar

        return encode_events_columnar(self.spec.registry,
                                      [log for _, log in tagged_logs])

    def init_carry(self, models) -> dict[str, np.ndarray]:
        """Per-lane initial carry: each lane starts at ITS model's init record
        (models may disagree about a shared column's default). ``models`` as
        :meth:`families` takes it; one masked store a model and column."""
        fam = self.families(models)
        out = {f.name: np.zeros(fam.shape, dtype=f.dtype)
               for f in self.spec.registry.state.fields}
        for i, model in enumerate(self.bases):
            lanes = fam == i
            for name, v in self.parts[model].init_state_tree().items():
                out[name][lanes] = v
        return out

    def decode_states(self, models: Sequence[str],
                      states: Mapping[str, np.ndarray]) -> list[Any]:
        """Decode the folded union columns lane by lane through each lane's own
        model state schema."""
        out = []
        for i, m in enumerate(models):
            schema = self.parts[m].registry.state
            rec = {f.name: states[f.name][i] for f in schema.fields}
            out.append(schema.from_record(rec))
        return out


def combine_replay_specs(specs: Mapping[str, ReplaySpec]) -> MixedReplay:
    """Merge model families into one replayable spec (sorted by model name so
    type-id assignment is deterministic).

    Shared column names are legal — the union layout promotes dtypes and each
    lane's handlers only touch their own model's fields — but one event CLASS
    may not belong to two models.

    The combined spec's own ``init_record`` is empty (all-zero lanes): a
    per-model initial state cannot be expressed globally because lanes of
    different models share columns. Models that declare a nonzero
    ``init_record`` are therefore REFUSED here — use
    :func:`combine_replay_specs_with_init` to acknowledge that, and always
    supply ``init_carry=mixed.init_carry(models)`` to the fold."""
    return _combine(specs, allow_nonzero_init=False)


def combine_replay_specs_with_init(specs: Mapping[str, ReplaySpec]) -> MixedReplay:
    """:func:`combine_replay_specs` for model sets with nonzero init records —
    the caller promises to pass ``init_carry=mixed.init_carry(models)``."""
    return _combine(specs, allow_nonzero_init=True)


def _combine(specs: Mapping[str, ReplaySpec], *,
             allow_nonzero_init: bool) -> MixedReplay:
    merged = SchemaRegistry()
    bases: dict[str, int] = {}
    handlers: dict[int, Any] = {}
    state_fields: dict[str, np.dtype] = {}
    offset = 0
    for name in sorted(specs):
        spec = specs[name]
        if not allow_nonzero_init and any(
                np.any(np.asarray(v) != 0) for v in spec.init_record.values()):
            raise ValueError(
                f"model {name!r} declares a nonzero init_record, which a "
                "combined spec cannot honor per-lane; use "
                "combine_replay_specs_with_init and pass "
                "init_carry=mixed.init_carry(models) to the fold")
        bases[name] = offset
        for schema in spec.registry.event_schemas:
            merged.register_event(schema.cls,
                                  type_id=offset + schema.type_id,
                                  fields=schema.fields)
        for tid, h in spec.handlers.by_type_id.items():
            handlers[offset + tid] = h
        for f in spec.registry.state.fields:
            if f.name in state_fields:
                state_fields[f.name] = np.promote_types(state_fields[f.name],
                                                        f.dtype)
            else:
                state_fields[f.name] = f.dtype
        offset += spec.registry.num_event_types

    fields = tuple(FieldSpec(n, state_fields[n]) for n in sorted(state_fields))
    cls = dataclasses.make_dataclass(
        "MixedState", [(f.name, object) for f in fields])
    merged.register_state(cls, fields=fields)
    combined = ReplaySpec(registry=merged,
                          handlers=ReplayHandlers(by_type_id=handlers),
                          init_record={})
    return MixedReplay(spec=combined, bases=bases, parts=dict(specs))
