"""The row-to-state step of the bulk paths (``codec/tensor.py``:
``state_columns`` + ``state_materializer``, behind ``decode_states``, the
restores' ``_write_back`` and the resident plane's gather lane), held to the
scalar chain it replaced: ``StateSchema.from_record`` a row, then
``restore._with_aggregate_id``, then the caller's hooks. What a row's class
can be asked is asked once a schema, never once a row."""

import dataclasses
from dataclasses import dataclass, field

import numpy as np
import pytest

from surge_tpu.codec import FieldSpec, StateSchema, decode_states
from surge_tpu.codec.tensor import state_columns, state_materializer
from surge_tpu.config import default_config
from surge_tpu.log import InMemoryLog
from surge_tpu.models import counter, shopping_cart
from surge_tpu.replay.resident_state import ResidentStatePlane
from surge_tpu.store.restore import _with_aggregate_id, _write_back

ROWS = 4096


# -- the classes a schema may name ------------------------------------------------------


@dataclass(frozen=True)
class Plain:
    a: int
    b: int


@dataclass(frozen=True)
class Excluded:
    """One excluded field of each annotation `_construct` knows a neutral value
    for, and one it does not (None)."""

    name: str
    a: int
    spare_int: int
    spare_float: float
    spare_bool: bool
    spare_other: bytes
    b: int


@dataclass(frozen=True)
class Defaults:
    a: int
    b: int
    label: str = "kept"
    tags: list = field(default_factory=lambda: ["fresh"])


@dataclass(frozen=True)
class WithId:
    aggregate_id: str
    a: int
    b: int


@dataclass(frozen=True)
class WithEmptyIdDefault:
    a: int
    b: int
    aggregate_id: str = ""


@dataclass(frozen=True)
class WithOwnId:
    a: int
    b: int
    aggregate_id: str = "its-own"


CLASSES = [Plain, Excluded, Defaults, WithId, WithEmptyIdDefault, WithOwnId]


def schema_of(cls, dtype_a=np.int32, dtype_b=np.int32) -> StateSchema:
    return StateSchema(cls=cls, fields=(FieldSpec("a", dtype_a),
                                        FieldSpec("b", dtype_b)))


def old_decode_states(schema, tree):
    """``decode_states`` as it was: a dict of numpy scalars a row through
    ``from_record``."""
    arrays = {f.name: np.asarray(tree[f.name]) for f in schema.fields}
    b = len(next(iter(arrays.values()))) if arrays else 0
    return [schema.from_record({n: a[i] for n, a in arrays.items()})
            for i in range(b)]


def same_state(got, want) -> bool:
    """Equal field for field, the value's type and bits included (nan, -0.0
    and a bool against a 1 tell apart)."""
    if type(got) is not type(want):
        return False
    for f in dataclasses.fields(want):
        v, w = getattr(got, f.name), getattr(want, f.name)
        if type(v) is not type(w) or repr(v) != repr(w):
            return False
        if type(v) not in (int, float, bool, str, list, type(None)):
            return False  # never a numpy scalar
    return True


def values_of(dtype) -> np.ndarray:
    dt = np.dtype(dtype)
    if dt.kind == "b":
        return np.array([True, False, False, True, True])
    if dt.kind == "f":
        return np.array([np.nan, np.inf, -np.inf, -0.0, 0.0, 1e-45, 1.5,
                         np.finfo(dt).max, 0.1], dtype=dt)
    info = np.iinfo(dt)
    return np.array([info.min, info.max, 0, 1, info.max // 3], dtype=dt)


# -- (b) equality with the old path -----------------------------------------------------


@pytest.mark.parametrize("dtype", ["int8", "int16", "int32", "uint8", "uint16",
                                   "bool", "float32"])
def test_decode_states_equals_from_record_a_row_for_every_dtype_kind(dtype):
    col = values_of(dtype)
    schema = schema_of(Plain, dtype, dtype)
    tree = {"a": col, "b": col[::-1].copy()}
    got, want = decode_states(schema, tree), old_decode_states(schema, tree)
    assert len(got) == len(col)
    assert all(same_state(g, w) for g, w in zip(got, want)), (got, want)
    if np.dtype(dtype).kind == "f":
        assert repr(got[3].a) == "-0.0" and repr(got[0].a) == "nan"
        assert got[5].a == float(np.float32(1e-45)) != 0.0  # the subnormal


@pytest.mark.parametrize("column, field_dtype", [
    ("int32", "bool"), ("bool", "int32"), ("float32", "int32"),
    ("int32", "float32"), ("uint8", "int32"), ("int64", "int32")])
def test_a_column_of_another_kind_than_its_field_converts_as_from_record(
        column, field_dtype):
    """The field's dtype kind decides the Python type, not the array's: an
    int32 column of a bool field gives bools."""
    col = np.array([0, 1, 2, 0, 1], dtype=column)
    schema = schema_of(Plain, field_dtype, field_dtype)
    tree = {"a": col, "b": col}
    got, want = decode_states(schema, tree), old_decode_states(schema, tree)
    assert all(same_state(g, w) for g, w in zip(got, want)), (got, want)
    assert type(got[0].a) is {"b": bool, "i": int, "f": float}[
        np.dtype(field_dtype).kind]


@pytest.mark.parametrize("cls", CLASSES, ids=lambda c: c.__name__)
def test_decode_states_equals_from_record_a_row_for_every_class(cls):
    schema = schema_of(cls)
    tree = {"a": np.arange(7, dtype=np.int32), "b": np.arange(7, 0, -1, dtype=np.int32)}
    got, want = decode_states(schema, tree), old_decode_states(schema, tree)
    assert len(got) == 7
    assert all(same_state(g, w) for g, w in zip(got, want)), (got, want)
    if cls is Excluded:
        assert (got[0].name, got[0].spare_int, got[0].spare_float,
                got[0].spare_bool, got[0].spare_other) == ("", 0, 0.0, False, None)
    if cls is Defaults:  # a factory's value is the row's own
        assert got[0].tags == ["fresh"] and got[0].tags is not got[1].tags


@pytest.mark.parametrize("hook", [False, True], ids=["no_hook", "decode_state"])
@pytest.mark.parametrize("cls", CLASSES, ids=lambda c: c.__name__)
def test_with_ids_the_materializer_is_the_scalar_restore_chain(cls, hook):
    """``from_record`` -> ``_with_aggregate_id`` -> ``decode_state``: an
    empty id is filled, a class's own kept, a class without the field left."""
    schema = schema_of(cls)
    tree = {"a": np.arange(5, dtype=np.int32), "b": np.arange(5, dtype=np.int32)}
    ids = [f"agg-{i}" for i in range(5)]
    seen = []

    def decode(agg_id, state):
        seen.append(agg_id)
        return (agg_id, state)

    make = state_materializer(schema, decode if hook else None, with_ids=True)
    cols = state_columns(schema, tree)
    got = [make(a, cols, j) for j, a in enumerate(ids)]
    want = [_with_aggregate_id(s, a)
            for a, s in zip(ids, old_decode_states(schema, tree))]
    if hook:
        assert seen == ids and [g[0] for g in got] == ids
        got = [g[1] for g in got]
    assert all(same_state(g, w) for g, w in zip(got, want)), (got, want)
    if cls in (WithId, WithEmptyIdDefault):
        assert [g.aggregate_id for g in got] == ids
    if cls is WithOwnId:
        assert {g.aggregate_id for g in got} == {"its-own"}


def test_state_columns_takes_the_first_rows_only():
    schema = schema_of(Plain)
    tree = {"a": np.arange(9, dtype=np.int32), "b": np.arange(9, dtype=np.int32)}
    assert state_columns(schema, tree, 3) == [[0, 1, 2], [0, 1, 2]]
    assert decode_states(StateSchema(cls=Plain, fields=()), {}) == []


def test_a_state_class_that_is_no_dataclass_raises_as_it_did():
    class Bare:
        def __init__(self, a, b):
            self.a, self.b = a, b

    schema = schema_of(Bare)
    tree = {"a": np.zeros(2, np.int32), "b": np.zeros(2, np.int32)}
    with pytest.raises(TypeError):
        old_decode_states(schema, tree)
    with pytest.raises(TypeError):
        decode_states(schema, tree)


# -- (a) the mechanism held -------------------------------------------------------------


class Recorder:
    """Recording hooks and store: every call in order."""

    def __init__(self):
        self.calls = []
        self.items = {}

    def decode_state(self, agg_id, state):
        self.calls.append(("decode", agg_id))
        return state

    def serialize_state(self, agg_id, state):
        self.calls.append(("serialize", agg_id))
        return f"{agg_id}:{state.a}".encode()

    def put(self, key, value):
        self.calls.append(("put", key))
        self.items[key] = value


@pytest.mark.parametrize("cls", [Plain, WithId], ids=lambda c: c.__name__)
def test_the_class_is_asked_once_a_chunk_and_built_once_a_row(cls, monkeypatch):
    built = []

    @dataclass(frozen=True)
    class Counted(cls):
        def __post_init__(self):
            built.append(1)

    asked = []
    fields = dataclasses.fields

    def counting_fields(obj):
        asked.append(obj)
        return fields(obj)

    monkeypatch.setattr(dataclasses, "fields", counting_fields)
    schema = schema_of(Counted)
    tree = {"a": np.arange(ROWS, dtype=np.int32), "b": np.zeros(ROWS, np.int32)}
    ids = [f"agg-{i:05d}" for i in range(ROWS)]
    rec, restored = Recorder(), set()

    states = decode_states(schema, tree)
    assert len(states) == ROWS and len(built) == ROWS  # one __init__ a row
    written = _write_back(rec, ids, states, rec.serialize_state,
                          rec.decode_state, restored)
    assert len(asked) < 16, len(asked)  # not once a row
    # a class with the field pays one `replace` a row for its id, as before;
    # one without pays nothing
    assert len(built) == ROWS * (2 if cls is WithId else 1)
    assert len(rec.items) == len(restored) == ROWS
    assert written == sum(len(v) for v in rec.items.values())


# -- (c) the hooks' contract ------------------------------------------------------------


@pytest.mark.parametrize("hook", [True, False], ids=["decode_state", "no_hook"])
def test_write_back_calls_each_hook_once_a_row_in_chunk_order(hook):
    schema = schema_of(WithId)
    tree = {"a": np.arange(6, dtype=np.int32), "b": np.zeros(6, np.int32)}
    ids = [f"agg-{i}" for i in (3, 1, 4, 0, 5, 2)]
    states = decode_states(schema, tree)
    states[2] = None  # skipped before any hook
    given = []
    rec, restored = Recorder(), {"already-there"}
    serialize = rec.serialize_state

    def serialize_state(agg_id, state):
        given.append((agg_id, state))
        return serialize(agg_id, state)

    written = _write_back(rec, ids, states, serialize_state,
                          rec.decode_state if hook else None, restored)
    kept = [a for a in ids if a != "agg-4"]
    steps = (["decode"] if hook else []) + ["serialize", "put"]
    assert rec.calls == [(step, a) for a in kept for step in steps]
    # the state a hook sees carries its id; the stored bytes are the hook's
    assert [(a, s.aggregate_id) for a, s in given] == [(a, a) for a in kept]
    assert rec.items == {a: f"{a}:{s.a}".encode() for a, s in given}
    assert written == sum(len(v) for v in rec.items.values())
    assert restored == {"already-there", *kept}


def test_write_back_asks_each_class_of_a_mixed_chunk():
    """A cpu-backend restore hands over whatever the model's fold returned:
    the id question follows the row's class."""
    states = [Plain(1, 1), WithId("", 2, 2), WithId("own", 3, 3), Plain(4, 4),
              WithOwnId(5, 5)]
    ids = ["p", "q", "r", "s", "t"]
    got = []
    _write_back(Recorder(), ids, states,
                lambda a, s: got.append(s) or b"x", None, set())
    assert got == [_with_aggregate_id(s, a) for a, s in zip(ids, states)]
    assert [getattr(s, "aggregate_id", None) for s in got] == [
        None, "q", "own", None, "its-own"]


# -- (d) the plane reads through the moved materializer ---------------------------------


@pytest.mark.parametrize("model", ["counter", "cart", "cart_without_hook"])
def test_the_planes_batch_read_is_its_scalar_read(model):
    """``_states_of_batch`` (the gather lane) against ``_state_of`` (the
    scalar chain the plane keeps for a spilled aggregate), row for row."""
    if model == "counter":
        spec, decode = counter.make_replay_spec(), None
    else:
        cart = shopping_cart.CartModel()
        spec = cart.replay_spec()
        decode = cart.decode_state if model == "cart" else None
    plane = ResidentStatePlane(
        InMemoryLog(), "events", spec, config=default_config(),
        deserialize_event=lambda raw: raw, serialize_state=lambda a, s: b"",
        decode_state=decode)
    rng = np.random.default_rng(5)
    k, padded = 37, 64
    rows = {f.name: rng.integers(0, 2 if f.dtype.kind == "b" else 1000, padded)
            .astype(f.dtype) for f in spec.registry.state.fields}
    ids = [f"agg-{j:03d}" for j in range(k)]
    got = plane._states_of_batch(ids, rows, k)
    want = [plane._state_of(a, {n: col[j] for n, col in rows.items()}, j)
            for j, a in enumerate(ids)]
    assert len(got) == k
    assert all(same_state(g, w) for g, w in zip(got, want)), (got[0], want[0])
    if model == "counter":
        assert [s.aggregate_id for s in got] == ids
    else:
        assert [s.cart_id for s in got] == (ids if decode else [""] * k)
