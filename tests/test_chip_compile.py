"""The cold fold's lane-row fetch compiled for a v5e that is described, not
attached (the TPU's compiler is installed here): what the chip's compiler
makes of the programs at the benchmark cells' own shapes. Nothing runs, so
nothing here is a time; results are held by tests/test_lane_rows.py.

The compiles run in ONE child process (this file, run as a script), started
by a fixture: the TPU's library then never loads into a pytest worker, whose
peak RSS a later test's own children would inherit (``ru_maxrss`` survives
``exec``: tests/test_restore_bounded.py measures just that), and only one
process at a time holds the library's lock."""

import json
import os
import re
import subprocess
import sys

import pytest

N = 1 << 27  # the cells' bucketed wire: 100M events + guard, to a power of two
LANES = 1 << 20
WIDTH, BS = 512, 8192
NO_TOPOLOGY = 3  # the child's exit code where no v5e can be described


def compile_report() -> dict:
    """``{program: {gather: [while loops, gathers, temp bytes]}}`` of the
    counter's and the cart's ``jit_fold`` (assoc backend), fetched both ways,
    compiled for one chip of a described v5e:2x2."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from surge_tpu.codec.wire import WireFormat
    from surge_tpu.models import counter, shopping_cart
    from surge_tpu.replay.engine import _PIECE_ROWS, _make_mk_word, _make_tile

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no compiler for it here: nothing to check
        print(f"no v5e:2x2 topology can be described here: {e}",
              file=sys.stderr)
        sys.exit(NO_TOPOLOGY)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def counts(compiled):
        text = compiled.as_text()
        return [len(re.findall(r"\bwhile\(", text)),
                len(re.findall(r"\bgather\(", text)),
                compiled.memory_analysis().temp_size_in_bytes]

    lanes = shape((LANES,), jnp.int32)
    work = shape((128,), jnp.int32)
    report = {}
    for name, model in (("counter_fold", counter), ("cart_fold", shopping_cart)):
        spec = model.make_replay_spec()
        wire = WireFormat(spec.registry, {"sequence_number": "ordinal"})
        slab = {f.name: shape((LANES,), f.dtype)
                for f in spec.registry.state.fields}
        side = {f.name: shape((N,), f.dtype) for f in wire.side_fields}
        report[name] = {}
        for gather in ("rows", "slices"):
            view, tile = _make_tile(spec, wire, WIDTH, BS, "assoc", gather)

            def fold(slab_state, flat_wire, side_flat, starts, lens, ords,
                     i0s, t_bases, k_n):
                buffers = view(flat_wire, side_flat)
                return jax.lax.fori_loop(
                    0, k_n, lambda k, st: tile(st, buffers, starts, lens,
                                               ords, i0s[k], t_bases[k]),
                    slab_state)

            report[name][gather] = counts(jax.jit(fold).lower(
                slab, shape((N, wire.nbytes), jnp.uint8), side, lanes, lanes,
                lanes, work, work, shape((), jnp.int32)).compile())
    # the upload's word build, one piece of the sources into the bucket: the
    # counter's three int32 sources, the cart's type ids alone
    report["mk_word"] = {}
    for name, model in (("counter", counter), ("cart", shopping_cart)):
        wire = WireFormat(model.make_replay_spec().registry,
                          {"sequence_number": "ordinal"})
        piece = shape((_PIECE_ROWS,), jnp.int32)
        memory = _make_mk_word(wire).lower(
            shape((N, wire.nbytes), jnp.uint8),
            shape((len(wire.packed_fields),), jnp.bool_), piece,
            [piece] * len(wire.packed_fields),
            shape((), jnp.int32)).compile().memory_analysis()
        report["mk_word"][name] = [memory.temp_size_in_bytes,
                                   memory.alias_size_in_bytes]
    return report


@pytest.fixture(scope="module")
def report():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TPU_LOG_DIR": "disabled",
           "PYTHONPATH": root}
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode == NO_TOPOLOGY:
        pytest.skip(proc.stderr.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_counters_fold_fetches_rows_with_one_gather_and_no_lane_loop(report):
    """The counter's ``jit_fold``, one array: the one ``while`` is the walk
    over the work list; the slices' second, 8192 trips of one slice, is one
    gather more."""
    rows, slices = (report["counter_fold"][g] for g in ("rows", "slices"))
    assert (rows[0], slices[0]) == (1, 2)
    # the tree fold's strided halvings are gathers too, in both lowerings
    assert rows[1] - slices[1] == 1
    # the widened word is the only buffer the fetch adds: 4 B an event
    assert 0 < rows[2] - slices[2] <= 4 * N + (1 << 20)


def test_the_carts_fold_fetches_rows_without_a_lane_loop(report):
    """The cart's ``jit_fold``, assoc backend: one ``while`` over the
    tiles where the parent nests one of 8192 trips for every array read."""
    loops, gathers, _ = report["cart_fold"]["rows"]
    assert loops == 1 and gathers >= 1
    assert report["cart_fold"]["slices"][0] > 1


@pytest.mark.parametrize("cell", ["counter", "cart"])
def test_the_word_build_places_a_piece_into_its_bucket_in_place(report, cell):
    """``jit_mk_word`` at the cells' shapes (a piece of 2^22 rows into the
    bucket of 2^27): the chip's compiler takes it, the donated bucket is
    the output (no second bucket on the device) and what it adds is under
    one piece of one int32 source."""
    temp, aliased = report["mk_word"][cell]
    assert aliased >= N  # the bucket, at one byte a row or more
    assert temp < 4 * (1 << 22)


if __name__ == "__main__":
    print(json.dumps(compile_report()))
