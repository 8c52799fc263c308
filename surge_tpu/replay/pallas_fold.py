"""Pallas TPU kernel for the resident tile scan (the hot op).

The XLA lowering of the tile fold — ``lax.scan`` of a vmapped per-event step —
spends most of its time in per-step loop machinery, not arithmetic: the
measured fold rate sits far below the VPU's throughput for the few scalar ops
each event handler performs. This kernel runs the WHOLE tile scan inside one
``pallas_call``: the `[width, lanes]` word slab streams HBM→VMEM once per lane
block, the carry lives in registers/VMEM across all ``width`` steps, and the
per-event dispatch is the branchless select form (compute every handler,
mask-combine — pure VPU data flow).

Gated by ``surge.replay.tile-backend = pallas`` (never picked by ``auto``); on
CPU the kernel runs in interpreter mode so tests exercise the exact same
program, on TPU it compiles through Mosaic. Gather/expand and the tile
work-list loop stay in XLA — only the dense scan moves into the kernel.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

#: most lanes per kernel grid cell (8 sublanes × 128 lanes when viewed 2-D)
_LANE_BLOCK = 1024
#: VMEM the double-buffered input blocks of one grid cell may take, inside
#: Mosaic's 16 MiB default scoped limit
_VMEM_BUDGET = 12 << 20


def _interpret() -> bool:
    """Interpreter mode on the cpu backend only (where tests run the exact same
    program); ``tpu`` compiles through Mosaic; nothing else is supported."""
    import jax

    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise NotImplementedError(
            f"the Pallas fold kernels are written for tpu (compiled) and cpu "
            f"(interpreted); the default backend is {backend!r}")
    return backend == "cpu"


def _lane_tiling(bs: int, lane_bytes: int) -> tuple:
    """``(padded lanes, lanes per grid cell)`` for a tile whose input slabs
    hold ``lane_bytes`` per lane. Mosaic takes a block whose lane dimension is
    a multiple of 128, and a cell's blocks (double-buffered) must fit VMEM: the
    lane block is the largest such multiple inside the budget, and the tile is
    padded to whole blocks. Padding lanes carry length 0 — every slot masks to
    the pad sentinel."""
    fit = _VMEM_BUDGET // (2 * lane_bytes) // 128 * 128
    lb = min(max(fit, 128), _LANE_BLOCK, -(-bs // 128) * 128)
    return -(-bs // lb) * lb, lb


def make_tile_scan(spec, wire, width: int, bs: int, unroll: int):
    """Build ``(carry {f: [bs]}, words u32 [width, bs], sides {name: [width, bs]},
    lens_rel i32 [bs], ord_rel i32 [bs]) -> carry`` as a pallas_call.

    ``lens_rel`` is each lane's remaining length within this tile
    (``lens - t_base``); ``ord_rel`` is the lane's ordinal base shifted by the
    tile offset, so the derived ordinal of local step t is ``ord_rel + t + 1``
    — identical to the XLA tile's global-t decode."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from surge_tpu.replay.engine import make_step_fn

    # the select (branchless) step, applied to [LB] vectors directly — no vmap:
    # handlers are scalar jnp expressions that broadcast over the lane vector
    step = make_step_fn(spec, "select")
    state_fields = [f.name for f in spec.registry.state.fields]
    side_names = sorted(f.name for f in wire.side_fields)
    bs_p, lb = _lane_tiling(bs, width * (4 + sum(
        np.dtype(f.dtype).itemsize for f in wire.side_fields)))
    pad = bs_p - bs
    interpret = _interpret()
    # Mosaic has no 1-bit memrefs or loop carries (it refuses the i8 -> i1
    # truncation): bool state columns cross the kernel boundary and ride the
    # loop as int32, and are bool only inside one step
    bools = {f.name for f in spec.registry.state.fields
             if np.dtype(f.dtype) == np.bool_}

    def widen(state):
        return {n: v.astype(jnp.int32) if n in bools else v
                for n, v in state.items()}

    def narrow(state):
        return {n: v != 0 if n in bools else v for n, v in state.items()}

    def kernel(*refs):
        words_ref = refs[0]
        side_refs = dict(zip(side_names, refs[1: 1 + len(side_names)]))
        k = 1 + len(side_names)
        lens_ref, ord_ref = refs[k], refs[k + 1]
        in_refs = dict(zip(state_fields, refs[k + 2: k + 2 + len(state_fields)]))
        out_refs = dict(zip(state_fields, refs[k + 2 + len(state_fields):]))

        lens = lens_ref[0, :]
        ordr = ord_ref[0, :]
        state0 = {name: in_refs[name][0, :] for name in state_fields}

        def body(t, state):
            word = words_ref[t, :]
            side_row = {name: r[t, :] for name, r in side_refs.items()}
            events = wire.decode_words(word, side_row, t < lens, ordr, t)
            return widen(step(narrow(state), events))

        state = jax.lax.fori_loop(0, width, body, state0, unroll=unroll)
        for name in state_fields:
            out_refs[name][0, :] = state[name]

    grid = (bs_p // lb,)
    slab_spec = pl.BlockSpec((width, lb), lambda i: (0, i))
    # per-lane vectors ride as [1, lanes] rows: a 1-D operand keeps XLA's own
    # 1024-lane tiling, which Mosaic refuses for any other block length
    vec_spec = pl.BlockSpec((1, lb), lambda i: (0, i))

    def lanes(x):  # pad the (last) lane axis up to the lane tiling
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x

    def row(x):
        return lanes(x)[None, :]

    def tile_scan(carry: Mapping[str, Any], words, sides: Mapping[str, Any],
                  lens_rel, ord_rel):
        carry = widen(carry)
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[slab_spec] + [slab_spec] * len(side_names)
                     + [vec_spec, vec_spec] + [vec_spec] * len(state_fields),
            out_specs=[vec_spec] * len(state_fields),
            out_shape=[jax.ShapeDtypeStruct((1, bs_p), carry[n].dtype)
                       for n in state_fields],
            interpret=interpret,
        )(lanes(words), *(lanes(sides[n]) for n in side_names),
          row(lens_rel), row(ord_rel), *(row(carry[n]) for n in state_fields))
        return narrow({n: col[0, :bs] for n, col in zip(state_fields, out)})

    return tile_scan
