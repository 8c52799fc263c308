"""The plain reference of the cart's item-demand projection: for every item
code the log carries, how often it was added, how many units, the highest unit
price seen. From the generator's columns alone, never from the segment; plain
numpy; imports nothing of the program and takes nothing it made.

Two forms of the same semantics. For every code, the whole-column form:
``count`` by ``np.bincount``, ``sum_quantity`` by an int64 ``np.add.at`` (then
shown to fit the device's int32), ``max_unit_price_cents`` by
``np.maximum.at``. For a sample of codes, a scalar loop, event by event.

A group is every code SOME event of the log carries, whatever its type (a
``CheckedOut`` carries code 0); a code no ``ItemAdded`` event carries reports
0 everywhere. A group's key is its code as a decimal string.
"""

from __future__ import annotations

import numpy as np

from benchmarks.gen_cart import ADDED, INT32_MAX, CartCorpus

OUTPUTS = ("count", "sum_quantity", "max_unit_price_cents")


def rollup(corpus: CartCorpus, codes: int) -> tuple:
    """``(present, {output: [codes] int64})``: ``present[c]`` whether any
    event carries code ``c``; the three outputs over the ``ItemAdded`` events,
    0 where there is none. ``codes`` is one more than the largest code."""
    present = np.bincount(corpus.item_code, minlength=codes) > 0
    added = corpus.type_ids == ADDED
    code = corpus.item_code[added]
    count = np.bincount(code, minlength=codes).astype(np.int64)
    units = np.zeros(codes, dtype=np.int64)
    np.add.at(units, code, corpus.quantity[added].astype(np.int64))
    dearest = np.zeros(codes, dtype=np.int64)  # prices are positive
    np.maximum.at(dearest, code, corpus.unit_price_cents[added].astype(np.int64))
    # the system reduces in the device dtype, int32: exact only while it fits
    largest = max(int(units.max(initial=0)), int(count.max(initial=0)))
    if largest > INT32_MAX:
        raise ValueError(f"an output of {largest} does not fit int32")
    return present, dict(zip(OUTPUTS, (count, units, dearest)))


def expected_rows(corpus: CartCorpus, codes: int) -> dict:
    """{key: (count, sum_quantity, max_unit_price_cents)} for every group."""
    present, out = rollup(corpus, codes)
    keep = np.flatnonzero(present)
    columns = [out[name][keep].tolist() for name in OUTPUTS]
    return {str(code): tuple(row)
            for code, *row in zip(keep.tolist(), *columns)}


def matched_events(corpus: CartCorpus) -> int:
    return int(np.count_nonzero(corpus.type_ids == ADDED))


def scalar_rows(corpus: CartCorpus, sample) -> dict:
    """{key: (count, sum_quantity, max_unit_price_cents)} for the codes in
    ``sample`` that some event carries, by a loop over their events one at a
    time in Python ints."""
    mine = np.flatnonzero(np.isin(corpus.item_code, np.asarray(sample)))
    rows: dict = {}
    for kind, code, quantity, price in zip(
            corpus.type_ids[mine].tolist(), corpus.item_code[mine].tolist(),
            corpus.quantity[mine].tolist(),
            corpus.unit_price_cents[mine].tolist()):
        row = rows.setdefault(code, [0, 0, 0])
        if kind != ADDED:
            continue
        row[0] += 1
        row[1] += quantity
        if price > row[2]:
            row[2] = price
    return {str(code): tuple(row) for code, row in rows.items()}
