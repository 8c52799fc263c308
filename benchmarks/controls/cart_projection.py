"""The control of the cart's projection cell: the plain reference put in the
scan's place, with the configuration's guarantees broken two ways.

``cart-projection-rebuild`` states that the scan covers every event of the
committed segment once and that every output of every group is exact. The
control's rows are the reference's own, made from a copy of the log in which
one group in a thousand has lost one ``ItemAdded`` event, as a scan that
skipped an acknowledged write would leave it, and another one in a thousand
is keyed one code off (every event of it carries the next code up, the
catalogue's last code among them, so its rows land under keys that are
another group's or no group's at all). It is judged by the very comparison a
run uses, against the whole log.
"""

from __future__ import annotations

import numpy as np

from benchmarks import gen_cart, reference_cart_projection
from benchmarks.drivers import cart_projection as driver
from benchmarks.drivers import cart_restore


def broken_copy(corpus: gen_cart.CartCorpus, codes: int) -> gen_cart.CartCorpus:
    """``corpus`` without the first ``ItemAdded`` event of every thousandth
    code, counted up from code 0, and with every event of every thousandth
    code, counted down from the last, moved to the next code up."""
    added = np.flatnonzero(corpus.type_ids == gen_cart.ADDED)
    first = added[np.unique(corpus.item_code[added], return_index=True)[1]]
    lost = first[corpus.item_code[first] % 1000 == 0]
    item_code = corpus.item_code.copy()
    item_code[(codes - 1 - item_code) % 1000 == 0] += 1
    lengths = corpus.lengths.copy()
    np.subtract.at(lengths, corpus.agg_idx[lost], 1)
    return gen_cart.CartCorpus(
        corpus.num_aggregates, lengths,
        *(np.delete(col, lost) for col in (
            corpus.agg_idx, corpus.type_ids, item_code, corpus.quantity,
            corpus.unit_price_cents)))


def control(run) -> list:
    corpus, _ids = cart_restore.build_inputs(run)
    codes = int(run.config["corpus"]["item_codes"])
    broken = broken_copy(corpus, codes)
    rows = reference_cart_projection.expected_rows(broken, codes + 1)
    return driver.judge(
        corpus, codes,
        [(rows, broken.num_events,
          reference_cart_projection.matched_events(broken))],
        run.config["check"]["scalar_sample_codes"], run.seed)
