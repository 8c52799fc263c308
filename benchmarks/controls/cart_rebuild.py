"""The control of the cart's rebuild cell: the plain reference put in the
program's place, with the one guarantee the configuration states broken.

``cart-rebuild`` states that every pulled state equals the fold of the cart's
whole log. The control folds a copy of the log in which one cart in a thousand
has lost its last event, as a rebuild over a log that had acknowledged a write
it never made durable would, and is judged by the very comparison a run uses,
against the whole log.
"""

from __future__ import annotations

import types

import numpy as np

from benchmarks import gen_cart, reference_cart
from benchmarks.drivers import cart_rebuild as driver


def lossy_copy(corpus: gen_cart.CartCorpus,
               victims: np.ndarray) -> gen_cart.CartCorpus:
    """``corpus`` without the last event of each cart in ``victims``."""
    victims = victims[corpus.lengths[victims] > 0]
    last = corpus.starts()[victims + 1] - 1
    lengths = corpus.lengths.copy()
    lengths[victims] -= 1
    return gen_cart.CartCorpus(
        corpus.num_aggregates, lengths,
        *(np.delete(col, last) for col in (
            corpus.agg_idx, corpus.type_ids, corpus.item_code, corpus.quantity,
            corpus.unit_price_cents)))


def control(run) -> list:
    corpus = gen_cart.cart_corpus(run.sizes["aggregates"], run.sizes["events"],
                                  run.seed, run.config["corpus"])
    lossy = lossy_copy(corpus, np.arange(0, corpus.num_aggregates, 1000))
    answer = types.SimpleNamespace(states=reference_cart.closed_form(lossy),
                                   num_events=lossy.num_events)
    return driver.judge(corpus, [answer],
                        run.config["check"]["scalar_fold_sample"], run.seed)
