"""The control of a rebuild cell: the plain reference put in the program's
place, with the one guarantee the configuration states broken.

``counter-rebuild`` states that every pulled state equals the fold of the
aggregate's whole log. The control folds a copy of the log in which one
aggregate in a thousand has lost its last event, as a rebuild over a log that
had acknowledged a write it never made durable would, and is judged by the very
comparison a run uses, against the whole log.
"""

from __future__ import annotations

import types

import numpy as np

from benchmarks import gen, reference
from benchmarks.drivers import rebuild as driver


def lossy_copy(corpus: gen.Corpus, victims: np.ndarray) -> gen.Corpus:
    """``corpus`` without the last event of each aggregate in ``victims``."""
    victims = victims[corpus.lengths[victims] > 0]
    last = corpus.starts()[victims + 1] - 1
    lengths = corpus.lengths.copy()
    lengths[victims] -= 1
    return gen.Corpus(corpus.num_aggregates, lengths,
                      np.delete(corpus.agg_idx, last),
                      np.delete(corpus.type_ids, last),
                      np.delete(corpus.inc, last), np.delete(corpus.dec, last))


def control(run) -> list:
    corpus = gen.counter_corpus(run.sizes["aggregates"], run.sizes["events"],
                                run.seed, run.config["corpus"])
    lossy = lossy_copy(corpus, np.arange(0, corpus.num_aggregates, 1000))
    count, version = reference.closed_form(lossy)
    answer = types.SimpleNamespace(states={"count": count, "version": version},
                                   num_events=lossy.num_events)
    return driver.judge(corpus, [answer],
                        run.config["check"]["scalar_fold_sample"], run.seed)
