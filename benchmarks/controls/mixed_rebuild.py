"""The control of the mixed rebuild's cell: the plain reference put in the
program's place, with the fault only this deployment can have.

``mixed-rebuild`` states that every pulled state equals the fold of its own
family's handlers over its whole log. The control answers with the reference's
own states, except that for one aggregate in a thousand the family's type-id
base is off by one, so that its events reach the handler next door (the
next type of its own family, or the first of another's, whose columns it then
moves), as a merge with a wrong offset table would, and is judged by the very
comparison a run uses.
"""

from __future__ import annotations

import types

import numpy as np

from benchmarks import gen_mixed, reference_mixed
from benchmarks.drivers import mixed_rebuild as driver


def shifted_answer(corpus: gen_mixed.MixedCorpus, victims: np.ndarray) -> dict:
    """The reference's union states, with the union aggregates in ``victims``
    folded under a type-id base one too high."""
    states = reference_mixed.closed_form(corpus)
    for i, family in enumerate(gen_mixed.FAMILIES):
        mine = victims[corpus.family[victims] == i]
        local = np.searchsorted(corpus.ids(family), mine)
        folded = reference_mixed.scalar_fold_sample(corpus, family, local,
                                                    shift=1)
        for j, k in zip(mine.tolist(), local.tolist()):
            for name, value in zip(reference_mixed.FIELDS, folded[k]):
                states[name][j] = value
    return states


def control(run) -> list:
    corpus = gen_mixed.mixed_corpus(run.sizes["aggregates"], run.sizes["events"],
                                    run.seed, run.config["corpus"])
    victims = np.arange(0, corpus.num_aggregates, 1000)
    answer = types.SimpleNamespace(states=shifted_answer(corpus, victims),
                                   num_events=corpus.num_events)
    return driver.judge(corpus, [answer],
                        run.config["check"]["scalar_fold_sample"], run.seed)
