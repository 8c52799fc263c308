"""The control of the cart's restore cell: the plain reference put in the
restore's place, with the configuration's guarantee broken two ways.

``cart-segment-restore`` states that after a restore the store holds, under
each cart's id, the bytes of the scalar fold of the cart's whole log,
``cart_id`` included. The control's store is the reference's own, made from a
copy of the log in which one cart in a thousand has lost its last event, as a
restore from a segment that missed an acknowledged write would leave it, and
with one cart in a thousand (another one) stored without its id, as the
restore left every cart before the model's ``decode_state`` hook. It is judged
by the very comparison a run uses, against the whole log.
"""

from __future__ import annotations

import json

import numpy as np

from benchmarks import reference_cart_restore
from benchmarks.controls.cart_rebuild import lossy_copy
from benchmarks.drivers import cart_restore as driver


def control(run) -> list:
    corpus, ids = driver.build_inputs(run)
    lossy = lossy_copy(corpus, np.arange(0, corpus.num_aggregates, 1000))
    store = reference_cart_restore.expected_store(lossy, ids)
    for b in range(500, corpus.num_aggregates, 1000):
        store[ids[b]]["cart_id"] = ""
    items = {key: json.dumps(state).encode() for key, state in store.items()}
    return driver.judge(corpus, ids,
                        [(items, lossy.num_events, lossy.num_aggregates)],
                        run.config["check"]["scalar_fold_sample"], run.seed)
