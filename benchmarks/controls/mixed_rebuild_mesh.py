"""The control of the sharded mixed rebuild's cell: the plain reference put in
the program's place, with the mixed control's fault and one more that only a
sharded rebuild can have.

``mixed-rebuild-mesh4`` states what ``mixed-rebuild`` states, and that the
states come back in the original aggregate order though four devices held
them. The control answers as the mixed control does (one aggregate in a
thousand folded under a type-id base off by one), and besides writes the last
device's states back one lane off: the last quarter of the aggregates, which
is what the last of four devices holds of a log dealt in aggregate order, each
holds its neighbour's state, as a pull that un-dealt that device's rows from
the wrong offset would leave them. Judged by the very comparison a run uses.
"""

from __future__ import annotations

import types

import numpy as np

from benchmarks import gen_mixed
from benchmarks.controls.mixed_rebuild import shifted_answer
from benchmarks.drivers import mixed_rebuild_mesh as driver


def one_lane_off(states: dict, devices: int) -> dict:
    """``states`` with the last device's share rolled by one aggregate."""
    n = len(next(iter(states.values())))
    lo = n - n // devices
    out = {}
    for name, col in states.items():
        col = np.array(col)
        col[lo:] = np.roll(col[lo:], 1)
        out[name] = col
    return out


def control(run) -> list:
    corpus = gen_mixed.mixed_corpus(run.sizes["aggregates"], run.sizes["events"],
                                    run.seed, run.config["corpus"])
    victims = np.arange(0, corpus.num_aggregates, 1000)
    states = one_lane_off(shifted_answer(corpus, victims), run.cell["chips"])
    answer = types.SimpleNamespace(states=states, num_events=corpus.num_events)
    return driver.judge(corpus, [answer],
                        run.config["check"]["scalar_fold_sample"], run.seed)
