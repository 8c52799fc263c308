"""Inputs from ``--seed``: the counter corpus, the preloaded node log, keys, arrivals.

Plain numpy; imports nothing of the program. The event mix and the lognormal
length law are copies of ``surge_tpu/replay/corpus.py:synth_counter_corpus``
(PERF.md lists the original for a later PR to delete); the answers a corpus
must fold to are in ``reference.py``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# the counter's event types, as its schema registers them (0..3)
INCREMENTED, DECREMENTED, NOOP, UNSERIALIZABLE = 0, 1, 2, 3


@dataclass
class Corpus:
    """A counter log, aggregate-sorted, time-ordered within an aggregate."""

    num_aggregates: int
    lengths: np.ndarray  # [B] int64 events per aggregate
    agg_idx: np.ndarray  # [N] int32
    type_ids: np.ndarray  # [N] int32
    inc: np.ndarray  # [N] int32, increment_by (0 where the type has none)
    dec: np.ndarray  # [N] int32, decrement_by

    @property
    def num_events(self) -> int:
        return int(self.type_ids.shape[0])

    def starts(self) -> np.ndarray:
        out = np.zeros(self.num_aggregates + 1, dtype=np.int64)
        np.cumsum(self.lengths, out=out[1:])
        return out


def log_lengths(law: dict, num_aggregates: int, num_events: int,
                rng: np.random.Generator, block: int | None = None) -> np.ndarray:
    """Events per aggregate under a configuration's ``corpus`` law, summing
    exactly to ``num_events``. ``fixed``: every log as long as the next (the
    remainder, where there is one, goes to the first logs). ``lognormal``:
    lognormal lengths of sigma ``length_sigma`` around the same mean.

    Where the law names a ``lengths_seed``, the lengths are drawn from that
    seed alone, and ``rng`` only orders them within each run of ``block``
    aggregates (the whole log where ``block`` is None): every seed then gets
    the same lengths, and the same events in each block, in its own order."""
    kind = law["length_law"]
    own = "lengths_seed" in law
    draw = np.random.default_rng(int(law["lengths_seed"])) if own else rng
    if kind == "fixed":
        lengths = np.full(num_aggregates, num_events // num_aggregates,
                          dtype=np.int64)
    elif kind == "lognormal":
        w = draw.lognormal(mean=0.0, sigma=float(law["length_sigma"]),
                           size=num_aggregates)
        lengths = np.floor(w * (num_events / w.sum())).astype(np.int64)
    else:
        raise ValueError(f"unknown length_law {kind!r}")
    lengths[: num_events - int(lengths.sum())] += 1
    if own:
        step = block or num_aggregates
        for lo in range(0, num_aggregates, step):
            rng.shuffle(lengths[lo:lo + step])
    return lengths


def counter_corpus(num_aggregates: int, num_events: int, seed: int,
                   law: dict) -> Corpus:
    """The counter's log under ``law`` (a configuration's ``corpus`` group):
    its ``length_law``, and its ``event_mix`` as the shares of increment (by
    1..3), decrement (by 1..2), no-op and unserializable."""
    rng = np.random.default_rng(seed)
    lengths = log_lengths(law, num_aggregates, num_events, rng)
    n = int(lengths.sum())
    agg_idx = np.repeat(np.arange(num_aggregates, dtype=np.int32), lengths)
    cuts = np.round(np.cumsum(law["event_mix"])[:3] * 10_000).astype(np.int64)
    draw = rng.integers(0, 10_000, size=n, dtype=np.uint16)
    type_ids = ((draw >= cuts[0]).astype(np.int32) + (draw >= cuts[1])
                + (draw >= cuts[2]))
    inc = np.where(type_ids == INCREMENTED,
                   rng.integers(1, 4, size=n, dtype=np.int32), 0).astype(np.int32)
    dec = np.where(type_ids == DECREMENTED,
                   rng.integers(1, 3, size=n, dtype=np.int32), 0).astype(np.int32)
    return Corpus(num_aggregates, lengths, agg_idx, type_ids, inc, dec)


def sample_aggregates(num_aggregates: int, k: int, seed: int,
                      always=()) -> np.ndarray:
    """``k`` aggregate indices drawn from the seed, plus the ones in ``always``."""
    rng = np.random.default_rng([seed, 0x5A])
    pick = rng.choice(num_aggregates, size=min(k, num_aggregates), replace=False)
    return np.unique(np.concatenate([pick, np.asarray(always, dtype=np.int64)]))


def preload_kinds(num_aggregates: int, per: int, seed: int) -> np.ndarray:
    """The node's preloaded log: per aggregate ``per`` events, 60% increment (0),
    30% decrement (1), 10% no-op (2), as ``chip_smoke.Served`` stamps them."""
    draw = np.random.default_rng(seed).integers(0, 100, size=(num_aggregates, per))
    return ((draw >= 60).astype(np.int8) + (draw >= 90)).astype(np.int8)


def zipf_ranks(n_keys: int, s: float, count: int) -> np.ndarray:
    """``count`` 0-based key ranks that follow the Zipf law ``r**-s`` exactly as
    far as ``count`` draws can: the law's quantiles at evenly spaced
    probabilities, so every seed gets the same multiset of ranks (the same hot
    keys as often) and only their order and their aggregates differ."""
    cum = np.cumsum(np.arange(1, n_keys + 1, dtype=np.float64) ** -s)
    u = (np.arange(count, dtype=np.float64) + 0.5) / count * cum[-1]
    return np.minimum(np.searchsorted(cum, u, side="left"), n_keys - 1)


def exponential_gaps(rate: float, count: int) -> np.ndarray:
    """The exponential law's quantiles at evenly spaced probabilities: Poisson
    inter-arrival gaps whose multiset is the same for every seed."""
    u = (np.arange(count, dtype=np.float64) + 0.5) / count
    return -np.log1p(-u) / rate


@dataclass
class Schedule:
    """An open-loop schedule: operation ``i`` is due ``due[i]`` seconds after the
    window opens, whatever became of the ones before it."""

    due: np.ndarray  # [N] float64 seconds, ascending
    is_command: np.ndarray  # [N] bool
    is_increment: np.ndarray  # [N] bool (commands only)
    key: np.ndarray  # [N] int64 aggregate index


def open_loop_schedule(traffic: dict, n_keys: int, seconds: float, seed: int,
                       rate: float | None = None) -> Schedule:
    """One general generator over a traffic file's parameters. Every seed has
    the same gaps, the same number of commands and reads and the same key
    ranks; the seed orders them and says which aggregate holds which rank."""
    rate = float(rate if rate is not None else traffic["rate_ops_per_s"])
    count = int(round(rate * seconds))
    rng = np.random.default_rng([seed, 0x0A])
    if traffic.get("arrivals", "poisson") == "poisson":
        gaps = rng.permutation(exponential_gaps(rate, count))
    else:
        gaps = np.full(count, 1.0 / rate)
    due = np.cumsum(gaps)
    due *= min(1.0, (seconds * (1 - 0.5 / count)) / max(due[-1], 1e-9))
    n_cmd = int(round(count * traffic["command_share"]))
    is_command = np.zeros(count, dtype=bool)
    is_command[:n_cmd] = True
    is_command = rng.permutation(is_command)
    is_increment = np.zeros(count, dtype=bool)
    inc = np.zeros(n_cmd, dtype=bool)
    inc[: int(round(n_cmd * traffic["increment_share"]))] = True
    is_increment[is_command] = rng.permutation(inc)
    law = traffic["keys"]
    if law["law"] == "zipf":
        ranks = rng.permutation(zipf_ranks(n_keys, float(law["s"]), count))
    else:
        ranks = rng.integers(0, n_keys, size=count)
    holder = rng.permutation(n_keys)  # rank -> aggregate index
    return Schedule(due, is_command, is_increment, holder[ranks])
