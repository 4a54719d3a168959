"""The one traffic generator: every mix is a data file of parameters.

A mix (``mixes/<name>.json``) sets:

  parties_per_round   K, the updates that make one round
  arrivals            "closed": a round's K updates are all due when the
                      round starts, and the next round starts once the last
                      fused model is ready (a buffered round, drained late);
                      "poisson": open loop, updates due at exponential
                      gaps at ``rate_updates_per_s``, one schedule for
                      every seed
  drain               "round": the aggregator is deployed once the round's K
                      updates are published (deferred, JIT or lazy);
                      "arrival": each update is folded as it arrives (eager)
  n_examples          [lo, hi]: each update's dataset size, the weight it
                      gets, drawn uniformly per update

Every seed gets the same work. The open loop's gaps are the exponential's
quantiles in one fixed shuffled order (``PATTERN_SEED``): every seed serves
the same arrival schedule, so a window of a given length holds the same
number of updates and rounds, each with the same arrivals inside it. The
seed draws the updates, which party's update arrives at each due time, and
its weight, per round. (Arrivals drawn per seed, even as whole rounds in a
seeded order, moved the median round latency by the seed more than two
runs of one seed moved it.)
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

#: the fixed order of the open loop's gaps, the same for every seed
PATTERN_SEED = 0


@dataclasses.dataclass(frozen=True)
class RoundPlan:
    order: np.ndarray       # (K,) which of the K party updates, in order
    n_examples: np.ndarray  # (K,) the weight of each, in that order
    due: Optional[np.ndarray]  # (K,) seconds from the window's start; None
    #                            where all are due when the round starts


@dataclasses.dataclass(frozen=True)
class Traffic:
    k: int
    closed: bool
    drain_each: bool
    n_rounds: Optional[int]  # None: as many as the window holds (closed)
    seed: int
    n_lo: int
    n_hi: int
    due: Optional[np.ndarray]  # (n_rounds*K,) open loop's due times

    def round(self, r: int) -> RoundPlan:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 1, r]))
        order = rng.permutation(self.k)
        n_ex = rng.integers(self.n_lo, self.n_hi + 1, size=self.k)
        due = None if self.due is None else self.due[r * self.k:(r + 1) * self.k]
        return RoundPlan(order, n_ex, due)


def make(mix: dict, seed: int, seconds: float) -> Traffic:
    k = int(mix["parties_per_round"])
    lo, hi = (int(x) for x in mix["n_examples"])
    if k < 1 or not 1 <= lo <= hi:
        raise ValueError(f"bad mix: K={k}, n_examples=[{lo}, {hi}]")
    arrivals, drain = mix["arrivals"], mix["drain"]
    if drain not in ("round", "arrival"):
        raise ValueError(f"unknown drain {drain!r}")
    if arrivals == "closed":
        return Traffic(k, True, drain == "arrival", None, seed, lo, hi, None)
    if arrivals != "poisson":
        raise ValueError(f"unknown arrivals {arrivals!r}")
    rate = float(mix["rate_updates_per_s"])
    n_rounds = int(rate * seconds) // k
    n = n_rounds * k
    if n_rounds < 1:
        raise ValueError(f"a {seconds} s window at {rate}/s holds no round")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(np.random.default_rng(PATTERN_SEED).permutation(gaps))
    return Traffic(k, False, drain == "arrival", n_rounds, seed, lo, hi, due)
