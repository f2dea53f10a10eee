"""The one traffic generator: a mix file's parameters -> a request plan.

A mix is a JSON file of parameters:

    arrival   "poisson": open loop at ``rate_rps``; "backlog": every request
              is due at the window's start and each serve call takes
              ``take`` of them
    rate_rps  offered rate of a poisson mix
    arrival_seed
              the seed of a poisson mix's arrival schedule
    take      requests per serve call (null: every request that is due)
    pool      distinct images, generated from the seed and cycled
    warm      batch sizes the set-up serves once, so that every program
              the window runs is compiled before it starts

Poisson gaps are the exponential distribution's quantiles at (k + 0.5) / n,
scaled so that all n = rate * seconds arrivals fall inside the window, and
put in an order drawn from the mix's ``arrival_seed``. Every ``--seed`` then
offers the same requests at the same times: the seed draws the weights, the
images and which image each request sends, not how much work there is or
when it comes, so a run's tail does not depend on the seed's luck.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Plan:
    offsets: np.ndarray | None   # due times from the window's start (s);
    #                              None = backlog (all due at the start)
    take: int | None             # requests per serve call (None = all due)
    image_of: np.ndarray         # request k serves pool image image_of[k % len]
    warm: tuple[int, ...]        # batch sizes served once in set-up


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per purpose, from any whole-number seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def poisson_offsets(rate_rps: float, seconds: float,
                    gen: np.random.Generator) -> np.ndarray:
    n = max(1, int(round(rate_rps * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds * (n - 0.5) / n / gaps.sum()
    return np.cumsum(gen.permutation(gaps))


def plan(mix: dict, seconds: float, seed: int) -> Plan:
    pool = int(mix["pool"])
    image_of = rng(seed, 1).permutation(pool)
    take = mix.get("take")
    if mix["arrival"] == "poisson":
        offsets = poisson_offsets(float(mix["rate_rps"]), seconds,
                                  rng(int(mix["arrival_seed"]), 2))
    elif mix["arrival"] == "backlog":
        offsets = None
        if not take:
            raise ValueError("a backlog mix needs `take` (requests per call)")
    else:
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    return Plan(offsets=offsets, take=int(take) if take else None,
                image_of=image_of, warm=tuple(int(b) for b in mix["warm"]))
