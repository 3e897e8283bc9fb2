"""Deletion-request traffic: seeded Poisson arrivals and a Zipf victim sampler.

``client_sampler`` and ``iter_poisson_trace`` are copies of the generator in
``repro.service.workload`` (kept here so that a change to the program cannot
change the yardstick); ``tests/test_harness.py`` holds them element for
element against the program's generator for one seed.  No cell sends
deletion requests yet; a deletions cell's driver reads its arrivals here.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np


def client_sampler(pool: Sequence[int], seed: int, skew: float = 0.0,
                   replace: bool = True):
    """Seeded victim sampler over ``pool``; client at popularity rank r is
    drawn with probability proportional to ``1 / (r+1)**skew``."""
    rng = np.random.default_rng(seed)
    order = list(rng.permutation(np.asarray(list(pool))))
    probs = np.array([1.0 / (r + 1) ** skew for r in range(len(order))])
    probs /= probs.sum()

    def sample(k: int = 1) -> List[int]:
        nonlocal order, probs
        if not replace and k > len(order):
            raise ValueError(f"pool exhausted: {k} requested, "
                             f"{len(order)} left")
        idx = rng.choice(len(order), size=k, replace=replace, p=probs)
        out = [int(order[i]) for i in idx]
        if not replace:
            drawn = set(idx.tolist())
            keep = [i for i in range(len(order)) if i not in drawn]
            order = [order[i] for i in keep]
            probs = probs[keep]
            if probs.sum() > 0:
                probs = probs / probs.sum()
        return out

    return sample


def iter_poisson_trace(pool: Sequence[int], n: int, rate: float,
                       seed: int = 0, skew: float = 0.0):
    """Yields ``(t, victims)`` for ``n`` one-victim requests with
    Exponential(1/rate) inter-arrival times, in the program generator's RNG
    order."""
    rng = np.random.default_rng(seed)
    sample = client_sampler(pool, seed + 1, skew, True)
    t = 0.0
    for _ in range(n):
        t += float(rng.exponential(1.0 / rate))
        yield t, tuple(sample(1))
