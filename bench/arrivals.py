"""Open-loop arrival times from a seed.

The arrivals of a Poisson process at ``rate`` per second, with the work held
fixed across seeds. The inter-arrival gaps come in blocks of ``block``
arrivals; every block holds the same gaps, the quantiles of the
exponential distribution at (i + 1/2) / block, in an order drawn from the
seed. So every seed offers the same n = round(rate * seconds) requests in
exactly ``seconds``, with the same load over every stretch of ``block``
arrivals; the seed changes only the order inside each block. (With one
shuffle over the whole window, the seed moved the p95 latency of the serve
cells by a fifth: where the bursts fell decided it.) The arithmetic follows
the seeded open-loop generator of ``repro.serve.traffic``, with times on
the wall clock.
"""
from __future__ import annotations

import numpy as np


def schedule(seed: int, rate: float, seconds: float, block: int,
             salt: int = 0) -> np.ndarray:
    """Due times in [0, seconds), ascending."""
    n = max(int(round(rate * seconds)), 1)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, salt])
    gaps = []
    for start in range(0, n, block):
        m = min(block, n - start)
        gaps.append(rng.permutation(-np.log1p(-(np.arange(m) + 0.5) / m)))
    gaps = np.concatenate(gaps)
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])
