"""Open-loop request schedules, a pure function of the seed.

A schedule of ``rate * seconds`` requests whose gaps are the exponential
distribution's quantiles at (k + 1/2) / n and whose sizes are the
log-uniform integers' quantiles at the same points, each put in an order
drawn from the seed. So every seed offers exactly the same gaps and sizes,
only in a different order: the arrivals of a Poisson process at ``rate``
and log-uniform sizes over [size_min, size_max], with none of the
seed-to-seed swing in total work that independent draws would bring.
"""
from __future__ import annotations

import math

import numpy as np


def open_loop(seed: int, rate: float, seconds: float, size_min: int, size_max: int):
    """(send offsets in seconds, ascending; request sizes in rows)."""
    n = max(1, round(rate * seconds))
    u = (np.arange(n, dtype=np.float64) + 0.5) / n
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    gaps = -np.log1p(-u) / rate
    lo, hi = math.log(size_min), math.log(size_max + 1)
    sizes = np.minimum(np.floor(np.exp(lo + u * (hi - lo))), size_max).astype(np.int64)
    gaps = gaps[rng.permutation(n)]
    sizes = sizes[rng.permutation(n)]
    return np.cumsum(gaps), sizes


def request_rows(seed: int, total: int, features: int) -> np.ndarray:
    """(total, features) f32 raw rows, standard normal like the HIGGS-like
    training features, from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    return rng.standard_normal((total, features), dtype=np.float32)
