"""Open-loop request schedules, from a traffic mix's parameters and a seed.

Every seed gets the same work in another order: the request count is
``rate * seconds``; the gaps between arrivals are the quantiles of the
exponential distribution (a Poisson process's gaps), scaled to span the
window and shuffled; the request sizes are the quantiles of a log-uniform
distribution on ``[size_min, size_max]``, shuffled.  The ids of a request
are drawn Zipf(``zipf``) over the pool, whose popularity order is a fixed
permutation (``perm_seed``), so the hot ids are the same for every seed.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def zipf_cdf(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return np.cumsum(w) / w.sum()


def schedule(seed: int, seconds: float, rate: float, pool: Tuple[int, int],
             zipf: float, size_min: int, size_max: int, perm_seed: int
             ) -> List[Tuple[float, np.ndarray]]:
    """``[(offset_s, ids)]`` sorted by offset, offsets in ``[0, seconds)``."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5E7]))
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    gaps *= seconds / gaps.sum()
    offsets = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    lo, hi = np.log(size_min), np.log(size_max + 1)
    sizes = np.minimum(np.floor(np.exp(lo + q * (hi - lo))), size_max).astype(int)
    sizes = rng.permutation(sizes)
    n_pool = pool[1] - pool[0]
    order = pool[0] + np.random.default_rng(perm_seed).permutation(n_pool)
    cdf = zipf_cdf(n_pool, zipf)
    ranks = np.minimum(np.searchsorted(cdf, rng.random(int(sizes.sum()))), n_pool - 1)
    ids = order[ranks].astype(np.int32)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    return [(float(offsets[i]), ids[bounds[i]:bounds[i + 1]]) for i in range(n)]


def latencies(due: np.ndarray, done: np.ndarray, waited: float) -> np.ndarray:
    """Seconds from each request's due time to its answer.  A request with
    no answer (shed, failed, or never came: ``done`` is NaN) counts as
    missing every limit: it is charged the whole time the run waited."""
    due, done = np.asarray(due, np.float64), np.asarray(done, np.float64)
    return np.where(np.isnan(done), waited - due, done - due)


def percentile(values: np.ndarray, q: float) -> float:
    """``q``-th percentile (0..100), linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))
