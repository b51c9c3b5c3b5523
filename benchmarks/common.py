"""Shared benchmark utilities: k-means + NMI (no sklearn offline), CSV row
emission in the required ``name,us_per_call,derived`` format."""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

ROWS: List[Tuple[str, float, str]] = []

# --smoke (benchmarks.run): every benchmark runs ~2 steps so the suite
# exercises each module's full code path in seconds.  Numbers emitted in
# smoke mode are NOT measurements — the mode exists so benchmarks can't
# silently rot between perf runs.
SMOKE = False


def steps(n: int, smoke_n: int = 2) -> int:
    """Loop-count helper: the requested count, or ``smoke_n`` under --smoke."""
    return min(n, smoke_n) if SMOKE else n


def emit(name: str, us_per_call: float, derived: str):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}")


BENCH_MODES = ("native", "interpret")


def bench_entry(name: str, *, mode: str, dtype: str, **fields) -> dict:
    """Canonical BENCH_*.json record.  Every entry MUST carry its execution
    ``mode`` ("native" = the real backend, "interpret" = pallas interpret /
    CPU semantics check — NOT a perf measurement) and the decode ``dtype``
    ("float32" / "bfloat16" / "int8"), so a number can never be read
    without the context that decides whether it means anything.  Writers
    build entries through this helper; tools/ci.sh --bench asserts dtype
    and the quality columns on the committed BENCH_compression.json."""
    if mode not in BENCH_MODES:
        raise ValueError(f"bench entry {name!r}: mode must be one of "
                         f"{BENCH_MODES}, got {mode!r}")
    if not dtype or not isinstance(dtype, str):
        raise ValueError(f"bench entry {name!r}: missing dtype")
    return {"name": name, "mode": mode, "dtype": dtype, **fields}


# ---------------- tiny kmeans + NMI (paper §B.1.4 evaluation) --------------

def kmeans(x: np.ndarray, k: int, iters: int = 30, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    centers = x[rng.choice(x.shape[0], k, replace=False)].copy()
    assign = np.zeros(x.shape[0], np.int64)
    for _ in range(iters):
        d = ((x[:, None, :] - centers[None]) ** 2).sum(-1)
        new_assign = d.argmin(1)
        if (new_assign == assign).all():
            break
        assign = new_assign
        for j in range(k):
            pts = x[assign == j]
            if len(pts):
                centers[j] = pts.mean(0)
    return assign


def nmi(a: np.ndarray, b: np.ndarray) -> float:
    """Normalized mutual information (sqrt normalisation)."""
    a = np.asarray(a)
    b = np.asarray(b)
    n = a.shape[0]
    ua, ub = np.unique(a), np.unique(b)
    cont = np.zeros((len(ua), len(ub)))
    for i, x in enumerate(ua):
        for j, y in enumerate(ub):
            cont[i, j] = np.sum((a == x) & (b == y))
    p = cont / n
    pa = p.sum(1, keepdims=True)
    pb = p.sum(0, keepdims=True)
    with np.errstate(divide="ignore", invalid="ignore"):
        mi = np.nansum(p * np.log(p / (pa @ pb)))
        ha = -np.nansum(pa * np.log(pa))
        hb = -np.nansum(pb * np.log(pb))
    return float(mi / max(np.sqrt(ha * hb), 1e-12))
