"""Seeded, vectorized graph generators and the node codes built from them.

A configuration's graph is a pure function of the ``graph`` section of its
file (``graph_seed`` included), so every ``--seed`` of a cell trains or
serves the same graph.  ``load`` builds it once per checkout and keeps it in
``bench/.cache/`` (git-ignored); later runs read the arrays back.

Two generators:

* ``powerlaw``: Chung-Lu edges with power-law expected degrees and
  homophilous labels (a share ``homophily`` of edges lands inside the
  source's class).  The edge count is exact: ``n_nodes * mean_degree / 2``
  undirected edges, so the mean degree of the symmetric graph is the
  configured one.
* ``bipartite``: consumer x merchant transactions.  Each consumer prefers
  ``affinity`` categories; each transaction picks one of them and a merchant
  of that category with Zipf popularity.  Merchants are labelled by
  category; consumers carry their first preferred category as a label that
  no serving request reads.

Codes are the paper's Algorithm 1 (sign of a Gaussian random projection of
each adjacency row against its column median), computed here with numpy and
scipy and packed in the program's documented storage layout (bit ``i`` of a
row in word ``i // 32`` at bit ``i % 32``; each code element MSB-first).
"""

from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path
from typing import Dict, Tuple

import numpy as np

CACHE_DIR = Path(__file__).resolve().parents[1] / ".cache"


class Graph:
    """Symmetric CSR adjacency (rows sorted by column), labels and codes."""

    def __init__(self, indptr, indices, labels, codes, meta):
        self.indptr = indptr
        self.indices = indices
        self.labels = labels
        self.codes = codes
        self.meta = meta
        self._keys = None

    @property
    def n_nodes(self) -> int:
        return int(self.indptr.shape[0] - 1)

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)

    def edge_keys(self) -> np.ndarray:
        """Sorted ``row * n + col`` of every stored entry (built once)."""
        if self._keys is None:
            rows = np.repeat(np.arange(self.n_nodes, dtype=np.int64), self.degrees())
            self._keys = rows * self.n_nodes + self.indices
        return self._keys


def _csr(src: np.ndarray, dst: np.ndarray, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric CSR from undirected, deduplicated, loop-free edges."""
    rows = np.concatenate([src, dst])
    cols = np.concatenate([dst, src])
    keys = np.sort(rows.astype(np.int64) * n + cols)
    rows, cols = keys // n, keys % n
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return indptr, cols.astype(np.int32)


def _draw(rng, cum: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Indices drawn with probability proportional to the weights whose
    cumulative sums are ``cum``, each restricted to ``[lo, hi)`` of it."""
    u = lo + rng.random(lo.shape[0]) * (hi - lo)
    return np.minimum(np.searchsorted(cum, u, side="right"), cum.shape[0] - 1)


def _unique_edges(rng, draw, target: int, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Exactly ``target`` distinct undirected loop-free edges from repeated
    batches of ``draw(count) -> (src, dst)``; a seeded subset trims the
    surplus."""
    keys = np.empty(0, np.int64)
    while keys.shape[0] < target:
        s, d = draw(int(1.15 * (target - keys.shape[0])) + 1024)
        lo, hi = np.minimum(s, d), np.maximum(s, d)
        keep = lo != hi
        keys = np.unique(np.concatenate([keys, lo[keep] * n + hi[keep]]))
    if keys.shape[0] > target:
        keys = np.sort(rng.choice(keys, target, replace=False))
    return keys // n, keys % n


def powerlaw(graph_seed: int, n_nodes: int, mean_degree: float, n_classes: int,
             degree_exponent: float, homophily: float):
    rng = np.random.default_rng(graph_seed)
    labels = rng.integers(0, n_classes, n_nodes).astype(np.int32)
    w = (1.0 - rng.random(n_nodes)) ** (-1.0 / (degree_exponent - 1.0))
    w *= mean_degree / w.mean()
    w = np.minimum(w, np.sqrt(n_nodes * mean_degree))   # Chung-Lu's bound
    target = int(round(n_nodes * mean_degree / 2))
    # global order and class-grouped order share one weight vector
    cum = np.cumsum(w)
    by_class = np.argsort(labels, kind="stable")
    cum_c = np.cumsum(w[by_class])
    starts = np.searchsorted(labels[by_class], np.arange(n_classes))
    ends = np.append(starts[1:], n_nodes)
    c_lo = np.where(starts > 0, cum_c[np.maximum(starts - 1, 0)], 0.0)
    c_hi = cum_c[ends - 1]
    zero, total = np.zeros(1), np.full(1, cum[-1])

    def draw(k):
        src = _draw(rng, cum, np.broadcast_to(zero, k), np.broadcast_to(total, k))
        dst = _draw(rng, cum, np.broadcast_to(zero, k), np.broadcast_to(total, k))
        same = rng.random(k) < homophily
        cl = labels[src[same]]
        dst[same] = by_class[_draw(rng, cum_c, c_lo[cl], c_hi[cl])]
        return src.astype(np.int64), dst.astype(np.int64)

    src, dst = _unique_edges(rng, draw, target, n_nodes)
    indptr, indices = _csr(src, dst, n_nodes)
    return indptr, indices, labels


def bipartite(graph_seed: int, n_consumers: int, n_merchants: int,
              n_categories: int, tx_per_consumer: float, affinity: int,
              zipf: float):
    rng = np.random.default_rng(graph_seed)
    n = n_consumers + n_merchants
    cat = rng.integers(0, n_categories, n_merchants).astype(np.int32)
    order = np.argsort(cat, kind="stable")
    starts = np.searchsorted(cat[order], np.arange(n_categories))
    ends = np.append(starts[1:], n_merchants)
    rank = np.arange(n_merchants) - np.repeat(starts, ends - starts)
    cum = np.cumsum(1.0 / (rank + 1.0) ** zipf)
    c_lo = np.where(starts > 0, cum[np.maximum(starts - 1, 0)], 0.0)
    c_hi = np.where(ends > starts, cum[np.maximum(ends - 1, 0)], c_lo)
    aff = rng.integers(0, n_categories, (n_consumers, affinity)).astype(np.int32)
    k = np.maximum(1, rng.poisson(tx_per_consumer, n_consumers))
    src = np.repeat(np.arange(n_consumers, dtype=np.int64), k)
    tx_cat = aff[src, rng.integers(0, affinity, src.shape[0])]
    empty = c_hi[tx_cat] <= c_lo[tx_cat]
    merchant = order[_draw(rng, cum, c_lo[tx_cat], c_hi[tx_cat])]
    merchant[empty] = rng.integers(0, n_merchants, int(empty.sum()))
    keys = np.unique(src * n + (merchant + n_consumers))
    indptr, indices = _csr(keys // n, keys % n, n)
    labels = np.concatenate([aff[:, 0], cat]).astype(np.int32)
    return indptr, indices, labels


GENERATORS = {"powerlaw": powerlaw, "bipartite": bipartite}


def lsh_codes(indptr: np.ndarray, indices: np.ndarray, code_seed: int,
              c: int, m: int) -> np.ndarray:
    """Algorithm 1 on the adjacency: ``(n, n_words)`` uint32 packed codes."""
    import scipy.sparse as sp
    n = indptr.shape[0] - 1
    n_bits = m * (int(c).bit_length() - 1)
    n_words = -(-n_bits // 32)
    adj = sp.csr_matrix((np.ones(indices.shape[0], np.float32), indices, indptr),
                        shape=(n, n))
    rng = np.random.default_rng(code_seed)
    words = np.zeros((n, n_words), np.uint32)
    for w in range(n_words):
        width = min(32, n_bits - 32 * w)
        u = adj @ rng.standard_normal((n, width), dtype=np.float32)
        bits = (u > np.median(u, axis=0)).astype(np.uint32)
        words[:, w] = (bits << np.arange(width, dtype=np.uint32)).sum(
            axis=1, dtype=np.uint32)
    return words


def unpack_codes(words: np.ndarray, c: int, m: int) -> np.ndarray:
    """``(n, n_words)`` uint32 -> ``(n, m)`` int32 in ``[0, c)``."""
    b = int(c).bit_length() - 1
    bits = (words[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    bits = bits.reshape(words.shape[0], -1)[:, :m * b].reshape(-1, m, b)
    return (bits.astype(np.int32) << np.arange(b - 1, -1, -1)).sum(-1).astype(np.int32)


def build(spec: Dict, c: int, m: int) -> Graph:
    args = {k: v for k, v in spec.items() if k not in ("kind", "code_seed", "split")}
    indptr, indices, labels = GENERATORS[spec["kind"]](**args)
    codes = lsh_codes(indptr, indices, spec["code_seed"], c, m)
    return Graph(indptr, indices, labels, codes, dict(spec, c=c, m=m))


def load(spec: Dict, c: int, m: int, cache_dir: Path = CACHE_DIR) -> Graph:
    """The graph of a configuration, from the cache when it was built
    before in this checkout."""
    key = json.dumps(dict(spec, c=c, m=m), sort_keys=True)
    path = cache_dir / f"graph-{hashlib.sha256(key.encode()).hexdigest()[:16]}.npz"
    if path.exists():
        with np.load(path) as z:
            return Graph(z["indptr"], z["indices"], z["labels"], z["codes"],
                         json.loads(str(z["meta"])))
    g = build(spec, c, m)
    cache_dir.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f"{path.stem}.{os.getpid()}.tmp.npz")
    np.savez(tmp, indptr=g.indptr, indices=g.indices, labels=g.labels,
             codes=g.codes, meta=json.dumps(g.meta))
    tmp.replace(path)
    return g
