"""The seeded graph generators and the codes built from them."""

import sys
from pathlib import Path

import numpy as np

from bench import graphs


def test_powerlaw_degree_classes_and_determinism():
    a = graphs.powerlaw(3, 4000, 20.0, 7, 2.5, 0.8)
    b = graphs.powerlaw(3, 4000, 20.0, 7, 2.5, 0.8)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    indptr, indices, labels = a
    assert indptr[-1] == 4000 * 20             # mean degree exactly 20
    assert set(np.unique(labels)) == set(range(7))
    rows = np.repeat(np.arange(4000), np.diff(indptr))
    assert not np.any(rows == indices)          # no self loops
    keys = rows * 4000 + indices
    assert np.all(np.diff(keys) > 0)            # sorted, no duplicates
    assert np.array_equal(np.sort(keys), np.sort(indices.astype(np.int64) * 4000 + rows))
    assert (labels[rows] == labels[indices]).mean() > 0.5   # homophilous
    deg = np.diff(indptr)
    assert deg.max() > 5 * deg.mean()           # heavy tail


def test_bipartite_sides_and_labels():
    indptr, indices, labels = graphs.bipartite(0, 2000, 300, 16, 5, 3, 1.1)
    n = 2300
    rows = np.repeat(np.arange(n), np.diff(indptr))
    # every edge joins a consumer to a merchant
    assert np.all((rows < 2000) != (indices < 2000))
    assert np.all(np.diff(indptr)[:2000] >= 1)
    assert set(np.unique(labels[2000:])) <= set(range(16))
    assert np.diff(indptr)[:2000].mean() > 4


def test_split_fractions_of_the_program_split():
    sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))
    from repro.graph.generate import train_val_test_split
    tr, va, te = train_val_test_split(0, 10000, (0.08, 0.02, 0.9))
    assert (len(tr), len(va), len(te)) == (800, 200, 9000)


def test_codes_match_the_program_storage_layout():
    sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))
    from repro.core.codes import unpack_codes
    indptr, indices, _ = graphs.powerlaw(1, 1500, 10.0, 5, 2.5, 0.8)
    words = graphs.lsh_codes(indptr, indices, 0, 256, 16)
    assert words.shape == (1500, 4) and words.dtype == np.uint32
    mine = graphs.unpack_codes(words, 256, 16)
    assert np.array_equal(mine, np.asarray(unpack_codes(words, 256, 16)))
    # median threshold: every bit is set on half of the nodes
    bits = (words[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    assert np.all(np.abs(bits.mean(0) - 0.5) < 0.01)
    assert np.array_equal(words, graphs.lsh_codes(indptr, indices, 0, 256, 16))


def test_load_caches_the_build(tmp_path):
    spec = dict(kind="powerlaw", graph_seed=2, code_seed=0, n_nodes=800,
                mean_degree=6.0, n_classes=3, degree_exponent=2.5, homophily=0.8,
                split=[0.5, 0.25, 0.25])
    a = graphs.load(spec, 16, 8, cache_dir=tmp_path)
    assert len(list(tmp_path.glob("graph-*.npz"))) == 1
    b = graphs.load(spec, 16, 8, cache_dir=tmp_path)
    for k in ("indptr", "indices", "labels", "codes"):
        assert np.array_equal(getattr(a, k), getattr(b, k))
