"""Streaming graph-engine tests (ISSUE 1): dedup-decode equivalence,
prefetch determinism + resume, isolated-node self-sampling, config plumbing
for the Algorithm-1 encoding knobs, and the import-health gate."""

import dataclasses
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_gnn import paper_gnn_config
from repro.core import embedding as emb_lib
from repro.core import lsh
from repro.graph import CSRMatrix, FrontierBatch, NeighborSampler, powerlaw_graph
from repro.graph.engine import (FullGraphBatch, GNNModel, PrefetchIterator,
                                SageBatchSource)
from repro.graph.runtime import GraphRuntime, GraphSource, RuntimeSpec
from repro.models import gnn
from repro.optim import AdamWConfig
from repro.train import LoopConfig, init_gnn_train_state, make_gnn_train_step, run_training

KEY = jax.random.PRNGKey(0)
N = 1200


@pytest.fixture(scope="module")
def graph():
    return powerlaw_graph(0, N, avg_degree=8, n_classes=8, homophily=0.9)


@pytest.fixture(scope="module")
def cfg():
    base = paper_gnn_config("sage", n_nodes=N, n_classes=8, fanout=5)
    return dataclasses.replace(
        base, embedding=dataclasses.replace(base.embedding, c=16, m=8, d_c=64, d_m=64))


@pytest.fixture(scope="module")
def params(graph, cfg):
    adj, _ = graph
    codes = emb_lib.make_codes(KEY, cfg.embedding_config(), aux=adj)
    return GNNModel(cfg).init(KEY, codes=codes)


# ---------------------------------------------------------------------------
# dedup decode
# ---------------------------------------------------------------------------

def test_frontier_reconstructs_levels(graph, cfg):
    adj, _ = graph
    sampler = NeighborSampler(adj, cfg.fanouts, max_deg=32, seed=0)
    ids = np.random.default_rng(1).choice(N, 64, replace=False).astype(np.int32)
    levels = sampler.sample(ids, rng=np.random.default_rng(2))
    fb = FrontierBatch.from_levels(levels, pad_to=128)
    assert fb.unique.shape[0] % 128 == 0
    assert int(fb.n_unique) <= fb.unique.shape[0]
    # the frontier must be lossless: unique[index_maps[i]] == levels[i]
    for lvl, rebuilt in zip(levels, fb.levels()):
        np.testing.assert_array_equal(rebuilt, lvl)
    np.testing.assert_array_equal(fb.targets, ids)
    # and genuinely deduplicated
    assert int(fb.n_unique) == np.unique(np.concatenate(
        [l.ravel() for l in levels])).shape[0]


def test_dedup_decode_bit_identical(graph, cfg, params):
    """Dedup decode (one lookup over the frontier + gathers) must reproduce
    the naive per-position decode exactly on a seeded batch."""
    adj, _ = graph
    sampler = NeighborSampler(adj, cfg.fanouts, max_deg=32, seed=0)
    ids = np.random.default_rng(3).choice(N, 64, replace=False).astype(np.int32)
    levels = sampler.sample(ids, rng=np.random.default_rng(4))
    fb = FrontierBatch.from_levels(levels)

    model = GNNModel(cfg)
    h_naive = model.apply(params, [jnp.asarray(l) for l in levels])
    h_dedup = model.apply(params, jax.device_put(fb))
    np.testing.assert_array_equal(np.asarray(h_naive), np.asarray(h_dedup))


def test_dedup_decode_dense_kind(graph):
    """The frontier path is embedding-kind agnostic (dense table too)."""
    adj, _ = graph
    cfg = dataclasses.replace(
        paper_gnn_config("sage", n_nodes=N, n_classes=8, fanout=5, kind="dense"))
    sampler = NeighborSampler(adj, cfg.fanouts, max_deg=32, seed=0)
    params = GNNModel(cfg).init(KEY)
    ids = np.arange(32, dtype=np.int32)
    levels = sampler.sample(ids, rng=np.random.default_rng(5))
    fb = FrontierBatch.from_levels(levels)
    h_naive = GNNModel(cfg).apply(params, [jnp.asarray(l) for l in levels])
    h_dedup = GNNModel(cfg).apply(params, jax.device_put(fb))
    np.testing.assert_array_equal(np.asarray(h_naive), np.asarray(h_dedup))


def test_isolated_node_self_sampling():
    """Isolated nodes still self-sample through the frontier path."""
    # node 4 has no edges
    adj = CSRMatrix.from_edges([0, 1, 2], [1, 2, 3], n_nodes=5)
    sampler = NeighborSampler(adj, (3, 3), max_deg=4, seed=0)
    ids = np.array([4, 0], dtype=np.int32)
    fb = sampler.sample_frontier(ids, pad_to=8, rng=np.random.default_rng(0))
    levels = fb.levels()
    # every neighbour drawn for isolated node 4 is node 4 itself
    np.testing.assert_array_equal(levels[1][0], np.full(3, 4))
    np.testing.assert_array_equal(levels[2][0], np.full((3, 3), 4))
    assert 4 in np.asarray(fb.unique[:int(fb.n_unique)])


# ---------------------------------------------------------------------------
# prefetch pipeline
# ---------------------------------------------------------------------------

def _sources(graph, cfg, batch_size=32, seed=7):
    adj, labels = graph
    def make():
        sampler = NeighborSampler(adj, cfg.fanouts, max_deg=32, seed=0)
        return SageBatchSource(sampler, np.arange(N), labels, batch_size, seed=seed)
    return make


def test_prefetch_matches_sync_sequence(graph, cfg):
    make = _sources(graph, cfg)
    sync = make()
    expect = [sync.next_batch() for _ in range(8)]
    pf = PrefetchIterator(make(), depth=3)
    try:
        got = [pf.next_batch() for _ in range(8)]
    finally:
        pf.close()
    for a, b in zip(expect, got):
        np.testing.assert_array_equal(a["labels"], np.asarray(b["labels"]))
        np.testing.assert_array_equal(a["frontier"].unique,
                                      np.asarray(b["frontier"].unique))
        for ma, mb in zip(a["frontier"].index_maps, b["frontier"].index_maps):
            np.testing.assert_array_equal(ma, np.asarray(mb))


def test_prefetch_state_resume(graph, cfg):
    """state_dict reflects *consumed* batches (not produced-ahead ones), so
    restoring it replays exactly the un-consumed suffix."""
    make = _sources(graph, cfg)
    pf = PrefetchIterator(make(), depth=3)
    try:
        for _ in range(3):
            pf.next_batch()
        snap = pf.state_dict()
        expect = [np.asarray(pf.next_batch()["labels"]) for _ in range(3)]
    finally:
        pf.close()
    assert snap == {"step": 3, "seed": 7, "shard": 0, "n_shards": 1}

    pf2 = PrefetchIterator(make(), depth=3)
    try:
        pf2.next_batch()          # run ahead, then rewind via load_state_dict
        pf2.load_state_dict(snap)
        got = [np.asarray(pf2.next_batch()["labels"]) for _ in range(3)]
    finally:
        pf2.close()
    np.testing.assert_array_equal(np.stack(expect), np.stack(got))


def test_prefetch_reusable_after_close(graph, cfg):
    """close() pauses (rewinds to last consumed batch); next_batch resumes
    the exact sequence — so run_training may close a caller-owned iterator
    and the caller can keep using it (e.g. staged training)."""
    make = _sources(graph, cfg)
    sync = make()
    expect = [np.asarray(sync.next_batch()["labels"]) for _ in range(6)]
    pf = PrefetchIterator(make(), depth=3)
    try:
        got = [np.asarray(pf.next_batch()["labels"]) for _ in range(3)]
        pf.close()                       # drops produced-ahead batches
        got += [np.asarray(pf.next_batch()["labels"]) for _ in range(3)]
    finally:
        pf.close()
    np.testing.assert_array_equal(np.stack(expect), np.stack(got))


def test_prefetch_propagates_source_errors(graph, cfg):
    class Boom:
        def next_batch(self):
            raise RuntimeError("boom")
    pf = PrefetchIterator(Boom(), depth=1)
    try:
        with pytest.raises(RuntimeError, match="boom"):
            pf.next_batch()
    finally:
        pf.close()


# ---------------------------------------------------------------------------
# unified model API + engine training
# ---------------------------------------------------------------------------

def test_unified_api_dispatch(graph, cfg, params):
    adj, _ = graph
    model = GNNModel(cfg)
    with pytest.raises(TypeError):
        model.apply(params, object())
    gcfg = dataclasses.replace(cfg, model="gcn")
    gparams = GNNModel(gcfg).init(
        KEY, codes=emb_lib.make_codes(KEY, gcfg.embedding_config(), aux=adj))
    h = GNNModel(gcfg).apply(gparams, FullGraphBatch(
        adj.with_self_loops().normalized("sym")))
    assert h.shape == (N, cfg.hidden)


def test_engine_trains_through_generic_loop(graph, cfg):
    """make_gnn_train_step + PrefetchIterator + run_training: loss drops."""
    adj, labels = graph
    codes = emb_lib.make_codes(KEY, cfg.embedding_config(), aux=adj)
    state = init_gnn_train_state(KEY, cfg, codes=codes)
    sampler = NeighborSampler(adj, cfg.fanouts, max_deg=32, seed=0)
    source = SageBatchSource(sampler, np.arange(N), labels, 128, seed=0)
    data_iter = PrefetchIterator(source, depth=2)
    res = run_training(make_gnn_train_step(cfg), state, data_iter,
                       LoopConfig(total_steps=30))
    assert np.mean(res.losses[-5:]) < np.mean(res.losses[:5]) - 0.1


def test_dedup_training_tracks_naive_decode(graph):
    """Under training, dedup decode scatter-adds gradients into unique rows
    while the naive path decodes every position, so the two loss curves
    differ only by f32 summation order: within 1e-3 over 10 steps."""
    spec = RuntimeSpec(
        graph=GraphSource(kind="powerlaw", seed=0, n_nodes=N, n_classes=8,
                          avg_degree=8, homophily=0.9),
        model=paper_gnn_config("sage", n_nodes=N, n_classes=8, fanout=5),
        optimizer=AdamWConfig(lr=1e-2, weight_decay=0.0),
        batch_size=64, data_seed=1, prefetch_depth=0,
    ).with_updates(c=16, m=8, d_c=64, d_m=64)
    losses = {}
    for dedup in (True, False):
        rt = GraphRuntime.from_spec(spec.with_updates(dedup=dedup),
                                    graph=graph)
        try:
            losses[dedup] = np.asarray(rt.train(10).losses)
        finally:
            rt.close()
    assert len(losses[True]) == 10
    gap = np.abs(losses[True] - losses[False]).max()
    assert gap < 1e-3, (gap, losses)


# ---------------------------------------------------------------------------
# encoding-knob plumbing (threshold / hops)
# ---------------------------------------------------------------------------

def test_threshold_and_hops_plumbed(graph):
    adj, _ = graph
    base = emb_lib.EmbeddingConfig(kind="hash_full", n_entities=N, d_e=32,
                                   c=16, m=8, d_c=64, d_m=64)
    for threshold, hops in (("zero", 1), ("median", 2)):
        cfg = dataclasses.replace(base, threshold=threshold, hops=hops)
        got = emb_lib.make_codes(KEY, cfg, aux=adj)
        want = lsh.encode_lsh(KEY, adj, 16, 8, threshold=threshold, hops=hops)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # and the knob actually changes the encoding
        default = emb_lib.make_codes(KEY, base, aux=adj)
        assert not np.array_equal(np.asarray(got), np.asarray(default))


def test_spec_plumbs_encoding_knobs():
    cfg = paper_gnn_config("sage", n_nodes=100, n_classes=4)
    spec = dataclasses.replace(cfg.embedding, threshold="zero", hops=2)
    ecfg = dataclasses.replace(cfg, embedding=spec).embedding_config()
    assert ecfg.threshold == "zero" and ecfg.hops == 2


# ---------------------------------------------------------------------------
# tooling: import-health gate
# ---------------------------------------------------------------------------

def test_check_imports_tool():
    """The collect gate passes on the current tree (missing optional deps
    must skip, never break collection)."""
    root = Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, str(root / "tools" / "check_imports.py"), "--src-only"],
        capture_output=True, text=True, cwd=str(root), timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
