"""GraphRuntime (ISSUE 4): the declarative spec front door.

Asserts the redesign's contracts:
  (a) spec-built training is bit-identical to the hand-wired PR-1 pipeline
      (graph → codes → state → sampler → source → step) for 5 steps;
  (b) ``GraphInferenceEngine.embed`` matches ``GNNModel.apply`` on the same
      frontier (miss-only cached decode is bitwise-invisible at serving);
  (c) spec → checkpoint → resume round-trips exactly (spec rides in the
      manifest; ``GraphRuntime.resume`` rebuilds the pipeline from it);
  (d) a sharded spec is a pure field change (``multidevice``-marked);
  plus: cached-pallas decode is a pure field change, the miss-only cache
  lookup is bitwise-equal to the select-based one, and specs survive JSON.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs.paper_gnn import paper_gnn_config
from repro.core import embedding as emb_lib
from repro.core.backend import CachedDecodeBackend, CacheState
from repro.graph import NeighborSampler, powerlaw_graph
from repro.graph.engine import (GNNModel, SageBatchSource,
                                ShardedSageBatchSource)
from repro.graph.generate import train_val_test_split
from repro.graph.runtime import (FullGraphSource, GraphRuntime, GraphSource,
                                 RuntimeSpec)
from repro.optim import AdamWConfig
from repro.train import init_gnn_train_state, make_gnn_train_step

KEY = jax.random.PRNGKey(0)
N = 1200
BATCH = 64
OPT = AdamWConfig(lr=1e-2, weight_decay=0.0)
GRAPH_SRC = GraphSource(kind="powerlaw", seed=0, n_nodes=N, n_classes=8,
                        avg_degree=8, homophily=0.9)


def _cfg(**emb_kw):
    base = paper_gnn_config("sage", n_nodes=N, n_classes=8, fanout=5)
    return dataclasses.replace(base, embedding=dataclasses.replace(
        base.embedding, c=16, m=8, d_c=64, d_m=64, lookup_impl="gather",
        **emb_kw))


def _spec(**kw):
    spec = RuntimeSpec(graph=GRAPH_SRC, model=_cfg(), optimizer=OPT,
                       batch_size=BATCH, prefetch_depth=0)
    return spec.with_updates(**kw) if kw else spec


@pytest.fixture(scope="module")
def graph():
    return GRAPH_SRC.build()


# ---------------------------------------------------------------------------
# (a) spec-built training == hand-wired PR-1 pipeline, bitwise
# ---------------------------------------------------------------------------

def _handwired_losses(graph, cfg, n_steps):
    """The exact pre-runtime wiring from examples/train_gnn_hash.py (PR 1)."""
    adj, labels = graph
    codes = np.asarray(emb_lib.make_codes(KEY, cfg.embedding_config(),
                                          aux=adj))
    state = init_gnn_train_state(KEY, cfg, codes=codes)
    step = jax.jit(make_gnn_train_step(cfg, OPT))
    sampler = NeighborSampler(adj, cfg.fanouts, max_deg=64, seed=0)
    tr, _va, _te = train_val_test_split(0, N)
    src = SageBatchSource(sampler, tr, labels, BATCH, seed=0)
    losses = []
    for _ in range(n_steps):
        state, m = step(state, jax.device_put(src.next_batch()))
        losses.append(float(m["loss"]))
    return losses, state


def test_spec_training_bit_identical_to_handwired(graph):
    handwired, _ = _handwired_losses(graph, _cfg(), 5)
    rt = GraphRuntime.from_spec(_spec(), graph=graph)
    res = rt.train(5)
    rt.close()
    assert res.losses == handwired          # bitwise, not approx


def test_prefetch_is_a_knob_not_a_code_path(graph):
    """prefetch_depth must not change the batch stream (exact resume
    semantics carry over from the engine)."""
    sync = GraphRuntime.from_spec(_spec(prefetch_depth=0), graph=graph)
    pf = GraphRuntime.from_spec(_spec(prefetch_depth=2), graph=graph)
    try:
        assert sync.train(4).losses == pf.train(4).losses
    finally:
        sync.close()
        pf.close()


# Later steps of two decode backends agree to f32 rounding, not bitwise: the
# gather VJP is a scatter-add and the pallas VJP a one-hot einsum, so their
# codebook gradients round differently, and 4 Adam updates carry that into
# the loss (observed gap ~3e-7 relative at step 4).
STEP_RTOL_F32 = 1e-5


def test_interpret_true_on_tpu_is_an_error(graph, monkeypatch):
    """Interpret mode on a TPU runtime would be a silent slowdown."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        GraphRuntime.from_spec(_spec(interpret=True), graph=graph)


def test_cached_pallas_is_a_spec_field_change(graph):
    """1-shard gather → cached-pallas decode is a ``with_updates`` call.
    The pallas forward is bitwise the gather oracle and staleness-0 caching
    is bit-exact, so the step-0 loss is bitwise equal; the later steps stay
    within ``STEP_RTOL_F32``."""
    base = GraphRuntime.from_spec(_spec(), graph=graph)
    cached = GraphRuntime.from_spec(
        _spec(lookup_impl="pallas", cache_capacity=2048, cache_staleness=0),
        graph=graph)
    try:
        want, got = base.train(5).losses, cached.train(5).losses
        assert got[0] == want[0]                # bitwise, not approx
        np.testing.assert_allclose(got, want, rtol=STEP_RTOL_F32, atol=0)
    finally:
        base.close()
        cached.close()


# ---------------------------------------------------------------------------
# (b) serving engine == direct model forward on the same frontier
# ---------------------------------------------------------------------------

def test_engine_embed_matches_model_apply(graph):
    rt = GraphRuntime.from_spec(_spec(), graph=graph)
    rt.train(3)
    engine = rt.serve(serve_batch=32)
    model = GNNModel(rt.cfg, interpret=rt.interpret)
    ids = np.arange(24, dtype=np.int32)

    # request 0: cold cache (everything misses), request 1+: hot (the
    # frontier is content-keyed, so repeat requests resample identically)
    for request in range(3):
        fb = engine.frontier_for(ids)
        h_direct = np.asarray(model.apply(rt.params, jax.device_put(fb)))
        h_engine = engine.embed(ids)
        np.testing.assert_array_equal(h_engine, h_direct[:len(ids)])
    stats = engine.stats()
    assert stats["hits"] > 0, "hot requests must actually hit the cache"
    assert stats["rows_decoded"] < stats["rows_total"], \
        "miss-only decode must pay fewer rows than the full frontier"
    rt.close()


def test_engine_is_serving_protocol():
    from repro.serving import Engine
    from repro.serving.gnn import GraphInferenceEngine
    assert issubclass(GraphInferenceEngine, Engine)  # runtime_checkable


def test_missonly_lookup_bitwise_equals_select_lookup():
    """The miss-only cache path (host partition + padded miss-prefix) must
    return exactly what the select-based ``lookup`` returns, for any mix of
    hits / stale entries / absent ids / invalid padding rows."""
    rng = np.random.default_rng(0)
    d, C, U = 8, 16, 24
    cache = CachedDecodeBackend(staleness=0)
    state = CacheState.create(C, d)
    table = jax.numpy.asarray(rng.standard_normal((64, d)).astype(np.float32))
    decode = lambda ids: table[ids]

    # warm the cache with ids 0..15
    warm = np.arange(16, dtype=np.int32)
    _, state = cache.lookup(state, jax.numpy.asarray(warm), decode)

    ids = np.concatenate([warm[:12], np.arange(40, 48, dtype=np.int32),
                          np.full(4, 0, np.int32)]).astype(np.int32)
    valid = np.concatenate([np.ones(20, bool), np.zeros(4, bool)])

    out_ref, state_ref = cache.lookup(
        state, jax.numpy.asarray(ids), decode,
        valid=jax.numpy.asarray(valid))

    perm, n_miss = CachedDecodeBackend.plan_missonly(
        np.asarray(state.node_ids), ids, valid)
    assert n_miss == 8                       # exactly the absent ids
    assert set(ids[perm[:n_miss]]) == set(range(40, 48))
    n_dec = 8
    out_mo, state_mo = cache.lookup_missonly(
        state, jax.numpy.asarray(ids[perm]), decode, n_dec,
        valid=jax.numpy.asarray(valid[perm]))

    inv = np.empty_like(perm)
    inv[perm] = np.arange(U)
    np.testing.assert_array_equal(np.asarray(out_mo)[inv][valid],
                                  np.asarray(out_ref)[valid])
    # identical accounting and identical cached contents (as id→value sets)
    assert int(state_mo.hits) == int(state_ref.hits)
    assert int(state_mo.misses) == int(state_ref.misses)
    ref_map = {int(i): np.asarray(state_ref.values)[k]
               for k, i in enumerate(np.asarray(state_ref.node_ids)) if i >= 0}
    mo_map = {int(i): np.asarray(state_mo.values)[k]
              for k, i in enumerate(np.asarray(state_mo.node_ids)) if i >= 0}
    assert ref_map.keys() == mo_map.keys()
    for k in ref_map:
        np.testing.assert_array_equal(ref_map[k], mo_map[k])


# ---------------------------------------------------------------------------
# (c) spec → checkpoint → resume round-trip
# ---------------------------------------------------------------------------

def test_spec_checkpoint_resume_roundtrip(graph, tmp_path):
    full_spec = _spec(ckpt_dir=str(tmp_path / "full"), ckpt_every=4)
    rt_full = GraphRuntime.from_spec(full_spec, graph=graph)
    res_full = rt_full.train(8)
    rt_full.close()

    part_spec = _spec(ckpt_dir=str(tmp_path / "part"), ckpt_every=4)
    rt_part = GraphRuntime.from_spec(part_spec, graph=graph)
    rt_part.train(4)
    rt_part.close()

    # resume knows NOTHING but the directory: the spec comes from the
    # checkpoint manifest and must round-trip exactly, and the trained
    # params must be live IMMEDIATELY (evaluate/serve before any train)
    rt_res = GraphRuntime.resume(str(tmp_path / "part"), graph=graph)
    assert rt_res.spec == part_spec
    for a, b in zip(jax.tree.leaves(rt_part.state["params"]),
                    jax.tree.leaves(rt_res.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    res_tail = rt_res.train(8)
    assert res_tail.resumed_from == 4
    assert res_tail.losses == res_full.losses[4:]
    for a, b in zip(jax.tree.leaves(rt_full.state["params"]),
                    jax.tree.leaves(rt_res.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rt_res.close()


def test_spec_json_roundtrip():
    spec = _spec(lookup_impl="pallas", cache_capacity=512, n_shards=2,
                 total_steps=77)
    restored = RuntimeSpec.from_json(spec.to_json())
    assert restored == spec
    # and through a plain-dict (manifest) cycle too
    assert RuntimeSpec.from_dict(json.loads(json.dumps(spec.to_dict()))) == spec


def test_with_updates_routes_fields():
    spec = _spec()
    s = spec.with_updates(n_shards=4, lookup_impl="sharded:gather", hidden=64)
    assert s.n_shards == 4
    assert s.model.embedding.lookup_impl == "sharded:gather"
    assert s.model.hidden == 64
    with pytest.raises(TypeError):
        spec.with_updates(not_a_field=1)


# ---------------------------------------------------------------------------
# full-graph model family through the same front door
# ---------------------------------------------------------------------------

def test_fullgraph_runtime_train_and_evaluate(graph):
    cfg = dataclasses.replace(
        paper_gnn_config("gcn", n_nodes=N, n_classes=8),
        embedding=dataclasses.replace(_cfg().embedding))
    rt = GraphRuntime.from_spec(_spec(model=cfg), graph=graph)
    assert isinstance(rt.source, FullGraphSource)
    res = rt.train(12)
    assert res.losses[-1] < res.losses[0]
    ev = rt.evaluate("val")
    assert ev["n"] == len(rt.splits["val"])
    assert 0.0 <= ev["accuracy"] <= 1.0
    # evaluate is deterministic
    assert rt.evaluate("test") == rt.evaluate("test")
    rt.close()


def test_evaluate_counts_every_split_node_once(graph):
    rt = GraphRuntime.from_spec(_spec(), graph=graph)
    ev = rt.evaluate("val", batch_size=48)   # forces a wrapped final batch
    assert ev["n"] == len(rt.splits["val"])
    assert rt.evaluate("val", batch_size=48) == ev   # deterministic
    rt.close()


# ---------------------------------------------------------------------------
# (d) sharded spec: a field change, under the multidevice CI leg
# ---------------------------------------------------------------------------

@pytest.mark.multidevice(4)
def test_sharded_spec_is_a_field_change(graph):
    spec = _spec(lookup_impl="sharded:gather")
    rt1 = GraphRuntime.from_spec(spec, graph=graph)
    res1 = rt1.train(3)
    rt1.close()

    rt4 = GraphRuntime.from_spec(spec.with_updates(n_shards=4), graph=graph)
    assert isinstance(rt4.source, ShardedSageBatchSource)
    assert rt4.mesh is not None and rt4.mesh.shape["data"] == 4
    res4 = rt4.train(3)
    rt4.close()
    # the (seed, shard, step) contract: step-0 forward loss is bitwise equal
    assert res1.losses[0] == res4.losses[0]


def test_sharded_spec_fails_loudly_without_devices(graph):
    if jax.device_count() >= 4:
        pytest.skip("only meaningful on a single-device run")
    with pytest.raises(ValueError, match="n_shards"):
        GraphRuntime.from_spec(_spec(n_shards=4), graph=graph)
