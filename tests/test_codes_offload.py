"""Host-offloaded codes placement (ISSUE 10).

The tentpole contract: ``EmbeddingSpec(codes_placement="host")`` keeps the
packed ``codes_buf`` in host RAM — the prefetch producer gathers each
frontier's rows into the batch's ``codes`` leaf — and the runtime stays
**bitwise** identical to the replicated default on every path:

  (a) the new ``codes`` leaf is a well-behaved pytree citizen: flatten /
      unflatten round-trips, old 4-tuple aux still unflattens (ckpt compat),
      and ``frontier_batch_shardings`` row-shards it with ``unique``;
  (b) prefetch ``state_dict`` resume replays the exact batch+codes stream;
  (c) train / evaluate / embed / serve_many parity host vs device, as a
      hypothesis property across backends (incl. cached staleness-0) and as
      4-shard ``sharded`` / ``owner`` runs under the multidevice marker;
  (d) spec → checkpoint → resume keeps the placement and the bit pattern;
  (e) the memory claim: host params carry no ``codes_buf`` and the producer
      accounts the per-batch code stream instead.
"""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.paper_gnn import paper_gnn_config
from repro.core import embedding as emb_lib
from repro.graph import NeighborSampler, powerlaw_graph
from repro.graph.engine import PrefetchIterator, SageBatchSource
from repro.graph.generate import train_val_test_split
from repro.graph.runtime import GraphRuntime, GraphSource, RuntimeSpec
from repro.graph.sampler import FrontierBatch, attach_codes
from repro.optim import AdamWConfig
from repro.parallel.policy import frontier_batch_shardings

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


@pytest.fixture(scope="module")
def codes(graph):
    adj, _ = graph
    return np.asarray(emb_lib.make_codes(KEY, _cfg().embedding_config(),
                                         aux=adj))


def _frontier(graph, codes=None, seed=0):
    adj, labels = graph
    sampler = NeighborSampler(adj, _cfg().fanouts, max_deg=64, seed=0)
    tr, _va, _te = train_val_test_split(0, N)
    src = SageBatchSource(sampler, tr, labels, BATCH, seed=seed)
    fb = src.next_batch()["frontier"]
    return attach_codes(fb, codes) if codes is not None else fb


def _param_codes_buf_bytes(params) -> int:
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if any("codes_buf" in str(getattr(p, "key", p)) for p in path):
            total += int(np.asarray(leaf).nbytes)
    return total


# ---------------------------------------------------------------------------
# (a) leaf hygiene: pytree round-trip, aux compat, shardings
# ---------------------------------------------------------------------------

def test_codes_leaf_pytree_roundtrip(graph, codes):
    fb = _frontier(graph, codes)
    assert fb.codes is not None and fb.codes.dtype == np.uint32
    assert fb.codes.shape[0] == fb.unique.shape[0]     # row-aligned
    leaves, treedef = jax.tree_util.tree_flatten(fb)
    assert np.array_equal(np.asarray(leaves[-1]), fb.codes)  # last leaf
    fb2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert np.array_equal(np.asarray(fb2.codes), fb.codes)
    assert np.array_equal(np.asarray(fb2.unique), np.asarray(fb.unique))
    # attach is idempotent: a second attach must not regather
    assert attach_codes(fb, codes) is fb


def test_codes_roundtrip_under_jit(graph, codes):
    fb = _frontier(graph, codes)
    out = jax.jit(lambda b: (b.codes.sum(), b.unique.sum()))(fb)
    assert int(out[0]) == int(np.asarray(fb.codes, np.uint64).sum() % (1 << 32))


def test_old_aux_unflattens_without_codes(graph):
    """Pre-ISSUE-10 treedefs carry a 4-tuple aux — they must still
    unflatten (checkpointed treedefs, pickled batches)."""
    fb = _frontier(graph)          # no codes
    assert fb.codes is None
    leaves, _ = jax.tree_util.tree_flatten(fb)
    old_aux = (len(fb.index_maps), fb.valid is not None,
               fb.plan is not None, fb.n_decode)
    fb2 = FrontierBatch.tree_unflatten(old_aux, leaves)
    assert fb2.codes is None
    assert np.array_equal(np.asarray(fb2.unique), np.asarray(fb.unique))


def test_codes_leaf_rides_frontier_shardings(graph, codes):
    """``frontier_batch_shardings`` must row-shard the codes leaf exactly
    like ``unique`` (that alignment is what makes sharded/owner decode see
    only shard-local rows) and pass ``codes=None`` through untouched."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    batch = {"frontier": _frontier(graph, codes), "labels": np.zeros(BATCH)}
    sh = frontier_batch_shardings(batch, mesh)
    fbs = sh["frontier"]
    assert isinstance(fbs.codes, NamedSharding)
    assert fbs.codes.spec == P("data") == fbs.unique.spec
    sh_none = frontier_batch_shardings(
        {"frontier": _frontier(graph), "labels": np.zeros(BATCH)}, mesh)
    assert sh_none["frontier"].codes is None


# ---------------------------------------------------------------------------
# (b) prefetch state_dict resume replays the exact batch+codes stream
# ---------------------------------------------------------------------------

def test_prefetch_resume_replays_codes_stream(graph, codes):
    adj, labels = graph
    sampler = NeighborSampler(adj, _cfg().fanouts, max_deg=64, seed=0)
    tr, _va, _te = train_val_test_split(0, N)

    def gather(batch):
        batch = dict(batch)
        batch["frontier"] = attach_codes(batch["frontier"], codes)
        return batch

    it = PrefetchIterator(SageBatchSource(sampler, tr, labels, BATCH, seed=0),
                          depth=2, code_gather=gather)
    try:
        for _ in range(3):
            it.next_batch()
        sd = it.state_dict()
        want = it.next_batch()["frontier"]
    finally:
        it.close()
    assert want.codes is not None

    it2 = PrefetchIterator(SageBatchSource(sampler, tr, labels, BATCH,
                                           seed=0),
                           depth=2, code_gather=gather)
    try:
        it2.load_state_dict(sd)
        got = it2.next_batch()["frontier"]
    finally:
        it2.close()
    assert np.array_equal(np.asarray(got.unique), np.asarray(want.unique))
    assert np.array_equal(np.asarray(got.codes), np.asarray(want.codes))


def test_prefetch_stats_account_code_stream(graph):
    rt = GraphRuntime.from_spec(
        _spec(codes_placement="host", prefetch_depth=2), graph=graph)
    try:
        rt.train(3)
        st = rt.data_iter.stats()
    finally:
        rt.close()
    assert st["n_produced"] >= 3
    for k in ("sample_us", "code_gather_us", "put_us"):
        assert st[k] > 0.0, k
    assert st["transferred_code_bytes_per_batch"] > 0


# ---------------------------------------------------------------------------
# (c) bitwise parity host vs device: property across backends + serving
# ---------------------------------------------------------------------------

BACKEND_VARIANTS = (
    {"lookup_impl": "gather"},
    {"lookup_impl": "onehot"},
    {"lookup_impl": "pallas"},
    # staleness-0 hot-node cache: the cached lookup decodes only misses but
    # must stay bitwise — with batch codes it slices the miss prefix
    {"lookup_impl": "pallas", "cache_capacity": 2048, "cache_staleness": 0},
)


def test_host_placement_is_bitwise_property(graph):
    """Property: for any backend variant and batch stream, host placement's
    losses AND embeddings are bit-for-bit the replicated run's (the host
    row gather commutes with decode)."""
    pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=6, deadline=None)
    @given(variant=st.integers(0, len(BACKEND_VARIANTS) - 1),
           data_seed=st.integers(0, 3))
    def check(variant, data_seed):
        emb_kw = BACKEND_VARIANTS[variant]
        dev = GraphRuntime.from_spec(_spec(data_seed=data_seed, **emb_kw),
                                     graph=graph)
        host = GraphRuntime.from_spec(
            _spec(data_seed=data_seed, codes_placement="host",
                  prefetch_depth=2, **emb_kw), graph=graph)
        try:
            assert dev.train(2).losses == host.train(2).losses
            ids = np.arange(4, dtype=np.int32)
            np.testing.assert_array_equal(dev.embed(ids), host.embed(ids))
        finally:
            dev.close()
            host.close()

    check()


def test_host_placement_bitwise_each_backend(graph):
    """Deterministic fallback for the property above (runs even without
    hypothesis): every backend variant, fixed stream, 2-step loss parity."""
    for emb_kw in BACKEND_VARIANTS:
        dev = GraphRuntime.from_spec(_spec(**emb_kw), graph=graph)
        host = GraphRuntime.from_spec(
            _spec(codes_placement="host", prefetch_depth=2, **emb_kw),
            graph=graph)
        try:
            assert dev.train(2).losses == host.train(2).losses, emb_kw
        finally:
            dev.close()
            host.close()


def test_eval_and_serve_many_parity(graph):
    """evaluate() and the serving microbatch concat (serve_many) are
    bitwise host == device — codes attach after the miss-first permutation,
    so the concatenated union frontier stays row-aligned."""
    dev = GraphRuntime.from_spec(_spec(), graph=graph)
    host = GraphRuntime.from_spec(
        _spec(codes_placement="host", prefetch_depth=2), graph=graph)
    try:
        assert dev.train(3).losses == host.train(3).losses
        assert dev.evaluate("val") == host.evaluate("val")

        rng = np.random.default_rng(7)
        reqs = [rng.integers(0, N, size=int(rng.integers(4, 32)))
                .astype(np.int32) for _ in range(4)]
        eng_d = dev.serve(serve_batch=64, max_coalesce=4)
        eng_h = host.serve(serve_batch=64, max_coalesce=4)
        for rd, rh in zip(eng_d.serve_many(reqs), eng_h.serve_many(reqs)):
            np.testing.assert_array_equal(np.asarray(rd.embeddings),
                                          np.asarray(rh.embeddings))
            np.testing.assert_array_equal(np.asarray(rd.logits),
                                          np.asarray(rh.logits))
        # single-request path too
        np.testing.assert_array_equal(
            np.asarray(eng_d.serve(reqs[0]).embeddings),
            np.asarray(eng_h.serve(reqs[0]).embeddings))
    finally:
        dev.close()
        host.close()


@pytest.mark.multidevice(n=4)
@pytest.mark.parametrize("impl", ["sharded:gather", "owner:gather"])
def test_4shard_host_placement_bitwise(graph, impl):
    """4-shard sharded/owner runs: the row-sharded codes leaf lands each
    shard's rows on its own device and the losses stay bitwise."""
    spec = _spec(lookup_impl=impl, n_shards=4, prefetch_depth=2)
    dev = GraphRuntime.from_spec(spec, graph=graph)
    try:
        l_dev = dev.train(2).losses
    finally:
        dev.close()
    host = GraphRuntime.from_spec(spec.with_updates(codes_placement="host"),
                                  graph=graph)
    try:
        assert _param_codes_buf_bytes(host.state["params"]) == 0
        assert host.train(2).losses == l_dev
    finally:
        host.close()


# ---------------------------------------------------------------------------
# (d) spec → checkpoint → resume keeps placement and bit pattern
# ---------------------------------------------------------------------------

def test_ckpt_resume_keeps_host_placement_bitwise(graph, tmp_path):
    ref = GraphRuntime.from_spec(_spec(), graph=graph)
    try:
        ref_losses = ref.train(4).losses
    finally:
        ref.close()

    spec = _spec(codes_placement="host", prefetch_depth=2,
                 ckpt_dir=str(tmp_path / "h"), ckpt_every=2)
    rt = GraphRuntime.from_spec(spec, graph=graph)
    try:
        head = rt.train(2).losses
    finally:
        rt.close()

    # resume knows nothing but the directory: placement rides the manifest
    rt2 = GraphRuntime.resume(str(tmp_path / "h"), graph=graph)
    try:
        assert rt2.codes_on_host
        assert _param_codes_buf_bytes(rt2.state["params"]) == 0
        tail = rt2.train(4)
        assert tail.resumed_from == 2
        assert head + tail.losses == ref_losses       # bitwise, end to end
    finally:
        rt2.close()


def test_spec_json_roundtrip_codes_placement():
    spec = _spec(codes_placement="host")
    back = RuntimeSpec.from_json(spec.to_json())
    assert back.model.embedding.codes_placement == "host"
    assert back == spec


# ---------------------------------------------------------------------------
# (e) memory contract + loud failure modes
# ---------------------------------------------------------------------------

def test_host_params_carry_no_codes_buf(graph):
    dev = GraphRuntime.from_spec(_spec(), graph=graph)
    host = GraphRuntime.from_spec(_spec(codes_placement="host"), graph=graph)
    try:
        resident_dev = _param_codes_buf_bytes(dev.state["params"])
        resident_host = _param_codes_buf_bytes(host.state["params"])
        assert resident_dev > 0
        assert resident_host == 0
    finally:
        dev.close()
        host.close()


def test_host_code_bytes_do_not_grow_with_the_graph():
    """At one batch and ``frontier_cap``, host placement keeps no
    ``codes_buf`` on the device and streams the same code bytes per batch
    on a 2,000- and an 8,000-node graph; the replicated buffer of device
    placement grows with the graph."""
    resident = {}
    streamed = {}
    for n in (2_000, 8_000):
        base = paper_gnn_config("sage", n_nodes=n, n_classes=8, fanout=5)
        for placement in ("device", "host"):
            model = dataclasses.replace(base, embedding=dataclasses.replace(
                base.embedding, c=16, m=8, d_c=64, d_m=64,
                lookup_impl="gather", codes_placement=placement))
            spec = RuntimeSpec(
                graph=GraphSource(kind="powerlaw", seed=0, n_nodes=n,
                                  n_classes=8, avg_degree=8),
                model=model, optimizer=OPT, batch_size=32, pad_to=64,
                frontier_cap=1024, prefetch_depth=2)
            rt = GraphRuntime.from_spec(spec)
            try:
                resident[n, placement] = _param_codes_buf_bytes(
                    rt.state["params"])
                for _ in range(2):
                    rt.data_iter.next_batch()
                streamed[n, placement] = rt.data_iter.stats()[
                    "transferred_code_bytes_per_batch"]
            finally:
                rt.close()
    assert resident[2_000, "host"] == resident[8_000, "host"] == 0
    assert streamed[2_000, "host"] == streamed[8_000, "host"] > 0
    assert 0 < resident[2_000, "device"] < resident[8_000, "device"]
    assert streamed[2_000, "device"] == streamed[8_000, "device"] == 0


def test_unknown_placement_fails_at_init():
    ecfg = _cfg(codes_placement="hbm").embedding_config()
    with pytest.raises(ValueError, match="codes_placement"):
        emb_lib.init_embedding(KEY, ecfg)


def test_host_lookup_without_batch_codes_fails_loudly(graph):
    ecfg = _cfg(codes_placement="host").embedding_config()
    params = emb_lib.init_embedding(KEY, ecfg)
    with pytest.raises(ValueError, match="codes"):
        emb_lib.embed_lookup(params, np.arange(4), ecfg)


def test_fullgraph_rejects_host_placement(graph):
    cfg = dataclasses.replace(
        paper_gnn_config("gcn", n_nodes=N, n_classes=8),
        embedding=dataclasses.replace(
            _cfg().embedding, codes_placement="host"))
    with pytest.raises(ValueError, match="full-graph"):
        GraphRuntime.from_spec(_spec(model=cfg), graph=graph)
