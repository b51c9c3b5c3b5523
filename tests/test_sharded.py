"""Sharded streaming engine (ISSUE 3): the (seed, shard, step) sampling
contract, the stacked multi-shard frontier, the "sharded" decode backend,
and their end-to-end agreement with the single-shard path.

Single-device tests always run (the backend degrades to its base with no
mesh / a 1-sized data axis); tests needing a real multi-device mesh carry
the ``multidevice`` marker and skip — never error — below 2 devices (the
``tools/ci.sh --multidevice`` leg forces 8 host devices and runs them).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_gnn import paper_gnn_config
from repro.core import backend as backend_mod
from repro.core import embedding as emb_lib
from repro.graph import FrontierBatch, NeighborSampler, powerlaw_graph
from repro.graph.sampler import OwnerPlan
from repro.graph.engine import (GNNModel, PrefetchIterator, SageBatchSource,
                                ShardedSageBatchSource, default_frontier_cap)
from repro.parallel.policy import make_frontier_placement
from repro.parallel.sharding import use_sharding
from repro.train import (LoopConfig, init_gnn_train_state, make_gnn_train_step,
                         run_training)

KEY = jax.random.PRNGKey(0)
N = 1200
N_SHARDS = 4
BATCH = 64          # global batch; per-shard = BATCH // N_SHARDS


@pytest.fixture(scope="module")
def graph():
    return powerlaw_graph(0, N, avg_degree=8, n_classes=8, homophily=0.9)


def _cfg(lookup_impl="sharded:gather", **emb_kw):
    base = paper_gnn_config("sage", n_nodes=N, n_classes=8, fanout=5)
    return dataclasses.replace(base, embedding=dataclasses.replace(
        base.embedding, c=16, m=8, d_c=64, d_m=64, lookup_impl=lookup_impl,
        **emb_kw))


@pytest.fixture(scope="module")
def codes(graph):
    adj, _ = graph
    # numpy, not a device array: the train state is donated per step, so a
    # shared device buffer would be deleted out from under the next init
    return np.asarray(emb_lib.make_codes(KEY, _cfg().embedding_config(),
                                         aux=adj))


def _mesh(n):
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:n]), ("data",))


# ---------------------------------------------------------------------------
# sharded sampling contract (single device)
# ---------------------------------------------------------------------------

def test_shard_union_bit_identical_to_single(graph):
    """The N per-shard batches concatenated == the 1-shard batch, per level,
    for several steps — the (seed, shard, step) slicing contract."""
    adj, labels = graph
    sampler = NeighborSampler(adj, (5, 5), max_deg=32, seed=0)
    single = SageBatchSource(sampler, np.arange(N), labels, BATCH, seed=7)
    shards = [SageBatchSource(sampler, np.arange(N), labels,
                              BATCH // N_SHARDS, seed=7, shard=s,
                              n_shards=N_SHARDS) for s in range(N_SHARDS)]
    for _ in range(3):
        g = single.next_batch()
        parts = [s.next_batch() for s in shards]
        for i, lvl in enumerate(g["frontier"].levels()):
            cat = np.concatenate(
                [np.asarray(p["frontier"].levels()[i]) for p in parts], axis=0)
            np.testing.assert_array_equal(np.asarray(lvl), cat)
        np.testing.assert_array_equal(
            g["labels"], np.concatenate([p["labels"] for p in parts]))


def test_shard_state_dict_roundtrip(graph):
    adj, labels = graph
    sampler = NeighborSampler(adj, (5, 5), max_deg=32, seed=0)
    src = SageBatchSource(sampler, np.arange(N), labels, 16, seed=3,
                          shard=2, n_shards=N_SHARDS)
    src.next_batch()
    snap = src.state_dict()
    assert snap == {"step": 1, "seed": 3, "shard": 2, "n_shards": N_SHARDS}
    want = src.next_batch()
    src.load_state_dict(snap)
    got = src.next_batch()
    np.testing.assert_array_equal(np.asarray(want["frontier"].unique),
                                  np.asarray(got["frontier"].unique))
    # a different shard layout must refuse the state
    other = SageBatchSource(sampler, np.arange(N), labels, 16, seed=3,
                            shard=1, n_shards=N_SHARDS)
    with pytest.raises(AssertionError):
        other.load_state_dict(snap)


def test_sharded_source_stacked_layout_and_resume(graph):
    """The stacked batch groups rows per shard block, offsets index maps
    into the owning block, masks each block's padding, and resumes through
    PrefetchIterator exactly."""
    adj, labels = graph
    sampler = NeighborSampler(adj, (5, 5), max_deg=32, seed=0)
    src = ShardedSageBatchSource(sampler, np.arange(N), labels,
                                 BATCH // N_SHARDS, n_shards=N_SHARDS,
                                 seed=7, pad_to=64)
    cap = src.frontier_cap
    batch = src.next_batch()
    fb = batch["frontier"]
    assert fb.unique.shape[0] == N_SHARDS * cap
    assert fb.valid is not None and fb.valid.shape == fb.unique.shape
    # each level-0 block points into its own shard's rows
    tgt = np.asarray(fb.index_maps[0])
    per = BATCH // N_SHARDS
    for s in range(N_SHARDS):
        blk = tgt[s * per:(s + 1) * per]
        assert (blk >= s * cap).all() and (blk < (s + 1) * cap).all()
    # stacked maps reconstruct the exact global levels of the 1-shard source
    single = SageBatchSource(sampler, np.arange(N), labels, BATCH, seed=7)
    g = single.next_batch()
    for lvl, got in zip(g["frontier"].levels(), fb.levels()):
        np.testing.assert_array_equal(np.asarray(lvl), np.asarray(got))

    pf = PrefetchIterator(src, depth=2)
    try:
        pf.next_batch()
        snap = pf.state_dict()
        want = np.asarray(pf.next_batch()["labels"])
        pf.load_state_dict(snap)
        got = np.asarray(pf.next_batch()["labels"])
    finally:
        pf.close()
    np.testing.assert_array_equal(want, got)


def test_no_cross_level_draw_correlation_past_path_stride():
    """Path counters repeat across levels once the global batch exceeds the
    path stride (1024): target gpos 1024 shares its counter range with child
    k=0 of gpos 0.  The per-level subkey must decorrelate those draws —
    without it, the two streams are bit-identical whenever the node ids
    coincide (regression for the sample_hashed keying scheme)."""
    from repro.graph import CSRMatrix
    # node 0's only neighbour is node 1; node 1 has many distinct neighbours
    src = [0] + [1] * 40
    dst = [1] + list(range(2, 42))
    adj = CSRMatrix.from_edges(np.array(src), np.array(dst), n_nodes=42)
    sampler = NeighborSampler(adj, (4, 4), max_deg=64, seed=0)
    ids = np.zeros(1025, np.int32)
    ids[1024] = 1                       # same node as gpos 0's forced child
    from repro.graph.sampler import stream_key
    levels = sampler.sample_hashed(ids, np.arange(1025, dtype=np.uint64),
                                   stream_key(0, 0))
    assert levels[1][0, 0] == 1         # child k=0 of gpos 0 is node 1
    # child-of-child draws (level 2, key_1) vs target-1024 level-1 draws
    # (key_0) share the counter range but must not share the stream
    assert not np.array_equal(levels[2][0, 0, :], levels[1][1024, :])


def test_frontier_cap_exact_padding_and_overflow():
    levels = [np.arange(8), np.arange(8).repeat(3).reshape(8, 3)]
    fb = FrontierBatch.from_levels(levels, cap=16)
    assert fb.unique.shape == (16,) and int(fb.n_unique) == 8
    with pytest.raises(ValueError, match="cap"):
        FrontierBatch.from_levels(levels, cap=4)
    # default cap: worst case bounded by the graph size, pad_to-aligned
    assert default_frontier_cap(16, (5, 5), 64, n_nodes=N) == \
        -(-min(16 * 31, N) // 64) * 64


# ---------------------------------------------------------------------------
# sharded backend (single device: degrades to base)
# ---------------------------------------------------------------------------

def test_sharded_backend_registry_and_fallback():
    assert "sharded" in backend_mod.available_backends()
    be = backend_mod.get_backend("sharded:gather")
    assert be.base.name == "gather"
    with pytest.raises(ValueError, match="unknown decode backend"):
        backend_mod.get_backend("nope")
    with pytest.raises(ValueError, match="no ':"):
        backend_mod.get_backend("gather:onehot")
    with pytest.raises(ValueError, match="wrap itself"):
        backend_mod.get_backend("sharded:sharded")

    # no mesh -> bitwise the base backend
    key = jax.random.PRNGKey(1)
    codes = jax.random.randint(key, (32, 8), 0, 16)
    cb = jax.random.normal(jax.random.fold_in(key, 1), (8, 16, 64))
    w0 = jax.random.normal(jax.random.fold_in(key, 2), (64,))
    ref = backend_mod.get_backend("gather").decode(codes, cb, w0)
    np.testing.assert_array_equal(np.asarray(be.decode(codes, cb, w0)),
                                  np.asarray(ref))


def test_sharded_selectable_through_model_and_serving(graph, codes):
    """lookup_impl="sharded" resolves everywhere the registry is routed —
    the GNN frontier path and the serving engine — and on one device the
    hidden states are bitwise the gather path's."""
    adj, labels = graph
    cfg_sh = _cfg("sharded:gather")
    cfg_ref = _cfg("gather")
    params = GNNModel(cfg_ref).init(KEY, codes=codes)
    sampler = NeighborSampler(adj, (5, 5), max_deg=32, seed=0)
    fb = SageBatchSource(sampler, np.arange(N), labels, 32,
                         seed=1).next_batch()["frontier"]
    h_ref = GNNModel(cfg_ref).apply(params, jax.device_put(fb))
    h_sh = GNNModel(cfg_sh).apply(params, jax.device_put(fb))
    np.testing.assert_array_equal(np.asarray(h_ref), np.asarray(h_sh))

    from repro.configs import get_config, reduced
    from repro.models import init_lm
    from repro.serving import DecodeEngine
    lm_cfg = reduced(get_config("qwen1.5-0.5b"))
    lm_params = init_lm(jax.random.PRNGKey(0), lm_cfg)
    eng = DecodeEngine(lm_cfg, lm_params, s_max=32,
                       decode_backend="sharded:gather")
    assert eng.decode_backend == "sharded:gather"
    with pytest.raises(ValueError, match="unknown decode backend"):
        DecodeEngine(lm_cfg, lm_params, s_max=32, decode_backend="bogus")


# ---------------------------------------------------------------------------
# multi-device: backend parity, end-to-end bit-identity, sharded cache
# ---------------------------------------------------------------------------

@pytest.mark.multidevice(n=4)
def test_sharded_decode_matches_gather_oracle():
    """Forward is bitwise the gather oracle (rows accumulate identically on
    whichever shard holds them); grads match within f32 tolerance (the psum
    reduces partial codebook grads in a different order)."""
    mesh = _mesh(4)
    key = jax.random.PRNGKey(0)
    B, m, c, d_c = 64, 8, 16, 128
    codes = jax.random.randint(key, (B, m), 0, c)
    cb = jax.random.normal(jax.random.fold_in(key, 1), (m, c, d_c))
    w0 = jax.random.normal(jax.random.fold_in(key, 2), (d_c,))
    oracle = backend_mod.get_backend("gather")
    sb = backend_mod.get_backend("sharded:gather")

    for scale in (w0, None):
        ref = oracle.decode(codes, cb, scale)
        with use_sharding(mesh):
            out = jax.jit(lambda c, b, s: sb.decode(c, b, s))(codes, cb, scale)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def loss(fn):
        return lambda cb_, w0_: (fn(codes, cb_, w0_) ** 2).sum()
    with use_sharding(mesh):
        assert backend_mod.resolve_auto() == "sharded"
        g_sh = jax.jit(jax.grad(loss(sb.decode), argnums=(0, 1)))(cb, w0)
    g_ref = jax.grad(loss(oracle.decode), argnums=(0, 1))(cb, w0)
    for a, b in zip(g_sh, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.multidevice(n=4)
def test_sharded_decode_pads_unaligned_batch():
    mesh = _mesh(4)
    key = jax.random.PRNGKey(3)
    codes = jax.random.randint(key, (30, 8), 0, 16)   # 30 % 4 != 0
    cb = jax.random.normal(jax.random.fold_in(key, 1), (8, 16, 64))
    ref = backend_mod.get_backend("gather").decode(codes, cb, None)
    with use_sharding(mesh):
        out = backend_mod.get_backend("sharded:gather").decode(codes, cb, None)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def _run_stream(graph, codes, cfg, n_shards, mesh, steps=3, seed=0,
                owner=False):
    adj, labels = graph
    sampler = NeighborSampler(adj, cfg.fanouts, max_deg=32, seed=0)
    src = ShardedSageBatchSource(sampler, np.arange(N), labels,
                                 BATCH // n_shards, n_shards=n_shards,
                                 seed=seed, pad_to=64, owner_plan=owner)
    place = make_frontier_placement(mesh) if mesh is not None else None
    state = init_gnn_train_state(KEY, cfg, codes=codes)
    it = PrefetchIterator(src, depth=2, device=place)
    try:
        res = run_training(make_gnn_train_step(cfg, mesh=mesh), state, it,
                           LoopConfig(total_steps=steps))
    finally:
        it.close()
    return res.losses


@pytest.mark.multidevice(n=4)
def test_4shard_run_loss_bit_identical_to_1shard(graph, codes):
    """Acceptance (ISSUE 3): with a 4-way data mesh, the 4-shard streaming
    GNN run's forward loss is bit-identical to the 1-shard run on step 0 —
    same global batch (sampling contract), same decoded rows (sharded
    backend over the gather base), same combine (full-batch, post-gather).
    """
    cfg = _cfg("sharded:gather")
    l1 = _run_stream(graph, codes, cfg, 1, None)
    l4 = _run_stream(graph, codes, cfg, N_SHARDS, _mesh(N_SHARDS))
    assert l1[0] == l4[0], f"step-0 loss diverged: {l1[0]} vs {l4[0]}"
    # later steps may only drift by f32 accumulation (grad psum order)
    assert max(abs(a - b) for a, b in zip(l1, l4)) < 1e-3


# ---------------------------------------------------------------------------
# owner-computes decode (ISSUE 5): plan, backend, end-to-end, property
# ---------------------------------------------------------------------------

def _owner_source(graph, n_shards=N_SHARDS, seed=7, owner_plan=True, **kw):
    adj, labels = graph
    sampler = NeighborSampler(adj, (5, 5), max_deg=32, seed=0)
    return ShardedSageBatchSource(sampler, np.arange(N), labels,
                                  BATCH // n_shards, n_shards=n_shards,
                                  seed=seed, pad_to=64, owner_plan=owner_plan,
                                  **kw)


def test_owner_backend_registry_and_fallback():
    assert "owner" in backend_mod.available_backends()
    be = backend_mod.get_backend("owner:gather")
    assert be.base.name == "gather"
    with pytest.raises(ValueError, match="wrap itself"):
        backend_mod.get_backend("owner:owner")
    with pytest.raises(ValueError, match="wrap itself"):
        backend_mod.get_backend("owner:sharded")
    with pytest.raises(ValueError, match="wrap itself"):
        backend_mod.get_backend("sharded:owner")

    # no mesh -> bitwise the base backend, with or without a plan
    key = jax.random.PRNGKey(1)
    codes = jax.random.randint(key, (32, 8), 0, 16)
    cb = jax.random.normal(jax.random.fold_in(key, 1), (8, 16, 64))
    w0 = jax.random.normal(jax.random.fold_in(key, 2), (64,))
    ref = backend_mod.get_backend("gather").decode(codes, cb, w0)
    np.testing.assert_array_equal(np.asarray(be.decode(codes, cb, w0)),
                                  np.asarray(ref))
    np.testing.assert_array_equal(
        np.asarray(be.decode_frontier(codes, cb, w0, plan=None)),
        np.asarray(ref))


def test_owner_plan_routes_every_valid_row_once(graph):
    """Host-side contract of ``build_owner_plan``: simulating the exchange
    in numpy with ids as payloads, every valid frontier row receives the id
    it asked for, each owner's decode list is distinct ids ≡ owner (mod n),
    and the total decoded rows equal the stacked frontier's global unique
    count (the cross-shard dedup)."""
    src = _owner_source(graph)
    batch = src.next_batch()
    fb = batch["frontier"]
    plan = fb.plan
    assert plan is not None
    n, cap = src.n_shards, src.frontier_cap
    unique = np.asarray(fb.unique).reshape(n, cap)
    valid = np.asarray(fb.valid).reshape(n, cap)
    n_uniques = [int(valid[s].sum()) for s in range(n)]

    # owner o's decode list: distinct, owned by o
    global_unique = np.unique(np.concatenate(
        [unique[s, :n_uniques[s]] for s in range(n)]))
    for o in range(n):
        k = int(plan.n_owned[o])
        recv = np.stack([unique[s][np.clip(plan.req_rows[s, o], 0, cap - 1)]
                         for s in range(n)]).reshape(-1)
        owned = recv[plan.owned_src[o, :k]]
        assert len(np.unique(owned)) == k and (owned % n == o).all()
    assert int(plan.n_owned.sum()) == global_unique.shape[0]
    assert int(plan.n_owned.sum()) < sum(n_uniques)   # real cross-shard dedup

    # full exchange simulation: payload = the id itself
    out = np.full((n, cap), -1, np.int64)
    for o in range(n):
        recv = np.stack([unique[s][np.clip(plan.req_rows[s, o], 0, cap - 1)]
                         for s in range(n)]).reshape(-1)
        dec = recv[plan.owned_src[o]]                 # "decode" = identity
        for s in range(n):
            back = dec[plan.ret_idx[o, s]]            # (oc,)
            rows = plan.req_rows[s, o]
            ok = rows < cap
            out[s, rows[ok]] = back[ok]
    for s in range(n):
        np.testing.assert_array_equal(out[s, :n_uniques[s]],
                                      unique[s, :n_uniques[s]])


def test_owner_plan_overflow_falls_back_loudly(graph):
    """Caps too small for the workload: the source must warn and emit the
    batch WITHOUT a plan (decode falls back), never truncate rows."""
    src = _owner_source(graph, owner_cap=2, owner_unique_cap=8)
    with pytest.warns(UserWarning, match="owner plan overflow"):
        batch = src.next_batch()
    fb = batch["frontier"]
    assert fb.plan is None
    # the batch itself is intact — the 1-shard reconstruction still holds
    adj, labels = graph
    sampler = NeighborSampler(adj, (5, 5), max_deg=32, seed=0)
    single = SageBatchSource(sampler, np.arange(N), labels, BATCH, seed=7)
    g = single.next_batch()
    for lvl, got in zip(g["frontier"].levels(), fb.levels()):
        np.testing.assert_array_equal(np.asarray(lvl), np.asarray(got))


def _serial_owner_plan(uniques, n_uniques, n, owner_cap, owner_unique_cap):
    """Reference: the plan built loop by loop, one requester and then one
    owner after another, with no pool."""
    cap = uniques[0].shape[0]
    req_rows = np.full((n, n, owner_cap), cap, np.int32)
    requests = [[None] * n for _ in range(n)]
    for s in range(n):
        ids = uniques[s][:n_uniques[s]]
        for o in range(n):
            rows = np.nonzero(ids % n == o)[0]
            if rows.shape[0] > owner_cap:
                return None
            req_rows[s, o, :rows.shape[0]] = rows
            requests[s][o] = ids[rows]
    owned_src = np.zeros((n, owner_unique_cap), np.int32)
    ret_idx = np.zeros((n, n, owner_cap), np.int32)
    n_owned = np.zeros((n,), np.int32)
    for o in range(n):
        flat = np.full((n * owner_cap,), -1, np.int64)
        for s in range(n):
            k = requests[s][o].shape[0]
            flat[s * owner_cap:s * owner_cap + k] = requests[s][o]
        pos = np.nonzero(flat >= 0)[0]
        uniq, first, inv = np.unique(flat[pos], return_index=True,
                                     return_inverse=True)
        if uniq.shape[0] > owner_unique_cap:
            return None
        owned_src[o, :uniq.shape[0]] = pos[first]
        n_owned[o] = uniq.shape[0]
        ridx = np.zeros((n * owner_cap,), np.int32)
        ridx[pos] = inv.astype(np.int32)
        ret_idx[o] = ridx.reshape(n, owner_cap)
    return OwnerPlan(req_rows, owned_src, ret_idx, n_owned)


def _serial_batches(graph, n, seed, cap, plan_caps, steps, start=0):
    """Reference: the stacked batches of ``steps`` steps from ``start``,
    sampled shard after shard by plain ``SageBatchSource``s and planned by
    ``_serial_owner_plan`` when ``plan_caps`` = (owner_cap,
    owner_unique_cap) is given."""
    adj, labels = graph
    sampler = NeighborSampler(adj, (5, 5), max_deg=32, seed=0)
    shards = [SageBatchSource(sampler, np.arange(N), labels, BATCH // n,
                              seed=seed, pad_to=64, shard=s, n_shards=n,
                              frontier_cap=cap) for s in range(n)]
    for sh in shards:
        sh.step = start
    out = []
    for _ in range(steps):
        fbs = [sh.next_batch() for sh in shards]
        labels_ = np.concatenate([p["labels"] for p in fbs])
        fbs = [p["frontier"] for p in fbs]
        unique = np.concatenate([fb.unique for fb in fbs])
        maps = tuple(np.concatenate([fb.index_maps[i] + s * cap
                                     for s, fb in enumerate(fbs)])
                     for i in range(len(fbs[0].index_maps)))
        valid = np.concatenate([np.arange(cap, dtype=np.int32) < int(fb.n_unique)
                                for fb in fbs])
        n_unique = np.int32(sum(int(fb.n_unique) for fb in fbs))
        plan = None if plan_caps is None else _serial_owner_plan(
            [fb.unique for fb in fbs], [int(fb.n_unique) for fb in fbs], n,
            *plan_caps)
        out.append({"frontier": FrontierBatch(unique, maps, n_unique, valid,
                                              plan),
                    "labels": labels_})
    return out


def _assert_bitwise_equal(got, want):
    got_leaves, got_def = jax.tree.flatten(got)
    want_leaves, want_def = jax.tree.flatten(want)
    assert got_def == want_def
    for a, b in zip(got_leaves, want_leaves):
        a, b = np.asarray(a), np.asarray(b)
        assert (a.dtype, a.shape) == (b.dtype, b.shape)
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", ["plan", "no_plan", "auto", "resume",
                                  "stress16"])
def test_pooled_sharded_source_bit_identical_to_serial_build(graph, case):
    """The source samples its shards and builds the owner plan on the
    process's thread pool; every leaf of every batch, the plan included, is
    bit for bit the serial build's: over several steps, without a plan,
    after the ``"auto"`` duplication peek, resumed through ``state_dict``
    into a fresh source, and with 16 shards (more parts than pool threads)
    under a shortened thread switch interval."""
    import sys
    n = 16 if case == "stress16" else N_SHARDS
    owner_plan = {"no_plan": False, "auto": "auto"}.get(case, True)
    interval = sys.getswitchinterval()
    if case == "stress16":
        sys.setswitchinterval(1e-6)
    try:
        src = _owner_source(graph, n_shards=n, owner_plan=owner_plan)
        start = 0
        if case == "resume":
            src.next_batch()
            src.next_batch()
            snap = src.state_dict()
            src = _owner_source(graph, n_shards=n)
            src.load_state_dict(snap)
            start = 2
        got = [src.next_batch() for _ in range(3)]
    finally:
        sys.setswitchinterval(interval)
    plan_caps = ((src.owner_cap, src.owner_unique_cap) if src.owner_plan
                 else None)
    want = _serial_batches(graph, n, 7, src.frontier_cap, plan_caps, 3,
                           start=start)
    if case == "auto":
        # the peek measured the reference's own step-0 duplication, and it
        # is past the threshold, so the peeked batch carries a plan
        rows = sum(int(b["frontier"].n_unique)
                   for b in _serial_batches(graph, n, 7, src.frontier_cap,
                                            None, 1))
        assert src.duplication_measured == src.frontier_cap * n / rows
        assert src.owner_plan
    assert (plan_caps is None) == (case == "no_plan")
    assert all(b["frontier"].plan is not None for b in want) == src.owner_plan
    for g, w in zip(got, want):
        _assert_bitwise_equal(g, w)


@pytest.mark.parametrize("caps", [dict(owner_cap=2), dict(owner_unique_cap=8)],
                         ids=["bucket", "owned"])
def test_pooled_owner_plan_overflow_counters_and_threads(graph, caps):
    """With the pool, a bucket (first phase) or an owner's unique set
    (second phase) over its cap still gives a batch with no plan, the
    warning and one ``owner_plan_overflows``; the source reports its pool
    threads and its shards' own sampling time; and 50 sources built and
    dropped start no threads beyond the one shared pool."""
    import threading

    from repro.graph.engine import SHARD_POOL_WORKERS
    src = _owner_source(graph, **caps)
    with pytest.warns(UserWarning, match="owner plan overflow"):
        batch = src.next_batch()
    assert batch["frontier"].plan is None
    stats = src.stats()
    assert stats["owner_plan_overflows"] == 1 and stats["owned_rows"] == 0
    assert stats["shard_workers"] > 1 and stats["shard_sample_us"] > 0

    before = threading.active_count()
    for seed in range(50):
        _owner_source(graph, seed=seed).next_batch()
    assert threading.active_count() <= before + SHARD_POOL_WORKERS
    pool_threads = [t for t in threading.enumerate()
                    if t.name.startswith("shard-pool")]
    assert 0 < len(pool_threads) <= SHARD_POOL_WORKERS


def test_owner_spec_field_roundtrip():
    """An owner-decode run is one RuntimeSpec field change, and the owner
    knobs ride through JSON (checkpoint-resume safe)."""
    import json

    from repro.graph.runtime import GraphSource, RuntimeSpec
    spec = RuntimeSpec(
        graph=GraphSource(n_nodes=N, n_classes=8),
        model=paper_gnn_config("sage", n_nodes=N, n_classes=8, fanout=5),
    ).with_updates(lookup_impl="owner:gather", n_shards=4,
                   owner_cap=128, owner_unique_cap=256)
    assert spec.model.embedding.lookup_impl == "owner:gather"
    assert (spec.owner_cap, spec.owner_unique_cap) == (128, 256)
    restored = RuntimeSpec.from_dict(json.loads(spec.to_json()))
    assert restored == spec


def test_owner_caps_default_sizing():
    from repro.graph.sampler import default_owner_caps
    # a 4-shard, cap-7168 workload: cap·1.25/n request slots, cap/2 decode
    # rows (the duplication-threshold inequality, both sublane-rounded)
    assert default_owner_caps(7168, 4) == (2240, 3584)
    # never exceed the trivially safe bounds (cap, n_shards·owner_cap)
    oc, ou = default_owner_caps(16, 16)
    assert oc <= 16 and ou <= 16 * oc


def test_owner_hashed_frontiers_never_overflow_default_caps(graph):
    """Property (ISSUE 5 satellite): frontiers drawn by the splitmix64
    counter-based sampler never overflow the default capacities — every
    (requester, owner) bucket fits the ``cap/n_shards`` expectation with the
    default safety factor, every owner's unique set fits ``cap/2``, and the
    plan therefore always builds (the loud fallback never fires in
    practice)."""
    hypothesis = pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    from repro.graph.sampler import default_owner_caps
    adj, labels = graph
    sampler = NeighborSampler(adj, (5, 5), max_deg=32, seed=0)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), step=st.integers(0, 1000))
    def check(seed, step):
        src = ShardedSageBatchSource(sampler, np.arange(N), labels,
                                     BATCH // N_SHARDS, n_shards=N_SHARDS,
                                     seed=seed, pad_to=64, owner_plan=True)
        for sh in src.shards:
            sh.step = step
        batch = src.next_batch()
        fb = batch["frontier"]
        # no bucket or owned-unique overflow: the plan built (no fallback)
        assert fb.plan is not None, (seed, step)
        cap = src.frontier_cap
        oc, _ = default_owner_caps(cap, N_SHARDS)
        unique = np.asarray(fb.unique).reshape(N_SHARDS, cap)
        valid = np.asarray(fb.valid).reshape(N_SHARDS, cap)
        for s in range(N_SHARDS):
            ids = unique[s][valid[s]]
            counts = np.bincount(ids % N_SHARDS, minlength=N_SHARDS)
            assert counts.max() <= oc, (seed, step, counts.max(), oc)

    check()


@pytest.mark.multidevice(n=4)
def test_owner_decode_matches_gather_oracle(graph):
    """Tentpole acceptance: forward through the owner exchange is bitwise
    the gather oracle on every valid row (a row's decode is computed once,
    on its owner, from the same code row); codebook/W0 grads match the
    oracle within f32 tolerance (cotangents are scatter-added per owner and
    the disjoint owner partials psummed in a different order)."""
    mesh = _mesh(N_SHARDS)
    src = _owner_source(graph)
    fb = src.next_batch()["frontier"]
    assert fb.plan is not None
    key = jax.random.PRNGKey(0)
    m, c, d_c = 8, 16, 128
    ctable = jax.random.randint(key, (N, m), 0, c)
    codes = jnp.asarray(np.asarray(ctable)[np.asarray(fb.unique)])
    cb = jax.random.normal(jax.random.fold_in(key, 1), (m, c, d_c))
    w0 = jax.random.normal(jax.random.fold_in(key, 2), (d_c,))
    valid = np.asarray(fb.valid)
    vm = jnp.asarray(valid)[:, None]

    oracle = backend_mod.get_backend("gather")
    ob = backend_mod.get_backend("owner:gather")
    for scale in (w0, None):
        ref = oracle.decode(codes, cb, scale)
        with use_sharding(mesh):
            out = jax.jit(lambda co, b, s: ob.decode_frontier(
                co, b, s, plan=fb.plan))(codes, cb, scale)
        np.testing.assert_array_equal(np.asarray(out)[valid],
                                      np.asarray(ref)[valid])

    def loss(fn):
        return lambda cb_, w0_: ((fn(cb_, w0_) * vm) ** 2).sum()
    with use_sharding(mesh):
        g_own = jax.jit(jax.grad(
            loss(lambda b, s: ob.decode_frontier(codes, b, s, plan=fb.plan)),
            argnums=(0, 1)))(cb, w0)
    g_ref = jax.grad(loss(lambda b, s: oracle.decode(codes, b, s)),
                     argnums=(0, 1))(cb, w0)
    for a, b in zip(g_own, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.multidevice(n=2)
def test_auto_prefers_owner_past_duplication_threshold():
    with use_sharding(_mesh(2)):
        assert backend_mod.resolve_auto(duplication=3.0) == "owner"
        assert backend_mod.resolve_auto(duplication=1.2) == "sharded"
        assert backend_mod.resolve_auto() == "sharded"
    assert backend_mod.resolve_auto(duplication=3.0) in ("onehot", "pallas")


@pytest.mark.multidevice(n=4)
def test_4shard_owner_run_loss_bit_identical_to_1shard(graph, codes):
    """Acceptance (ISSUE 5): the owner-computes 4-shard streaming run's
    step-0 forward loss is bit-identical to the 1-shard run — hub rows
    decode once on their owner, from the same codes, through the same
    gather-order accumulation."""
    cfg_own = _cfg("owner:gather")
    l1 = _run_stream(graph, codes, _cfg("sharded:gather"), 1, None)
    l4 = _run_stream(graph, codes, cfg_own, N_SHARDS, _mesh(N_SHARDS),
                     owner=True)
    assert l1[0] == l4[0], f"step-0 loss diverged: {l1[0]} vs {l4[0]}"
    assert max(abs(a - b) for a, b in zip(l1, l4)) < 1e-3


@pytest.mark.multidevice(n=4)
def test_owner_cached_staleness0_bit_exact(graph, codes):
    """Satellite (ISSUE 5): CachedDecodeBackend over the owner exchange at
    staleness 0 reproduces the uncached owner run exactly (the cache wraps
    the whole exchange; every access re-decodes at staleness 0)."""
    mesh = _mesh(N_SHARDS)
    l_plain = _run_stream(graph, codes, _cfg("owner:gather"),
                          N_SHARDS, mesh, steps=6, seed=7, owner=True)
    l_cached = _run_stream(graph, codes,
                           _cfg("owner:gather", cache_capacity=256,
                                cache_staleness=0),
                           N_SHARDS, mesh, steps=6, seed=7, owner=True)
    assert l_plain == l_cached


@pytest.mark.multidevice(n=4)
def test_cached_decode_staleness0_bit_exact_under_sharding(graph, codes):
    """Satellite (ISSUE 3): CachedDecodeBackend at staleness 0 over a
    shard-partitioned frontier reproduces the uncached sharded run exactly
    (the stacked batch's per-block `valid` mask keeps padding rows out of
    the cache, and every access re-decodes at staleness 0)."""
    mesh = _mesh(N_SHARDS)
    l_plain = _run_stream(graph, codes, _cfg("sharded:gather"),
                          N_SHARDS, mesh, steps=6, seed=7)
    l_cached = _run_stream(graph, codes,
                           _cfg("sharded:gather", cache_capacity=256,
                                cache_staleness=0),
                           N_SHARDS, mesh, steps=6, seed=7)
    assert l_plain == l_cached
