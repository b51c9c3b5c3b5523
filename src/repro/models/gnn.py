"""The paper's GNN stack (§4/§5.2): GraphSAGE, GCN, SGC, GIN with the
compressed-embedding layer as the input features.

GraphSAGE follows Figure 4 exactly: sample -> code lookup -> decode ->
mean-aggregate -> concat -> linear(+ReLU), two layers, minibatched via
NeighborSampler.  GCN / SGC / GIN are full-graph (paper §C.1 trains them
without minibatches) over the normalised CSR adjacency; their input feature
matrix is the decoder output for ALL nodes (blocked decode), which is the
memory trade the paper makes for these models too.

Link prediction (§5.2): dot-product scores on final representations with
uniform negative sampling, BCE loss, hits@K evaluation.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import GNNConfig
from repro.core import embedding as emb_lib
from repro.graph.csr import CSRMatrix
from repro.graph.sampler import FrontierBatch
from repro.nn import module as nn
from repro.parallel import sharding

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_gnn(key, cfg: GNNConfig, codes: Optional[Array] = None, aux=None) -> nn.Params:
    ks = nn.split_keys(key, ["embed", "l1", "l2", "out", "eps"])
    ecfg = cfg.embedding_config()
    params: nn.Params = {
        "embed": emb_lib.init_embedding(ks["embed"], ecfg, codes=codes, aux=aux),
    }
    d_e, H = cfg.d_e, cfg.hidden
    if cfg.model == "sage":
        params["w1"] = nn.dense_init(ks["l1"], (2 * d_e, H))
        params["b1"] = jnp.zeros((H,), jnp.float32)
        params["w2"] = nn.dense_init(ks["l2"], (2 * H, H))
        params["b2"] = jnp.zeros((H,), jnp.float32)
    elif cfg.model == "gcn":
        params["w1"] = nn.dense_init(ks["l1"], (d_e, H))
        params["b1"] = jnp.zeros((H,), jnp.float32)
        params["w2"] = nn.dense_init(ks["l2"], (H, H))
        params["b2"] = jnp.zeros((H,), jnp.float32)
    elif cfg.model == "sgc":
        params["w1"] = nn.dense_init(ks["l1"], (d_e, H))
        params["b1"] = jnp.zeros((H,), jnp.float32)
    elif cfg.model == "gin":
        params["eps1"] = jnp.zeros((), jnp.float32)
        params["eps2"] = jnp.zeros((), jnp.float32)
        params["mlp1"] = {
            "w1": nn.dense_init(ks["l1"], (d_e, H)), "b1": jnp.zeros((H,), jnp.float32),
            "w2": nn.dense_init(jax.random.fold_in(ks["l1"], 1), (H, H)),
            "b2": jnp.zeros((H,), jnp.float32),
        }
        params["mlp2"] = {
            "w1": nn.dense_init(ks["l2"], (H, H)), "b1": jnp.zeros((H,), jnp.float32),
            "w2": nn.dense_init(jax.random.fold_in(ks["l2"], 1), (H, H)),
            "b2": jnp.zeros((H,), jnp.float32),
        }
    else:
        raise ValueError(cfg.model)
    if cfg.task == "node":
        params["w_out"] = nn.dense_init(ks["out"], (H, cfg.n_classes))
        params["b_out"] = jnp.zeros((cfg.n_classes,), jnp.float32)
    return params


# ---------------------------------------------------------------------------
# GraphSAGE (minibatched, Figure 4)
# ---------------------------------------------------------------------------

@jax.named_scope("sage")
def _sage_combine(params, h0: Array, h1: Array, h2: Array) -> Array:
    """Figure-4 aggregate/concat/linear stack on decoded level features
    h0 (B, de), h1 (B, f1, de), h2 (B, f1, f2, de)."""
    # layer 1 (applied to targets and first neighbours)
    agg0 = h1.mean(axis=1)                                          # (B, de)
    z0 = jax.nn.relu(jnp.concatenate([agg0, h0], -1) @ params["w1"] + params["b1"])
    agg1 = h2.mean(axis=2)                                          # (B, f1, de)
    z1 = jax.nn.relu(jnp.concatenate([agg1, h1], -1) @ params["w1"] + params["b1"])

    # layer 2 (targets only)
    aggz = z1.mean(axis=1)                                          # (B, H)
    z = jax.nn.relu(jnp.concatenate([aggz, z0], -1) @ params["w2"] + params["b2"])
    return z


def sage_forward(params, levels: List[Array], cfg: GNNConfig,
                 backend=None) -> Array:
    """Naive path — levels: [targets (B,), l1 (B,f1), l2 (B,f1,f2)] node ids,
    each decoded independently (B + B·f1 + B·f1·f2 decoder rows)."""
    ecfg = cfg.embedding_config()
    lk = lambda ids: emb_lib.embed_lookup(params["embed"], ids, ecfg,
                                          backend=backend)
    h0 = lk(levels[0])                                              # (B, de)
    h1 = lk(levels[1])                                              # (B, f1, de)
    h2 = lk(levels[2])                                              # (B, f1, f2, de)
    return _sage_combine(params, h0, h1, h2)


def sage_forward_frontier(params, fb: FrontierBatch, cfg: GNNConfig,
                          backend=None) -> Array:
    """Dedup-decode path: ONE batched decode-backend call over the unique
    frontier (exactly the (U, m) shape the Pallas kernel wants), then cheap
    gathers rebuild the per-level tensors.  Decoder rows per batch drop from
    B + B·f1 + B·f1·f2 to the (padded) unique-frontier count — the batch's
    duplication factor in decode throughput."""
    ecfg = cfg.embedding_config()
    ids = sharding.logical(fb.unique, "frontier")
    # batch-carried packed code rows (codes_placement="host"): row-aligned
    # with the frontier, so they shard on the same axis as the ids
    codes = (None if fb.codes is None
             else sharding.logical(fb.codes, "frontier", None))
    hu = emb_lib.embed_lookup(params["embed"], ids, ecfg,
                              backend=backend, plan=fb.plan,
                              codes=codes)                          # (U, de)
    hu = sharding.logical(hu, "frontier", None)
    h0 = hu[fb.index_maps[0]]                                       # (B, de)
    h1 = hu[fb.index_maps[1]]                                       # (B, f1, de)
    h2 = hu[fb.index_maps[2]]                                       # (B, f1, f2, de)
    return _sage_combine(params, h0, h1, h2)


def sage_forward_frontier_cached(params, fb: FrontierBatch, cfg: GNNConfig,
                                 cache_state, backend=None):
    """Hot-node-cached twin of ``sage_forward_frontier``.

    The unique-frontier decode goes through a ``CachedDecodeBackend`` keyed
    by node id: ids whose cached embedding is within the staleness budget are
    served from the cache (no gradient — they are constants from an earlier
    codebook version); the rest decode fresh through the backend and are
    written back.  Returns ``(hidden, new_cache_state)``."""
    from repro.core.backend import CachedDecodeBackend

    ecfg = cfg.embedding_config()
    cache = CachedDecodeBackend(staleness=ecfg.cache_staleness)
    ids = sharding.logical(fb.unique, "frontier")
    # frontier padding rows repeat unique[0] — mask them out of the cache so
    # they don't burn LRU slots or skew the hit/miss accounting (sharded
    # stacked frontiers carry an explicit mask: padding is per shard block,
    # not a global suffix)
    valid = fb.valid_mask()
    codes = (None if fb.codes is None
             else sharding.logical(fb.codes, "frontier", None))
    # the cache lookup wraps the whole owner exchange: decode_fn sees the
    # full (unpermuted) frontier ids, so the batch's OwnerPlan (and the
    # row-aligned batch codes) stay valid
    hu, new_state = cache.lookup(
        cache_state, ids,
        lambda i: emb_lib.embed_lookup(params["embed"], i, ecfg,
                                       backend=backend, plan=fb.plan,
                                       codes=codes),
        valid=valid)
    hu = sharding.logical(hu, "frontier", None)
    h0 = hu[fb.index_maps[0]]
    h1 = hu[fb.index_maps[1]]
    h2 = hu[fb.index_maps[2]]
    return _sage_combine(params, h0, h1, h2), new_state


def sage_forward_frontier_missonly(params, fb: FrontierBatch, cfg: GNNConfig,
                                   cache_state, n_decode: int, backend=None):
    """Serving twin of ``sage_forward_frontier_cached``: the frontier has
    been permuted miss-first host-side (``CachedDecodeBackend.
    plan_missonly``) so only the first ``n_decode`` rows — a static,
    shape-bucketed count — enter the decoder; every other valid row is
    served from the hot-node cache.  Returns ``(hidden, new_cache_state)``,
    bitwise identical to the uncached frontier forward."""
    from repro.core.backend import CachedDecodeBackend

    ecfg = cfg.embedding_config()
    cache = CachedDecodeBackend(staleness=ecfg.cache_staleness)
    ids = sharding.logical(fb.unique, "frontier")
    # decode_fn only sees the miss prefix ids[:n_decode]; the row-aligned
    # batch codes are sliced to match
    hu, new_state = cache.lookup_missonly(
        cache_state, ids,
        lambda i: emb_lib.embed_lookup(
            params["embed"], i, ecfg, backend=backend,
            codes=None if fb.codes is None else fb.codes[:i.shape[0]]),
        n_decode, valid=fb.valid_mask())
    hu = sharding.logical(hu, "frontier", None)
    h0 = hu[fb.index_maps[0]]
    h1 = hu[fb.index_maps[1]]
    h2 = hu[fb.index_maps[2]]
    return _sage_combine(params, h0, h1, h2), new_state


# ---------------------------------------------------------------------------
# full-graph models
# ---------------------------------------------------------------------------

def _all_features(params, cfg: GNNConfig) -> Array:
    ecfg = cfg.embedding_config()
    if ecfg.kind == "dense":
        return params["embed"]["table"]
    ids = jnp.arange(cfg.n_nodes, dtype=jnp.int32)
    return emb_lib.embed_lookup(params["embed"], ids, ecfg)


def fullgraph_forward(params, adj_norm: CSRMatrix, cfg: GNNConfig) -> Array:
    """Returns final hidden for all nodes (n, H)."""
    X = _all_features(params, cfg)
    if cfg.model == "gcn":
        h = jax.nn.relu(adj_norm.matmat(X) @ params["w1"] + params["b1"])
        h = adj_norm.matmat(h) @ params["w2"] + params["b2"]
        return h
    if cfg.model == "sgc":
        h = adj_norm.matmat(adj_norm.matmat(X))
        return h @ params["w1"] + params["b1"]
    if cfg.model == "gin":
        def gmlp(m, h):
            return jax.nn.relu(h @ m["w1"] + m["b1"]) @ m["w2"] + m["b2"]
        h = gmlp(params["mlp1"], (1 + params["eps1"]) * X + adj_norm.matmat(X))
        h = jax.nn.relu(h)
        h = gmlp(params["mlp2"], (1 + params["eps2"]) * h + adj_norm.matmat(h))
        return h
    raise ValueError(cfg.model)


# ---------------------------------------------------------------------------
# losses / metrics
# ---------------------------------------------------------------------------

def node_logits(params, hidden: Array, cfg: GNNConfig) -> Array:
    return hidden @ params["w_out"] + params["b_out"]


def node_loss(logits: Array, labels: Array) -> Array:
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def link_scores(hidden: Array, edges: Array) -> Array:
    """edges (E, 2) -> dot-product scores (E,)."""
    return jnp.sum(hidden[edges[:, 0]] * hidden[edges[:, 1]], axis=-1)


def link_loss(hidden: Array, pos_edges: Array, neg_edges: Array) -> Array:
    pos = link_scores(hidden, pos_edges)
    neg = link_scores(hidden, neg_edges)
    return (jnp.mean(jax.nn.softplus(-pos)) + jnp.mean(jax.nn.softplus(neg)))


def hits_at_k(pos_scores, neg_scores, k: int) -> float:
    """OGB hits@K: fraction of positives ranked above the K-th negative."""
    import numpy as np
    neg = np.sort(np.asarray(neg_scores))[::-1]
    thresh = neg[min(k, len(neg)) - 1]
    return float((np.asarray(pos_scores) > thresh).mean())


def accuracy(logits, labels) -> float:
    import numpy as np
    return float((np.asarray(jnp.argmax(logits, -1)) == np.asarray(labels)).mean())


def hit_rate_at_k(logits, labels, k: int) -> float:
    """§5.3 hit@k: label within top-k predicted categories."""
    import numpy as np
    topk = np.asarray(jax.lax.top_k(logits, k)[1])
    return float((topk == np.asarray(labels)[:, None]).any(axis=1).mean())
