"""Uniform neighbour sampling (GraphSAGE, paper §4 / Fig. 4).

Sampling happens host-side (numpy) against the padded neighbour table and
yields fixed-shape device batches:

  step 0: batch of target nodes                     (B,)
  step 1: fanout[0] first neighbours per target     (B, f1)
  step 2: fanout[1] second neighbours per first     (B, f1, f2)

Isolated nodes self-sample (pad with the node itself), matching the common
GraphSAGE implementation behaviour.

Dedup-decode frontier (``sample_frontier``): minibatches of a real graph
contain massive node overlap across levels — hubs appear hundreds of times
in one ``(B, f1, f2)`` tensor.  ``FrontierBatch`` carries the *unique* node
frontier plus int32 index maps per level, so the embedding decoder runs once
per unique node and the per-level tensors are rebuilt with cheap gathers
(``unique[index_maps[i]] == levels[i]``).  The frontier is padded to a
multiple of ``pad_to`` so jit sees a small, bounded set of shapes (or to an
exact ``cap`` so sharded runs can stack equal-size per-shard frontiers).

Sharded sampling (``sample_hashed``): multi-host data parallelism slices one
*global* batch across shards, and every target's neighbour subtree must be
reproducible no matter which shard draws it.  Stateful generators can't give
that (the draw for position i depends on how many positions preceded it), so
neighbour slots are counter-based: slot k under the subtree node at path
``p`` is ``mix64(level_key ^ (p * PATH_STRIDE + k + 1)) % degree``, where
``level_key`` folds the tree level into ``stream_key(seed, step)``.  Path
counters are unique *within* a level by construction (children of distinct
parents get distinct counter ranges); the per-level key makes cross-level
counter reuse harmless — without it, a global batch larger than the path
stride would correlate a deep target's draws with a shallow child's.  The
draw is a pure function of ``(seed, step, global position, path)`` —
slicing the batch by shard cannot change any subtree, which is what makes
the N-shard union bit-identical to the 1-shard batch.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.graph.csr import CSRMatrix

# Counter layout for hashed sampling: a subtree node at path id p draws its
# k-th neighbour from counter p*_PATH_STRIDE + k + 1 (the +1 keeps child path
# ids distinct from their parent).  Fanouts must stay below the stride.
_PATH_STRIDE = np.uint64(1024)


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser — a bijective avalanche mix on uint64."""
    with np.errstate(over="ignore"):
        x = np.asarray(x, np.uint64)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def stream_key(seed: int, step: int) -> np.uint64:
    """Per-(seed, step) key for counter-based sampling — shard-independent,
    so every shard of one step draws from the same keyed hash function."""
    with np.errstate(over="ignore"):
        k = np.uint64(seed) * np.uint64(0x9E3779B97F4A7C15) + np.uint64(step)
    return np.uint64(_mix64(k))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class OwnerPlan:
    """Host-built routing plan for the owner-computes cross-shard decode
    (``lookup_impl="owner"``, see ``core.backend.OwnerBackend``).

    Frontier rows are hash-partitioned by ``owner = node_id % n_shards``;
    every array below is **stacked along the shard axis** (leading dim
    ``n_shards``) so the same data-axis placement that shards the frontier
    rows puts each shard's slice of the plan on its device.  All shapes are
    static (``owner_cap`` request slots per (requester, owner) pair,
    ``owner_unique_cap`` decode rows per owner), so jit sees one shape per
    source configuration no matter how the per-step buckets fill.

    ``req_rows``   (n, n, owner_cap) int32 — [requester s][owner o][slot] =
                   row index into s's local ``cap`` frontier block, or the
                   sentinel ``cap`` for unused slots (dropped on scatter).
    ``owned_src``  (n, owner_unique_cap) int32 — [owner o][j] = position in
                   o's received flat (n·owner_cap,) request buffer of the
                   representative occurrence of its j-th owned-unique id
                   (0-padded past ``n_owned[o]``).
    ``ret_idx``    (n, n, owner_cap) int32 — [owner o][requester s][slot] =
                   index into o's decoded (owner_unique_cap,) rows answering
                   that request slot (0-padded).
    ``n_owned``    (n,) int32 — true owned-unique count per owner: the rows
                   each device actually decodes (the dedup accounting the
                   benchmarks report as ``rows_decoded_per_device``).
    """

    req_rows: np.ndarray
    owned_src: np.ndarray
    ret_idx: np.ndarray
    n_owned: np.ndarray

    def tree_flatten(self):
        return (self.req_rows, self.owned_src, self.ret_idx,
                self.n_owned), None

    @classmethod
    def tree_unflatten(cls, _aux, leaves):
        return cls(*leaves)

    @property
    def n_shards(self) -> int:
        return self.req_rows.shape[0]

    @property
    def owner_cap(self) -> int:
        return self.req_rows.shape[2]

    @property
    def owner_unique_cap(self) -> int:
        return self.owned_src.shape[1]


# Default safety factor for the per-(requester, owner) request buckets: a
# bucket's expected fill is n_unique_s / n_shards ≤ cap / n_shards, so the
# 1.25 headroom absorbs hash skew across the id residue classes (asserted
# never to overflow on splitmix64-drawn frontiers in tests/test_sharded.py).
OWNER_SAFETY = 1.25


def default_owner_caps(cap: int, n_shards: int,
                       safety: float = OWNER_SAFETY) -> Tuple[int, int]:
    """Static capacities ``(owner_cap, owner_unique_cap)`` for the owner
    exchange, sized from the per-shard frontier ``cap``.

    ``owner_cap`` (request slots per (requester, owner) pair) is the
    expected bucket fill ``cap / n_shards`` with ``safety`` headroom.
    ``owner_unique_cap`` (decode rows per owner) is ``cap / 2``: the owner
    decode is only selected when measured duplication
    ``frontier_rows / unique_rows`` exceeds ``OWNER_DUP_THRESHOLD`` (= 2, see
    ``core.backend``), and duplication > 2 *implies* per-owner unique
    ``global_unique / n ≤ (Σ_s n_unique_s) / n < cap / 2`` — the capacity
    rule and the selection threshold are the same inequality.  Both are
    rounded up to the sublane multiple (8); overflow at runtime falls back
    loudly (``build_owner_plan`` returns None), never truncates."""
    def up8(x: int) -> int:
        return -(-int(x) // 8) * 8
    oc = min(up8(-(-cap * safety // n_shards)), cap)
    ou = min(up8(-(-cap // 2)), n_shards * oc)
    return int(oc), int(ou)


def remap_shard_state(state: dict, n_shards: int, shard: int = 0) -> dict:
    """Remap a batch-source ``state_dict`` onto a different shard count.

    This is the sampler-state half of an exact rescale
    (``repro.elastic.rescale``).  It is *exact* because the hashed draw is a
    pure function of ``(seed, step, global position, path)``: the global
    batch at a given ``(seed, step)`` does not depend on ``n_shards`` at all
    — shards merely slice it — so carrying ``(seed, step)`` over and
    stamping the new layout reproduces, bit for bit, the stream a run at
    the new shard count would have drawn from scratch.  The only
    requirement (checked by ``rescale_spec``) is that the *global* batch
    size stays fixed and divides evenly by the new shard count.

    ``miss_shadow`` (the single-shard cache-miss replay state, see
    ``engine.MissPlanningSource``) is layout-dependent and is deliberately
    dropped: the rescaled run replans misses against its own cache.
    """
    return {
        "step": int(state["step"]),
        "seed": int(state["seed"]),
        "shard": int(shard),
        "n_shards": int(n_shards),
    }


def bucket_requests(ids: np.ndarray, n_shards: int, owner_cap: int,
                    req_rows: np.ndarray) -> Optional[List[np.ndarray]]:
    """One requester's half of ``build_owner_plan``: bucket its valid
    frontier ``ids`` by owner ``id % n_shards``, write each bucket's row
    indices into ``req_rows[o]`` (the requester's (n, owner_cap) slice of
    the plan) and return the n per-owner id lists, or ``None`` when a bucket
    exceeds ``owner_cap``."""
    own = ids % n_shards
    requests = []
    for o in range(n_shards):
        rows = np.nonzero(own == o)[0]
        if rows.shape[0] > owner_cap:
            return None                         # bucket overflow: loud fallback
        req_rows[o, :rows.shape[0]] = rows
        requests.append(ids[rows])
    return requests


def dedup_owner(requests: Sequence[np.ndarray], owner_cap: int,
                owner_unique_cap: int, owned_src: np.ndarray,
                ret_idx: np.ndarray) -> Optional[int]:
    """One owner's half of ``build_owner_plan``: dedup the id lists its n
    requesters sent (``requests[s]``), write ``owned_src`` / ``ret_idx``
    (the owner's slices of the plan) and return its owned-unique count, or
    ``None`` when that exceeds ``owner_unique_cap``."""
    n = len(requests)
    # the received buffer: requester s's segment at offset s*owner_cap
    flat = np.full((n * owner_cap,), -1, np.int64)
    for s, req in enumerate(requests):
        flat[s * owner_cap:s * owner_cap + req.shape[0]] = req
    pos = np.nonzero(flat >= 0)[0]
    uniq, first, inv = np.unique(flat[pos], return_index=True,
                                 return_inverse=True)
    if uniq.shape[0] > owner_unique_cap:
        return None                             # owned overflow: loud fallback
    owned_src[:uniq.shape[0]] = pos[first]
    ridx = np.zeros((n * owner_cap,), np.int32)
    ridx[pos] = inv.astype(np.int32)
    ret_idx[...] = ridx.reshape(n, owner_cap)
    return int(uniq.shape[0])


def build_owner_plan(uniques: Sequence[np.ndarray], n_uniques: Sequence[int],
                     n_shards: int, owner_cap: int, owner_unique_cap: int, *,
                     map_fn: Callable) -> Optional[OwnerPlan]:
    """Build the owner-computes exchange plan for one stacked frontier.

    ``uniques``: the n_shards per-shard frontier blocks (each (cap,) int32,
    valid prefix of length ``n_uniques[s]``).  Rows are bucketed by
    ``id % n_shards``; each owner dedups the requests it receives across all
    requesters so every distinct owned id is decoded exactly once.  Returns
    ``None`` when any (requester, owner) bucket exceeds ``owner_cap`` or any
    owner's unique set exceeds ``owner_unique_cap`` — the caller must fall
    back loudly (emit the batch without a plan), NEVER truncate: a dropped
    row would silently decode to zeros.

    Two phases, each a ``map_fn`` over independent parts that write disjoint
    slices of the plan: ``bucket_requests`` per requester, then
    ``dedup_owner`` per owner.  ``map_fn`` is the caller's map (the sharded
    source passes its thread pool's); the plan is the same whichever runs
    the parts."""
    n = int(n_shards)
    cap = int(np.asarray(uniques[0]).shape[0])
    req_rows = np.full((n, n, owner_cap), cap, np.int32)
    requests = list(map_fn(
        lambda s: bucket_requests(np.asarray(uniques[s])[:int(n_uniques[s])],
                                  n, owner_cap, req_rows[s]),
        range(n)))
    if any(r is None for r in requests):
        return None
    owned_src = np.zeros((n, owner_unique_cap), np.int32)
    ret_idx = np.zeros((n, n, owner_cap), np.int32)
    n_owned = list(map_fn(
        lambda o: dedup_owner([requests[s][o] for s in range(n)], owner_cap,
                              owner_unique_cap, owned_src[o], ret_idx[o]),
        range(n)))
    if any(k is None for k in n_owned):
        return None
    return OwnerPlan(req_rows, owned_src, ret_idx,
                     np.asarray(n_owned, np.int32))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FrontierBatch:
    """Deduplicated sampled minibatch.

    ``unique``     (U_pad,) int32 — unique node ids, padded by repeating
                   ``unique[0]`` (padding rows decode to valid embeddings
                   that no index map points at).
    ``index_maps`` per level, int32 indices into ``unique`` with the naive
                   level shapes: (B,), (B, f1), (B, f1, f2), ...
    ``n_unique``   () int32 — true unique count before padding (a leaf, not
                   static metadata, so varying it never retriggers jit).
    ``valid``      optional (U_pad,) bool — explicit non-padding-row mask.
                   ``None`` (the single-frontier case) means the prefix mask
                   ``arange(U_pad) < n_unique``; sharded *stacked* batches
                   (``ShardedSageBatchSource``) carry per-shard segments
                   whose padding is interleaved, so they set it explicitly.
    ``plan``       optional ``OwnerPlan`` — host-built routing for the
                   owner-computes cross-shard decode; only stacked sharded
                   batches whose source enables it carry one.  Padding rows
                   of a planned batch are decoded to zeros instead of
                   duplicate embeddings (no index map points at them).
    ``n_decode``   optional int — static miss-first decode count.  Set by
                   ``graph.engine.MissPlanningSource``: the frontier has been
                   permuted so rows [0, n_decode) are the planned cache
                   misses and every valid row past it is a predicted cache
                   hit (``CachedDecodeBackend.lookup_missonly`` semantics).
                   Static (pytree aux, not a leaf): each bucketed value
                   retraces jit once, exactly like the serving engine's
                   miss buckets.
    ``codes``      optional (U_pad, n_words) uint32 — the frontier rows of
                   the packed code buffer (``codes_buf[unique]``), gathered
                   host-side when ``codes_placement="host"`` so the device
                   never holds the full O(#nodes) buffer.  Row-aligned with
                   ``unique`` (attach AFTER any permutation/stacking).
    """

    unique: np.ndarray
    index_maps: Tuple[np.ndarray, ...]
    n_unique: np.ndarray
    valid: Optional[np.ndarray] = None
    plan: Optional[OwnerPlan] = None
    n_decode: Optional[int] = None
    codes: Optional[np.ndarray] = None

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        leaves = (self.unique, self.n_unique) + tuple(self.index_maps)
        aux = (len(self.index_maps), self.valid is not None,
               self.plan is not None, self.n_decode,
               self.codes is not None)
        if self.valid is not None:
            leaves = leaves + (self.valid,)
        if self.plan is not None:
            leaves = leaves + (self.plan,)
        if self.codes is not None:
            leaves = leaves + (self.codes,)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        # aux grew a trailing has_codes flag; accept the old 4-tuple too so
        # treedefs pickled before the codes leaf still unflatten.
        n_maps, has_valid, has_plan, n_decode = aux[:4]
        has_codes = aux[4] if len(aux) > 4 else False
        maps = tuple(leaves[2:2 + n_maps])
        rest = list(leaves[2 + n_maps:])
        valid = rest.pop(0) if has_valid else None
        plan = rest.pop(0) if has_plan else None
        codes = rest.pop(0) if has_codes else None
        return cls(leaves[0], maps, leaves[1], valid, plan, n_decode, codes)

    # -- construction ----------------------------------------------------
    @classmethod
    def from_levels(cls, levels: Sequence[np.ndarray], pad_to: int = 256,
                    cap: Optional[int] = None) -> "FrontierBatch":
        """Dedup a naive level list into a frontier + per-level index maps.

        ``cap`` pads the frontier to exactly that many rows instead of the
        next ``pad_to`` multiple — sharded runs need every shard's frontier
        the same size so the stacked (n_shards·cap,) axis splits evenly
        across devices.  Raises when the true unique count exceeds it."""
        levels = [np.asarray(l) for l in levels]
        flat = np.concatenate([l.ravel() for l in levels])
        uniq, inv = np.unique(flat, return_inverse=True)
        n_unique = uniq.shape[0]
        if cap is None:
            cap = -(-n_unique // max(pad_to, 1)) * max(pad_to, 1)
        elif n_unique > cap:
            raise ValueError(
                f"frontier has {n_unique} unique nodes > cap={cap}; raise "
                f"frontier_cap (or shrink batch/fanout)")
        if cap > n_unique:
            uniq = np.concatenate(
                [uniq, np.full(cap - n_unique, uniq[0], uniq.dtype)])
        maps, off = [], 0
        for l in levels:
            maps.append(inv[off:off + l.size].reshape(l.shape).astype(np.int32))
            off += l.size
        return cls(uniq.astype(np.int32), tuple(maps), np.int32(n_unique))

    def valid_mask(self):
        """(U_pad,) bool — True on genuine (non-padding) frontier rows."""
        if self.valid is not None:
            return self.valid
        import jax.numpy as jnp
        return jnp.arange(self.unique.shape[0], dtype=jnp.int32) < self.n_unique

    @property
    def targets(self):
        """Level-0 (target) node ids, reconstructed from the frontier."""
        return self.unique[self.index_maps[0]]

    def levels(self) -> List[np.ndarray]:
        """Rebuild the naive level list (testing / fallback path)."""
        return [self.unique[m] for m in self.index_maps]


def attach_codes(fb: FrontierBatch, host_codes: np.ndarray) -> FrontierBatch:
    """Gather the frontier's packed code rows from the host buffer.

    ``codes_placement="host"``'s producer-side step: a numpy fancy-index
    ``host_codes[fb.unique]`` (identical bit pattern to the device-side
    ``jnp.take(codes_buf, ids)`` it replaces), attached as the batch's
    ``codes`` leaf.  MUST run after any frontier permutation or stacking —
    it keys off the *final* ``unique`` — which is why the prefetch producer
    and the serving engine call it outermost, on the emitted batch."""
    if fb.codes is not None:
        return fb
    ids = np.asarray(fb.unique)
    rows = np.ascontiguousarray(
        np.asarray(host_codes, np.uint32)[ids])     # (U_pad, n_words)
    return dataclasses.replace(fb, codes=rows)


class NeighborSampler:
    def __init__(self, adj: CSRMatrix, fanouts: Sequence[int], max_deg: int = 64, seed: int = 0):
        self.fanouts = tuple(fanouts)
        self.table, self.deg = adj.neighbor_padded(max_deg)
        self.max_deg = max_deg
        self.rng = np.random.default_rng(seed)

    def _sample_level(self, nodes: np.ndarray, fanout: int,
                      rng: Optional[np.random.Generator] = None) -> np.ndarray:
        """nodes: (...,) -> (..., fanout) sampled neighbour ids."""
        rng = rng if rng is not None else self.rng
        flat = nodes.reshape(-1)
        deg = np.minimum(self.deg[flat], self.max_deg)
        idx = rng.integers(0, np.maximum(deg, 1)[:, None], (flat.shape[0], fanout))
        nbr = self.table[flat[:, None], idx]
        # isolated nodes (-1 entries): fall back to self
        nbr = np.where(nbr < 0, flat[:, None], nbr)
        return nbr.reshape(*nodes.shape, fanout).astype(np.int32)

    def sample(self, batch_nodes: np.ndarray,
               rng: Optional[np.random.Generator] = None) -> List[np.ndarray]:
        """Returns [targets (B,), level1 (B,f1), level2 (B,f1,f2), ...].

        ``rng`` overrides the sampler's stateful generator — pass a per-step
        seeded generator to make the batch a pure function of the step index
        (restart-safe resume, prefetch == sync determinism).
        """
        levels = [batch_nodes.astype(np.int32)]
        cur = batch_nodes
        for f in self.fanouts:
            cur = self._sample_level(cur, f, rng=rng)
            levels.append(cur)
        return levels

    def sample_frontier(self, batch_nodes: np.ndarray, pad_to: int = 256,
                        rng: Optional[np.random.Generator] = None) -> FrontierBatch:
        """Sample and dedup in one call (the engine's fast path)."""
        return FrontierBatch.from_levels(self.sample(batch_nodes, rng=rng), pad_to=pad_to)

    # -- counter-based (shard-sliceable) sampling ------------------------
    def _sample_level_hashed(self, nodes: np.ndarray, path_ids: np.ndarray,
                             fanout: int, key: np.uint64):
        """Hashed twin of ``_sample_level``: neighbour slot k of the subtree
        node at path id p draws ``mix64(key ^ (p*STRIDE + k + 1)) % deg`` —
        no generator state, so any slice of the batch reproduces exactly.
        Returns (neighbours, child path ids)."""
        if fanout >= int(_PATH_STRIDE):
            raise ValueError(f"fanout {fanout} >= path stride {_PATH_STRIDE}")
        flat = nodes.reshape(-1)
        pids = path_ids.reshape(-1).astype(np.uint64)
        deg = np.minimum(self.deg[flat], self.max_deg)
        with np.errstate(over="ignore"):
            counters = (pids[:, None] * _PATH_STRIDE
                        + np.arange(1, fanout + 1, dtype=np.uint64))
            u = _mix64(counters ^ key)
        idx = (u % np.maximum(deg, 1)[:, None].astype(np.uint64)).astype(np.int64)
        nbr = self.table[flat[:, None], idx]
        nbr = np.where(nbr < 0, flat[:, None], nbr)   # isolated: self-sample
        return (nbr.reshape(*nodes.shape, fanout).astype(np.int32),
                counters.reshape(*nodes.shape, fanout))

    def sample_hashed(self, batch_nodes: np.ndarray, gpos: np.ndarray,
                      key: np.uint64) -> List[np.ndarray]:
        """Deterministic sharded sampling: the subtree below the target at
        *global* batch position ``gpos[i]`` is a pure function of
        ``(key, gpos[i])`` (``key = stream_key(seed, step)``), so shards
        sampling disjoint slices of one global batch reproduce exactly the
        levels a single host would have drawn for the whole batch."""
        levels = [np.asarray(batch_nodes).astype(np.int32)]
        cur = levels[0]
        pids = np.asarray(gpos, np.uint64) + np.uint64(1)   # 0 is never a path
        for lvl, f in enumerate(self.fanouts):
            # per-level subkey: counters are only unique within a level, so
            # re-keying each level keeps a deep node's draws independent of a
            # shallow node's even when their counters coincide (which happens
            # as soon as the global batch exceeds _PATH_STRIDE)
            lkey = np.uint64(_mix64(key + np.uint64(lvl) + np.uint64(1)))
            cur, pids = self._sample_level_hashed(cur, pids, f, lkey)
            levels.append(cur)
        return levels

    def minibatches(self, nodes: np.ndarray, batch_size: int, shuffle: bool = True):
        """Yield (levels, batch_node_ids); final short batch is wrapped (padded
        by resampling from the start) so shapes stay static for jit."""
        for batch in self._batch_ids(nodes, batch_size, shuffle):
            yield self.sample(batch), batch

    def frontier_minibatches(self, nodes: np.ndarray, batch_size: int,
                             shuffle: bool = True, pad_to: int = 256):
        """Dedup-decode twin of ``minibatches``: yields (FrontierBatch, ids)."""
        for batch in self._batch_ids(nodes, batch_size, shuffle):
            yield self.sample_frontier(batch, pad_to=pad_to), batch

    def _batch_ids(self, nodes: np.ndarray, batch_size: int, shuffle: bool):
        order = self.rng.permutation(nodes) if shuffle else np.asarray(nodes)
        n = order.shape[0]
        for s in range(0, n, batch_size):
            batch = order[s: s + batch_size]
            if batch.shape[0] < batch_size:
                pad = order[: batch_size - batch.shape[0]]
                batch = np.concatenate([batch, pad])
            yield batch
