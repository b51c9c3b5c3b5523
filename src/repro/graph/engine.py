"""Streaming graph-training engine (sample → lookup → decode → train).

Three pieces restructure the minibatch path end to end:

* **Dedup-decode batches** — ``SageBatchSource`` emits ``FrontierBatch``es
  (unique-node frontier + per-level int32 index maps, see
  ``repro.graph.sampler``), so the embedding decoder runs once per unique
  node instead of once per sampled position.

* **Async prefetch** — ``PrefetchIterator`` wraps any batch source in a
  double-buffered host→device pipeline: a background thread runs the numpy
  sampler and ``jax.device_put``s the next batch(es) while the jitted train
  step consumes the current one.  ``state_dict``/``load_state_dict`` are
  forwarded with consumer-side semantics (the state of the *last consumed*
  batch, not the last produced one), so fault-tolerant resume through
  ``repro.train.loop.run_training`` remains exact.

* **Unified model API** — ``GNNModel.apply(params, batch)`` accepts a
  sampled ``FrontierBatch``, a naive level list, or a ``FullGraphBatch``
  handle, collapsing the divergent ``sage_forward`` / ``fullgraph_forward``
  entry points so training steps, benchmarks and examples stop
  special-casing the model family.

Batch sources are deterministic per step index (each batch is a pure
function of ``(seed, shard, step)``), which is what makes prefetching, crash
resume, data-parallel sharding and the sync/async equivalence tests exact
rather than statistical.

* **Sharded streaming** — ``SageBatchSource(shard=s, n_shards=N)`` slices
  one global per-step batch (same ``TokenStream`` contract);
  ``ShardedSageBatchSource`` stacks the N per-shard frontiers into a single
  batch whose rows are grouped per shard, so the ``"sharded"`` decode
  backend (``repro.core.backend``) decodes shard-local under ``shard_map``
  and an N-shard run is a config change (mesh + ``lookup_impl``), not new
  code.
"""

from __future__ import annotations

import dataclasses
import os
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import GNNConfig
from repro.graph.csr import CSRMatrix
from repro.graph.sampler import FrontierBatch, NeighborSampler
from repro.models import gnn

Batch = Union[FrontierBatch, "FullGraphBatch", Sequence[Any]]


# ---------------------------------------------------------------------------
# unified model API
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FullGraphBatch:
    """Full-graph "batch": a handle on the normalised adjacency.  ``apply``
    returns hidden states for ALL nodes (the paper trains GCN/SGC/GIN
    without minibatches, §C.1)."""

    adj: CSRMatrix

    def tree_flatten(self):
        return (self.adj,), None

    @classmethod
    def tree_unflatten(cls, _aux, leaves):
        return cls(leaves[0])


class GNNModel:
    """Single entry point over the paper's GNN family.

    ``apply(params, batch)`` dispatches on the batch type at trace time:
      FrontierBatch   -> dedup-decode minibatched GraphSAGE
      list of levels  -> naive minibatched GraphSAGE (reference path)
      FullGraphBatch  -> full-graph GCN / SGC / GIN (or CSRMatrix directly)

    The embedding decode goes through the ``DecodeBackend`` selected by the
    config's ``lookup_impl`` (resolved once here, not per trace);
    ``interpret=True`` runs the pallas backend in interpret mode (CPU CI).
    ``duplication`` is the measured frontier duplication hint ``auto``
    backend selection uses to prefer the owner-computes decode over the
    plain sharded one (``core.backend.resolve_auto``).
    ``apply_cached(params, batch, cache_state)`` is the hot-node-cache twin
    for the frontier path — it returns ``(hidden, new_cache_state)``.
    """

    def __init__(self, cfg: GNNConfig, interpret: bool = False,
                 duplication: Optional[float] = None):
        from repro.core.backend import get_backend
        self.cfg = cfg
        policy = cfg.embedding_config().decoder_config().precision_policy()
        self.backend = get_backend(cfg.embedding.lookup_impl,
                                   interpret=interpret,
                                   duplication=duplication,
                                   policy=policy)

    def init(self, key, codes=None, aux=None):
        return gnn.init_gnn(key, self.cfg, codes=codes, aux=aux)

    def apply(self, params, batch: Batch):
        if isinstance(batch, FrontierBatch):
            return gnn.sage_forward_frontier(params, batch, self.cfg,
                                             backend=self.backend)
        if isinstance(batch, FullGraphBatch):
            return gnn.fullgraph_forward(params, batch.adj, self.cfg)
        if isinstance(batch, CSRMatrix):
            return gnn.fullgraph_forward(params, batch, self.cfg)
        if isinstance(batch, (list, tuple)):
            return gnn.sage_forward(params, list(batch), self.cfg,
                                    backend=self.backend)
        if isinstance(batch, dict):
            return self.apply(params, batch_view(batch))
        raise TypeError(f"GNNModel.apply: unsupported batch type {type(batch)!r}")

    def apply_cached(self, params, batch: Batch, cache_state):
        """Frontier batches decode through the hot-node cache; every other
        batch type falls back to ``apply`` with the state passed through.
        A frontier carrying a static ``n_decode`` (miss-first permuted by
        ``MissPlanningSource``) decodes only its planned-miss prefix."""
        if isinstance(batch, dict):
            batch = batch_view(batch)
        if isinstance(batch, FrontierBatch):
            if batch.n_decode is not None:
                return gnn.sage_forward_frontier_missonly(
                    params, batch, self.cfg, cache_state, batch.n_decode,
                    backend=self.backend)
            return gnn.sage_forward_frontier_cached(
                params, batch, self.cfg, cache_state, backend=self.backend)
        return self.apply(params, batch), cache_state

    def logits(self, params, hidden):
        return gnn.node_logits(params, hidden, self.cfg)


def batch_view(batch: Dict[str, Any]) -> Batch:
    """Extract the model-facing view from a batch dict produced by the
    sources below ({"frontier": ...}, {"levels": ...}) or the runtime's
    full-graph source ({"full": FullGraphBatch, "ids": ..., "labels": ...})."""
    if "frontier" in batch:
        return batch["frontier"]
    if "levels" in batch:
        return batch["levels"]
    if "full" in batch:
        return batch["full"]
    raise KeyError("batch dict has none of 'frontier' / 'levels' / 'full'")


# ---------------------------------------------------------------------------
# batch sources (host side, deterministic per step)
# ---------------------------------------------------------------------------

def _step_rng(seed: int, step: int) -> np.random.Generator:
    return np.random.default_rng((seed * 1_000_003 + 12_582_917) + step)


def default_frontier_cap(batch_size: int, fanouts, pad_to: int,
                         n_nodes: int) -> int:
    """Exact per-shard frontier size: the worst-case unique count (every
    sampled position distinct, bounded by the graph), rounded up to the
    padding multiple so stacked shard segments stay backend-aligned.

    Worst case is the *safe* default — an undersized cap raises mid-run —
    but real frontiers dedup far below it, so the stacked batch decodes
    padding rows (``measure_duplication`` reports rows over unique).  Runs
    that know their workload should pass a measured ``frontier_cap``."""
    worst = batch_size
    per_target = 1
    for f in fanouts:
        per_target *= f
        worst += batch_size * per_target
    cap = min(worst, int(n_nodes))
    return -(-cap // max(pad_to, 1)) * max(pad_to, 1)


class SageBatchSource:
    """Per-step GraphSAGE batch source over a node pool with labels.

    Deterministic in ``(seed, shard, step)`` — the same contract as
    ``data.tokens.TokenStream``: each step draws one *global* batch of
    ``batch_size * n_shards`` nodes from an rng seeded by ``(seed, step)``
    (identical on every shard), takes the shard's contiguous slice, and
    samples neighbourhoods counter-based (``NeighborSampler.sample_hashed``)
    keyed by the target's global batch position.  The union of the N shard
    batches is therefore *bit-identical* to the batch an ``n_shards=1``
    source of batch size ``batch_size * n_shards`` produces, and
    ``state_dict`` is just the step, so resume / prefetch replay stay exact
    per shard.

    ``dedup=True`` emits {"frontier": FrontierBatch, "labels": y};
    ``dedup=False`` emits {"levels": tuple, "labels": y} (naive reference).
    ``frontier_cap`` pads every frontier to that exact row count (sharded
    runs stack equal-size per-shard frontiers; ``None`` keeps the usual
    round-up-to-``pad_to`` padding).
    """

    def __init__(self, sampler: NeighborSampler, nodes, labels, batch_size: int,
                 seed: int = 0, dedup: bool = True, pad_to: int = 256,
                 shard: int = 0, n_shards: int = 1,
                 frontier_cap: Optional[int] = None):
        if not 0 <= shard < n_shards:
            raise ValueError(f"shard {shard} out of range for {n_shards} shards")
        self.sampler = sampler
        self.nodes = np.asarray(nodes)
        self.labels = np.asarray(labels)
        self.batch_size = int(batch_size)
        self.seed = int(seed)
        self.dedup = dedup
        self.pad_to = pad_to
        self.shard = int(shard)
        self.n_shards = int(n_shards)
        self.frontier_cap = frontier_cap
        self.step = 0

    def next_batch(self) -> Dict[str, Any]:
        from repro.graph import sampler as sampler_mod
        rng = _step_rng(self.seed, self.step)
        key = sampler_mod.stream_key(self.seed, self.step)
        self.step += 1
        global_b = self.batch_size * self.n_shards
        replace = global_b > self.nodes.shape[0]
        # the global draw is shard-independent; every shard consumes the rng
        # identically and keeps only its contiguous slice
        ids_g = rng.choice(self.nodes, global_b, replace=replace).astype(np.int32)
        lo = self.shard * self.batch_size
        ids = ids_g[lo:lo + self.batch_size]
        gpos = np.arange(lo, lo + self.batch_size, dtype=np.uint64)
        y = self.labels[ids].astype(np.int32)
        levels = self.sampler.sample_hashed(ids, gpos, key)
        if self.dedup:
            fb = FrontierBatch.from_levels(levels, pad_to=self.pad_to,
                                           cap=self.frontier_cap)
            return {"frontier": fb, "labels": y}
        return {"levels": tuple(levels), "labels": y}

    # -- checkpointable state -------------------------------------------
    def state_dict(self) -> Dict[str, int]:
        return {"step": self.step, "seed": self.seed,
                "shard": self.shard, "n_shards": self.n_shards}

    def load_state_dict(self, state: Dict[str, int]) -> None:
        assert int(state["seed"]) == self.seed, \
            "restoring a sage batch source from a different run"
        assert (int(state.get("shard", 0)) == self.shard
                and int(state.get("n_shards", 1)) == self.n_shards), \
            "restoring a sage batch source onto a different shard layout"
        self.step = int(state["step"])


# The sharded producer's thread pool: one for the process, made on first use
# and shared by every ``ShardedSageBatchSource`` (tests and the elastic
# manager build sources by the dozen, so none may start threads of its own).
# Its work is numpy that releases the GIL: hashing, gathers, sorts.
SHARD_POOL_WORKERS = min(os.cpu_count() or 1, 8)
_pool: Optional[ThreadPoolExecutor] = None
_pool_lock = threading.Lock()


def _shard_pool() -> ThreadPoolExecutor:
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(SHARD_POOL_WORKERS,
                                       thread_name_prefix="shard-pool")
        return _pool


def _timed_next_batch(src: SageBatchSource) -> Tuple[Dict[str, Any], float]:
    t0 = time.perf_counter()
    part = src.next_batch()
    return part, (time.perf_counter() - t0) * 1e6


class ShardedSageBatchSource:
    """All-shard view of the sharded stream: N per-shard ``SageBatchSource``s
    advanced in lockstep, their batches stacked into one *global* batch.

    The stacked frontier groups rows per shard — row block ``s`` is shard
    ``s``'s frontier, padded to exactly ``frontier_cap`` rows — so placing
    the ``unique`` axis on the mesh's data axis (``policy.
    frontier_batch_shardings``) puts each shard's rows on its own device and
    the ``"sharded"`` decode backend runs shard-local with zero resharding.
    Index maps are offset into the owning shard's block; cross-shard
    duplicate nodes decode once *per shard* (the price of skipping a global
    dedup synchronisation — exactly the multi-host trade).  ``valid`` marks
    each block's genuine prefix, since padding is interleaved per shard
    rather than a global suffix.

    In a true multi-host deployment each host runs only its own
    ``SageBatchSource(shard=s)``; this class is the single-process stand-in
    that drives all shards for tests, benchmarks and the forced-host-device
    CI leg.

    ``owner_plan`` attaches a host-built ``OwnerPlan`` to every batch (in
    the prefetch thread, alongside the sampling) so the ``"owner"`` decode
    backend can dedup hub rows across shards: ``True`` always plans,
    ``"auto"`` measures the step-0 duplication
    (``frontier_rows / unique_rows``) and plans only when it beats
    ``core.backend.OWNER_DUP_THRESHOLD`` — the same rule ``auto`` backend
    selection applies, so plan and backend stay in sync.  A batch whose
    buckets overflow the static ``owner_cap`` / ``owner_unique_cap``
    capacities is emitted WITHOUT a plan after a loud warning (the owner
    backend then falls back to the sharded row-partition decode) — rows are
    never silently truncated.

    The shards sample on the process's shard pool at once, and the plan
    is built there in two phases (per requester, then per owner; see
    ``build_owner_plan``); the stacking stays on the calling thread.  Every
    part is a pure function of ``(seed, shard, step)`` or of the four
    frontiers, so the batch is bit for bit the one a serial build gives.

    Each plan build is a ``repro.producer.owner_plan`` profiler span on the
    calling thread (inside the producer's ``repro.producer.sample``) around
    both pooled phases, and ``stats()`` counts what sampling and planning
    cost and yielded: ``shard_workers`` (the pool threads this source's
    shards run on), ``shard_sample_us`` (the sum over shards of each shard's
    own sample-and-dedup time, so over the sampling's wall time it reads
    how far the shards overlapped), ``owner_plan_us`` (the same clock reads
    as the span), ``owned_rows`` (the sum of every plan's ``n_owned``: the
    rows the owners decode) and ``owner_plan_overflows`` (batches emitted
    without a plan).  ``PrefetchIterator.stats()`` passes them on.
    """

    def __init__(self, sampler: NeighborSampler, nodes, labels,
                 batch_size: int, n_shards: int, seed: int = 0,
                 pad_to: int = 256, frontier_cap: Optional[int] = None,
                 owner_plan: Union[bool, str] = False,
                 owner_cap: Optional[int] = None,
                 owner_unique_cap: Optional[int] = None):
        if frontier_cap is None:
            frontier_cap = default_frontier_cap(
                batch_size, sampler.fanouts, pad_to, sampler.table.shape[0])
        self.n_shards = int(n_shards)
        self.frontier_cap = int(frontier_cap)
        self.seed = int(seed)
        self.shards = [
            SageBatchSource(sampler, nodes, labels, batch_size, seed=seed,
                            pad_to=pad_to, shard=s, n_shards=n_shards,
                            frontier_cap=self.frontier_cap)
            for s in range(self.n_shards)
        ]
        self._peek = None   # (step, parts) cache so a peek isn't resampled
        self.duplication_measured: Optional[float] = None
        if owner_plan == "auto":
            from repro.core.backend import OWNER_DUP_THRESHOLD
            self.duplication_measured = self.measure_duplication()
            owner_plan = self.duplication_measured > OWNER_DUP_THRESHOLD
        self.owner_plan = bool(owner_plan)
        from repro.graph.sampler import default_owner_caps
        oc, ou = default_owner_caps(self.frontier_cap, self.n_shards)
        for name, cap_ in (("owner_cap", owner_cap),
                           ("owner_unique_cap", owner_unique_cap)):
            if cap_ is not None and int(cap_) <= 0:
                raise ValueError(f"{name} must be positive, got {cap_} "
                                 f"(None = sized from frontier_cap)")
        self.owner_cap = oc if owner_cap is None else int(owner_cap)
        self.owner_unique_cap = (ou if owner_unique_cap is None
                                 else int(owner_unique_cap))
        self.shard_workers = min(SHARD_POOL_WORKERS, self.n_shards)
        self.shard_sample_us = 0.0
        self.owner_plan_us = 0.0
        self.owned_rows = 0
        self.owner_plan_overflows = 0

    def _sample_shards(self) -> Tuple[List[Dict[str, Any]], List[float]]:
        """Every shard's next batch, sampled on the pool, with each
        shard's own sample-and-dedup time in us."""
        timed = list(_shard_pool().map(_timed_next_batch, self.shards))
        return [p for p, _ in timed], [us for _, us in timed]

    def measure_duplication(self) -> float:
        """Measured decode duplication of the upcoming batch:
        ``frontier_rows / unique_rows`` per device — the per-device decode
        work (``frontier_cap``, padding included) over the mean per-shard
        unique count; the factor the owner decode can reclaim.  Peeks
        without consuming (shard steps are restored, and the sampled parts
        are cached so the next ``next_batch`` at the same step reuses
        instead of resampling), so resume stays exact and the step-0
        sampling cost is paid once."""
        step0 = self.shards[0].step
        parts, _ = self._sample_shards()
        for s in self.shards:
            s.step = step0
        self._peek = (step0, parts)
        total_unique = sum(int(p["frontier"].n_unique) for p in parts)
        return self.frontier_cap * self.n_shards / max(total_unique, 1)

    def next_batch(self) -> Dict[str, Any]:
        from repro.graph.sampler import build_owner_plan
        if self._peek is not None and self._peek[0] == self.shards[0].step:
            parts = self._peek[1]
            for s in self.shards:       # advance as next_batch would have
                s.step += 1
        else:
            parts, shard_us = self._sample_shards()
            self.shard_sample_us += sum(shard_us)
        self._peek = None
        cap = self.frontier_cap
        fbs = [p["frontier"] for p in parts]
        unique = np.concatenate([np.asarray(fb.unique) for fb in fbs])
        n_levels = len(fbs[0].index_maps)
        maps = tuple(
            np.concatenate([np.asarray(fb.index_maps[i]) + s * cap
                            for s, fb in enumerate(fbs)], axis=0)
            for i in range(n_levels))
        valid = np.concatenate([
            np.arange(cap, dtype=np.int32) < int(fb.n_unique) for fb in fbs])
        n_unique = np.int32(sum(int(fb.n_unique) for fb in fbs))
        labels = np.concatenate([p["labels"] for p in parts])
        plan = None
        if self.owner_plan:
            with TraceAnnotation("repro.producer.owner_plan"):
                t0 = time.perf_counter()
                plan = build_owner_plan(
                    [np.asarray(fb.unique) for fb in fbs],
                    [int(fb.n_unique) for fb in fbs],
                    self.n_shards, self.owner_cap, self.owner_unique_cap,
                    map_fn=_shard_pool().map)
                self.owner_plan_us += (time.perf_counter() - t0) * 1e6
            if plan is None:
                self.owner_plan_overflows += 1
                import warnings
                warnings.warn(
                    f"owner plan overflow: a (requester, owner) bucket "
                    f"exceeded owner_cap={self.owner_cap} or an owner's "
                    f"unique set exceeded owner_unique_cap="
                    f"{self.owner_unique_cap}; emitting the batch without a "
                    f"plan (decode falls back to the sharded row partition "
                    f"— correct, but no cross-shard dedup).  Raise the caps "
                    f"(RuntimeSpec.owner_cap / owner_unique_cap) if this "
                    f"recurs.", stacklevel=2)
            else:
                self.owned_rows += int(plan.n_owned.sum())
        return {"frontier": FrontierBatch(unique, maps, n_unique, valid, plan),
                "labels": labels}

    def stats(self) -> Dict[str, float]:
        """Sampling, and owner planning where the source plans, over every
        batch built so far; see the class docstring."""
        out = {"shard_workers": self.shard_workers,
               "shard_sample_us": self.shard_sample_us}
        if self.owner_plan:
            out.update(owner_plan_us=self.owner_plan_us,
                       owned_rows=self.owned_rows,
                       owner_plan_overflows=self.owner_plan_overflows)
        return out

    # -- checkpointable state -------------------------------------------
    def state_dict(self) -> Dict[str, int]:
        return {"step": self.shards[0].step, "seed": self.seed,
                "n_shards": self.n_shards}

    def load_state_dict(self, state: Dict[str, int]) -> None:
        assert int(state["seed"]) == self.seed, \
            "restoring a sharded sage batch source from a different run"
        assert int(state.get("n_shards", 1)) == self.n_shards, \
            "restoring a sharded sage batch source onto a different shard count"
        for sh in self.shards:
            sh.step = int(state["step"])


class MissPlanningSource:
    """Plan-ahead miss partition for *training* with the hot-node cache.

    Serving already decodes only cache misses (``serving.gnn``: the frontier
    is permuted miss-first against the live cache and only a bucketed prefix
    enters the decoder).  Training couldn't — the cache state evolves every
    step, and by the time the prefetch thread sees batch k+1 the device
    cache for batch k hasn't been updated yet.  This wrapper closes that
    gap: it advances a ``core.backend.HostCacheShadow`` (an exact numpy
    replica of the cache *bookkeeping* — the update depends only on the id
    sequence, never on decoded values) one step per produced batch, so the
    producer thread can partition batch k+1's misses while step k runs.

    Each emitted frontier is permuted miss-first with its index maps
    remapped through the inverse permutation, carries an explicit ``valid``
    mask (the prefix mask no longer survives the permutation) and a static
    bucketed ``n_decode`` (geometric ``pad_to`` doubling, one jit retrace
    per bucket — the serving engine's scheme).  The train step then takes
    the ``lookup_missonly`` path: only the prefix enters the decoder.

    A planned miss that turns out to hit is served from the cache anyway
    (harmless); a planned *hit* that misses would read zeros, which is why
    the shadow replays the device update exactly.  On checkpoint resume the
    runtime re-anchors the shadow from the restored device ``CacheState``
    (``sync_shadow``), covering state dicts that predate the shadow key.

    Only single-shard frontiers qualify: the permutation would break the
    per-shard row blocks of stacked sharded batches and the row indexing of
    an ``OwnerPlan`` (``next_batch`` raises on a planned batch).
    """

    def __init__(self, source, capacity: int, staleness: int = 0,
                 pad_to: int = 256):
        from repro.core.backend import HostCacheShadow
        self.source = source
        self.pad_to = max(1, int(pad_to))
        self.shadow = HostCacheShadow(capacity, staleness)

    def _bucket(self, n_miss: int, cap: int) -> int:
        if n_miss <= 0:
            return 0
        b = self.pad_to
        while b < n_miss:
            b *= 2
        return min(b, cap)

    def next_batch(self) -> Dict[str, Any]:
        batch = dict(self.source.next_batch())
        fb = batch["frontier"]
        if fb.plan is not None:
            raise ValueError(
                "MissPlanningSource: owner-planned batches cannot be "
                "miss-permuted (plan rows index the unpermuted frontier)")
        ids = np.asarray(fb.unique)
        U = ids.shape[0]
        valid = (np.asarray(fb.valid) if fb.valid is not None
                 else np.arange(U) < int(fb.n_unique))
        perm, n_miss = self.shadow.plan(ids, valid)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(U, dtype=np.int32)
        n_dec = self._bucket(n_miss, U)
        ids_p, valid_p = ids[perm], valid[perm]
        batch["frontier"] = FrontierBatch(
            unique=ids_p,
            index_maps=tuple(inv[np.asarray(m)] for m in fb.index_maps),
            n_unique=fb.n_unique, valid=valid_p, n_decode=n_dec)
        self.shadow.update(ids_p, valid_p, n_dec)
        return batch

    # -- checkpointable state -------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        sd = dict(self.source.state_dict())
        sd["miss_shadow"] = self.shadow.snapshot()
        return sd

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.source.load_state_dict(state)
        if "miss_shadow" in state:
            self.shadow.restore(state["miss_shadow"])
        else:
            # pre-shadow state dict: empty shadow plans everything as a
            # miss (safe); the runtime's resume re-syncs from the device
            # cache right after (sync_shadow)
            self.shadow.clear()

    def sync_shadow(self, cache_state) -> None:
        """Re-anchor the shadow to a restored device ``CacheState``."""
        self.shadow.sync_from_cache_state(cache_state)


# ---------------------------------------------------------------------------
# async prefetch
# ---------------------------------------------------------------------------

class PrefetchIterator:
    """Double-buffered host→device pipeline around a batch source.

    A daemon thread repeatedly calls ``source.next_batch()`` and
    ``jax.device_put``s the result, keeping up to ``depth`` batches in
    flight, so host-side numpy sampling and the H2D copy overlap with the
    jitted step consuming the previous batch.

    ``device`` may be a jax device/sharding (forwarded to
    ``jax.device_put``) or a *callable* ``batch -> placed_batch`` — sharded
    runs pass ``policy.make_frontier_placement(mesh)`` so each shard's
    frontier rows land on their own device straight off the host thread.

    ``code_gather`` is the codes-placement hook (``codes_placement="host"``):
    a host-side ``batch -> batch`` callable — typically ``attach_codes``
    partial-applied to the full packed buffer — run by the producer thread
    on each batch *before* the device put, so the frontier's code rows are
    gathered for batch k+1 while the device computes batch k.  The producer
    blocks on the transferred arrays after ``device_put``, which is what
    makes the pipeline genuinely double-buffered: the H2D copy of the next
    batch completes in the background, not lazily on first consumer use.

    Per-stage producer wall-clock is accumulated and exposed via
    ``stats()`` (``sample_us`` / ``code_gather_us`` / ``put_us`` +
    ``transferred_code_bytes``) — the honest axis for judging whether the
    host gather hides behind the device step.  Each stage is also a
    profiler span (``repro.producer.sample`` / ``.code_gather`` / ``.put``)
    around the same clock reads, so a trace places that time on the
    device's clock.

    Resume semantics: each queue item carries the source state captured
    *after* producing that batch; ``state_dict()`` returns the state of the
    last batch the consumer actually took, so a checkpoint taken after
    consuming k batches restores to exactly batch k+1 regardless of how far
    ahead the producer ran.
    """

    def __init__(self, source, depth: int = 2, device=None, code_gather=None):
        self.source = source
        self.depth = max(1, int(depth))
        self._device = device
        self._code_gather = code_gather
        self._lock = threading.Lock()     # serialises (re)starts vs producer
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._last_state = self._snapshot()
        # producer-side accounting (producer writes, stats() reads)
        self._n_produced = 0
        self._sample_us = 0.0
        self._code_gather_us = 0.0
        self._put_us = 0.0
        self._transferred_code_bytes = 0
        self._start()

    # -- internals -------------------------------------------------------
    def _snapshot(self):
        if hasattr(self.source, "state_dict"):
            return self.source.state_dict()
        return None

    def _start(self):
        self._stop = threading.Event()
        self._err = None
        self._q = queue.Queue(maxsize=self.depth)
        self._thread = threading.Thread(target=self._produce, daemon=True,
                                        name="engine-prefetch")
        self._thread.start()

    @staticmethod
    def _code_bytes(batch) -> int:
        """Bytes of batch-carried packed code rows (the per-batch H2D code
        traffic a host-placement run pays instead of a resident buffer)."""
        total = 0
        for leaf in jax.tree.leaves(
                batch, is_leaf=lambda x: isinstance(x, FrontierBatch)):
            if isinstance(leaf, FrontierBatch) and leaf.codes is not None:
                total += int(np.asarray(leaf.codes).nbytes)
        return total

    def _produce(self):
        import time as _time
        stop, q = self._stop, self._q
        try:
            while not stop.is_set():
                # each span opens and closes with the clock reads stats()
                # sums, so the trace and the counters time the same work
                with TraceAnnotation("repro.producer.sample"):
                    t0 = _time.perf_counter()
                    with self._lock:
                        if stop.is_set():
                            return
                        batch = self.source.next_batch()
                        state = self._snapshot()
                    t1 = _time.perf_counter()
                if self._code_gather is not None:
                    with TraceAnnotation("repro.producer.code_gather"):
                        batch = self._code_gather(batch)
                        self._transferred_code_bytes += self._code_bytes(batch)
                t2 = _time.perf_counter()
                with TraceAnnotation("repro.producer.put"):
                    if callable(self._device):
                        batch = self._device(batch)
                    else:
                        batch = jax.device_put(batch, self._device)
                    # block here, in the producer: the H2D copy of batch k+1
                    # completes while the consumer computes batch k (the actual
                    # double-buffering), and put_us measures the real transfer
                    jax.block_until_ready(batch)
                    t3 = _time.perf_counter()
                self._sample_us += (t1 - t0) * 1e6
                self._code_gather_us += (t2 - t1) * 1e6
                self._put_us += (t3 - t2) * 1e6
                self._n_produced += 1
                item = (batch, state)
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.05)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # surfaced on the consumer side
            self._err = e

    # -- consumer API ----------------------------------------------------
    def next_batch(self):
        if self._thread is None:    # closed (e.g. by run_training): restart
            self._start()
        thread, q = self._thread, self._q
        while True:
            try:
                batch, state = q.get(timeout=0.1)
            except queue.Empty:
                if self._err is not None:
                    raise self._err
                if thread is None or not thread.is_alive():
                    raise RuntimeError("prefetch producer exited without a batch")
                continue
            self._last_state = state
            return batch

    def close(self):
        """Stop the producer and drop any batches in flight.

        Acts as a *pause* when the source is checkpointable: the source is
        rewound to the last consumed batch, so a later ``next_batch`` (which
        restarts the producer lazily) continues the exact sequence — callers
        like ``run_training`` may close an iterator they don't own without
        rendering it unusable."""
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        if self._last_state is not None and hasattr(self.source, "load_state_dict"):
            self.source.load_state_dict(self._last_state)

    def stats(self) -> Dict[str, float]:
        """Cumulative producer-side accounting: per-stage wall-clock
        (``sample_us`` sampling + source bookkeeping, ``code_gather_us``
        host code-row gather, ``put_us`` device put incl. the blocking H2D
        copy), produced-batch count, and code-row transfer volume; plus the
        source's own counters where it keeps some (the owner plan's,
        ``ShardedSageBatchSource.stats``)."""
        n = self._n_produced
        source_stats = getattr(self.source, "stats", None)
        return {
            "n_produced": n,
            "sample_us": self._sample_us,
            "code_gather_us": self._code_gather_us,
            "put_us": self._put_us,
            "transferred_code_bytes": self._transferred_code_bytes,
            "transferred_code_bytes_per_batch": (
                self._transferred_code_bytes / n if n else 0.0),
            **(source_stats() if callable(source_stats) else {}),
        }

    # -- checkpointable state -------------------------------------------
    def state_dict(self):
        return self._last_state

    def load_state_dict(self, state) -> None:
        self.close()
        if hasattr(self.source, "load_state_dict"):
            self.source.load_state_dict(state)
        self._last_state = self._snapshot()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
