"""Batched GNN inference engine (the §5.3 merchant-system serving shape).

``GraphInferenceEngine`` is the GNN twin of the LM ``DecodeEngine`` behind
the shared ``serving.Engine`` protocol: frozen params, fixed-shape jitted
steps, a batched request entry point.  Per request:

    sample frontier  →  miss-only cached decode  →  forward  →  (h, logits)

The decode path is where serving differs from training: request streams
revisit hot (high-degree) nodes constantly and the params never change
between requests, so a decoded embedding never goes stale.  The engine
therefore keeps a device-resident ``CacheState`` across requests and
partitions every frontier host-side (``CachedDecodeBackend.plan_missonly``)
into a padded miss-prefix — **only cache misses enter the decoder**, and
``rows_decoded`` (vs the full frontier row count) is the win, asserted in
``tests/test_runtime.py``.

Cross-request dedup (``serve_many``, ISSUE 7): a microbatch of concurrent
requests — coalesced by ``serving.batcher.ServingBatcher`` — concatenates
its sampled levels and dedups them in ONE ``FrontierBatch``, so a hub node
requested by many users in the same microbatch samples and decodes exactly
once; per-request results are rebuilt by slicing the combined forward.
This stacks as the third dedup tier: within-request (PR 1) → shared hot
``CacheState`` across requests (PR 4) → union-of-misses decode across the
microbatch.

Fixed shapes: the request batch pads to ``serve_batch``, the request count
to a power-of-two bucket (filler requests repeat request 0's levels, whose
rows are already in the union — zero extra decode work), and the frontier
to an exact per-bucket cap, so the forward jits once per
(miss-bucket, request-bucket) pair — buckets grow geometrically from
``pad_to``, bounding compilations at ~log2(cap/pad_to) + 2 per request
bucket (``decode_buckets``, asserted in tests/test_serving.py).

Bit-exactness: hits are embeddings the same frozen params decoded earlier,
and the request frontier is content-keyed (a pure function of the engine
seed and the requested ids, NOT of arrival order), so a batched response
is bitwise the sequential ``serve()`` response no matter how requests
interleave — cache reuse and cross-request coalescing are both free at
serving time (tests/test_runtime.py, tests/test_serving.py).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GNNConfig
from repro.core import backend as backend_mod
from repro.core.backend import CachedDecodeBackend, CacheState
from repro.graph.sampler import FrontierBatch, NeighborSampler, _mix64
from repro.models import gnn as gnn_lib


@dataclasses.dataclass
class GraphServeResult:
    """One served request batch."""
    embeddings: np.ndarray              # (B, H) final hidden per node
    logits: Optional[np.ndarray]        # (B, n_classes) when task == "node"
    predictions: Optional[np.ndarray]   # (B,) argmax labels (node task)
    rows_decoded: int                   # decoder rows the microbatch paid
    rows_total: int                     # frontier rows (padded cap × requests)
    batch_requests: int = 1             # requests coalesced in the microbatch


class GraphInferenceEngine:
    """Frozen-params GNN serving over the minibatched GraphSAGE path.

    ``decode_backend`` pins the embedding decode path (same contract as
    ``DecodeEngine``): ``None`` keeps the config's ``lookup_impl``,
    ``"auto"`` resolves for the current runtime, unknown names fail here —
    at engine construction — not on the first request.  ``cache_capacity``
    sizes the cross-request hot-node cache (0 disables it; the default
    keeps ~4 frontiers' worth of rows).

    ``host_codes`` is the full packed code buffer when the params were built
    with ``codes_placement="host"`` (they then carry no ``codes_buf``): the
    engine gathers each serving frontier's code rows host-side — after the
    miss-first permutation, so rows stay row-aligned — and the device holds
    only O(frontier) code bytes per microbatch.
    """

    def __init__(self, cfg: GNNConfig, params, sampler: NeighborSampler, *,
                 decode_backend: Optional[str] = None, serve_batch: int = 256,
                 frontier_cap: Optional[int] = None, pad_to: int = 256,
                 cache_capacity: Optional[int] = None, seed: int = 0,
                 max_coalesce: int = 8, interpret: bool = False,
                 host_codes: Optional[np.ndarray] = None):
        if cfg.model != "sage":
            raise ValueError(
                f"GraphInferenceEngine serves minibatched GraphSAGE; got "
                f"model={cfg.model!r} (full-graph models evaluate via "
                f"GraphRuntime.evaluate)")
        if decode_backend is not None:
            resolved = (backend_mod.resolve_auto()
                        if decode_backend == "auto" else decode_backend)
            backend_mod.get_backend(resolved, interpret=interpret)
            # execution strategy is servable-time-swappable; the compression
            # FAMILY is baked into the trained params' layout and is not
            have = backend_mod.family_of(cfg.embedding.lookup_impl)
            want = backend_mod.family_of(resolved)
            if want != have:
                raise ValueError(
                    f"decode_backend={decode_backend!r} selects compression "
                    f"family {want!r} but the params were trained as "
                    f"{have!r} (lookup_impl={cfg.embedding.lookup_impl!r}); "
                    f"the family is a training-time choice")
            cfg = dataclasses.replace(
                cfg, embedding=dataclasses.replace(
                    cfg.embedding, lookup_impl=resolved))
        self.cfg = cfg
        self.params = params
        self.sampler = sampler
        self.serve_batch = int(serve_batch)
        self.pad_to = int(pad_to)
        self.seed = int(seed)
        self.max_coalesce = int(max_coalesce)
        if self.max_coalesce < 1:
            raise ValueError(f"max_coalesce must be >= 1, got {max_coalesce}")
        self.interpret = bool(interpret)
        ecfg = cfg.embedding_config()
        self._backend = backend_mod.get_backend(ecfg.lookup_impl,
                                                interpret=interpret)
        self.host_codes = (None if host_codes is None
                           else np.asarray(host_codes, np.uint32))
        if ecfg.codes_on_host and self.host_codes is None:
            raise ValueError(
                "codes_placement='host' params carry no codes_buf — pass "
                "host_codes (the full packed buffer) to the engine")

        from repro.graph.engine import default_frontier_cap
        self.frontier_cap = int(
            frontier_cap if frontier_cap is not None
            else default_frontier_cap(self.serve_batch, cfg.fanouts,
                                      self.pad_to, cfg.n_nodes))

        if cache_capacity is None:
            cache_capacity = (min(4 * self.frontier_cap, cfg.n_nodes)
                              if ecfg.is_compressed else 0)
        self.cache_capacity = int(cache_capacity)
        self.cached = ecfg.is_compressed and self.cache_capacity > 0
        # params are frozen at serve time, so the version counter never
        # bumps and staleness 0 still means "every hit is forever fresh"
        self._cache = CachedDecodeBackend(staleness=0)
        self._cache_state = (CacheState.create(
            self.cache_capacity, cfg.d_e,
            jax.numpy.dtype(cfg.compute_dtype)) if self.cached else None)

        self._fwd_cache: Dict[int, object] = {}
        self._requests = 0
        self._microbatches = 0
        self._rows_decoded = 0
        self._rows_total = 0
        self._compile_count = 0

    # -- internals -------------------------------------------------------
    def _request_rng(self, padded_ids: np.ndarray) -> np.random.Generator:
        """Content-keyed request PRNG: the neighbour draws for a request are
        a pure function of ``(engine seed, requested ids)`` — NOT of arrival
        order or a request counter — so a request coalesced into any
        microbatch samples exactly the frontier a sequential ``serve`` of
        the same ids would (the ordering-independence the batcher's bitwise
        contract rests on)."""
        with np.errstate(over="ignore"):
            h = _mix64(padded_ids.astype(np.uint64)
                       + (np.arange(padded_ids.shape[0], dtype=np.uint64)
                          + np.uint64(1))
                       * np.uint64(0x9E3779B97F4A7C15))
            key = _mix64(np.bitwise_xor.reduce(h)
                         ^ np.uint64(self.seed * 1_000_003 + 777_767_777))
        return np.random.default_rng(int(key))

    def _sample_levels(self, padded_ids: np.ndarray) -> List[np.ndarray]:
        """Sampled (un-dedup'd) level tensors for one padded request."""
        return self.sampler.sample(padded_ids,
                                   rng=self._request_rng(padded_ids))

    def frontier_for(self, node_ids) -> FrontierBatch:
        """The exact (padded, fixed-cap) frontier ``serve`` samples for a
        request — exposed so parity tests can run ``GNNModel.apply`` on the
        same batch.  Deterministic in ``(seed, node_ids)``."""
        ids = self._pad_request(np.asarray(node_ids, np.int32))
        fb = FrontierBatch.from_levels(self._sample_levels(ids),
                                       pad_to=self.pad_to,
                                       cap=self.frontier_cap)
        return self._attach_codes(fb)

    def _attach_codes(self, fb: FrontierBatch) -> FrontierBatch:
        if self.host_codes is None:
            return fb
        from repro.graph.sampler import attach_codes
        return attach_codes(fb, self.host_codes)

    def _pad_request(self, ids: np.ndarray) -> np.ndarray:
        if ids.shape[0] > self.serve_batch:
            raise ValueError(
                f"request batch {ids.shape[0]} > serve_batch "
                f"{self.serve_batch}; chunk requests host-side")
        if ids.shape[0] < self.serve_batch:
            ids = np.concatenate(
                [ids, np.full(self.serve_batch - ids.shape[0], ids[0],
                              ids.dtype)])
        return ids

    def _bucket(self, n_miss: int, cap: Optional[int] = None) -> int:
        """Geometric miss-count buckets: one jit shape per bucket.  ``cap``
        defaults to the single-request ``frontier_cap``; microbatches pass
        their combined (request-bucket × cap) frontier size."""
        cap = self.frontier_cap if cap is None else cap
        if n_miss <= 0:
            return 0
        b = self.pad_to
        while b < n_miss:
            b *= 2
        return min(b, cap)

    def _request_bucket(self, k: int) -> int:
        """Power-of-two request-count buckets (capped at ``max_coalesce``)
        so the combined forward sees a bounded set of batch shapes."""
        b = 1
        while b < k:
            b *= 2
        return min(b, self.max_coalesce)

    def decode_buckets(self, max_requests: int = 1) -> Tuple[int, ...]:
        """Every static decode-row bucket a ≤ ``max_requests`` microbatch
        can produce — the jitted forward compiles at most once per bucket
        per request-count bucket, which is the compile bound the
        shape-bucketing regression test pins (tests/test_serving.py)."""
        cap = self._request_bucket(max_requests) * self.frontier_cap
        if not self.cached:
            return (cap,)
        out, b = [0, cap], self.pad_to
        while b < cap:
            out.append(b)
            b *= 2
        return tuple(sorted(set(out)))

    def _forward(self, n_decode: int):
        if n_decode not in self._fwd_cache:
            cfg, backend = self.cfg, self._backend
            node_task = cfg.task == "node"

            if self.cached:
                def fwd(params, fb, cache_state):
                    self._compile_count += 1     # trace-time side effect
                    h, new_state = gnn_lib.sage_forward_frontier_missonly(
                        params, fb, cfg, cache_state, n_decode,
                        backend=backend)
                    logits = (gnn_lib.node_logits(params, h, cfg)
                              if node_task else None)
                    return h, logits, new_state
            else:
                def fwd(params, fb, cache_state):
                    self._compile_count += 1     # trace-time side effect
                    h = gnn_lib.sage_forward_frontier(params, fb, cfg,
                                                      backend=backend)
                    logits = (gnn_lib.node_logits(params, h, cfg)
                              if node_task else None)
                    return h, logits, cache_state
            self._fwd_cache[n_decode] = jax.jit(fwd)
        return self._fwd_cache[n_decode]

    # -- request API -----------------------------------------------------
    def serve(self, node_ids, **_ignored) -> GraphServeResult:
        """Serve one request batch of node ids (≤ ``serve_batch``)."""
        return self.serve_many([node_ids])[0]

    def serve_many(self, requests: Sequence, **_ignored
                   ) -> List[GraphServeResult]:
        """Serve a microbatch of requests with **cross-request frontier
        dedup**: all requests' sampled levels concatenate into one
        ``FrontierBatch``, so a node appearing in several requests decodes
        at most once per microbatch (and not at all when the shared hot
        cache holds it).  Responses are bitwise what sequential ``serve``
        calls on the same requests return — frontiers are content-keyed and
        decode is row-pure, so coalescing is invisible to clients."""
        reqs = [np.asarray(r, np.int32) for r in requests]
        if not reqs:
            return []
        k = len(reqs)
        if k > self.max_coalesce:
            raise ValueError(
                f"microbatch of {k} requests > max_coalesce="
                f"{self.max_coalesce}; raise max_coalesce at engine "
                f"construction (or lower the batcher's max_batch)")
        sizes = [r.shape[0] for r in reqs]
        per_levels = [self._sample_levels(self._pad_request(r))
                      for r in reqs]
        kb = self._request_bucket(k)
        # filler requests repeat request 0's levels: every one of their
        # rows is already in the union, so padding the request axis to its
        # bucket adds ZERO decode work
        per_levels += [per_levels[0]] * (kb - k)
        levels = [np.concatenate([pl[i] for pl in per_levels], axis=0)
                  for i in range(len(per_levels[0]))]
        cap = kb * self.frontier_cap
        fb = FrontierBatch.from_levels(levels, pad_to=self.pad_to, cap=cap)

        if self.cached:
            host_ids = np.asarray(self._cache_state.node_ids)
            valid = np.arange(cap) < int(fb.n_unique)
            perm, n_miss = CachedDecodeBackend.plan_missonly(
                host_ids, np.asarray(fb.unique), valid)
            inv = np.empty_like(perm)
            inv[perm] = np.arange(perm.shape[0], dtype=np.int32)
            fb = FrontierBatch(
                unique=np.asarray(fb.unique)[perm],
                index_maps=tuple(inv[np.asarray(m)] for m in fb.index_maps),
                n_unique=fb.n_unique,
                valid=valid[perm])
            # codes attach AFTER the miss-first permutation so the rows stay
            # aligned with the (permuted) unique frontier
            fb = self._attach_codes(fb)
            n_dec = self._bucket(n_miss, cap)
            h, logits, self._cache_state = self._forward(n_dec)(
                self.params, jax.device_put(fb), self._cache_state)
        else:
            fb = self._attach_codes(fb)
            n_dec = cap
            h, logits, _ = self._forward(-1)(self.params, jax.device_put(fb),
                                             None)

        rows_total = k * self.frontier_cap
        self._requests += k
        self._microbatches += 1
        self._rows_decoded += n_dec
        self._rows_total += rows_total

        h = np.asarray(h)
        logits = None if logits is None else np.asarray(logits)
        out = []
        for i, B in enumerate(sizes):
            lo = i * self.serve_batch
            hi = h[lo:lo + B]
            lg = None if logits is None else logits[lo:lo + B]
            preds = (None if lg is None
                     else lg.argmax(-1).astype(np.int32))
            out.append(GraphServeResult(
                embeddings=hi, logits=lg, predictions=preds,
                rows_decoded=n_dec, rows_total=rows_total,
                batch_requests=k))
        return out

    def embed(self, node_ids) -> np.ndarray:
        """Final hidden representations (B, H) — bitwise identical to
        ``GNNModel.apply`` on ``frontier_for(node_ids)``."""
        return self.serve(node_ids).embeddings

    def predict(self, node_ids) -> np.ndarray:
        """Argmax class per requested node (node-classification task)."""
        res = self.serve(node_ids)
        if res.predictions is None:
            raise ValueError("predict() needs a node-classification config")
        return res.predictions

    def stats(self) -> Dict[str, float]:
        """Cumulative serving counters since construction (or the last
        ``reset()``), plus ``compile_count`` — the number of jit traces the
        engine has paid over its LIFETIME (never reset: benchmarks call
        ``reset()`` after a warmup pass instead of hand-excluding the
        first, compile-paying request, and still see the true compile
        bill)."""
        out = {"requests": self._requests,
               "microbatches": self._microbatches,
               "rows_decoded": self._rows_decoded,
               "rows_total": self._rows_total,
               "rows_decoded_per_request": (
                   self._rows_decoded / max(self._requests, 1)),
               "compile_count": self._compile_count}
        if self.cached:
            st = self._cache_state
            hits, misses = int(st.hits), int(st.misses)
            out.update(hits=hits, misses=misses,
                       hit_rate=hits / max(hits + misses, 1))
        return out

    def reset(self) -> None:
        """Zero the cumulative request/row/hit counters WITHOUT touching
        the cache contents or the jit cache — call after a warmup pass so
        measured stats cover only steady-state traffic.  ``compile_count``
        survives (it is an engine-lifetime cost, not a per-window one)."""
        self._requests = 0
        self._microbatches = 0
        self._rows_decoded = 0
        self._rows_total = 0
        if self._cache_state is not None:
            self._cache_state = dataclasses.replace(
                self._cache_state,
                hits=jnp.zeros_like(self._cache_state.hits),
                misses=jnp.zeros_like(self._cache_state.misses))
