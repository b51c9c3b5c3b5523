"""Train / prefill / serve step factories.

``make_train_step(cfg, ocfg)`` returns a donated-state pjit-able function
  (state, batch) -> (state, metrics)
with: bf16 activations, f32 master params + Adam moments, allow_int grads
(packed code buffers ride along untouched), optional global-norm clip, and
LR schedule by step counter.

``make_prefill_step`` / ``make_serve_step`` cover the inference shapes:
prefill lowers the full-sequence forward that builds a cache; serve decodes
one token against the cache (the dry-run's decode_* / long_* cells).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import GNNConfig, LMConfig
from repro.models.lm import LMCache, init_cache, lm_forward, lm_loss
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.schedule import linear_warmup_cosine


@dataclasses.dataclass(frozen=True)
class TrainHyper:
    optimizer: AdamWConfig = dataclasses.field(default_factory=lambda: AdamWConfig(
        lr=1e-3, weight_decay=0.01, clip_norm=1.0))
    warmup_steps: int = 100
    total_steps: int = 10_000
    microbatches: int = 1      # gradient accumulation (activation-memory knob)


def init_train_state(key, cfg: LMConfig, codes=None, aux=None,
                     moments_dtype=jnp.float32) -> Dict[str, Any]:
    from repro.models.lm import init_lm
    params = init_lm(key, cfg, codes=codes, aux=aux)
    return {"params": params, "opt": adamw_init(params, moments_dtype),
            "step": jnp.zeros((), jnp.int32)}


def _grad_zeros(params):
    from repro.nn.module import trainable_mask
    mask = trainable_mask(params)
    return jax.tree.map(
        lambda p, m: jnp.zeros_like(p, dtype=jnp.float32) if m else p, params, mask)


def _grad_add(acc, g, params):
    from repro.nn.module import trainable_mask
    mask = trainable_mask(params)
    return jax.tree.map(
        lambda a, b, m: a + b.astype(jnp.float32) if m else a, acc, g, mask)


def _grad_scale(g, s, params):
    from repro.nn.module import trainable_mask
    mask = trainable_mask(params)
    return jax.tree.map(lambda x, m: x * s if m else x, g, mask)


def make_train_step(cfg: LMConfig, hyper: Optional[TrainHyper] = None) -> Callable:
    hyper = hyper or TrainHyper()
    k = max(1, hyper.microbatches)

    def train_step(state, batch):
        params = state["params"]
        if k == 1:
            loss, grads = jax.value_and_grad(
                lambda p: lm_loss(p, batch, cfg), allow_int=True)(params)
        else:
            # gradient accumulation over k microbatches (scan keeps one
            # microbatch's activations alive at a time)
            def to_mb(path, x):
                is_positions = any(getattr(p, "key", None) == "positions" for p in path)
                if is_positions:  # (3, B, S) -> (k, 3, B/k, S)
                    return x.reshape((x.shape[0], k, x.shape[1] // k) + x.shape[2:]).swapaxes(0, 1)
                return x.reshape((k, x.shape[0] // k) + x.shape[1:])
            mb = jax.tree_util.tree_map_with_path(to_mb, batch)

            def body(carry, mbatch):
                acc, loss_sum = carry
                loss, g = jax.value_and_grad(
                    lambda p: lm_loss(p, mbatch, cfg), allow_int=True)(params)
                return (_grad_add(acc, g, params), loss_sum + loss), None

            (gsum, loss_sum), _ = jax.lax.scan(
                body, (_grad_zeros(params), jnp.zeros((), jnp.float32)), mb,
                unroll=True if cfg.unroll_scan else 1)
            grads = _grad_scale(gsum, 1.0 / k, params)
            loss = loss_sum / k
        lr_scale = linear_warmup_cosine(
            state["step"], hyper.warmup_steps, hyper.total_steps)
        params, opt = adamw_update(params, grads, state["opt"],
                                   hyper.optimizer, lr_scale=lr_scale)
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        metrics = {"loss": loss, "lr_scale": lr_scale}
        return new_state, metrics

    return train_step


def init_gnn_train_state(key, cfg: GNNConfig, codes=None, aux=None) -> Dict[str, Any]:
    """Train state for the graph engine (same layout as the LM state).

    When the embedding config enables the hot-node decode cache
    (``cache_capacity > 0`` on a compressed kind) the state carries a
    ``"cache"`` entry (a ``core.backend.CacheState`` pytree) that the train
    step threads through and version-bumps after each optimizer update."""
    from repro.graph.engine import GNNModel
    params = GNNModel(cfg).init(key, codes=codes, aux=aux)
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    ecfg = cfg.embedding_config()
    if ecfg.is_compressed and ecfg.cache_capacity > 0:
        from repro.core.backend import CacheState
        state["cache"] = CacheState.create(
            ecfg.cache_capacity, cfg.d_e, jnp.dtype(cfg.compute_dtype))
    return state


def make_gnn_train_step(cfg: GNNConfig,
                        opt: Optional[AdamWConfig] = None,
                        interpret: bool = False,
                        mesh=None,
                        duplication: Optional[float] = None) -> Callable:
    """Node-classification train step over the unified ``GNNModel`` API.

    The batch is a dict from an engine batch source: either
    {"frontier": FrontierBatch, "labels": y} (dedup-decode path) or
    {"levels": tuple, "labels": y} (naive reference path) — the model
    dispatches on the batch view, so the step function is family-agnostic.

    The embedding decode runs on the backend named by the config's
    ``lookup_impl`` and gradients flow through that backend's (custom) VJP —
    for ``pallas`` the fused kernel forward pairs with the XLA scatter-add
    backward in ``kernels.hash_decode.ops``.  If the state carries a
    ``"cache"`` entry, the frontier decode is served through the hot-node
    cache, the updated cache rides along in the state, and its version is
    bumped after the optimizer touches the decoder parameters (that bump is
    what invalidates cached embeddings once they exceed the staleness
    budget).

    ``mesh`` makes the step trace under that sharding context: with
    ``lookup_impl="sharded"`` (or ``"auto"``) the frontier decode of a
    ``ShardedSageBatchSource`` batch runs shard-local on the mesh's data
    axis — the whole N-shard switch is this argument plus the batch source's
    ``n_shards``.  ``duplication`` (measured frontier_rows/unique_rows, from
    ``ShardedSageBatchSource.measure_duplication``) lets ``lookup_impl=
    "auto"`` prefer the owner-computes decode past the duplication
    threshold; batches carrying an ``OwnerPlan`` then dedup hub rows across
    shards.
    """
    from contextlib import nullcontext

    from repro.core.backend import CachedDecodeBackend
    from repro.graph.engine import GNNModel, batch_view
    from repro.models import gnn
    from repro.parallel.sharding import use_sharding
    _ctx = (lambda: use_sharding(mesh)) if mesh is not None else nullcontext
    with _ctx():
        model = GNNModel(cfg, interpret=interpret, duplication=duplication)
    ocfg = opt or AdamWConfig(lr=1e-2, weight_decay=0.0)

    def train_step(state, batch):
        with _ctx():
            return _train_step(state, batch)

    def _train_step(state, batch):
        view = batch_view(batch)
        cached = "cache" in state

        def _logits(p, h):
            # full-graph batches carry the training-node ids: the model
            # returns hidden for ALL nodes and the loss reads the subset
            logits = model.logits(p, h)
            if "ids" in batch:
                logits = logits[batch["ids"]]
            return logits

        if cached:
            def loss_fn(p, c):
                h, new_c = model.apply_cached(p, view, c)
                with jax.named_scope("head_loss"):
                    return gnn.node_loss(_logits(p, h), batch["labels"]), new_c
            (loss, new_cache), g = jax.value_and_grad(
                loss_fn, has_aux=True, allow_int=True)(
                    state["params"], state["cache"])
        else:
            def loss_fn(p):
                h = model.apply(p, view)
                with jax.named_scope("head_loss"):
                    return gnn.node_loss(_logits(p, h), batch["labels"])
            loss, g = jax.value_and_grad(loss_fn, allow_int=True)(state["params"])

        with jax.named_scope("adamw"):
            params, opt_state = adamw_update(state["params"], g, state["opt"], ocfg)
        new_state = {"params": params, "opt": opt_state, "step": state["step"] + 1}
        metrics = {"loss": loss}
        if cached:
            new_cache = CachedDecodeBackend.bump_version(new_cache)
            new_state["cache"] = new_cache
            metrics["cache_hits"] = new_cache.hits
            metrics["cache_misses"] = new_cache.misses
        return new_state, metrics

    return train_step


def make_prefill_step(cfg: LMConfig, s_max: int) -> Callable:
    """(params, tokens[, positions]) -> (last_logits, cache)."""
    def prefill_step(params, batch):
        B = batch["tokens"].shape[0]
        cache = init_cache(cfg, B, s_max, jnp.dtype(cfg.compute_dtype))
        logits, cache = lm_forward(params, batch["tokens"], cfg, cache=cache,
                                   positions=batch.get("positions"))
        return logits[:, -1], cache
    return prefill_step


def make_serve_step(cfg: LMConfig) -> Callable:
    """(params, cache, tokens (B,1[,nq])) -> (logits, cache) — one decode step."""
    def serve_step(params, cache: LMCache, batch):
        logits, cache = lm_forward(params, batch["tokens"], cfg, cache=cache,
                                   positions=batch.get("positions"))
        return logits[:, -1], cache
    return serve_step
