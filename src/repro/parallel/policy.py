"""Concrete sharding policies: params (TP ⊗ FSDP), caches, batches.

Policy:
  * 2-D weights (stacked (L, D_in, D_out) or flat): TP-shard the
    "parallel" dim over ``model`` — column-parallel for in-projections
    (w_gate/w_up/wq/wk/wv/head), row-parallel for out-projections
    (w_down/wo) — and FSDP-shard the other dim over ``data`` (ZeRO-style;
    XLA all-gathers per scan step and reduce-scatters grads).
  * attention weights only TP-shard when the *head count* divides the model
    axis (never split inside a head); granite (24H) and qwen2-vl (28H) fall
    back to FSDP-only attention.
  * MoE experts: E over ``model`` (EP ≡ TP axis), D over ``data``.
  * embedding: dense table vocab-parallel; compressed codes + decoder
    replicated (the decoder is ≤ 10 MB — that IS the paper's point).
  * KV caches: kv_heads over ``model`` when divisible, else the cache
    *sequence* dim takes ``model`` (flash-decoding style partial-softmax
    sharding); batch over (pod, data); batch==1 long-context gives the
    sequence dim the data axis too (SP decode).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import LMConfig


@dataclasses.dataclass(frozen=True)
class Strategy:
    """Distribution strategy knobs (the §Perf hillclimb surface).

    tp_attn/tp_ffn/tp_vocab: Megatron-style tensor parallelism over the
      ``model`` axis for the respective weights + activations.
    dp_over_model: fold the model axis into data parallelism (batch shards
      over pod×data×model) — the right call for small models where TP
      all-reduces dominate (e.g. qwen1.5-0.5b).
    fsdp: ZeRO-style parameter/optimizer sharding over the data axis.
    seq_shard_activations: sequence-shard the residual stream over `model`
      between blocks (Megatron sequence parallelism; pairs with tp).
    """
    tp_attn: bool = True
    tp_ffn: bool = True
    tp_vocab: bool = True
    dp_over_model: bool = False
    fsdp: bool = True
    seq_shard_activations: bool = False

    def batch_mesh_axes(self, mesh: Mesh) -> Tuple[str, ...]:
        axes = [a for a in ("pod", "data") if a in mesh.shape]
        if self.dp_over_model and "model" in mesh.shape:
            axes.append("model")
        return tuple(axes)


DEFAULT_STRATEGY = Strategy()


def rules_for(strategy: Strategy, mesh: Mesh):
    """ShardingRules (activation annotations) matching a Strategy."""
    from repro.parallel.sharding import DEFAULT_RULES, ShardingRules
    rules = dict(DEFAULT_RULES.rules)
    rules["batch"] = strategy.batch_mesh_axes(mesh)
    if not strategy.tp_attn or strategy.dp_over_model:
        rules["heads"] = None
        rules["kv_heads"] = None
    if not strategy.tp_ffn or strategy.dp_over_model:
        rules["d_ff"] = None
        rules["experts"] = None
        rules["ssm_heads"] = None
        rules["ssm_inner"] = None
    if not strategy.tp_vocab or strategy.dp_over_model:
        rules["vocab"] = None
    if strategy.seq_shard_activations:
        rules["seq"] = "model" if not strategy.dp_over_model else None
    return ShardingRules(rules=rules)


def _axsize(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    axes = (axes,) if isinstance(axes, str) else axes
    s = 1
    for a in axes:
        s *= mesh.shape.get(a, 1)
    return s


def _fits(dim: int, mesh: Mesh, axes) -> bool:
    axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
    if not all(a in mesh.shape for a in axes):
        return False
    return dim % _axsize(mesh, axes) == 0


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

_COL_PAR = re.compile(r"(w_gate|w_up|wq|wk|wv|head)$")
_ROW_PAR = re.compile(r"(w_down|wo)$")


def _leaf_spec(path_keys, leaf, cfg: LMConfig, mesh: Mesh,
               strategy: Strategy = DEFAULT_STRATEGY) -> P:
    path = "/".join(path_keys)
    shape = leaf.shape
    ndim = len(shape)
    model_sz = mesh.shape.get("model", 1)
    tp_attn = strategy.tp_attn and not strategy.dp_over_model
    tp_ffn = strategy.tp_ffn and not strategy.dp_over_model
    tp_vocab = strategy.tp_vocab and not strategy.dp_over_model
    if strategy.dp_over_model:
        fsdp_axes = (("pod", "data"), ("data",), ("model",))
    else:
        fsdp_axes = (("pod", "data"), ("data",))
    def fsdp_axis(dim):
        if not strategy.fsdp:
            return None
        for ax in fsdp_axes:
            if all(a in mesh.shape for a in ax) and _fits(dim, mesh, ax):
                return ax[0] if len(ax) == 1 else ax
        return None

    # ---- embedding subtree ----
    if "embed/" in path or path.startswith("embed"):
        if path.endswith("table"):  # dense NC table: vocab-parallel + FSDP
            spec = [None] * ndim
            if tp_vocab and _fits(shape[0], mesh, "model"):
                spec[0] = "model"
            if ndim > 1 and strategy.fsdp and _fits(shape[1], mesh, "data"):
                spec[1] = "data"
            return P(*spec)
        return P(*([None] * ndim))     # codes + decoder: replicated (tiny)

    # ---- attention projections: only split whole heads ----
    is_attn = "/attn/" in path or path.endswith("attn")
    leafname = path_keys[-2] if path_keys[-1] in ("w", "b") else path_keys[-1]
    if is_attn and path_keys[-1] == "w":
        n_heads = cfg.n_heads if leafname in ("wq", "wo") else cfg.n_kv_heads
        heads_ok = tp_attn and n_heads and n_heads % model_sz == 0
        spec = [None] * ndim
        if leafname in ("wq", "wk", "wv"):
            if heads_ok and _fits(shape[-1], mesh, "model"):
                spec[-1] = "model"
            spec[-2] = fsdp_axis(shape[-2])
        else:  # wo: row-parallel
            if heads_ok and _fits(shape[-2], mesh, "model"):
                spec[-2] = "model"
            spec[-1] = fsdp_axis(shape[-1])
        if spec[-1] == spec[-2] and spec[-1] is not None:
            spec[-2] = None
        return P(*spec)
    if is_attn and path_keys[-1] == "b":
        return P(*([None] * ndim))

    # ---- MoE experts: (L, E, D, F) / (L, E, F, D); router (L, D, E) ----
    if "/moe/" in path:
        spec = [None] * ndim
        if leafname in ("w_gate", "w_up", "w_down") and ndim >= 3:
            e_dim = ndim - 3
            if tp_ffn and _fits(shape[e_dim], mesh, "model"):
                spec[e_dim] = "model"
            d_dim = ndim - 2 if leafname != "w_down" else ndim - 1
            ax = fsdp_axis(shape[d_dim])
            if ax is not None and ax != spec[e_dim]:
                spec[d_dim] = ax
        elif leafname == "router":
            spec[-2] = fsdp_axis(shape[-2])
        return P(*spec)

    # ---- generic 2D+ weights ----
    if leafname in ("w_b", "w_c"):   # SSD B/C projections: N stays whole
        spec = [None] * ndim
        spec[-2] = fsdp_axis(shape[-2])
        return P(*spec)
    if ndim >= 2 and path_keys[-1].startswith("w") or leafname in ("head",):
        spec = [None] * ndim
        if _COL_PAR.search(leafname or "") or leafname in ("w_in", "head"):
            col, row = ndim - 1, ndim - 2
        elif _ROW_PAR.search(leafname or "") or leafname == "w_out":
            col, row = ndim - 2, ndim - 1
        else:
            col, row = ndim - 1, ndim - 2
        if ndim >= 2:
            tp_here = tp_vocab if leafname == "head" else tp_ffn
            if tp_here and _fits(shape[col], mesh, "model"):
                spec[col] = "model"
            ax = fsdp_axis(shape[row])
            if ax is not None and ax != spec[col]:
                spec[row] = ax
            return P(*spec)

    # ---- everything else (norms, biases, scalars, conv) ----
    return P(*([None] * len(shape)))


def params_shardings(cfg: LMConfig, params_tree, mesh: Mesh,
                     strategy: Strategy = DEFAULT_STRATEGY):
    """Maps an (abstract) param pytree to NamedShardings."""
    def fn(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        return NamedSharding(mesh, _leaf_spec(keys, leaf, cfg, mesh, strategy))
    return jax.tree_util.tree_map_with_path(fn, params_tree)


def state_shardings(cfg: LMConfig, state_tree, mesh: Mesh,
                    strategy: Strategy = DEFAULT_STRATEGY):
    """Shardings for {'params', 'opt': {'step','mu','nu'}, 'step'} — the
    Adam moments inherit their param's sharding (ZeRO: optimizer state is
    sharded at least as much as the weights)."""
    pshard = params_shardings(cfg, state_tree["params"], mesh, strategy)
    def moment_shard(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        return NamedSharding(mesh, _leaf_spec(keys, leaf, cfg, mesh, strategy))
    return {
        "params": pshard,
        "opt": {
            "step": NamedSharding(mesh, P()),
            "mu": jax.tree_util.tree_map_with_path(moment_shard, state_tree["opt"]["mu"]),
            "nu": jax.tree_util.tree_map_with_path(moment_shard, state_tree["opt"]["nu"]),
        },
        "step": NamedSharding(mesh, P()),
    }


# ---------------------------------------------------------------------------
# batches & caches
# ---------------------------------------------------------------------------

def batch_shardings(batch_tree, mesh: Mesh,
                    strategy: Strategy = DEFAULT_STRATEGY):
    """Token batches: leading dim over the DP axes; positions (3,B,S) on
    dim 1; everything else replicated on trailing dims."""
    baxes = strategy.batch_mesh_axes(mesh)

    def fn(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        b_dim = 1 if keys and keys[-1] == "positions" and len(leaf.shape) == 3 else 0
        spec = [None] * len(leaf.shape)
        ax = tuple(baxes)
        while ax and not _fits(leaf.shape[b_dim] if leaf.shape else 0, mesh, ax):
            ax = ax[1:]   # shed leading axes (see sharding._spec_for)
        if leaf.shape and ax:
            spec[b_dim] = ax if len(ax) > 1 else ax[0]
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(fn, batch_tree)


def frontier_batch_shardings(batch, mesh: Mesh, axis: Optional[str] = None):
    """Shardings for a streaming-engine batch dict ({"frontier":
    FrontierBatch, "labels": ...}): the frontier's row-parallel leaves
    (``unique`` ids and the ``valid`` mask) go on the data axis — shard s's
    block of a ``ShardedSageBatchSource`` stack lands on device s — while
    index maps, labels and counters stay replicated (they feed the
    post-all_gather combine, which every device runs on the full batch)."""
    from repro.graph.sampler import FrontierBatch
    from repro.parallel.sharding import data_axis

    axis = axis or data_axis(mesh)
    k = mesh.shape[axis]
    rep = NamedSharding(mesh, P())

    def rows(leaf):
        if leaf.shape and leaf.shape[0] % k == 0:
            return NamedSharding(mesh, P(axis))
        return rep

    def fn(v):
        if isinstance(v, FrontierBatch):
            # OwnerPlan leaves are stacked along the shard axis (leading dim
            # n_shards), so each shard's slice of the routing lands with its
            # frontier rows
            return FrontierBatch(
                unique=rows(v.unique),
                index_maps=tuple(rep for _ in v.index_maps),
                n_unique=rep,
                valid=None if v.valid is None else rows(v.valid),
                plan=None if v.plan is None else jax.tree.map(rows, v.plan),
                n_decode=v.n_decode,
                # batch-carried packed code rows (codes_placement="host"):
                # row-aligned with ``unique``, so they split the same way
                codes=None if v.codes is None else rows(v.codes))
        return jax.tree.map(lambda _: rep, v)

    return {key: fn(v) for key, v in batch.items()}


def make_frontier_placement(mesh: Mesh, axis: Optional[str] = None):
    """``device`` callable for ``PrefetchIterator``: the producer thread
    places each batch straight into the sharded layout above, so per-shard
    frontier rows never bounce through a single device."""
    def place(batch):
        return jax.device_put(batch, frontier_batch_shardings(batch, mesh, axis))
    return place


def kv_seq_mesh_axis(cfg: LMConfig, mesh: Mesh,
                     strategy: Strategy = DEFAULT_STRATEGY,
                     batch: int = 0):
    """Mesh axis carrying the KV-cache sequence dim (None if kv_heads take
    the model axis and batch takes data) — must match cache_shardings_policy
    so attention-score constraints line up with the cache layout."""
    model_sz = mesh.shape.get("model", 1)
    kv_model_ok = (cfg.n_kv_heads and cfg.n_kv_heads % model_sz == 0
                   and not strategy.dp_over_model)
    baxes = strategy.batch_mesh_axes(mesh)
    batch_shardable = batch > 1 and _fits(batch, mesh, baxes)
    if kv_model_ok:
        return None if batch_shardable else "data"
    return "model" if batch_shardable else tuple(
        a for a in ("data", "model") if a in mesh.shape)


def cache_shardings_policy(cfg: LMConfig, cache_tree, mesh: Mesh,
                           strategy: Strategy = DEFAULT_STRATEGY):
    """LMCache shardings (see module docstring for the kv_seq fallback)."""
    baxes = strategy.batch_mesh_axes(mesh)
    model_sz = mesh.shape.get("model", 1)
    kv_model_ok = (cfg.n_kv_heads and cfg.n_kv_heads % model_sz == 0
                   and not strategy.dp_over_model)

    def kv_spec(leaf):
        sites, B, S, K, Dh = leaf.shape
        spec = [None] * 5
        used_data = False
        if _fits(B, mesh, baxes) and B > 1:
            spec[1] = baxes if len(baxes) > 1 else baxes[0]
            used_data = True
        if kv_model_ok:
            spec[3] = "model"
            if not used_data and _fits(S, mesh, "data"):
                spec[2] = "data"      # SP decode (batch==1 long context)
        else:
            seq_axes = ("model",) if used_data else tuple(
                a for a in ("data", "model") if a in mesh.shape)
            seq_axes = tuple(a for a in seq_axes if a in mesh.shape)
            if seq_axes and _fits(S, mesh, seq_axes):
                spec[2] = seq_axes if len(seq_axes) > 1 else seq_axes[0]
        return NamedSharding(mesh, P(*spec))

    def ssm_spec(leaf):
        L, B, H, N, Pd = leaf.shape
        spec = [None] * 5
        if _fits(B, mesh, baxes) and B > 1:
            spec[1] = baxes if len(baxes) > 1 else baxes[0]
        if _fits(H, mesh, "model"):
            spec[2] = "model"
        return NamedSharding(mesh, P(*spec))

    def conv_spec(leaf):
        L, B, W, C = leaf.shape
        spec = [None] * 4
        if _fits(B, mesh, baxes) and B > 1:
            spec[1] = baxes if len(baxes) > 1 else baxes[0]
        return NamedSharding(mesh, P(*spec))

    from repro.models.lm import LMCache
    return LMCache(
        pos=NamedSharding(mesh, P()),
        kv_k=kv_spec(cache_tree.kv_k) if cache_tree.kv_k is not None else None,
        kv_v=kv_spec(cache_tree.kv_v) if cache_tree.kv_v is not None else None,
        ssm_state=ssm_spec(cache_tree.ssm_state) if cache_tree.ssm_state is not None else None,
        conv=conv_spec(cache_tree.conv) if cache_tree.conv is not None else None,
    )
