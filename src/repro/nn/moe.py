"""Top-k Mixture-of-Experts FFN.

Dispatch is sort-based: flatten (token, slot) assignments, argsort by expert
id, run grouped GEMMs with ``jax.lax.ragged_dot`` (verified CPU lowering +
grads), scatter-add back weighted by router probabilities.

Two execution paths:
  * ``moe_ffn``      — single-shard path, no token dropping (oracle + tests).
  * ``moe_ffn_ep``   — expert-parallel path under ``shard_map``: experts are
    sharded over the "model" mesh axis; each shard processes only the
    assignments routed to its local experts, bounded by a capacity factor
    (GShard-style dropping), then the partial outputs are psum-combined.
    Per-shard FLOPs scale as top_k/ep_degree — true EP compute scaling.

Expert-count padding: if n_experts is not divisible by the EP degree the
config pads with dummy experts whose router logits are masked to -inf
(granite's 40 experts on a 16-way model axis -> 48).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn import module as nn
from repro.parallel.sharding import current_mesh, current_rules, logical

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                  # per-expert hidden
    n_experts: int             # logical experts
    top_k: int
    n_experts_padded: int = 0  # 0 => n_experts
    capacity_factor: float = 1.25
    act: str = "swiglu"
    router_dtype: str = "float32"
    impl: str = "ep"           # "ep" (shard_map EP) | "dense" (see moe_dense_ffn)

    @property
    def e_pad(self) -> int:
        return self.n_experts_padded or self.n_experts


def init_moe(key, cfg: MoEConfig) -> nn.Params:
    ks = nn.split_keys(key, ["router", "gate", "up", "down"])
    E, D, F = cfg.e_pad, cfg.d_model, cfg.d_ff
    return {
        "router": nn.dense_init(ks["router"], (D, E)),
        "w_gate": nn.dense_init(ks["gate"], (E, D, F)),
        "w_up": nn.dense_init(ks["up"], (E, D, F)),
        "w_down": nn.dense_init(ks["down"], (E, F, D)),
    }


def _topk_argmax(probs: Array, k: int):
    """top-k as k rounds of argmax+mask.  Equivalent to lax.top_k (up to tie
    order) but partitions trivially along the token dim — lax.top_k made
    GSPMD all-gather the full (T, E) router probs (measured 18 GB/chip/step
    on granite train_4k; EXPERIMENTS.md §Perf H4c)."""
    ws, ids = [], []
    p = probs
    for _ in range(k):
        i = jnp.argmax(p, axis=-1)
        w = jnp.max(p, axis=-1)
        ws.append(w)
        ids.append(i.astype(jnp.int32))
        p = p * (1.0 - jax.nn.one_hot(i, p.shape[-1], dtype=p.dtype))
    return jnp.stack(ws, -1), jnp.stack(ids, -1)


def router_probs(params, x: Array, cfg: MoEConfig):
    """x (T, D) -> (weights (T, k), idx (T, k)).  Softmax over real experts,
    padding experts masked; top-k renormalised."""
    logits = (x.astype(jnp.dtype(cfg.router_dtype)) @
              params["router"].astype(jnp.dtype(cfg.router_dtype)))
    if cfg.e_pad != cfg.n_experts:
        pad_mask = jnp.arange(cfg.e_pad) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = _topk_argmax(probs, cfg.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w.astype(x.dtype), idx.astype(jnp.int32)


def _grouped_ffn(xs: Array, group_sizes: Array, params, cfg: MoEConfig,
                 pad_zero_expert: bool = False) -> Array:
    """xs (R, D) rows sorted by expert; group_sizes (E[+1],)."""
    wg, wu, wd = params["w_gate"], params["w_up"], params["w_down"]
    dt = xs.dtype
    wg, wu, wd = wg.astype(dt), wu.astype(dt), wd.astype(dt)
    if pad_zero_expert:
        wg = jnp.concatenate([wg, jnp.zeros_like(wg[:1])], 0)
        wu = jnp.concatenate([wu, jnp.zeros_like(wu[:1])], 0)
        wd = jnp.concatenate([wd, jnp.zeros_like(wd[:1])], 0)
    if cfg.act == "swiglu":
        g = jax.lax.ragged_dot(xs, wg, group_sizes)
        u = jax.lax.ragged_dot(xs, wu, group_sizes)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(jax.lax.ragged_dot(xs, wu, group_sizes))
    return jax.lax.ragged_dot(h, wd, group_sizes)


def moe_ffn(params, x: Array, cfg: MoEConfig) -> Array:
    """Single-shard MoE.  x (T, D) -> (T, D).  No dropping."""
    T, D = x.shape
    k = cfg.top_k
    w, idx = router_probs(params, x, cfg)
    e_flat = idx.reshape(-1)                            # (T*k,)
    t_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    w_flat = w.reshape(-1)
    order = jnp.argsort(e_flat)
    xs = jnp.take(x, t_flat[order], axis=0)
    group_sizes = jnp.bincount(e_flat, length=cfg.e_pad).astype(jnp.int32)
    ys = _grouped_ffn(xs, group_sizes, params, cfg)
    out = jnp.zeros_like(x)
    return out.at[t_flat[order]].add(ys * w_flat[order][:, None])


def _dense_expert_ffn(xs: Array, wg_e, wu_e, wd_e, cfg: MoEConfig) -> Array:
    """Plain dense FFN of ONE expert over its capacity slice (rows, D)."""
    if cfg.act == "swiglu":
        h = jax.nn.silu(xs @ wg_e) * (xs @ wu_e)
    else:
        h = jax.nn.gelu(xs @ wu_e)
    return h @ wd_e


def _ep_local_ffn(x, w, idx, params_local, cfg: MoEConfig, e_local: int,
                  capacity: int, axis_name: str) -> Array:
    """Runs on one EP shard inside shard_map.  params_local experts are the
    shard's slice; global expert range is [lo, lo + e_local).

    Per-expert capacity dropping (GShard-style): rows are sorted by local
    expert id; each local expert processes a fixed-size window of
    ``cap_e = capacity // e_local`` rows starting at its group offset (a
    dynamic_slice), as one DENSE matmul.  This keeps per-shard FLOPs at
    exactly cap_e·e_local·D·F on every backend — unlike ragged_dot, whose
    XLA:CPU reference lowering densifies over all groups (measured 16-38x
    FLOP inflation on dbrx; EXPERIMENTS.md §Perf).
    """
    T = x.shape[0]
    k = cfg.top_k
    dt = x.dtype
    shard = jax.lax.axis_index(axis_name)
    lo = shard * e_local
    e_flat = idx.reshape(-1) - lo                       # local ids
    t_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    w_flat = w.reshape(-1)
    local = (e_flat >= 0) & (e_flat < e_local)
    e_key = jnp.where(local, e_flat, e_local)           # non-local -> dummy
    order = jnp.argsort(e_key)
    e_s = e_key[order]
    t_s = t_flat[order]
    w_s = jnp.where(e_s < e_local, w_flat[order], 0.0)

    cap_e = max(1, capacity // e_local)
    group_sizes = jnp.bincount(e_s, length=e_local + 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(group_sizes)[:-1]])
    out = jnp.zeros_like(x)
    for e in range(e_local):                            # e_local is tiny: unrolled
        start = offsets[e]
        rows_t = jax.lax.dynamic_slice(t_s, (start,), (cap_e,))
        rows_w = jax.lax.dynamic_slice(w_s, (start,), (cap_e,))
        rows_e = jax.lax.dynamic_slice(e_s, (start,), (cap_e,))
        valid = rows_e == e                             # window may overrun
        xs = jnp.take(x, rows_t, axis=0)
        ys = _dense_expert_ffn(
            xs, params_local["w_gate"][e].astype(dt),
            params_local["w_up"][e].astype(dt),
            params_local["w_down"][e].astype(dt), cfg)
        out = out.at[rows_t].add(ys * (rows_w * valid)[:, None])
    return jax.lax.psum(out, axis_name)


def moe_dense_ffn(params, x: Array, cfg: MoEConfig) -> Array:
    """Dense-dispatch MoE: every expert runs on every token; router weights
    zero the non-selected ones.  FLOPs are E/top_k x the sparse ideal, which
    is the RIGHT trade for fine-grained experts under pure data parallelism
    (granite: E=40, d_ff=512 — expert GEMMs are too small to win from
    sort-based dispatch, and no EP axis is available under dp_over_model).
    Tokens stay batch-sharded; weights replicated; no collectives at all."""
    E = cfg.n_experts
    dt = x.dtype
    w, idx = router_probs(params, x, cfg)
    T = x.shape[0]
    wfull = jnp.zeros((T, E), dt).at[jnp.arange(T)[:, None], idx].add(w)
    wg = params["w_gate"][:E].astype(dt)
    wu = params["w_up"][:E].astype(dt)
    wd = params["w_down"][:E].astype(dt)
    if cfg.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("td,edf->tef", x, wg)) * jnp.einsum(
            "td,edf->tef", x, wu)
    else:
        h = jax.nn.gelu(jnp.einsum("td,edf->tef", x, wu))
    return jnp.einsum("tef,te,efd->td", h, wfull, wd)


def moe_ffn_ep(params, x: Array, cfg: MoEConfig) -> Array:
    """Expert-parallel MoE.  Falls back to ``moe_ffn`` without a mesh or when
    the model axis cannot partition the experts."""
    mesh = current_mesh()
    if mesh is None:
        return moe_ffn(params, x, cfg)
    model_axes = current_rules().resolve("experts")
    if model_axes is None:
        return moe_ffn(params, x, cfg)
    if isinstance(model_axes, str):
        model_axes = (model_axes,)
    ep = 1
    for a in model_axes:
        ep *= mesh.shape[a]
    if ep == 1 or cfg.e_pad % ep != 0:
        return moe_ffn(params, x, cfg)
    axis_name = model_axes[0] if len(model_axes) == 1 else model_axes
    e_local = cfg.e_pad // ep

    T = x.shape[0]
    w, idx = router_probs(params, x, cfg)

    batch_axes = current_rules().resolve("batch") or ()
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    bspec = batch_axes[0] if len(batch_axes) == 1 else (batch_axes or None)
    tokens_spec = bspec if (batch_axes and T % _size(mesh, batch_axes) == 0) else None

    # per-shard capacity against SHARD-LOCAL rows: each shard sees T_local·k
    # assignments of which ~e_local/E are for its experts
    t_local = T // _size(mesh, batch_axes) if tokens_spec is not None else T
    rows_local = t_local * cfg.top_k
    capacity = int(rows_local * e_local / cfg.e_pad * cfg.capacity_factor) + 1
    capacity = min(capacity, rows_local)

    fn = partial(_ep_local_ffn, cfg=cfg, e_local=e_local,
                 capacity=capacity, axis_name=axis_name)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(tokens_spec, None), P(tokens_spec, None),
                  P(tokens_spec, None),
                  {"w_gate": P(model_axes if len(model_axes) > 1 else model_axes[0], None, None),
                   "w_up": P(model_axes if len(model_axes) > 1 else model_axes[0], None, None),
                   "w_down": P(model_axes if len(model_axes) > 1 else model_axes[0], None, None)}),
        out_specs=P(tokens_spec, None),
        check_vma=False,
    )(x, w, idx, {k2: params[k2] for k2 in ("w_gate", "w_up", "w_down")})


def _size(mesh, axes) -> int:
    s = 1
    for a in axes:
        s *= mesh.shape[a]
    return s
