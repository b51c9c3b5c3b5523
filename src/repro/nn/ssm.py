"""Mamba2 mixer via the chunked SSD (state-space duality) form
(Dao & Gu, arXiv:2405.21060).

TPU adaptation: the chunked decomposition is already the MXU-native form —
the intra-chunk term is a masked (L×L) matmul and the inter-chunk term is a
short `lax.scan` over (H, N, P) states; no Pallas kernel is required (the
roofline confirms the layer is matmul-dominated).

Recurrence (per head h, state N, head-channels P):
    S_t = exp(dt_t·A_h) · S_{t-1} + (dt_t · x_t) ⊗ B_t
    y_t = C_t · S_t + D_h · x_t

Shapes: x (B,S,d_inner) viewed as (B,S,H,P); B_t/C_t (B,S,N) shared across
heads (n_groups=1); dt (B,S,H); A (H,) negative.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn import module as nn
from repro.nn.kvcache import SSMCache
from repro.parallel.sharding import logical

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_model: int
    d_state: int = 128          # N
    headdim: int = 64           # P
    expand: int = 2
    conv_width: int = 4
    chunk: int = 128            # SSD chunk length L
    dt_min: float = 0.001
    dt_max: float = 0.1

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        assert self.d_inner % self.headdim == 0
        return self.d_inner // self.headdim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.d_state


def init_ssm(key, cfg: SSMConfig) -> nn.Params:
    """Projections are stored per-component (w_z/w_x/w_b/w_c/w_dt instead of
    one fused w_in, and per-component depthwise convs) so every TP-sharded
    output dim aligns with SSD-head boundaries — the fused layout forced
    GSPMD to re-gather the (2·DI+2·N+H)-wide projection every layer because
    shard boundaries crossed the z/x/B/C/dt splits (measured 2.5x collective
    reduction on zamba2/mamba2).  Depthwise conv over
    the concatenation == concatenation of depthwise convs, so semantics are
    identical to the fused form."""
    ks = nn.split_keys(key, ["z", "x", "b", "c", "dtp", "conv", "dt", "out"])
    D, DI, N, H = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.n_heads
    # dt bias initialised so softplus(dt_bias) spans [dt_min, dt_max]
    u = jax.random.uniform(ks["dt"], (H,))
    dt_init = jnp.exp(u * (jnp.log(cfg.dt_max) - jnp.log(cfg.dt_min)) + jnp.log(cfg.dt_min))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))  # inverse softplus
    kcx, kcbc = jax.random.split(ks["conv"])
    return {
        "w_z": nn.dense_init(ks["z"], (D, DI)),
        "w_x": nn.dense_init(ks["x"], (D, DI)),
        "w_b": nn.dense_init(ks["b"], (D, N)),
        "w_c": nn.dense_init(ks["c"], (D, N)),
        "w_dt": nn.dense_init(ks["dtp"], (D, H)),
        "conv_x_w": nn.dense_init(kcx, (cfg.conv_width, DI),
                                  scale=1.0 / cfg.conv_width**0.5),
        "conv_x_b": jnp.zeros((DI,), jnp.float32),
        "conv_bc_w": nn.dense_init(kcbc, (cfg.conv_width, 2 * N),
                                   scale=1.0 / cfg.conv_width**0.5),
        "conv_bc_b": jnp.zeros((2 * N,), jnp.float32),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "norm_scale": jnp.ones((DI,), jnp.float32),
        "w_out": nn.dense_init(ks["out"], (DI, D)),
    }


def _causal_conv(x: Array, w: Array, b: Array, tail: Optional[Array] = None):
    """Depthwise causal conv along time.  x (B,S,C); w (W,C).  Returns
    (y (B,S,C), new_tail (B,W-1,C))."""
    W = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)       # (B, S+W-1, C)
    y = sum(xp[:, i: i + x.shape[1]] * w[i][None, None].astype(x.dtype)
            for i in range(W))
    new_tail = xp[:, -(W - 1):] if W > 1 else xp[:, :0]
    return y + b.astype(x.dtype), new_tail


def ssd_chunked(X: Array, dt: Array, A: Array, Bc: Array, Cc: Array,
                chunk: int, init_state: Optional[Array] = None
                ) -> Tuple[Array, Array]:
    """Chunked SSD scan.

    X (B,S,H,P) f32; dt (B,S,H) f32 (post-softplus); A (H,) negative;
    Bc/Cc (B,S,N).  Returns (Y (B,S,H,P), final_state (B,H,N,P))."""
    B, S, H, Pd = X.shape
    N = Bc.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L

    la = dt * A[None, None, :]                                   # (B,S,H) ≤ 0
    lar = la.reshape(B, nc, L, H)
    cs = jnp.cumsum(lar, axis=2)                                 # inclusive
    Xd = (X * dt[..., None]).reshape(B, nc, L, H, Pd)
    Br = Bc.reshape(B, nc, L, N)
    Cr = Cc.reshape(B, nc, L, N)

    # ---- intra-chunk (masked matmul) ----
    G = jnp.einsum("bcin,bcjn->bcij", Cr, Br)                    # (B,nc,L,L)
    dec = cs[:, :, :, None, :] - cs[:, :, None, :, :]            # (B,nc,L,L,H) i,j
    causal = jnp.tril(jnp.ones((L, L), bool))
    M = jnp.where(causal[None, None, :, :, None], jnp.exp(dec), 0.0)
    scores = G[..., None] * M                                    # (B,nc,L,L,H)
    Y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores, Xd)

    # ---- chunk states ----
    decay_to_end = jnp.exp(cs[:, :, -1:, :] - cs)                # (B,nc,L,H)
    S_chunk = jnp.einsum("bcln,bclh,bclhp->bchnp", Br, decay_to_end, Xd)

    # ---- inter-chunk scan ----
    T_c = jnp.exp(cs[:, :, -1, :])                               # (B,nc,H)
    if init_state is None:
        init_state = jnp.zeros((B, H, N, Pd), X.dtype)

    def body(s_prev, inp):
        t_c, s_c = inp                                           # (B,H), (B,H,N,P)
        s_new = t_c[:, :, None, None] * s_prev + s_c
        return s_new, s_prev                                     # emit state *before* chunk

    _final, S_prev = jax.lax.scan(
        body, init_state,
        (jnp.moveaxis(T_c, 1, 0), jnp.moveaxis(S_chunk, 1, 0)),
    )
    S_prev = jnp.moveaxis(S_prev, 0, 1)                          # (B,nc,H,N,P)

    Y_inter = jnp.einsum("bcln,bchnp->bclhp", Cr, S_prev) * jnp.exp(cs)[..., None]
    Y = (Y_intra + Y_inter).reshape(B, S, H, Pd)
    return Y, _final


def ssm_forward(
    params: nn.Params,
    x: Array,
    cfg: SSMConfig,
    cache: Optional[SSMCache] = None,
) -> Tuple[Array, Optional[SSMCache]]:
    """Full mixer. x (B,S,D).  cache!=None with S==1 -> single-step decode."""
    Bb, S, D = x.shape
    dt_all = x.dtype
    DI, N = cfg.d_inner, cfg.d_state
    z = x @ params["w_z"].astype(dt_all)
    xc = x @ params["w_x"].astype(dt_all)
    Bc = x @ params["w_b"].astype(dt_all)
    Cc = x @ params["w_c"].astype(dt_all)
    dt = x @ params["w_dt"].astype(dt_all)
    z = logical(z, "batch", "seq", "ssm_inner")
    xc = logical(xc, "batch", "seq", "ssm_inner")
    dt = logical(dt, "batch", "seq", "ssm_heads")

    tail = cache.conv if cache is not None else None
    tail_x = tail[..., :DI] if tail is not None else None
    tail_bc = tail[..., DI:] if tail is not None else None
    conv_x, new_tail_x = _causal_conv(xc, params["conv_x_w"], params["conv_x_b"], tail_x)
    bc = jnp.concatenate([Bc, Cc], axis=-1)
    conv_bc, new_tail_bc = _causal_conv(bc, params["conv_bc_w"], params["conv_bc_b"], tail_bc)
    xc = jax.nn.silu(conv_x)
    xc = logical(xc, "batch", "seq", "ssm_inner")
    conv_bc = jax.nn.silu(conv_bc)
    Bc = conv_bc[..., :N]
    Cc = conv_bc[..., N:]
    new_tail = (jnp.concatenate([new_tail_x, new_tail_bc], axis=-1)
                if cache is not None else None)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"][None, None])
    A = -jnp.exp(params["A_log"])                                 # (H,) < 0
    H, Pd = cfg.n_heads, cfg.headdim
    X = xc.reshape(Bb, S, H, Pd).astype(jnp.float32)
    X = logical(X, "batch", "seq", "ssm_heads", None)
    Bf = Bc.astype(jnp.float32)
    Cf = Cc.astype(jnp.float32)

    if cache is not None and S == 1:
        # single-step recurrence
        a = jnp.exp(dt[:, 0] * A[None, :])                        # (B,H)
        Xd0 = X[:, 0] * dt[:, 0][..., None]                       # (B,H,P)
        state = cache.state * a[:, :, None, None] + jnp.einsum(
            "bn,bhp->bhnp", Bf[:, 0], Xd0)
        y = jnp.einsum("bn,bhnp->bhp", Cf[:, 0], state)[:, None]  # (B,1,H,P)
        new_cache = SSMCache(state=state, conv=new_tail).shard()
    else:
        init = cache.state if cache is not None else None
        y, final_state = ssd_chunked(X, dt, A, Bf, Cf, cfg.chunk, init)
        new_cache = SSMCache(state=final_state, conv=new_tail).shard() if cache is not None else None

    y = y + params["D_skip"].astype(y.dtype)[None, None, :, None] * X
    y = y.reshape(Bb, S, DI).astype(dt_all)

    # gated RMSNorm (mamba2): norm(y * silu(z)) * scale
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    y = (yf * jax.lax.rsqrt(var + 1e-6) * params["norm_scale"]).astype(dt_all)

    out = y @ params["w_out"].astype(dt_all)
    return out, new_cache


def ssd_reference(X, dt, A, Bc, Cc):
    """Naive O(S) per-step recurrence oracle (tests)."""
    B, S, H, Pd = X.shape
    N = Bc.shape[-1]
    state = jnp.zeros((B, H, N, Pd), jnp.float32)
    ys = []
    for t in range(S):
        a = jnp.exp(dt[:, t] * A[None, :])                        # (B,H)
        state = state * a[:, :, None, None] + jnp.einsum(
            "bn,bhp->bhnp", Bc[:, t], X[:, t] * dt[:, t][..., None])
        ys.append(jnp.einsum("bn,bhnp->bhp", Cc[:, t], state))
    return jnp.stack(ys, axis=1)
