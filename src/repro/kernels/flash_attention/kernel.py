"""Pallas TPU kernel: FlashAttention-style blocked online-softmax attention
with native GQA (kv panels indexed by q-head // group via the BlockSpec
index map — no KV replication in HBM).

Grid: (B, H, Sq/block_q, Skv/block_k); the last axis is 'arbitrary'
(sequential) and carries the online-softmax state in VMEM scratch:
  m (block_q,)   running row max
  l (block_q,)   running row sum
  acc (block_q, D) running weighted values
Output is written once, at the final kv step.

VMEM at defaults (block_q=block_k=512, D=128, bf16 in / f32 acc):
  q 512·128·2 = 128 KiB, k/v panels 2·128 KiB, scores 512·512·4 = 1 MiB,
  acc 512·128·4 = 256 KiB  →  ≈ 1.8 MiB.

Causal skipping: fully-masked kv blocks short-circuit (pl.when), so the
causal pass does ~half the matmul work, matching the flash roofline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _flash_body(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                n_kblocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # visible iff the block intersects the causal triangle
    run = True
    if causal:
        run = (ik * block_k) <= (iq * block_q + block_q - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                    # (bq, bk)
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == n_kblocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention_bhsd(
    q: jnp.ndarray,       # (B, H, Sq, D)
    k: jnp.ndarray,       # (B, K, Skv, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, Sq, D = q.shape
    K, Skv = k.shape[1], k.shape[2]
    assert H % K == 0
    G = H // K
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    grid = (B, H, Sq // block_q, Skv // block_k)
    scale = 1.0 / (D ** 0.5)

    return pl.pallas_call(
        functools.partial(
            _flash_body, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, n_kblocks=grid[3],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        scratch_shapes=[
            pltpu.MemorySpace.VMEM((block_q,), jnp.float32),
            pltpu.MemorySpace.VMEM((block_q,), jnp.float32),
            pltpu.MemorySpace.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
