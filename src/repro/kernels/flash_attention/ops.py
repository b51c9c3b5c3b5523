"""jit'd wrapper with custom VJP for the flash-attention kernel.

Public layout matches nn.attention: q (B, S, H, D), k/v (B, S, K, D).
Forward: Pallas kernel (or the jnp oracle for unaligned shapes / CPU).
Backward: XLA recompute (standard memory-saving trade: the bwd re-runs the
reference attention under the residual-free recompute policy; a dedicated
bwd kernel is a further optimisation).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.flash_attention.ref import mha_ref


def _to_bhsd(x):
    return jnp.swapaxes(x, 1, 2)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, use_kernel):
    if use_kernel:
        return flash_attention_bhsd(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    return mha_ref(q, k, v, causal=causal)


def _fwd(q, k, v, causal, block_q, block_k, interpret, use_kernel):
    out = _flash(q, k, v, causal, block_q, block_k, interpret, use_kernel)
    return out, (q, k, v)


def _bwd(causal, block_q, block_k, interpret, use_kernel, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: mha_ref(q, k, v, causal=causal), q, k, v)
    return vjp(g)


_flash.defvjp(_fwd, _bwd)


def flash_attention(
    q: jnp.ndarray,       # (B, S, H, D)
    k: jnp.ndarray,       # (B, S, K, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jnp.ndarray:
    qh, kh, vh = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    Sq, Skv = qh.shape[2], kh.shape[2]
    if Sq % min(block_q, Sq) or Skv % min(block_k, Skv):
        use_kernel = False
    out = _flash(qh, kh, vh, causal, block_q, block_k, interpret, use_kernel)
    return _to_bhsd(out)
