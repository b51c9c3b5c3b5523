"""Pallas TPU kernel: fused random-projection + binarise + bit-pack
(one 32-bit code word per entity; Algorithm 1's inner loops).

This step is bandwidth-bound (A streams from HBM); the fusion keeps the
projection result, binarisation and bit-pack on-chip, so peak extra memory
is O(block) rather than O(n·32·4B).  At the paper's industrial scale
(n ≈ 10⁹ cards) a materialised projection would be ~128 GB — bigger than
HBM — so out-of-core encode *requires* this streaming form; thresholds are
supplied by the caller (exact median in-core, or a row-sampled median
estimate at out-of-core scale — see ops.lsh_encode_packed).

Grid: (n / block_n, d / block_d) — the d dimension accumulates into a VMEM
scratch; at the last d-step the thresholds (SMEM-resident, computed by the
host-level median pass) binarise the projection and the 32 bit-columns are
packed into one uint32 lane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _encode_body(a_ref, v_ref, t_ref, o_ref, acc_ref, *, n_dblocks: int):
    jd = pl.program_id(1)

    @pl.when(jd == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)           # (bn, bd)
    v = v_ref[...].astype(jnp.float32)           # (bd, w)
    acc_ref[...] += jax.lax.dot_general(
        a, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(jd == n_dblocks - 1)
    def _():
        u = acc_ref[...]                         # (bn, w)
        t = t_ref[...].astype(jnp.float32)       # (1, w)
        bits = (u > t).astype(jnp.uint32)
        shifts = jax.lax.broadcasted_iota(jnp.uint32, bits.shape, 1)
        word = jnp.sum(bits << shifts, axis=1, dtype=jnp.uint32, keepdims=True)
        o_ref[...] = word


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def lsh_encode_word(
    A: jnp.ndarray,          # (n, d)
    V: jnp.ndarray,          # (d, w)  w <= 32
    t: jnp.ndarray,          # (w,) thresholds
    *,
    block_n: int = 1024,
    block_d: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    n, d = A.shape
    w = V.shape[1]
    block_n = min(block_n, n)
    block_d = min(block_d, d)
    assert n % block_n == 0 and d % block_d == 0, (n, d, block_n, block_d)
    grid = (n // block_n, d // block_d)
    return pl.pallas_call(
        functools.partial(_encode_body, n_dblocks=grid[1]),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.uint32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_d, w), lambda i, j: (j, 0)),
            pl.BlockSpec((1, w), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        scratch_shapes=[pltpu.MemorySpace.VMEM((block_n, w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="lsh_encode_word",
    )(A, V, t.reshape(1, w))
