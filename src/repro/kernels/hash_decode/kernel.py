"""Pallas TPU kernel: fused compositional-code decode (DESIGN.md §3.1).

The decoder's codebook retrieval — on GPU a batch of ``m`` gathers — is
re-expressed for the MXU as ``m`` one-hot × codebook matmuls accumulated in
VMEM.  The one-hot matrices are built in-register from ``broadcasted_iota``
+ compare (never materialised in HBM); the codebooks stream through VMEM in
``(m·c, block_d)`` column panels, the codes block stays resident.

Quantized decode (int8 codebooks + per-(codebook, code) f32 ``scales``)
fuses the dequant into the same matmul: the one-hot row is scaled by
``scales[j, code]`` *before* the int8 panel contraction, so
``(onehot · s) @ q  ==  onehot @ (q · s)`` bitwise — each dot row has
exactly one nonzero — and the dequantized codebooks never materialise in
HBM.  That is the whole point: at c=256, m=16, d_c=512 the codebook
traffic drops 4x (int8 values + a (m, c) f32 scale table that is ~d_c/4x
smaller than the values).

Accumulation is always f32 (``preferred_element_type``) regardless of the
codebook storage dtype — the MixedPrecisionPolicy's ``reduce_dtype``.  The
dot states ``precision=HIGHEST``: on a TPU the default f32 matmul is one
bf16 pass, which rounds f32 codebooks to bf16 (2.0e-3 of the output scale
on a v5e, against 9.6e-8 at HIGHEST; docs/decode_backends.md).

Grid: (B / block_b, d_c / block_d); both parallel.
VMEM per step (defaults block_b=256, block_d=256, c=256, m=16, f32):
  codes 256×16×4 = 16 KiB, codebook panel 4096×256×4 = 4 MiB,
  acc 256×256×4 = 256 KiB, onehot (register/VMEM temp) 256×256×4 = 256 KiB
  — ≈ 4.5 MiB, comfortably inside a v5e core's 16 MiB working budget.
  int8 panels are 1 MiB; the (m, c) scale table 16 KiB, grid-resident.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _decode_body(codes_ref, cb_ref, w0_ref, scales_ref, o_ref, *, c: int, m: int):
    codes = codes_ref[...]                       # (bB, m) int32
    bB = codes.shape[0]
    acc = jnp.zeros((bB, o_ref.shape[1]), jnp.float32)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (bB, c), 1)
    for j in range(m):                           # m is small & static: unrolled
        onehot = (codes[:, j][:, None] == iota_c).astype(jnp.float32)
        if scales_ref is not None:
            # fused dequant: scale the single nonzero of each one-hot row by
            # scales[j, code] — bitwise-equal to dequantizing the panel, but
            # the panel stays int8 in VMEM
            onehot = onehot * scales_ref[j, :][None, :].astype(jnp.float32)
        panel = cb_ref[j * c: (j + 1) * c, :].astype(jnp.float32)
        acc += jax.lax.dot_general(
            onehot, panel, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    if w0_ref is not None:
        acc *= w0_ref[...].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_d", "interpret")
)
def hash_decode_fwd(
    codes: jnp.ndarray,            # (B, m) int32
    codebooks: jnp.ndarray,        # (m, c, d_c) — f32 / bf16 / int8
    w0: Optional[jnp.ndarray] = None,      # (d_c,) or None
    scales: Optional[jnp.ndarray] = None,  # (m, c) f32 dequant scales or None
    *,
    block_b: int = 256,
    block_d: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, m = codes.shape
    m2, c, d_c = codebooks.shape
    assert m2 == m
    block_b = min(block_b, B)
    block_d = min(block_d, d_c)
    assert B % block_b == 0 and d_c % block_d == 0, (B, d_c, block_b, block_d)

    cb2d = codebooks.reshape(m * c, d_c)
    grid = (B // block_b, d_c // block_d)

    in_specs = [
        pl.BlockSpec((block_b, m), lambda i, j: (i, 0)),
        pl.BlockSpec((m * c, block_d), lambda i, j: (0, j)),
    ]
    args = [codes, cb2d]
    if w0 is not None:
        in_specs.append(pl.BlockSpec((1, block_d), lambda i, j: (0, j)))
        args.append(w0.reshape(1, d_c))
    if scales is not None:
        # the scale table is tiny — grid-resident, every program sees all of it
        in_specs.append(pl.BlockSpec((m, c), lambda i, j: (0, 0)))
        args.append(scales.astype(jnp.float32))

    have_w0, have_scales = w0 is not None, scales is not None

    def body(*refs):
        codes_ref, cb_ref = refs[0], refs[1]
        k = 2
        w0_ref = refs[k] if have_w0 else None
        k += int(have_w0)
        scales_ref = refs[k] if have_scales else None
        _decode_body(codes_ref, cb_ref, w0_ref, scales_ref, refs[-1], c=c, m=m)

    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((B, d_c), jnp.float32),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_d), lambda i, j: (i, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="hash_decode",
    )(*args)
