"""Pallas TPU kernel: fused compositional-code decode.

The decoder's codebook retrieval — on GPU a batch of ``m`` gathers — is
re-expressed for the MXU as ``m`` one-hot × codebook matmuls accumulated in
VMEM.  The one-hot matrices are built in-register from ``broadcasted_iota``
+ compare (never materialised in HBM); the codebooks stream through VMEM in
``(m·c, block_d)`` column panels, the codes block stays resident.

Exact decode in single bf16 MXU passes.  A one-hot row holds one 1, which
bf16 holds exactly, so a contraction is exact whenever the codebook operand
is: ``split_bf16`` cuts each f32 codebook value by truncation into three
bf16 parts with ``hi + mid + lo == x`` exactly, and the kernel runs one
single-pass bf16 dot per part (f32 accumulation) — three passes where
``precision=HIGHEST`` runs six, half of them on the one-hot's zero low
parts.  bf16 codebooks are their own single part.  The per-codebook sums
``(hi + mid) + lo`` are exact, and the ``m`` codebooks accumulate in order
j = 0..m-1, the ``gather`` oracle's order, so the output equals it bitwise
(up to the sign of a zero).  The split runs once per call, outside the
kernel, as one small XLA elementwise op.

Quantized decode (int8 codebooks + per-(codebook, code) f32 ``scales``)
fuses the dequant into the same matmul: the one-hot row is scaled by
``scales[j, code]`` *before* the int8 panel contraction, so
``(onehot · s) @ q  ==  onehot @ (q · s)`` bitwise — each dot row has
exactly one nonzero — and the dequantized codebooks never materialise in
HBM.  That is the whole point: at c=256, m=16, d_c=512 the codebook
traffic drops 4x (int8 values + a (m, c) f32 scale table that is ~d_c/4x
smaller than the values).  This path keeps its f32 one-hot at
``precision=HIGHEST``.

Accumulation is always f32 (``preferred_element_type``) regardless of the
codebook storage dtype — the MixedPrecisionPolicy's ``reduce_dtype``.

Grid: (d_c / block_d, B / block_b), the batch innermost, both parallel: a
column panel's block index changes only on the outer axis, so each panel is
fetched once per call, not once per batch block.
VMEM per step (defaults block_b=256, block_d=256, c=256, m=16, f32
codebooks): three bf16 part panels 3×4096×256×2 = 6 MiB (12 MiB double
buffered), codes 256×16×4 = 16 KiB, acc 256×256×4 = 256 KiB, onehot
256×256×2 = 128 KiB — inside the v5e core's 16 MiB scoped budget.  bf16
codebooks take one 2 MiB panel; int8 panels are 1 MiB and the (m, c) scale
table 16 KiB, grid-resident.
"""

from __future__ import annotations

import functools
import operator
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


_HI_BITS = 0xFFFF0000   # sign, exponent and the 7 stored bits bf16 keeps


def _truncate_to_bf16(x: jnp.ndarray) -> jnp.ndarray:
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(_HI_BITS)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def split_bf16(x: jnp.ndarray) -> jnp.ndarray:
    """f32 ``x`` -> bf16 ``(3, *x.shape)`` parts ``hi, mid, lo`` with
    ``hi + mid + lo == x`` exactly, summed in any order.

    Each part is cut by truncation (``hi`` = ``x``'s top 8 significant bits,
    ``mid`` the next 8 of the remainder, ``lo`` the rest), so all three
    carry ``x``'s sign and disjoint bits and every partial sum is exact.  A
    round-to-nearest split is not order-safe: ``x = 2 - 2^-8 + 2^-23``
    gives ``2, -2^-8, 2^-23``, and ``2 + 2^-23`` rounds back to 2.  Exact
    for ``|x| >= 2^-103`` (every part then a normal bf16, which a TPU does
    not flush) and for ±0, whose parts sum to +0."""
    x = x.astype(jnp.float32)
    hi = _truncate_to_bf16(x)
    r = x - hi
    mid = _truncate_to_bf16(r)
    return jnp.stack([hi, mid, r - mid]).astype(jnp.bfloat16)


def _decode_body(codes_ref, cb_ref, w0_ref, scales_ref, o_ref, *, c: int, m: int):
    codes = codes_ref[...]                       # (bB, m) int32
    bB = codes.shape[0]
    acc = jnp.zeros((bB, o_ref.shape[1]), jnp.float32)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (bB, c), 1)
    for j in range(m):                           # m is small & static: unrolled
        hit = codes[:, j][:, None] == iota_c
        rows = slice(j * c, (j + 1) * c)
        if scales_ref is None:
            # one single-pass bf16 dot per exact part: (hi + mid) + lo.
            # DEFAULT is stated: None would take jax_default_matmul_precision
            onehot = jnp.where(hit, 1.0, 0.0).astype(jnp.bfloat16)
            t = functools.reduce(operator.add, [
                jax.lax.dot_general(
                    onehot, cb_ref[p, rows, :], (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)
                for p in range(cb_ref.shape[0])])
        else:
            # fused dequant: scale the single nonzero of each one-hot row by
            # scales[j, code] — bitwise-equal to dequantizing the panel, but
            # the panel stays int8 in VMEM
            onehot = hit.astype(jnp.float32) * scales_ref[j, :][None, :]
            t = jax.lax.dot_general(
                onehot, cb_ref[0, rows, :].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        acc += t
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
    if scales is not None or codebooks.dtype == jnp.bfloat16:
        panels = cb2d[None]        # int8 (dequant in the body) or bf16: one part
    else:
        panels = split_bf16(cb2d)  # three exact bf16 parts
    n_parts = panels.shape[0]
    grid = (d_c // block_d, B // block_b)

    in_specs = [
        pl.BlockSpec((block_b, m), lambda j, i: (i, 0)),
        pl.BlockSpec((n_parts, m * c, block_d), lambda j, i: (0, 0, j)),
    ]
    args = [codes, panels]
    if w0 is not None:
        in_specs.append(pl.BlockSpec((1, block_d), lambda j, i: (0, j)))
        args.append(w0.reshape(1, d_c))
    if scales is not None:
        # the scale table is tiny — grid-resident, every program sees all of it
        in_specs.append(pl.BlockSpec((m, c), lambda j, i: (0, 0)))
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
        out_specs=pl.BlockSpec((block_b, block_d), lambda j, i: (i, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="hash_decode",
    )(*args)
