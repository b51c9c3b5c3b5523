"""jit'd wrapper for the hash-decode kernel with custom VJP.

Forward runs the Pallas kernel (or the jnp oracle when ``use_kernel=False``,
or for unaligned shapes off the TPU); backward is expressed in XLA:
    d_codebooks[j, code, :] += g ⊙ w0       (scatter-add == onehotᵀ @ g)
    d_w0 = Σ_b g ⊙ codebook_sum             (recomputed, not saved)
Codes are integers — no gradient flows to them.

``quantize="int8"`` runs the decode against per-(codebook, code) absmax
int8 values with the dequant fused into the kernel (scales operand).  The
f32/bf16 master codebooks stay the differentiable primal: the codebook
cotangent is a value-independent scatter-add of the output cotangent, so
the straight-through estimator through round() is exactly the unquantized
backward; only ``d_w0`` (which linearizes through the decoded values) uses
the dequantized codebooks to match the forward.
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.hash_decode.kernel import hash_decode_fwd
from repro.kernels.hash_decode.ref import hash_decode_ref

# f32 min tile (sublane, lane) on TPU — a block that isn't a multiple of
# this fails Mosaic layout even when it divides the array.
_SUBLANE = 8
_LANE = 128

# (B, d_c, reason) triples already warned about — one warning per distinct
# (shape, reason), so a new fallback cause is never silenced by an earlier,
# unrelated one.  Tests reset via ``reset_fallback_warnings()``.
_warned_fallback: set = set()

# The backward one-hot contractions state f32 precision: a TPU's default
# f32 matmul is one bf16 pass, which rounds the output cotangent to bf16
# (1.7e-3 of the codebook gradient's scale on a v5e, vs the exact
# scatter-add of the gather oracle).
_HIGHEST = jax.lax.Precision.HIGHEST


def reset_fallback_warnings() -> None:
    """Clear the warn-once memory (test hook: lets a test assert the
    fallback warning fires regardless of what ran before it)."""
    _warned_fallback.clear()


def _fallback_reasons(B: int, d_c: int, block_b: int, block_d: int,
                      *, c: Optional[int] = None, m: Optional[int] = None,
                      quantized: bool = False) -> List[str]:
    """Why the kernel can't run these shapes ([] == it can): the (clamped)
    blocks must divide the array dims AND be hardware-tileable, and the
    quantized path's (m, c) scale table must itself be a legal tile.  The
    old check ``B % min(block_b, B)`` was vacuously 0 whenever ``block_b >
    B`` — it reported e.g. B=100 as aligned, which only works in interpret
    mode (100 is not a sublane multiple) and silently diverged from TPU
    behaviour."""
    bb, bd = min(block_b, B), min(block_d, d_c)
    reasons = []
    if B % bb != 0 or d_c % bd != 0:
        reasons.append("block-divide")
    if bb % _SUBLANE != 0 or bd % _LANE != 0:
        reasons.append("block-tile")
    if quantized and (m % _SUBLANE != 0 or c % _LANE != 0):
        reasons.append("scales-tile")
    return reasons


def _aligned(B: int, d_c: int, block_b: int, block_d: int) -> bool:
    """True iff the (unquantized) kernel can run — see _fallback_reasons."""
    return not _fallback_reasons(B, d_c, block_b, block_d)


def quantize_codebooks(codebooks: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(codebook, code) absmax int8 quantization (the
    ``optim/compress.py`` idiom at code-vector granularity).

    codebooks (m, c, d_c) any float -> (q int8 (m, c, d_c), scales f32
    (m, c)); all-zero code vectors get scale 1 so dequant is exact."""
    cb = codebooks.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(cb), axis=2)
    scales = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(cb / scales[:, :, None]), -127, 127).astype(jnp.int8)
    return q, scales


def dequantize_codebooks(q: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """(q int8 (m, c, d_c), scales f32 (m, c)) -> f32 (m, c, d_c)."""
    return q.astype(jnp.float32) * scales.astype(jnp.float32)[:, :, None]


@jax.custom_vjp
def quantize_dequantize(codebooks: jnp.ndarray) -> jnp.ndarray:
    """dequant(quantize(cb)): the decode-visible value of int8-stored
    codebooks, with a straight-through (identity) backward to the float
    masters.  The XLA backends use this to bitwise-match the fused kernel's
    scaled-one-hot dequant (same f32 products, see kernel.py)."""
    return dequantize_codebooks(*quantize_codebooks(codebooks))


def _qdq_fwd(codebooks):
    return quantize_dequantize(codebooks), jnp.zeros((), codebooks.dtype)


def _qdq_bwd(dtype_token, g):
    return (g.astype(dtype_token.dtype),)


quantize_dequantize.defvjp(_qdq_fwd, _qdq_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _hash_decode(codes, codebooks, w0, block_b, block_d, interpret, use_kernel):
    if use_kernel:
        return hash_decode_fwd(codes, codebooks, w0,
                               block_b=block_b, block_d=block_d,
                               interpret=interpret)
    return hash_decode_ref(codes, codebooks, w0)


def _fwd(codes, codebooks, w0, block_b, block_d, interpret, use_kernel):
    out = _hash_decode(codes, codebooks, w0, block_b, block_d, interpret, use_kernel)
    return out, (codes, codebooks, w0)


def _bwd(block_b, block_d, interpret, use_kernel, res, g):
    codes, codebooks, w0 = res
    m, c, _ = codebooks.shape
    g = g.astype(jnp.float32)
    gw = g * w0.astype(jnp.float32)[None, :] if w0 is not None else g
    onehot = (codes[:, :, None] == jnp.arange(c)[None, None, :]).astype(jnp.float32)
    d_cb = jnp.einsum("bmc,bd->mcd", onehot, gw,
                      precision=_HIGHEST).astype(codebooks.dtype)
    if w0 is not None:
        summed = jnp.einsum("bmc,mcd->bd", onehot,
                            codebooks.astype(jnp.float32), precision=_HIGHEST)
        d_w0 = jnp.einsum("bd,bd->d", g, summed,
                          precision=_HIGHEST).astype(w0.dtype)
    else:
        d_w0 = None
    return None, d_cb, d_w0


_hash_decode.defvjp(_fwd, _bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _hash_decode_int8(codes, codebooks, w0, block_b, block_d, interpret, use_kernel):
    q, scales = quantize_codebooks(codebooks)
    if use_kernel:
        return hash_decode_fwd(codes, q, w0, scales,
                               block_b=block_b, block_d=block_d,
                               interpret=interpret)
    return hash_decode_ref(codes, q, w0, scales=scales)


def _fwd_int8(codes, codebooks, w0, block_b, block_d, interpret, use_kernel):
    out = _hash_decode_int8(codes, codebooks, w0, block_b, block_d, interpret,
                            use_kernel)
    return out, (codes, codebooks, w0)


def _bwd_int8(block_b, block_d, interpret, use_kernel, res, g):
    codes, codebooks, w0 = res
    m, c, _ = codebooks.shape
    g = g.astype(jnp.float32)
    gw = g * w0.astype(jnp.float32)[None, :] if w0 is not None else g
    onehot = (codes[:, :, None] == jnp.arange(c)[None, None, :]).astype(jnp.float32)
    # straight-through to the float masters: the codebook cotangent never
    # reads codebook VALUES, so it is identical to the unquantized backward
    d_cb = jnp.einsum("bmc,bd->mcd", onehot, gw,
                      precision=_HIGHEST).astype(codebooks.dtype)
    if w0 is not None:
        # d_w0 linearizes through the decoded values — use what the forward
        # actually decoded (the dequantized codebooks), not the masters
        deq = dequantize_codebooks(*quantize_codebooks(codebooks))
        summed = jnp.einsum("bmc,mcd->bd", onehot, deq, precision=_HIGHEST)
        d_w0 = jnp.einsum("bd,bd->d", g, summed,
                          precision=_HIGHEST).astype(w0.dtype)
    else:
        d_w0 = None
    return None, d_cb, d_w0


_hash_decode_int8.defvjp(_fwd_int8, _bwd_int8)


def hash_decode(
    codes: jnp.ndarray,
    codebooks: jnp.ndarray,
    w0: Optional[jnp.ndarray] = None,
    *,
    block_b: int = 256,
    block_d: int = 256,
    interpret: bool = False,
    use_kernel: bool = True,
    quantize: str = "none",
) -> jnp.ndarray:
    """codes (B, m) int32, codebooks (m, c, d_c) -> (B, d_c) f32.

    ``quantize="int8"`` decodes against absmax-int8 codebooks with the
    dequant fused into the kernel; gradients flow straight-through to the
    float masters (module docstring).

    Unaligned shapes raise on a TPU runtime; elsewhere (interpret mode on
    the CPU) they fall back to the jnp reference path with a one-time
    warning per (shape, reason).  Callers pad to block multiples first
    (``core.backend.PallasBackend`` does exactly that)."""
    if quantize not in ("none", "int8"):
        raise ValueError(f"quantize={quantize!r} not supported "
                         f"(expected 'none' or 'int8'; int4 packing is a "
                         f"documented future extension)")
    B = codes.shape[0]
    m, c, d_c = codebooks.shape
    if use_kernel:
        reasons = _fallback_reasons(B, d_c, block_b, block_d, c=c, m=m,
                                    quantized=(quantize == "int8"))
        if reasons:
            reason = "+".join(reasons)
            if jax.default_backend() == "tpu":
                raise ValueError(
                    f"hash_decode: shapes B={B}, d_c={d_c} not tileable with "
                    f"blocks ({block_b}, {block_d}) [{reason}]; pad inputs "
                    f"(repro.core.backend.PallasBackend does) or pass "
                    f"use_kernel=False for the reference path")
            key = (B, d_c, reason)
            if key not in _warned_fallback:
                _warned_fallback.add(key)
                warnings.warn(
                    f"hash_decode: shapes B={B}, d_c={d_c} not tileable with "
                    f"blocks ({block_b}, {block_d}) [{reason}]; falling back "
                    f"to the jnp reference path (pad inputs, e.g. via "
                    f"repro.core.backend.PallasBackend, to run the kernel)",
                    stacklevel=2)
            use_kernel = False
    if quantize == "int8":
        return _hash_decode_int8(codes, codebooks, w0, block_b, block_d,
                                 interpret, use_kernel)
    return _hash_decode(codes, codebooks, w0, block_b, block_d, interpret,
                        use_kernel)
