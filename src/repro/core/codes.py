"""Compositional-code storage layout (paper §3.1 footnote 1, §3.2).

A code vector of length ``m`` with cardinality ``c`` (``c`` a power of two)
is stored as ``n_bit = m * log2(c)`` bits.  Following the paper's example,
each element is written MSB-first: ``[2, 0, 3, 1]`` with ``c=4`` becomes the
bit string ``10 00 11 01``.

TPU adaptation: bits are packed into 32-bit lanes
(``uint32`` words, little-endian within a word: bit ``i`` of the code row
lives in word ``i // 32`` at bit position ``i % 32``).  All conversions are
vectorised shift/mask ops that fuse into the decode prologue.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

WORD_BITS = 32


def bits_per_code(c: int) -> int:
    """log2(c); validates that c is a power of two >= 2."""
    if c < 2 or (c & (c - 1)) != 0:
        raise ValueError(f"code cardinality c must be a power of two >= 2, got {c}")
    return int(c).bit_length() - 1


def n_bits(c: int, m: int) -> int:
    """Total bits per entity: m * log2(c)."""
    if m < 1:
        raise ValueError(f"code length m must be >= 1, got {m}")
    return m * bits_per_code(c)


def n_words(c: int, m: int) -> int:
    """uint32 words per entity."""
    return -(-n_bits(c, m) // WORD_BITS)


def pack_bits(bits) -> jnp.ndarray:
    """(n, n_bit) bool -> (n, n_words) uint32 (little-endian within words)."""
    bits = jnp.asarray(bits, jnp.uint32)
    n, nb = bits.shape
    nw = -(-nb // WORD_BITS)
    pad = nw * WORD_BITS - nb
    if pad:
        bits = jnp.pad(bits, ((0, 0), (0, pad)))
    bits = bits.reshape(n, nw, WORD_BITS)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return (bits << shifts).sum(axis=-1, dtype=jnp.uint32)


def unpack_bits(packed, nb: int) -> jnp.ndarray:
    """(n, n_words) uint32 -> (n, nb) bool."""
    packed = jnp.asarray(packed, jnp.uint32)
    n, nw = packed.shape
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(n, nw * WORD_BITS)[:, :nb].astype(jnp.bool_)


def bits_to_codes(bits, c: int, m: int) -> jnp.ndarray:
    """(n, n_bit) bool -> (n, m) int32, each element in [0, c).  MSB-first."""
    b = bits_per_code(c)
    bits = jnp.asarray(bits, jnp.int32).reshape(bits.shape[0], m, b)
    weights = (1 << jnp.arange(b - 1, -1, -1, dtype=jnp.int32))
    return (bits * weights).sum(-1).astype(jnp.int32)


def codes_to_bits(codes, c: int, m: int) -> jnp.ndarray:
    """(n, m) int -> (n, n_bit) bool.  MSB-first per element."""
    b = bits_per_code(c)
    codes = jnp.asarray(codes, jnp.int32)
    shifts = jnp.arange(b - 1, -1, -1, dtype=jnp.int32)
    bits = (codes[..., None] >> shifts) & 1
    return bits.reshape(codes.shape[0], m * b).astype(jnp.bool_)


def pack_codes(codes, c: int, m: int) -> jnp.ndarray:
    """(n, m) int codes -> (n, n_words) uint32 packed storage."""
    return pack_bits(codes_to_bits(codes, c, m))


def unpack_codes(packed, c: int, m: int) -> jnp.ndarray:
    """(n, n_words) uint32 -> (n, m) int32 codes.

    This is the decode-path prologue: pure shift/mask (VPU friendly), no
    gathers beyond the row fetch itself.
    """
    b = bits_per_code(c)
    packed = jnp.asarray(packed, jnp.uint32)
    lead = packed.shape[:-1]
    # global bit index of the MSB..LSB of each code element
    elem = jnp.arange(m)[:, None]                       # (m, 1)
    off = jnp.arange(b)[None, :]                        # (1, b)
    bit_idx = elem * b + off                            # (m, b) MSB-first order
    word_idx = (bit_idx // WORD_BITS).astype(jnp.int32)
    bit_in_word = (bit_idx % WORD_BITS).astype(jnp.uint32)
    words = jnp.take(packed, word_idx.reshape(-1), axis=-1)
    bits = (words >> bit_in_word.reshape(-1)) & jnp.uint32(1)
    bits = bits.reshape(*lead, m, b).astype(jnp.int32)
    weights = (1 << jnp.arange(b - 1, -1, -1, dtype=jnp.int32))
    return (bits * weights).sum(-1).astype(jnp.int32)


def position_codes(ids, c: int, m: int, seed: int = 0) -> jnp.ndarray:
    """(B,) entity ids -> (B, m) int32 position-hash codes in [0, c).

    The ``hashemb`` compression family's hash functions (arXiv:2109.00101):
    ``m`` independent stateless hashes of the entity id, recomputed at
    lookup time — no per-entity ``codes_buf`` exists, so id-side memory is
    zero and unseen ids hash without retraining.  Each position ``j`` mixes
    ``id`` with a per-position odd key through a splitmix32-style finalizer
    (xor-shift + odd-multiply avalanche, pure uint32 shift/mask/mul — VPU
    friendly and identical on host and device), then keeps the top
    ``log2(c)`` bits (the best-mixed ones).  Deterministic in
    ``(ids, c, m, seed)``.
    """
    b = bits_per_code(c)
    if m < 1:
        raise ValueError(f"code length m must be >= 1, got {m}")
    ids = jnp.asarray(ids, jnp.uint32)[:, None]             # (B, 1)
    # per-position keys: golden-ratio stride, odd so multiplication is a
    # bijection on uint32
    j = jnp.arange(m, dtype=jnp.uint32)[None, :]            # (1, m)
    key = (j * jnp.uint32(0x9E3779B9)
           + jnp.uint32(2 * seed + 1) * jnp.uint32(0x85EBCA6B))
    x = ids ^ key
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return (x >> jnp.uint32(32 - b)).astype(jnp.int32)      # top-b bits


def count_collisions(codes) -> int:
    """Number of entities sharing a code with an earlier entity.

    ``codes`` is any 2D per-entity code representation (packed words or
    integer codes).  Returns ``n - n_unique`` (the paper's Fig. 3 metric).
    Host-side (numpy) — used by benchmarks, not in the training path.
    """
    arr = np.asarray(codes)
    return int(arr.shape[0] - np.unique(arr, axis=0).shape[0])


def code_capacity(c: int, m: int) -> int:
    """Number of distinct representable entities (2**n_bit)."""
    return 1 << n_bits(c, m)
