"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lsh as core_lsh
from repro.kernels.flash_attention import flash_attention, mha_ref
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.hash_decode import hash_decode, hash_decode_ref
from repro.kernels.hash_decode import ops as hd_ops
from repro.kernels.lsh_encode.kernel import lsh_encode_word
from repro.kernels.lsh_encode.ops import lsh_encode_packed
from repro.kernels.lsh_encode.ref import lsh_encode_word_ref


# ---------------- hash_decode ----------------

@pytest.mark.parametrize("B,m,c,d_c", [
    (256, 16, 256, 512),   # paper §5.3 hyper-params
    (128, 128, 2, 512),    # paper §B.2 (c=2, m=128)
    (512, 8, 64, 256),
    (256, 32, 16, 384),
    (128, 4, 4, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hash_decode_sweep(B, m, c, d_c, dtype):
    key = jax.random.PRNGKey(0)
    codes = jax.random.randint(key, (B, m), 0, c)
    cb = jax.random.normal(jax.random.fold_in(key, 1), (m, c, d_c), dtype)
    w0 = jax.random.normal(jax.random.fold_in(key, 2), (d_c,), dtype)
    # f32: m-term sums accumulate in different orders kernel-vs-ref
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    for w in (None, w0):
        out = hash_decode(codes, cb, w, interpret=True, block_b=128, block_d=128)
        ref = hash_decode_ref(codes, cb, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=tol, atol=tol)


def test_hash_decode_grads_match_ref():
    key = jax.random.PRNGKey(3)
    codes = jax.random.randint(key, (128, 8), 0, 16)
    cb = jax.random.normal(key, (8, 16, 128))
    w0 = jax.random.normal(jax.random.fold_in(key, 1), (128,))
    gk = jax.grad(lambda cb, w0: (hash_decode(codes, cb, w0, interpret=True) ** 2).sum(),
                  argnums=(0, 1))(cb, w0)
    gr = jax.grad(lambda cb, w0: (hash_decode_ref(codes, cb, w0) ** 2).sum(),
                  argnums=(0, 1))(cb, w0)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_hash_decode_unaligned_falls_back():
    codes = jax.random.randint(jax.random.PRNGKey(0), (100, 8), 0, 16)  # 100 % 128 != 0
    cb = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 96))
    out = hash_decode(codes, cb, None, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(hash_decode_ref(codes, cb, None)),
                               rtol=1e-5, atol=1e-5)


def test_hash_decode_unaligned_raises_on_tpu(monkeypatch):
    """On a TPU an untileable shape is an error, never a quiet fall back
    to the reference path."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    codes = jnp.zeros((100, 8), jnp.int32)
    cb = jnp.zeros((8, 16, 96))
    with pytest.raises(ValueError, match="not tileable"):
        hash_decode(codes, cb, None)


@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_hash_decode_unaligned_backward(quantize):
    """The fallback path must keep the custom VJP: unaligned shapes
    (B=100, d_c=96 — neither sublane- nor lane-tileable) take the jnp
    reference forward, and gradients must still match grad-of-ref."""
    key = jax.random.PRNGKey(5)
    codes = jax.random.randint(key, (100, 8), 0, 16)
    cb = jax.random.normal(jax.random.fold_in(key, 1), (8, 16, 96))
    w0 = jax.random.normal(jax.random.fold_in(key, 2), (96,))

    def ref_loss(cb, w0):
        if quantize == "int8":
            cb = hd_ops.quantize_dequantize(cb)
        return (hash_decode_ref(codes, cb, w0) ** 2).sum()

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        gk = jax.grad(lambda cb, w0: (hash_decode(
            codes, cb, w0, interpret=True, quantize=quantize) ** 2).sum(),
            argnums=(0, 1))(cb, w0)
    gr = jax.grad(ref_loss, argnums=(0, 1))(cb, w0)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_hash_decode_fallback_warns_once_per_shape_and_reason():
    hd_ops.reset_fallback_warnings()
    codes = jax.random.randint(jax.random.PRNGKey(0), (100, 8), 0, 16)
    cb = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 96))
    with pytest.warns(UserWarning, match="falling back"):
        hash_decode(codes, cb, None, interpret=True)
    # same (shape, reason): silent on repeat
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        hash_decode(codes, cb, None, interpret=True)
    # a NEW reason on the same shape must not be silenced by the earlier
    # one: int8 adds the scales-tile requirement (m=8 ok, c=16 < 128 lane)
    with pytest.warns(UserWarning, match="scales-tile"):
        hash_decode(codes, cb, None, interpret=True, quantize="int8")
    # the reset hook restores a clean slate
    hd_ops.reset_fallback_warnings()
    with pytest.warns(UserWarning, match="falling back"):
        hash_decode(codes, cb, None, interpret=True)


@pytest.mark.parametrize("B,m,c,d_c", [
    (256, 16, 256, 512),   # paper §5.3 shape, scales (m, c) tileable
    (128, 8, 128, 128),
])
def test_hash_decode_int8_kernel_matches_ref(B, m, c, d_c):
    """Fused int8 dequant in the kernel == quantize-dequantize-then-decode:
    the scaled-one-hot contraction performs the same f32 products, so the
    match is exact, not approximate."""
    key = jax.random.PRNGKey(11)
    codes = jax.random.randint(key, (B, m), 0, c)
    cb = jax.random.normal(jax.random.fold_in(key, 1), (m, c, d_c))
    w0 = jax.random.normal(jax.random.fold_in(key, 2), (d_c,))
    for w in (None, w0):
        out = hash_decode(codes, cb, w, interpret=True,
                          block_b=128, block_d=128, quantize="int8")
        ref = hash_decode_ref(codes, hd_ops.quantize_dequantize(cb), w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def _hard_f32(key, shape):
    """Random normal f32 values at exponents 2^-100..2^100, of both signs,
    with the split's hard cases (2 - 2^-8 + 2^-23, ±0) laid over them."""
    k1, k2 = jax.random.split(key)
    x = np.asarray(jax.random.normal(k1, shape, jnp.float32))
    exps = np.asarray(jax.random.randint(k2, shape, -100, 101))
    x = (x * np.exp2(exps.astype(np.float64))).astype(np.float32).ravel()
    hard = np.array([2 - 2**-8 + 2**-23, -(2 - 2**-8 + 2**-23), 0.0, -0.0,
                     1.0, -1.0, 2.0**-100, -(2.0**100), 3 * 2.0**-23],
                    np.float32)
    x[::97][:hard.size] = hard
    return jnp.asarray(x.reshape(shape))


def _bits(x):
    """f32 bit patterns with -0 folded into +0: an accumulator that starts
    at +0 (the kernel's) turns a -0 sum into +0, the gather's keeps it."""
    return (np.asarray(x, np.float32) + np.float32(0)).view(np.uint32)


def test_split_bf16_exact_in_every_order():
    """hi + mid + lo == x bit for bit in all six orders of summation (-0
    sums to +0), so the MXU may add the parts in any order."""
    import itertools
    from repro.kernels.hash_decode.kernel import split_bf16
    x = np.concatenate([
        np.asarray(jax.random.normal(jax.random.PRNGKey(21), (100_000,))),
        np.asarray(_hard_f32(jax.random.PRNGKey(22), (4096,))).ravel(),
        np.exp2(np.arange(-100, 101)).astype(np.float32),
        -np.exp2(np.arange(-100, 101)).astype(np.float32),
    ]).astype(np.float32)
    parts = jax.jit(split_bf16)(jnp.asarray(x))
    assert parts.dtype == jnp.bfloat16 and parts.shape == (3,) + x.shape
    parts = np.asarray(parts.astype(jnp.float32))
    for a, b, c in itertools.permutations(range(3)):
        np.testing.assert_array_equal(
            _bits((parts[a] + parts[b]) + parts[c]), _bits(x))
    # -0 splits into (-0, +0, +0): its sum is +0 in every order
    neg0 = parts[:, np.signbit(x) & (x == 0)]
    assert neg0.size and (neg0.sum(axis=0) == 0).all()


@pytest.mark.parametrize("block_d", [128, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hash_decode_bitwise_equals_gather(dtype, block_d):
    """At the paper widths the kernel's output equals the gather oracle's
    bit for bit, for f32 codebooks (three exact bf16 parts) and bf16 ones
    (one part), with and without w0: every product is exact and the m
    codebooks accumulate in the gather's order."""
    from repro.core.backend import GatherBackend
    from repro.kernels.hash_decode.kernel import hash_decode_fwd
    B, m, c, d_c = 256, 16, 256, 512
    key = jax.random.PRNGKey(23)
    codes = jax.random.randint(key, (B, m), 0, c)
    cb = _hard_f32(jax.random.fold_in(key, 1), (m, c, d_c)).astype(dtype)
    w0 = jax.random.normal(jax.random.fold_in(key, 2), (d_c,), dtype)
    gather = GatherBackend()
    for w in (None, w0):
        out = hash_decode_fwd(codes, cb, w, block_d=block_d, interpret=True)
        ref = gather.decode(codes, cb, w)
        assert out.dtype == jnp.float32
        np.testing.assert_array_equal(_bits(out), _bits(ref))


def test_hash_decode_int8_unchanged_bitwise():
    """The int8 path keeps its scaled one-hot at HIGHEST: bitwise equal to
    hash_decode_ref's dequantized codebooks summed in codebook order (the
    int8 gather oracle; the ref's one einsum sums in another order)."""
    from repro.core.backend import GatherBackend, MixedPrecisionPolicy
    from repro.kernels.hash_decode.kernel import hash_decode_fwd
    B, m, c, d_c = 256, 16, 256, 512
    key = jax.random.PRNGKey(24)
    codes = jax.random.randint(key, (B, m), 0, c)
    cb = jax.random.normal(jax.random.fold_in(key, 1), (m, c, d_c))
    w0 = jax.random.normal(jax.random.fold_in(key, 2), (d_c,))
    q, scales = hd_ops.quantize_codebooks(cb)
    gather = GatherBackend(MixedPrecisionPolicy(quantize="int8"))
    for w in (None, w0):
        out = hash_decode_fwd(codes, q, w, scales, interpret=True)
        np.testing.assert_array_equal(_bits(out),
                                      _bits(gather.decode(codes, cb, w)))


@pytest.mark.parametrize("dtype,passes", [(jnp.float32, 3),
                                          (jnp.bfloat16, 1)])
def test_hash_decode_single_pass_bf16_structure(dtype, passes):
    """The kernel body holds only single-pass bf16 x bf16 dots with f32
    accumulation, ``passes`` per codebook, and the batch is the grid's
    innermost axis (each codebook panel is fetched once per call)."""
    from repro.kernels.hash_decode.kernel import hash_decode_fwd
    B, m, c, d_c, block_b, block_d = 768, 16, 256, 512, 256, 256
    # traced as the benchmark runs it: a dot that states no precision
    # would take this global default
    with jax.default_matmul_precision("highest"):
        jaxpr = jax.make_jaxpr(lambda codes, cb, w0: hash_decode_fwd(
            codes, cb, w0, block_b=block_b, block_d=block_d,
            interpret=True))(
            jnp.zeros((B, m), jnp.int32), jnp.zeros((m, c, d_c), dtype),
            jnp.zeros((d_c,), dtype))

    def eqns(jp):
        for e in jp.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    calls = [e for e in eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    call = calls[0]
    assert call.params["grid_mapping"].grid == (d_c // block_d, B // block_b)
    dots = [e for e in eqns(call.params["jaxpr"])
            if e.primitive.name == "dot_general"]
    assert len(dots) == passes * m
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
        assert e.params["preferred_element_type"] == jnp.float32
        assert e.params["precision"] == (jax.lax.Precision.DEFAULT,) * 2


def test_quantize_codebooks_roundtrip_bound():
    """Absmax int8: dequant error per element <= scale/2, scale = absmax/127,
    and all-zero code vectors reconstruct exactly (scale forced to 1)."""
    cb = jax.random.normal(jax.random.PRNGKey(4), (4, 8, 64))
    cb = cb.at[0, 0].set(0.0)
    q, scales = hd_ops.quantize_codebooks(cb)
    assert q.dtype == jnp.int8 and scales.shape == (4, 8)
    deq = hd_ops.dequantize_codebooks(q, scales)
    err = np.abs(np.asarray(deq - cb))
    bound = np.asarray(scales)[:, :, None] / 2 + 1e-7
    assert (err <= bound).all()
    np.testing.assert_array_equal(np.asarray(deq[0, 0]), np.zeros(64))
    # straight-through backward: identity to the float masters
    g = jax.grad(lambda cb: hd_ops.quantize_dequantize(cb).sum())(cb)
    np.testing.assert_array_equal(np.asarray(g), np.ones_like(np.asarray(cb)))


def test_int8_codebooks_take_a_quarter_of_the_bytes():
    """At the paper's widths (m=16, c=256, d_c=512) int8 codebooks plus
    their per-code f32 scales hold at most 1/3.5 of the f32 bytes."""
    cb = jnp.zeros((16, 256, 512), jnp.float32)
    q, scales = hd_ops.quantize_codebooks(cb)
    assert q.dtype == jnp.int8 and scales.shape == (16, 256)
    assert (q.nbytes + scales.nbytes) * 3.5 <= cb.nbytes


# ---------------- lsh_encode ----------------

@pytest.mark.parametrize("n,d,w", [(2048, 512, 32), (1024, 256, 16), (512, 128, 32)])
def test_lsh_encode_word_sweep(n, d, w):
    key = jax.random.PRNGKey(1)
    A = jax.random.normal(key, (n, d))
    V = jax.random.normal(jax.random.fold_in(key, 1), (d, w))
    t = jnp.median(A @ V, axis=0)
    out = lsh_encode_word(A, V, t, block_n=256, block_d=128, interpret=True)[:, 0]
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(lsh_encode_word_ref(A, V, t)))


def test_lsh_encode_packed_equals_core():
    A = jax.random.normal(jax.random.PRNGKey(2), (1024, 256))
    a = lsh_encode_packed(jax.random.PRNGKey(7), A, 16, 16,
                          block_n=256, block_d=128, interpret=True)
    b = core_lsh.encode_lsh(jax.random.PRNGKey(7), A, 16, 16)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------- flash_attention ----------------

@pytest.mark.parametrize("B,H,K,S,D,causal", [
    (2, 4, 2, 256, 64, True),
    (1, 8, 8, 128, 64, False),
    (2, 4, 1, 256, 128, True),
    (1, 2, 2, 512, 64, True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_sweep(B, H, K, S, D, causal, dtype):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, S, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, K, S, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, K, S, D), dtype)
    out = flash_attention_bhsd(q, k, v, causal=causal, block_q=64, block_k=64,
                               interpret=True)
    ref = mha_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_wrapper_grads():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 128, 4, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 128, 2, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 128, 2, 64))

    def ref_bshd(q, k, v):
        sw = lambda x: jnp.swapaxes(x, 1, 2)
        return sw(mha_ref(sw(q), sw(k), sw(v)))

    gk = jax.grad(lambda *a: (flash_attention(*a, block_q=64, block_k=64,
                                              interpret=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (ref_bshd(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)
