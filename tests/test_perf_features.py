"""Tests for the §Perf-driven features: chunked CE, dense-dispatch MoE,
one-hot cache writes, strategy resolver."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import init_lm, lm_loss
from repro.nn.kvcache import KVCache
from repro.nn.moe import MoEConfig, init_moe, moe_dense_ffn, moe_ffn

KEY = jax.random.PRNGKey(0)


def test_chunked_ce_equals_full_loss():
    cfg = reduced(get_config("yi-9b"))
    p = init_lm(KEY, cfg)
    tokens = jax.random.randint(jax.random.fold_in(KEY, 1), (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    l_full = lm_loss(p, batch, cfg)
    cfg_c = dataclasses.replace(cfg, loss_vocab_chunk=64)
    l_chunk = lm_loss(p, batch, cfg_c)
    np.testing.assert_allclose(float(l_full), float(l_chunk), rtol=1e-5)
    g1 = jax.grad(lambda p: lm_loss(p, batch, cfg), allow_int=True)(p)
    g2 = jax.grad(lambda p: lm_loss(p, batch, cfg_c), allow_int=True)(p)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        if a.dtype.kind == "f":
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=1e-5)


def test_moe_dense_equals_sorted():
    cfg = MoEConfig(d_model=32, d_ff=64, n_experts=8, top_k=2)
    p = init_moe(KEY, cfg)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (64, 32))
    np.testing.assert_allclose(np.asarray(moe_dense_ffn(p, x, cfg)),
                               np.asarray(moe_ffn(p, x, cfg)),
                               rtol=2e-4, atol=2e-4)


def test_moe_dense_respects_padding():
    cfg = MoEConfig(d_model=16, d_ff=32, n_experts=5, top_k=2, n_experts_padded=8)
    p = init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (32, 16))
    out = moe_dense_ffn(p, x, cfg)
    assert bool(jnp.isfinite(out).all())


def test_kvcache_onehot_decode_write_equals_dus():
    cache = KVCache.zeros(2, 8, 2, 4, jnp.float32)
    k1 = jax.random.normal(KEY, (2, 3, 2, 4))          # chunked prefill: DUS
    cache = cache.update(k1, k1)
    k2 = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 1, 2, 4))  # decode: onehot
    cache = cache.update(k2, k2)
    np.testing.assert_allclose(np.asarray(cache.k[:, :3]), np.asarray(k1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(cache.k[:, 3]), np.asarray(k2[:, 0]), rtol=1e-6)
    assert int(cache.pos) == 4
    assert np.asarray(cache.k[:, 4:]).sum() == 0


def test_kvcache_full_replace_prefill():
    cache = KVCache.zeros(1, 4, 1, 2, jnp.float32)
    k = jax.random.normal(KEY, (1, 4, 1, 2))
    cache = cache.update(k, k)
    np.testing.assert_allclose(np.asarray(cache.k), np.asarray(k), rtol=1e-6)


def test_strategy_rules():
    from repro.parallel.policy import Strategy, rules_for
    # needs only mesh *shape* metadata; single-device mesh objects are fine
    from repro.parallel.sharding import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    r_tp = rules_for(Strategy(), mesh)
    assert r_tp.rules["d_ff"] == "model" and r_tp.rules["batch"] == ("data",)
    r_dp = rules_for(Strategy(dp_over_model=True), mesh)
    assert r_dp.rules["d_ff"] is None
    assert r_dp.rules["batch"] == ("data", "model")
