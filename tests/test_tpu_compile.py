"""Compile-only checks against a described TPU v5e (no chip needed).

The TPU compiler is installed, so programs compile for a chip that is
described and not attached.  That catches what interpret mode cannot: a
kernel Mosaic refuses, a step that does not fit the chip's 16 GB, a quiet
fall back from the kernel to the reference.  Nothing runs, so nothing here
is a result or a time.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and a test
worker that loaded it at import would keep it from the others.  All such
compiles live in this one file so one worker holds the library.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30
# the paper widths (c=256, m=16, d_c=512) at the kernel benchmark batch
B, M, C, D_C = 8192, 16, 256, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_hash_decode_compiles_for_v5e(one_chip, dtype):
    from repro.kernels.hash_decode.kernel import hash_decode_fwd
    args = [jax.ShapeDtypeStruct((B, M), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((M, C, D_C), jnp.dtype(dtype),
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((D_C,), jnp.float32, sharding=one_chip)]
    if dtype == "int8":
        args.append(jax.ShapeDtypeStruct((M, C), jnp.float32,
                                         sharding=one_chip))
    compiled = jax.jit(hash_decode_fwd).lower(*args).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "hash_decode" in text


def test_sage_train_step_compiles_for_v5e(one_chip):
    """The one-chip SAGE train step at the paper widths (batch 1024, fanout
    15, the 53,248-row frontier ``chip_smoke.py`` runs), from shapes of the
    state and of one frontier batch: it compiles with the kernel in it and
    fits the chip's memory."""
    from repro.configs.paper_gnn import paper_gnn_config
    from repro.core.codes import n_words
    from repro.graph import NeighborSampler, powerlaw_graph
    from repro.graph.engine import SageBatchSource
    from repro.train import init_gnn_train_state, make_gnn_train_step

    n_nodes, batch, cap = 100_000, 1024, 53_248
    cfg = paper_gnn_config("sage", n_nodes=n_nodes, n_classes=16, fanout=15)
    cfg = dataclasses.replace(cfg, embedding=dataclasses.replace(
        cfg.embedding, lookup_impl="pallas"))
    ecfg = cfg.embedding_config()
    codes = jax.ShapeDtypeStruct((n_nodes, n_words(ecfg.c, ecfg.m)),
                                 jnp.uint32)
    state = jax.eval_shape(
        lambda c: init_gnn_train_state(jax.random.PRNGKey(0), cfg, codes=c),
        codes)
    state = jax.tree.map(lambda x: _sds(x, one_chip), state)

    # batch shapes depend on batch size, fanout and cap, not on the graph,
    # so a small graph gives the full-size frontier layout
    adj, labels = powerlaw_graph(0, 3000, avg_degree=10, n_classes=16)
    sampler = NeighborSampler(adj, cfg.fanouts, max_deg=64, seed=0)
    src = SageBatchSource(sampler, np.arange(3000), labels, batch,
                          frontier_cap=cap)
    frontier_batch = jax.tree.map(lambda x: _sds(np.asarray(x), one_chip),
                                  src.next_batch())

    step = make_gnn_train_step(cfg, interpret=False)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        state, frontier_batch).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, mem
