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
import re

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


@pytest.fixture(scope="module")
def sage_step(one_chip):
    """The one-chip SAGE train step at the paper widths (batch 1024, fanout
    15, the 53,248-row frontier ``chip_smoke.py`` runs), compiled for the
    described chip from shapes of the state and of one frontier batch."""
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
    return jax.jit(step, donate_argnums=(0,)).lower(
        state, frontier_batch).compile()


def test_sage_train_step_compiles_for_v5e(sage_step):
    """The step compiles with the kernel in it and fits the chip's memory."""
    assert 'custom_call_target="tpu_custom_call"' in sage_step.as_text()
    mem = sage_step.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, mem


def test_sage_train_step_ops_carry_layer_scopes(sage_step):
    """The step's named scopes reach the compiled ops' ``op_name`` metadata
    (the backward's as ``transpose(jvp(decode))``), and the kernel's custom
    call keeps the instruction name the benchmark's trace reduction finds."""
    from bench import trace as tr
    text = sage_step.as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    scopes = {part for name in op_names for part in re.split(r"[/()]", name)}
    for scope in ("decode", "decoder_mlp", "sage", "head_loss", "adamw"):
        assert scope in scopes, scope
    assert any("transpose(jvp(decode))" in name for name in op_names)
    kernels = [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%([\w.-]+) = .*custom-call\(.*"
        r'custom_call_target="tpu_custom_call"', text, re.M)]
    assert kernels and all(tr.KERNEL.search(k) for k in kernels), kernels


@pytest.fixture(scope="module")
def owner_step(topo):
    """The four-chip owner-decode SAGE train step of the benchmark's
    ``sage-products.train-owner4`` cell (1,024 targets a shard, fanout 15,
    ``frontier_cap`` 122,880, owner caps 38,400 / 73,728) compiled for the
    described ``v5e:2x2``: the state replicated, the frontier and its owner
    plan placed as the runtime places them."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.configs.paper_gnn import paper_gnn_config
    from repro.core.codes import n_words
    from repro.graph import NeighborSampler, powerlaw_graph
    from repro.graph.engine import ShardedSageBatchSource
    from repro.parallel.policy import frontier_batch_shardings
    from repro.train import init_gnn_train_state, make_gnn_train_step

    n_nodes, per_shard, cap = 500_000, 1024, 122_880
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4), ("data",))
    cfg = paper_gnn_config("sage", n_nodes=n_nodes, n_classes=47, fanout=15)
    cfg = dataclasses.replace(cfg, embedding=dataclasses.replace(
        cfg.embedding, lookup_impl="owner:pallas"))
    ecfg = cfg.embedding_config()
    codes = jax.ShapeDtypeStruct((n_nodes, n_words(ecfg.c, ecfg.m)),
                                 jnp.uint32)
    state = jax.eval_shape(
        lambda c: init_gnn_train_state(jax.random.PRNGKey(0), cfg, codes=c),
        codes)
    state = jax.tree.map(
        lambda x: _sds(x, NamedSharding(mesh, PartitionSpec())), state)

    # the batch's shapes (caps, plan) do not depend on the graph's size
    adj, labels = powerlaw_graph(0, 3000, avg_degree=10, n_classes=47)
    sampler = NeighborSampler(adj, cfg.fanouts, max_deg=64, seed=0)
    src = ShardedSageBatchSource(sampler, np.arange(3000), labels, per_shard,
                                 n_shards=4, frontier_cap=cap, owner_plan=True,
                                 owner_cap=38_400, owner_unique_cap=73_728)
    batch = src.next_batch()
    assert batch["frontier"].plan is not None
    batch = jax.tree.map(lambda x, s: _sds(np.asarray(x), s), batch,
                         frontier_batch_shardings(batch, mesh))

    step = make_gnn_train_step(cfg, interpret=False, mesh=mesh)
    with jax.default_matmul_precision("highest"):
        return jax.jit(step, donate_argnums=(0,)).lower(state, batch).compile()


def test_owner_train_step_compiles_for_v5e_2x2(owner_step):
    """The owner step compiles with the kernel in it, once (the backward's
    repeated decode of the owned rows leaves no second kernel call), and
    fits each chip's memory."""
    text = owner_step.as_text()
    kernels = re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = .*custom-call\(.*"
                         r'custom_call_target="tpu_custom_call"', text, re.M)
    assert kernels == ["hash_decode.1"], kernels
    mem = owner_step.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, mem


def test_owner_exchange_collectives_carry_their_scope(owner_step):
    """Every all-to-all of the owner exchange, forward and backward, and the
    codebook gradient's psum carry ``owner_exchange`` under the step's
    ``decode`` scope in their ``op_name``."""
    from repro.core.backend import EXCHANGE_SCOPE
    ops = re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = .*?"
                     r'metadata=\{op_name="([^"]*)"', owner_step.as_text(), re.M)
    a2a = [(n, o) for n, o in ops if re.match(r"all[-_]to[-_]all", n)]
    assert {o.split("/shard_map/")[0] for _, o in a2a} == {
        "jit(train_step)/jvp(decode)", "jit(train_step)/transpose(jvp(decode))"}
    assert all(f"/{EXCHANGE_SCOPE}/" in o for _, o in a2a), a2a
    psums = [o for n, o in ops if n.startswith("all-reduce") and "decode" in o]
    assert psums and all(
        o.startswith("jit(train_step)/transpose(jvp(decode))")
        and f"/{EXCHANGE_SCOPE}/psum" in o for o in psums), psums
