"""Distribution tests — run in SUBPROCESSES with a forced 8-device host
platform so the main pytest process keeps its single-device view."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_moe_ep_equals_single_shard():
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.sharding import make_mesh, use_sharding
        from repro.nn.moe import MoEConfig, init_moe, moe_ffn, moe_ffn_ep
        key = jax.random.PRNGKey(0)
        cfg = MoEConfig(d_model=32, d_ff=64, n_experts=8, top_k=2, capacity_factor=4.0)
        p = init_moe(key, cfg)
        x = jax.random.normal(key, (64, 32))
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_sharding(mesh):
            out_ep = jax.jit(lambda p, x: moe_ffn_ep(p, x, cfg))(p, x)
        np.testing.assert_allclose(np.asarray(out_ep), np.asarray(moe_ffn(p, x, cfg)),
                                   rtol=2e-4, atol=2e-4)
    """)


def test_sharded_train_step_matches_single_device():
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.parallel.sharding import make_mesh, use_sharding
        from repro.parallel.policy import state_shardings, batch_shardings
        from repro.train.step import TrainHyper, init_train_state, make_train_step
        cfg = reduced(get_config('qwen1.5-0.5b'))
        key = jax.random.PRNGKey(0)
        state = init_train_state(key, cfg)
        tokens = jax.random.randint(key, (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        step = make_train_step(cfg, TrainHyper(total_steps=10))
        s1, m1 = jax.jit(step)(jax.tree.map(lambda x: x, state), batch)

        mesh = make_mesh((4, 2), ('data', 'model'))
        with use_sharding(mesh):
            st_sh = state_shardings(cfg, jax.eval_shape(lambda: init_train_state(key, cfg)), mesh)
            b_sh = batch_shardings(jax.eval_shape(lambda: batch), mesh)
            s2, m2 = jax.jit(step, in_shardings=(st_sh, b_sh),
                             out_shardings=(st_sh, None))(state, batch)
        np.testing.assert_allclose(float(m1['loss']), float(m2['loss']), rtol=1e-4)
        for a, b in zip(jax.tree.leaves(s1['params']), jax.tree.leaves(s2['params'])):
            if a.dtype.kind == 'f':
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=5e-3, atol=5e-4)
        print('sharded == single-device OK')
    """)


def test_dp_over_model_strategy_matches():
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.parallel.sharding import make_mesh, use_sharding
        from repro.parallel.policy import (Strategy, rules_for, state_shardings,
                                           batch_shardings)
        from repro.train.step import TrainHyper, init_train_state, make_train_step
        cfg = reduced(get_config('qwen1.5-0.5b'))
        key = jax.random.PRNGKey(0)
        state = init_train_state(key, cfg)
        tokens = jax.random.randint(key, (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        step = make_train_step(cfg, TrainHyper(total_steps=10))
        s1, m1 = jax.jit(step)(jax.tree.map(lambda x: x, state), batch)
        strat = Strategy(dp_over_model=True)
        mesh = make_mesh((4, 2), ('data', 'model'))
        with use_sharding(mesh, rules_for(strat, mesh)):
            st_sh = state_shardings(cfg, jax.eval_shape(lambda: init_train_state(key, cfg)), mesh, strat)
            b_sh = batch_shardings(jax.eval_shape(lambda: batch), mesh, strat)
            s2, m2 = jax.jit(step, in_shardings=(st_sh, b_sh),
                             out_shardings=(st_sh, None))(state, batch)
        np.testing.assert_allclose(float(m1['loss']), float(m2['loss']), rtol=1e-4)
    """)


def test_elastic_restart_different_mesh():
    """Checkpoint on mesh (4,2), restore + continue on mesh (2,2) with 4
    devices — elastic-scaling restart."""
    run_devices("""
        import os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.parallel.sharding import make_mesh, use_sharding
        from repro.parallel.policy import state_shardings, batch_shardings
        from repro.train.checkpoint import CheckpointManager
        from repro.train.step import TrainHyper, init_train_state, make_train_step
        cfg = reduced(get_config('qwen1.5-0.5b'))
        key = jax.random.PRNGKey(0)
        state = init_train_state(key, cfg)
        tokens = jax.random.randint(key, (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'labels': tokens}
        step = make_train_step(cfg, TrainHyper(total_steps=10))

        d = tempfile.mkdtemp()
        ck = CheckpointManager(d, async_save=False)
        mesh8 = make_mesh((4, 2), ('data', 'model'))
        with use_sharding(mesh8):
            st_sh = state_shardings(cfg, jax.eval_shape(lambda: init_train_state(key, cfg)), mesh8)
            b_sh = batch_shardings(jax.eval_shape(lambda: batch), mesh8)
            s1, _ = jax.jit(step, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None))(state, batch)
        ck.save(1, s1)

        # "restart" on a smaller mesh: a real restart runs in a fresh process,
        # so re-create the step closure (also keeps jax<0.5 from reusing the
        # mesh8-traced jaxpr — its trace cache ignores the mesh context)
        step = make_train_step(cfg, TrainHyper(total_steps=10))
        mesh4 = make_mesh((2, 2), ('data', 'model'))
        restored, _ = ck.restore(1, jax.eval_shape(lambda: init_train_state(key, cfg)))
        with use_sharding(mesh4):
            st_sh4 = state_shardings(cfg, jax.eval_shape(lambda: init_train_state(key, cfg)), mesh4)
            restored = jax.tree.map(lambda arr, sh: jax.device_put(arr, sh), restored, st_sh4)
            b_sh4 = batch_shardings(jax.eval_shape(lambda: batch), mesh4)
            s2, m2 = jax.jit(step, in_shardings=(st_sh4, b_sh4), out_shardings=(st_sh4, None))(restored, batch)
        assert np.isfinite(float(m2['loss']))
        assert int(s2['step']) == 2
        print('elastic restart OK')
    """)


def test_gradient_compression_int8():
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.parallel.sharding import make_mesh
        from repro.optim.compress import psum_compressed, compress_gradients_int8, decompress_gradients_int8

        g = jax.random.normal(jax.random.PRNGKey(0), (1024,))
        q, s = compress_gradients_int8(g)
        back = decompress_gradients_int8(q, s, g.shape)
        rel = float(jnp.linalg.norm(back - g) / jnp.linalg.norm(g))
        assert rel < 0.01, rel   # int8 block quant ~0.4% error

        mesh = make_mesh((8,), ('data',))
        gs = jax.random.normal(jax.random.PRNGKey(1), (8, 256))
        def worker(g, r):
            return psum_compressed(g, 'data', r)
        out, res = jax.jit(jax.shard_map(worker, mesh=mesh,
            in_specs=(P('data', None), P('data', None)),
            out_specs=(P('data', None), P('data', None)), check_vma=False))(
            gs[:, None, :].reshape(8, 256) * 0 + gs, jnp.zeros((8, 256)))
        ref = jnp.mean(gs, axis=0)
        got = out[0]
        rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
        assert rel < 0.05, rel
        print('psum_compressed OK', rel)
    """, n_devices=8)


def test_gpipe_matches_sequential():
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.sharding import make_mesh
        from repro.parallel.pipeline import gpipe, pipeline_reference
        S, M, mb, T, D = 4, 8, 2, 8, 16
        key = jax.random.PRNGKey(0)
        stage_params = {
            'w': jax.random.normal(key, (S, 2, D, D)) * 0.1,   # 2 layers/stage
            'b': jax.random.normal(jax.random.fold_in(key, 1), (S, 2, D)) * 0.1,
        }
        def stage_fn(p, x):
            for i in range(2):
                x = jnp.tanh(x @ p['w'][i] + p['b'][i])
            return x
        xs = jax.random.normal(jax.random.fold_in(key, 2), (M, mb, T, D))
        mesh = make_mesh((2, 4), ('data', 'model'))
        out = gpipe(stage_fn, stage_params, xs, mesh, axis='model')
        ref = pipeline_reference(stage_fn, stage_params, xs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

        # differentiable: grads match the sequential reference
        def loss_pp(p):
            return (gpipe(stage_fn, p, xs, mesh, axis='model') ** 2).sum()
        def loss_ref(p):
            return (pipeline_reference(stage_fn, p, xs) ** 2).sum()
        g1 = jax.grad(loss_pp)(stage_params)
        g2 = jax.grad(loss_ref)(stage_params)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
        print('gpipe fwd+bwd == sequential OK')
    """)
