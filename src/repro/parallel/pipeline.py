"""GPipe-style pipeline parallelism as a shard_map primitive (DESIGN.md §6).

``gpipe(stage_fn, stage_params, microbatches, mesh, axis)`` runs
``n_stages = mesh.shape[axis]`` pipeline stages, one per shard of ``axis``:
each schedule tick, every stage applies its layer chunk to its live
microbatch and rotates the result to the next stage with
``lax.ppermute`` — the classic circular-pipeline schedule
(n_micro + n_stages − 1 ticks; bubble fraction (S−1)/(M+S−1)).

The rotation is differentiable (ppermute's transpose is the reverse
permutation), so the same primitive serves training; the bubble cost is
analytic, not hidden — report it alongside the roofline when using PP
(the dry-run's per-chip FLOPs don't model idle ticks).

Scope note: this is the PP building block (correctness-tested vs the
sequential reference on a host mesh).  The production profiles in
launch/profiles.py use TP/EP/DP — at the assigned shapes those dominated PP
in napkin math (16 stages on the model axis give a 48% bubble at 16
microbatches); PP becomes the right tool at longer pipelines-per-pod or
with interleaved schedules, both of which layer on top of this primitive.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


Array = jnp.ndarray


def _pipeline_shard(stage_params, microbatches, *, stage_fn: Callable,
                    axis: str, n_stages: int):
    """Runs on one stage shard.  stage_params: this stage's layer stack
    (leading dim = layers-per-stage); microbatches (M, mb, S, D) replicated."""
    stage = jax.lax.axis_index(axis)
    # shard_map keeps the sharded stage dim at local size 1: squeeze it
    stage_params = jax.tree.map(lambda p: p[0], stage_params)
    M = microbatches.shape[0]
    ticks = M + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    mb_shape = microbatches.shape[1:]

    def tick(carry, t):
        live, out_acc = carry
        # stage 0 injects microbatch t (or zeros in the drain phase)
        inject = jnp.where(
            t < M,
            jax.lax.dynamic_index_in_dim(microbatches, jnp.minimum(t, M - 1),
                                         keepdims=False),
            jnp.zeros(mb_shape, microbatches.dtype))
        x = jnp.where(stage == 0, inject, live)
        y = stage_fn(stage_params, x)
        # the final stage's output for microbatch (t - (S-1)) is ready
        emit_idx = t - (n_stages - 1)
        is_emit = (emit_idx >= 0) & (stage == n_stages - 1)
        out_acc = jax.lax.cond(
            emit_idx >= 0,
            lambda acc: acc.at[jnp.maximum(emit_idx, 0)].add(
                jnp.where(is_emit, y, 0.0)),
            lambda acc: acc,
            out_acc)
        live_next = jax.lax.ppermute(y, axis, perm)
        return (live_next, out_acc), None

    init = (jnp.zeros(mb_shape, microbatches.dtype),
            jnp.zeros((M,) + mb_shape, microbatches.dtype))
    (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(ticks))
    # outputs are zero everywhere except the final stage: psum broadcasts
    return jax.lax.psum(outputs, axis)


def gpipe(stage_fn: Callable, stage_params, microbatches: Array,
          mesh: Mesh, axis: str = "model") -> Array:
    """Pipeline-parallel apply.

    stage_fn(params_one_stage, x (mb, S, D)) -> (mb, S, D)
    stage_params: pytree with leading dim n_stages (sharded over ``axis``)
    microbatches: (M, mb, S, D), replicated over ``axis``
    Returns (M, mb, S, D) — equal to running all stages sequentially.
    """
    n_stages = mesh.shape[axis]

    def strip_stage(spec_tree):
        return jax.tree.map(lambda _: P(axis), spec_tree)

    fn = partial(_pipeline_shard, stage_fn=stage_fn, axis=axis,
                 n_stages=n_stages)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(strip_stage(stage_params), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, microbatches)


def pipeline_reference(stage_fn: Callable, stage_params, microbatches: Array
                       ) -> Array:
    """Sequential oracle: run every stage on every microbatch in order."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def run_mb(x):
        for s in range(n_stages):
            params_s = jax.tree.map(lambda p: p[s], stage_params)
            x = stage_fn(params_s, x)
        return x

    return jax.vmap(run_mb)(microbatches)
