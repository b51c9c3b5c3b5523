"""Training loop with fault tolerance + straggler monitoring.

Responsibilities:
  * auto-resume: on start, restore the newest valid checkpoint (params,
    optimizer, step counter, data-pipeline state) and continue — the
    restart path after a node failure.
  * periodic + final checkpointing (async, atomic).
  * straggler monitor: per-step wall-time EWMA; steps slower than
    ``straggler_factor``× the EWMA are logged and counted (on a fleet this
    signal feeds the backup-worker / re-slice policy; here it is the hook +
    test surface).
  * simple metrics log (CSV) for the examples/benchmarks.
  * step fences for elastic training: an optional ``fence`` callback runs
    every ``fence_every`` completed steps; raising ``FenceInterrupt`` from
    it stops the loop cleanly at a step boundary (state is consistent, no
    final checkpoint is written) — the hook ``repro.elastic.manager`` uses
    to detect dead shards and hand control to the rescale path.
  * profiler spans: each iteration is a ``repro.train.step`` step marker
    (``jax.profiler.StepTraceAnnotation``) holding the non-overlapping
    spans ``repro.train.next_batch``, ``.dispatch``, ``.sync``, ``.fence``
    and ``.checkpoint``, on the device trace's clock when a profiler trace
    is taken; without one each costs about a microsecond.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.train.checkpoint import CheckpointManager


class FenceInterrupt(Exception):
    """Raised by a step-fence callback to stop the loop at a step boundary.

    The loop returns normally with ``LoopResult.interrupted_at`` set to the
    number of completed steps; no final checkpoint is written, because the
    interrupting party (e.g. ``repro.elastic.ElasticManager``) owns what
    happens next — peer transfer, rescale, or abort."""


@dataclasses.dataclass
class LoopConfig:
    total_steps: int
    ckpt_every: int = 200
    log_every: int = 20
    straggler_factor: float = 3.0
    ewma_alpha: float = 0.1
    fence_every: int = 1   # steps between fence-callback invocations


@dataclasses.dataclass
class LoopResult:
    state: Any
    losses: list
    step_times: list
    stragglers: int
    resumed_from: Optional[int]
    interrupted_at: Optional[int] = None   # completed steps at FenceInterrupt


def run_training(
    train_step: Callable,
    state: Any,
    data_iter,
    loop_cfg: LoopConfig,
    ckpt: Optional[CheckpointManager] = None,
    to_device: Callable = lambda b: b,
    on_metrics: Optional[Callable[[int, Dict], None]] = None,
    extra_base: Optional[Dict] = None,
    prejitted: bool = False,
    fence: Optional[Callable[[int], None]] = None,
    topology: Optional[Dict] = None,
) -> LoopResult:
    """``extra_base``: JSON-able dict merged into every checkpoint's
    ``extra`` manifest (e.g. the GraphRuntime spec, so a checkpoint is
    self-describing enough to rebuild its whole pipeline).

    ``prejitted``: ``train_step`` is already a donated-state jitted
    callable — use it as-is so repeat ``run_training`` calls (chunked
    training) reuse its compile cache instead of re-tracing.

    ``fence(step)``: called after every ``fence_every``-th completed step
    (``step`` is the 0-based index just finished) and may raise
    ``FenceInterrupt`` to stop the loop at that boundary.

    ``topology``: JSON-able shard-layout descriptor stamped into every
    checkpoint manifest and validated on auto-resume (a mismatched resume
    raises ``repro.train.TopologyMismatch``)."""
    resumed_from = None
    start_step = 0
    if ckpt is not None:
        restored = ckpt.restore_latest(state, expect_topology=topology)
        if restored is not None:
            start_step, state, extra = restored
            resumed_from = start_step
            if hasattr(data_iter, "load_state_dict") and "data" in extra:
                data_iter.load_state_dict(extra["data"])

    losses, step_times = [], []
    stragglers = 0
    interrupted_at = None
    ewma = None
    jitted = train_step if prejitted else jax.jit(train_step,
                                                  donate_argnums=(0,))

    try:
        for step in range(start_step, loop_cfg.total_steps):
            with StepTraceAnnotation("repro.train.step", step_num=step):
                with TraceAnnotation("repro.train.next_batch"):
                    batch = to_device(data_iter.next_batch())
                t0 = time.perf_counter()
                with TraceAnnotation("repro.train.dispatch"):
                    state, metrics = jitted(state, batch)
                with TraceAnnotation("repro.train.sync"):
                    loss = float(metrics["loss"])   # blocks: device sync = honest timing
                dt = time.perf_counter() - t0
                step_times.append(dt)
                losses.append(loss)

                if ewma is None:
                    ewma = dt
                else:
                    if dt > loop_cfg.straggler_factor * ewma:
                        stragglers += 1
                    ewma = (1 - loop_cfg.ewma_alpha) * ewma + loop_cfg.ewma_alpha * dt

                if on_metrics and step % loop_cfg.log_every == 0:
                    on_metrics(step, {"loss": loss, "step_time": dt, "ewma": ewma})

                if fence is not None and (step + 1) % loop_cfg.fence_every == 0:
                    try:
                        with TraceAnnotation("repro.train.fence"):
                            fence(step)
                    except FenceInterrupt:
                        interrupted_at = step + 1
                        break

                if ckpt is not None and (step + 1) % loop_cfg.ckpt_every == 0:
                    with TraceAnnotation("repro.train.checkpoint"):
                        extra = dict(extra_base or {})
                        if hasattr(data_iter, "state_dict"):
                            extra["data"] = data_iter.state_dict()
                        ckpt.save(step + 1, state, extra, topology=topology)

        if ckpt is not None and interrupted_at is None:
            extra = dict(extra_base or {})
            if hasattr(data_iter, "state_dict"):
                extra["data"] = data_iter.state_dict()
            ckpt.save(loop_cfg.total_steps, state, extra, topology=topology)
            ckpt.wait()
    finally:
        # async prefetch iterators (repro.graph.engine.PrefetchIterator) own a
        # producer thread; stop it whether the loop finished or raised
        if hasattr(data_iter, "close"):
            data_iter.close()

    return LoopResult(state=state, losses=losses, step_times=step_times,
                      stragglers=stragglers, resumed_from=resumed_from,
                      interrupted_at=interrupted_at)
