"""Profiler spans of the train loop and of the prefetch producer: a few
steps of ``run_training`` fed by a ``PrefetchIterator``, traced on the CPU
with ``jax.profiler.trace`` and read back from the ``.xplane.pb`` with
``jax.profiler.ProfileData``."""

import time
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph.engine import PrefetchIterator
from repro.train import CheckpointManager, LoopConfig, run_training

STEPS = 6
CKPT_EVERY = 2
SAMPLE_S = 0.003          # each stage takes milliseconds, so a span's own
GATHER_S = 0.001          # microseconds of overhead cannot blur the sums
LOOP_CHILDREN = ("repro.train.next_batch", "repro.train.dispatch",
                 "repro.train.sync", "repro.train.fence")


class SlowSource:
    """Deterministic batches that take a few milliseconds to sample."""

    def __init__(self):
        self.step = 0

    def next_batch(self):
        time.sleep(SAMPLE_S)
        x = np.full((8, 4), float(self.step), np.float32)
        self.step += 1
        return {"x": x}

    def state_dict(self):
        return {"step": self.step}

    def load_state_dict(self, state):
        self.step = int(state["step"])


def slow_gather(batch):
    time.sleep(GATHER_S)
    return batch


def train_step(state, batch):
    w = state["w"] - 0.1 * batch["x"].mean(0)
    return {"w": w}, {"loss": jnp.sum(w * w)}


def read_spans(logdir):
    """``{name: [(start_s, end_s)]}`` of every ``repro.*`` host span."""
    from jax.profiler import ProfileData
    path = max(Path(logdir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    spans = defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    s = ev.start_ns * 1e-9
                    spans[ev.name].append((s, s + ev.duration_ns * 1e-9))
    return {k: sorted(v) for k, v in spans.items()}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    logdir = tmp_path_factory.mktemp("trace")
    fenced = []
    with jax.profiler.trace(str(logdir)):
        # built inside the trace, and closed by run_training before it
        # ends, so every batch the producer timed is in the trace
        it = PrefetchIterator(SlowSource(), depth=2, code_gather=slow_gather)
        ckpt = CheckpointManager(str(tmp_path_factory.mktemp("ckpt")),
                                 async_save=False)
        res = run_training(train_step, {"w": jnp.zeros(4)}, it,
                           LoopConfig(total_steps=STEPS, ckpt_every=CKPT_EVERY),
                           ckpt=ckpt, fence=fenced.append)
    assert it._thread is None             # the producer has stopped
    return read_spans(logdir), it.stats(), res, fenced


def test_one_step_marker_per_step(traced):
    spans, _, res, fenced = traced
    assert len(res.losses) == STEPS and fenced == list(range(STEPS))
    assert len(spans["repro.train.step"]) == STEPS


def test_loop_spans_nest_in_their_step_and_do_not_overlap(traced):
    spans = traced[0]
    names = LOOP_CHILDREN + ("repro.train.checkpoint",)
    for lo, hi in spans["repro.train.step"]:
        inside = sorted((s, e, n) for n in names for s, e in spans.get(n, ())
                        if s >= lo and e <= hi)
        # one of each, in the loop's order, then a checkpoint where one is due
        assert [n for _, _, n in inside][:4] == list(LOOP_CHILDREN)
        assert [n for _, _, n in inside][4:] in ([], ["repro.train.checkpoint"])
        assert all(a[1] <= b[0] for a, b in zip(inside, inside[1:]))
    for n in LOOP_CHILDREN:
        assert len(spans[n]) == STEPS, n


def test_checkpoint_span_when_one_is_due(traced):
    spans = traced[0]
    due = spans["repro.train.step"][CKPT_EVERY - 1::CKPT_EVERY]
    assert len(spans["repro.train.checkpoint"]) == len(due)
    for (lo, hi), (s, e) in zip(due, spans["repro.train.checkpoint"]):
        assert lo <= s and e <= hi


def test_producer_spans_per_batch(traced):
    spans, stats = traced[0], traced[1]
    n = stats["n_produced"]
    assert n >= STEPS
    assert len(spans["repro.producer.put"]) == n
    assert len(spans["repro.producer.code_gather"]) == n
    # a producer stopped at the top of its loop opens one sample span more
    assert n <= len(spans["repro.producer.sample"]) <= n + 1


def test_producer_spans_time_what_stats_counts(traced):
    spans, stats = traced[0], traced[1]
    traced_s = sum(e - s for name, ivs in spans.items()
                   if name.startswith("repro.producer.") for s, e in ivs)
    counted_s = 1e-6 * (stats["sample_us"] + stats["code_gather_us"]
                        + stats["put_us"])
    assert counted_s >= stats["n_produced"] * (SAMPLE_S + GATHER_S)
    assert abs(traced_s - counted_s) <= 0.1 * counted_s, (traced_s, counted_s)
