"""The readers of the train loop's spans (``bench/spans.py`` and the
``next_batch_share``, ``idle_input_share``, ``idle_sync_share`` and
``idle_loop_share`` metrics), on hand-built traces."""

import numpy as np
import pytest

from bench import run
from bench import spans as sp
from bench import trace as tr
from bench.drivers import Reading

SPLIT = ("idle_input_share.train", "idle_sync_share.train",
         "idle_loop_share.train")
READERS = ("next_batch_share.train",) + SPLIT


def read(name, reading):
    return run.metric_reader(run.ROOT, name)(reading)


def reading(ops, host, chips=1, kind="train"):
    """A reading as ``bench.drivers.read_trace`` fills it."""
    t = tr.Trace(ops, host)
    win = t.window()
    r = Reading(kind=kind, cfg={}, peak={}, chips=chips, counters={},
                trace=t, window=win)
    devs = r.device_ids()
    r.window_s = win[1] - win[0]
    r.busy_s = float(np.mean([t.busy(d, win) for d in devs]))
    return r


def loop(*steps):
    """Host spans of the loop thread: each step is ``(lo, hi, children)``."""
    out = [("python", "bench.window", 0.0, 10.0)]
    for lo, hi, children in steps:
        out.append(("python", sp.STEP, lo, hi))
        out += [("python", f"repro.train.{n}", s, e) for n, s, e in children]
    return out


# device 0 is busy 0-2, 3-5 and 6-7 of the window 0-10 (its first op began
# before the window): idle 2-3, 5-6 and 7-10
OPS = [("a", -1.0, 2.0), ("b", 3.0, 4.0), ("c", 3.5, 5.0), ("d", 6.0, 7.0)]
STEPS = loop(
    (0.0, 4.0, [("next_batch", 1.5, 2.5), ("dispatch", 2.5, 2.7),
                ("sync", 2.7, 3.5), ("fence", 3.5, 3.6)]),
    (4.0, 8.5, [("next_batch", 4.0, 4.2), ("dispatch", 4.2, 5.2),
                ("sync", 5.2, 6.5), ("fence", 6.5, 6.6)]))
# the producer's thread is a `python` line too; its spans count for nothing
PRODUCER = [("python", "repro.producer.sample", 2.0, 3.0),
            ("python", "repro.producer.put", 7.0, 10.0)]


def test_each_idle_gap_lands_by_what_the_loop_was_doing():
    r = reading({0: OPS}, STEPS + PRODUCER)
    # 2-2.5 in next_batch; 2.7-3 and 5.2-6 in sync; 2.5-2.7 in dispatch,
    # 5-5.2 in dispatch, 7-8.5 after the fence and 8.5-10 after the last step
    assert np.isclose(read("idle_input_share.train", r), 5.0)
    assert np.isclose(read("idle_sync_share.train", r), 11.0)
    assert np.isclose(read("idle_loop_share.train", r), 34.0)
    assert np.isclose(read("next_batch_share.train", r), 12.0)


@pytest.mark.parametrize("chips", [1, 2])
def test_idle_split_adds_up_to_device_idle_share(chips):
    ops = {0: OPS, 1: [("e", 0.5, 1.5), ("f", 2.2, 5.5), ("g", 9.0, 11.0)]}
    r = reading(ops, STEPS + PRODUCER, chips=chips)
    parts = [read(name, r) for name in SPLIT]
    assert min(parts) >= 0.0
    assert abs(sum(parts) - read("device_idle_share.train", r)) < 1e-9


def test_spans_are_clipped_to_the_window():
    host = loop((-2.0, 3.0, [("next_batch", -2.0, 1.0), ("sync", 1.0, 3.0)]),
                (3.0, 12.0, [("next_batch", 3.0, 4.0), ("sync", 9.5, 12.0)]))
    r = reading({0: [("a", 0.5, 9.8)]}, host)
    assert np.isclose(read("next_batch_share.train", r), 20.0)
    assert np.isclose(read("idle_input_share.train", r), 5.0)     # 0-0.5
    assert np.isclose(read("idle_sync_share.train", r), 2.0)      # 9.8-10
    assert np.isclose(read("idle_loop_share.train", r), 0.0)


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_in_a_serve_reading(name):
    assert read(name, reading({0: OPS}, STEPS, kind="serve")) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_a_trace(name):
    assert read(name, Reading(kind="train", cfg={}, peak={}, chips=1,
                              counters={})) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_the_loops_step_spans(name):
    """A program that writes no loop spans: nothing to read, which
    ``bench.run`` refuses for a metric the cell lists."""
    host = [(ln, n, s, e) for ln, n, s, e in STEPS if n != sp.STEP]
    assert read(name, reading({0: OPS}, host)) is None


def test_overlap_of_two_unions():
    a = tr.union([(0, 2), (3, 5), (8, 9)])
    b = tr.union([(1, 4), (4.5, 8.5)])
    assert np.isclose(sp.overlap(a, b), 1 + 1 + 0.5 + 0.5)
    assert sp.overlap(a, []) == 0.0
