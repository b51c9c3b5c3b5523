"""The open-loop schedule and the latency arithmetic."""

import numpy as np

from bench import load

MIX = dict(rate=300.0, pool=(1000, 1500), zipf=1.1, size_min=1, size_max=256,
           perm_seed=0)


def test_schedule_is_deterministic_in_the_seed():
    a = load.schedule(2 ** 31 + 5, 2.0, **MIX)
    b = load.schedule(2 ** 31 + 5, 2.0, **MIX)
    assert len(a) == len(b) == 600
    for (ta, ia), (tb, ib) in zip(a, b):
        assert ta == tb and np.array_equal(ia, ib)
    c = load.schedule(7, 2.0, **MIX)
    assert any(not np.array_equal(ia, ic) for (_, ia), (_, ic) in zip(a, c))


def test_every_seed_gets_the_same_work_in_another_order():
    a, b = load.schedule(1, 2.0, **MIX), load.schedule(2, 2.0, **MIX)
    assert sorted(i.size for _, i in a) == sorted(i.size for _, i in b)
    ga = np.sort(np.diff([t for t, _ in a]))
    gb = np.sort(np.diff([t for t, _ in b]))
    assert np.allclose(np.sort(np.concatenate([ga, [2.0 - a[-1][0]]])),
                       np.sort(np.concatenate([gb, [2.0 - b[-1][0]]])))
    offs = np.array([t for t, _ in a])
    assert offs[0] == 0.0 and np.all(np.diff(offs) > 0) and offs[-1] < 2.0


def test_ids_are_zipf_over_a_fixed_popularity_order():
    ids = np.concatenate([i for _, i in load.schedule(3, 20.0, **MIX)])
    assert ids.min() >= 1000 and ids.max() < 1500
    counts = np.bincount(ids - 1000, minlength=500)
    order = 1000 + np.random.default_rng(0).permutation(500)
    top = counts[order[0] - 1000]
    assert top == counts.max()
    # Zipf(1.1): the hottest id draws about 1 / H(500, 1.1) of all ids
    share = 1.0 / (1.0 / np.arange(1, 501) ** 1.1).sum()
    assert abs(top / ids.size - share) < 0.1 * share


def test_sizes_are_log_uniform_within_bounds():
    sizes = np.array([i.size for _, i in load.schedule(4, 10.0, **MIX)])
    assert sizes.min() == 1 and sizes.max() == 256
    assert abs(np.median(np.log(sizes)) - np.log(16)) < 0.3


def test_latency_from_due_time_and_shed_requests_miss():
    due = np.array([0.0, 0.1, 0.2, 0.3])
    done = np.array([0.05, 0.4, np.nan, 0.35])     # the third was shed
    lat = load.latencies(due, done, waited=10.0)
    assert np.allclose(lat, [0.05, 0.3, 9.8, 0.05])
    # with one request in four missing, the 99th percentile is the miss
    assert load.percentile(lat, 99) > 9.0
    assert np.isclose(load.percentile(np.arange(101.0), 99), 99.0)


def test_host_watch_sees_a_collection_and_a_stall():
    import gc
    import time

    from bench.drivers import HostWatch

    with HostWatch() as w:
        gc.collect()
        t = time.perf_counter()
        sum(range(5_000_000))          # C code holding the interpreter
        held = time.perf_counter() - t
        time.sleep(0.03)
    s = w.summary
    assert s["gc_collections"][2] >= 1 and s["gc_pause_max_s"] > 0
    # the watch thread could not run while the sum held the interpreter,
    # and the process was on the CPU all that time
    assert s["stall_max_s"] > 0.5 * held
    assert s["stall_cpu_s"] > 0.5 * s["stall_max_s"]
    assert gc.callbacks.count(w._on_gc) == 0
