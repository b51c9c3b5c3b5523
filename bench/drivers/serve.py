"""Open-loop GraphSAGE serving through ``GraphRuntime.serve``'s batcher.

Set-up builds the runtime, installs the benchmark's weights and codes,
starts the serving tier (``ServingBatcher`` over ``GraphInferenceEngine``),
compiles every (request-count bucket x miss bucket) shape the engine can
use, and fills the hot-node cache with warm-up requests from another
stream of the same mix.

The window sends each request of the seed's schedule at its due time,
whether or not earlier ones have been answered.  Every request is timed
from its due time to the moment its result is available; a request the
batcher sheds counts as missing (its latency is the whole time the run
waited for it).  ``serve_completed_per_s`` is the requests completed inside
the window over its length; ``serve_p99_ms`` is the 99th percentile over
all requests due in it.

Afterwards a seeded sample of the completed requests, the largest among
them, is recomputed by the reference from the same weights, codes and
sampled neighbours; the run compares the logits.
"""

from __future__ import annotations

import time
from concurrent.futures import TimeoutError as FutureTimeout
from functools import partial

import numpy as np

from bench import graphs, load, model
from bench.drivers import (Context, HostWatch, Outcome, Reading, free,
                           non_edges, now, peak_memory_bytes, program_graph,
                           read_trace, runtime_spec, settle, traced)
from bench.reference import sage as ref


def warm_shapes(engine, pool) -> int:
    """Compile every shape the engine's forward can be called with: one
    microbatch per request-count bucket records the batch it builds, then
    each is run at every miss bucket.  Returns the shapes warmed."""
    import jax
    captured = {}
    forward = engine._forward

    def spy(n_dec):
        fn = forward(n_dec)

        def call(params, fb, cache_state):
            captured[int(fb.unique.shape[0])] = fb
            return fn(params, fb, cache_state)
        return call

    engine._forward = spy
    try:
        kb = 1
        while kb <= engine.max_coalesce:
            engine.serve_many([np.arange(pool[0], pool[0] + 4, dtype=np.int32) + i
                               for i in range(kb)])
            kb *= 2
    finally:
        del engine._forward
    n = 0
    for cap, fb in captured.items():
        buckets = {engine._bucket(k, cap) for k in [0, cap] + [1 << i for i in range(31)]}
        for n_dec in sorted(buckets):
            jax.block_until_ready(engine._forward(n_dec)(engine.params, fb,
                                                         engine._cache_state))
            n += 1
    return n


def _mark(done, i, _fut):
    done[i] = time.perf_counter()


def run(ctx: Context) -> Outcome:
    import jax
    from repro.graph.runtime import GraphRuntime
    from repro.serving.batcher import BatchingSpec, Overloaded

    cfg, mc, tf = ctx.cfg, ctx.cfg["model"], ctx.traffic
    pool = tuple(cfg["serve"]["request_pool"])
    phases = {"start_s": now() - ctx.t0}
    graph = graphs.load(cfg["graph"], mc["c"], mc["m"])
    phases["graph_s"] = now() - ctx.t0
    spec = runtime_spec(cfg, ctx.seed, graph.n_nodes)
    rt = GraphRuntime.from_spec(spec, graph=program_graph(graph))
    phases["runtime_s"] = now() - ctx.t0
    rt.state["params"] = model.install(rt.state["params"], model.init(ctx.seed, mc),
                                       jax.numpy.asarray(graph.codes), mc)
    rt.close()                       # serving never reads the training feed
    batcher = rt.serve(batching=BatchingSpec(**cfg["serve"]["batching"]))
    engine = batcher.engine
    shapes = warm_shapes(engine, pool)
    phases["shapes_s"] = now() - ctx.t0

    mix = dict(rate=tf["rate_per_s"], pool=pool, zipf=tf["zipf"],
               size_min=tf["size_min"], size_max=tf["size_max"],
               perm_seed=cfg["graph"]["graph_seed"])
    warm = load.schedule(ctx.seed + 1, tf["warmup_requests"] / tf["rate_per_s"], **mix)
    depth = cfg["serve"]["batching"]["queue_depth"] // 2
    for i in range(0, len(warm), depth):
        for f in [batcher.submit(ids) for _, ids in warm[i:i + depth]]:
            f.result()
    engine.reset()
    sched = load.schedule(ctx.seed, ctx.seconds, **mix)
    n = len(sched)
    done = np.full(n, np.nan)
    late = np.zeros(n)
    shed = np.zeros(n, bool)
    futs = [None] * n
    settle()
    setup_s = now() - ctx.t0

    # -- the window ----------------------------------------------------------
    b0, e0, c0 = batcher.stats(), engine.stats(), ctx.compiles.n
    with HostWatch(tick=not ctx.trace) as host, traced(ctx):
        w0 = now()
        for i, (off, ids) in enumerate(sched):
            due = w0 + off
            wait = due - now()
            if wait > 0:
                time.sleep(wait)
            late[i] = now() - due
            try:
                futs[i] = batcher.submit(ids)
            except Overloaded:
                shed[i] = True
                continue
            futs[i].add_done_callback(partial(_mark, done, i))
        wait = w0 + ctx.seconds - now()
        if wait > 0:
            time.sleep(wait)
        w1 = now()
    b1, e1, compiles = batcher.stats(), engine.stats(), ctx.compiles.n - c0
    due_t = w0 + np.array([off for off, _ in sched])
    sizes = np.array([ids.shape[0] for _, ids in sched])
    completed_in = int(np.sum(done <= w1))

    # -- drain: a late answer is late, not wrong -----------------------------
    limit = w1 + tf["drain_s"]
    never = errors = 0
    for f in futs:
        if f is None:
            continue
        try:
            f.result(timeout=max(0.0, limit - now()))
        except FutureTimeout:
            never += 1
        except Exception:          # noqa: BLE001 — an error is a failed request
            errors += 1
    waited = now()
    lat = load.latencies(due_t, done, waited)

    rng = np.random.default_rng(np.random.SeedSequence([int(ctx.seed), 0xC0FFEE]))
    ok = [i for i in range(n) if futs[i] is not None and futs[i].done()
          and futs[i].exception() is None]
    largest = max(ok, key=lambda i: sizes[i])
    rest = [i for i in ok if i != largest]
    k = min(tf["sample_requests"] - 1, len(rest))
    pick = sorted([largest] + rng.choice(rest, k, replace=False).tolist())
    served = [futs[i].result().logits for i in pick]
    levels = [engine.frontier_for(sched[i][1]).levels() for i in pick]
    mem = peak_memory_bytes(ctx.chips)

    window_ok = done[~np.isnan(done)]
    counters = {
        "window_s": w1 - w0, "requests_due": n,
        "completed": b1["completed"] - b0["completed"],
        "microbatches": b1["microbatches"] - b0["microbatches"],
        "engine_requests": e1["requests"] - e0["requests"],
        "rows_decoded": e1["rows_decoded"] - e0["rows_decoded"],
        "hits": e1.get("hits", 0) - e0.get("hits", 0),
        "misses": e1.get("misses", 0) - e0.get("misses", 0),
        "target_rows": float(sizes[done <= w1].sum()),
        "compiles_in_window": compiles,
    }
    reading = Reading(kind="serve", cfg=cfg, peak=ctx.peak, chips=ctx.chips,
                      counters=counters)
    if ctx.trace:
        read_trace(ctx, reading)

    free(batcher, rt)
    batcher = engine = rt = None

    # -- the reference -------------------------------------------------------
    t_ref = now()
    weights = model.init(ctx.seed, mc)
    want = ref.serve_logits(weights, graph.codes, levels, mc, prec="highest")
    if ctx.system == "control":
        served = ref.serve_logits(weights, graph.codes, levels, mc, prec="high")
    if ctx.system == "fault_altered":
        served[0] = served[0].copy()
        served[0][0, 0] += 1e-3 * float(np.abs(served[0]).max())
    want = [w[: s.shape[0]] for w, s in zip(want, served)]
    scale = max(float(np.abs(w).max()) for w in want)
    gap = max(float(np.abs(s - w).max()) for s, w in zip(served, want)) / scale
    lim = cfg["limits"]
    checks = [
        ("logit_gap", gap, lim["logit_gap"]),
        ("non_edges", float(sum(non_edges(graph, lv) for lv in levels)), 0.0),
        ("never_answered", float(never), 0.0),
        ("compiles_in_window", float(compiles), 0.0),
    ]
    correct = all(np.isfinite(v) and v <= l for _, v, l in checks)
    diag = {
        "reference_s": now() - t_ref, "setup_phases_s": phases,
        "requests_due": n, "shed": int(shed.sum()), "errors": errors,
        "completed_in_window": completed_in,
        "queued_at_window_end": b1["queued"],
        "p50_ms": 1e3 * load.percentile(lat, 50),
        "generator_late_p99_ms": 1e3 * load.percentile(late, 99),
        "generator_late_max_ms": 1e3 * float(late.max()),
        "answered_after_window": int(np.sum(window_ok > w1)),
        "shapes_warmed": shapes, "compared_requests": len(pick),
        "host": host.summary,
    }
    return Outcome(
        correct=bool(correct), attempted=n, failed=int(shed.sum()) + errors + never,
        end_to_end={"serve_p99_ms": 1e3 * load.percentile(lat, 99),
                    "serve_completed_per_s": completed_in / (w1 - w0),
                    "setup_s": setup_s, "peak_hbm_gb": mem / 1e9},
        checks=checks, memory_peak_bytes=mem, reading=reading, diagnostics=diag)
