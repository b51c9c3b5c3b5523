"""Streaming GraphSAGE training through ``GraphRuntime.train``.

Set-up builds one runtime (graph, codes, state, sampler, prefetch, the
compiled step), installs the benchmark's weights and codes, and drives it
through its first three steps with the window's own call and feed; those
steps compile the step and are what the reference follows.  The window is
one more ``train`` call on the same runtime, stopped at the first step
boundary after ``seconds`` by a fence.

``train_nodes_per_s`` is the labelled targets of every step in the window
over the window's wall time.  After the window the program is freed and the
reference runs the three set-up steps from the same weights on the same
sampled batches; the run compares the three losses, the first gradient as
the optimizer got it (AdamW's first moment after one step, over 1 - beta1)
and the parameters' change after three steps, leaf by leaf.
"""

from __future__ import annotations

import numpy as np

from bench import graphs, model
from bench.drivers import (Context, HostWatch, Outcome, Reading, free,
                           non_edges, now, peak_memory_bytes, program_graph,
                           read_trace, runtime_spec, settle, traced)
from bench.reference import sage as ref

SETUP_STEPS = 3


def _norms(tree):
    return {k: float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in tree.items()}


def leaf_gaps(prog, refv, keep=None):
    """Each leaf's gap between the program's norm and the reference's, over
    the larger of that leaf's reference norm and the median leaf's."""
    keys = [k for k in refv if keep is None or k in keep]
    med = float(np.median([refv[k] for k in keys]))
    return {k: abs(prog[k] - refv[k]) / max(refv[k], med) for k in keys}


def norm_gap(prog, refv, keep=None):
    """The worst leaf's gap (``leaf_gaps``)."""
    return max(leaf_gaps(prog, refv, keep).values())


def reference_step(cfg, labels, prec: str, fault: str = ""):
    """The reference in the program's place: a step over the program's
    state layout and batches, for the control and the planted faults:

    frozen  the step returns the state it was given
    half    half of the batch left out, the mean taken over the rest
    local   the exchange between chips left out: every chip steps on its
            own shard's gradient, so the state device 0 keeps is the step
            over its shard alone (the first 1/n_shards of the batch)
    """
    import jax
    mc, oc = cfg["model"], cfg["optimizer"]
    labels = jax.numpy.asarray(labels)
    paths = model.program_paths(mc)
    keep = {"half": 0.5, "local": 1.0 / cfg["runtime"]["n_shards"]}.get(fault, 1.0)

    def put(tree, path, value):
        if len(path) == 1:
            return dict(tree, **{path[0]: value})
        return dict(tree, **{path[0]: put(tree[path[0]], path[1:], value)})

    def step(state, batch):
        fb = batch["frontier"]
        params = model.to_bench(state["params"], mc)
        words = state["params"]["embed"]["codes_buf"]
        maps = list(fb.index_maps)
        y = labels[fb.unique[maps[0]]]
        loss, g = jax.value_and_grad(ref.loss)(params, words, fb.unique, maps, y,
                                               mc, prec, keep=keep)
        if fault == "frozen":
            return state, {"loss": loss}
        opt = {k: model.to_bench(state["opt"][k], mc) for k in ("mu", "nu")}
        n = state["opt"]["step"] + 1
        new, opt = ref.adamw(params, g, opt, n.astype("float32"), oc)
        p, mu, nu = state["params"], state["opt"]["mu"], state["opt"]["nu"]
        for k, path in paths.items():
            p, mu, nu = put(p, path, new[k]), put(mu, path, opt["mu"][k]), put(nu, path, opt["nu"][k])
        return (dict(state, params=p, opt={"step": n, "mu": mu, "nu": nu}),
                {"loss": loss})

    return jax.jit(step)


def run(ctx: Context) -> Outcome:
    import jax
    from repro.graph.runtime import GraphRuntime
    from repro.train import FenceInterrupt

    cfg, mc = ctx.cfg, ctx.cfg["model"]
    phases = {"start_s": now() - ctx.t0}
    graph = graphs.load(cfg["graph"], mc["c"], mc["m"])
    phases["graph_s"] = now() - ctx.t0
    spec = runtime_spec(cfg, ctx.seed, graph.n_nodes)
    rt = GraphRuntime.from_spec(spec, graph=program_graph(graph))
    phases["runtime_s"] = now() - ctx.t0
    weights = model.init(ctx.seed, mc)
    rt.state["params"] = model.install(rt.state["params"], weights,
                                       jax.numpy.asarray(graph.codes), mc)
    del weights
    if ctx.system == "control":
        rt._jitted_step = reference_step(cfg, graph.labels, "high")
    elif ctx.system.startswith("fault_"):
        rt._jitted_step = reference_step(cfg, graph.labels, "highest",
                                         fault=ctx.system[len("fault_"):])

    # -- the first steps, through the window's own call and feed ----------
    recorded = []
    feed = rt.data_iter.next_batch

    def record():
        batch = feed()
        fb = jax.device_get(batch["frontier"])
        recorded.append([np.asarray(fb.unique)[np.asarray(m)] for m in fb.index_maps])
        return batch

    rt.data_iter.next_batch = record
    first = rt.train(1)
    g1 = {k: np.asarray(v) / (1.0 - cfg["optimizer"]["b1"])
          for k, v in jax.device_get(model.to_bench(rt.state["opt"]["mu"], mc)).items()}
    rest = rt.train(SETUP_STEPS - 1)
    p3 = jax.device_get(model.to_bench(rt.state["params"], mc))
    setup_losses = first.losses + rest.losses

    # the window's feed keeps each batch's distinct-row count (a device
    # scalar, read after the window) for the roofline and MFU readers
    n_unique = []

    def count():
        batch = feed()
        n_unique.append(batch["frontier"].n_unique)
        return batch

    rt.data_iter.next_batch = count
    settle()
    setup_s = now() - ctx.t0

    # -- the window ----------------------------------------------------------
    stats0 = rt.data_iter.stats()
    compiles0 = ctx.compiles.n
    deadline = [0.0]

    def fence(_step):
        if now() >= deadline[0]:
            raise FenceInterrupt

    with HostWatch(tick=not ctx.trace) as host, traced(ctx):
        w0 = now()
        deadline[0] = w0 + ctx.seconds
        res = rt.train(1 << 40, fence=fence)
        w1 = now()
    window = w1 - w0
    compiles = ctx.compiles.n - compiles0
    stats1 = rt.data_iter.stats()
    del rt.data_iter.next_batch
    steps = len(res.losses)
    unique_rows = float(sum(np.asarray(u).sum() for u in jax.device_get(n_unique[:steps])))
    batch = cfg["runtime"]["batch_size"]
    mem = peak_memory_bytes(ctx.chips)
    window_nonfinite = int((~np.isfinite(np.asarray(res.losses))).sum())

    n_prod = stats1["n_produced"] - stats0["n_produced"]
    host_us = sum(stats1[k] - stats0[k] for k in ("sample_us", "code_gather_us", "put_us"))
    counters = {
        "steps": steps, "window_s": window, "batch": batch,
        "step_time_sum_s": float(np.sum(res.step_times)),
        "producer_us": host_us, "produced": n_prod,
        "unique_rows": unique_rows,
        "compiles_in_window": compiles,
    }
    reading = Reading(kind="train", cfg=cfg, peak=ctx.peak, chips=ctx.chips,
                      counters=counters)
    if ctx.trace:
        read_trace(ctx, reading)

    free(rt)
    rt = None

    # -- the reference, after the window and with the program freed --------
    t_ref = now()
    w_ref = model.init(ctx.seed, mc)
    w0_host = jax.device_get(w_ref)
    ref_losses, ref_g1, ref_p3 = ref.train(w_ref, graph.codes, graph.labels,
                                           recorded, mc, cfg["optimizer"])
    del w_ref
    g_prog, g_ref = _norms(g1), _norms(ref_g1)
    moved = {k for k, v in g_ref.items()
             if v >= 1e-3 * float(np.median(list(g_ref.values())))}
    d_prog = _norms({k: p3[k] - w0_host[k] for k in p3})
    d_ref = _norms({k: ref_p3[k] - w0_host[k] for k in ref_p3})
    lim = cfg["limits"]
    checks = [
        ("loss_gap", max(abs(a - b) / abs(b) for a, b in zip(setup_losses, ref_losses)),
         lim["loss_gap"]),
        ("grad_gap", norm_gap(g_prog, g_ref), lim["grad_gap"]),
        ("change_gap", norm_gap(d_prog, d_ref, keep=moved), lim["change_gap"]),
        ("non_edges", float(sum(non_edges(graph, lv) for lv in recorded)), 0.0),
        ("window_nonfinite_losses", float(window_nonfinite), 0.0),
        ("compiles_in_window", float(compiles), 0.0),
    ]
    correct = all(np.isfinite(v) and v <= l for _, v, l in checks)
    diag = {"reference_s": now() - t_ref, "setup_phases_s": phases,
            "setup_losses": setup_losses, "ref_losses": ref_losses,
            "steps": steps, "window_s": window,
            "left_out_leaves": sorted(set(g_ref) - moved),
            "grad_norms": {k: [g_prog[k], g_ref[k]] for k in sorted(g_ref)},
            "grad_gap_by_leaf": leaf_gaps(g_prog, g_ref),
            "change_gap_by_leaf": leaf_gaps(d_prog, d_ref, keep=moved),
            "unique_rows_per_step": unique_rows / steps if steps else None,
            "host": host.summary,
            "step_ms_median": 1e3 * float(np.median(res.step_times)) if steps else None}
    return Outcome(
        correct=bool(correct), attempted=steps, failed=0,
        end_to_end={"train_nodes_per_s": steps * batch / window,
                    "setup_s": setup_s, "peak_hbm_gb": mem / 1e9},
        checks=checks, memory_peak_bytes=mem, reading=reading,
        diagnostics=diag)
