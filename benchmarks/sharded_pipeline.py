"""Sharded streaming pipeline benchmark (ISSUE 3, runtime-fronted in
ISSUE 4, owner-computes decode in ISSUE 5).

Times the end-to-end streaming GNN train step at 1 and 4 shards — plus a
4-shard **owner-computes** run (``lookup_impl="owner:gather"``, hub rows
deduped across shards) — and checks the step-0 forward-loss bit-identity
contract the tests assert.  The whole pipeline — batch source selection,
mesh, frontier placement, owner plan, prefetch — comes from
``GraphRuntime.from_spec``; the three legs differ by exactly two
``RuntimeSpec`` fields (``n_shards``, ``lookup_impl``).  Emits the usual
CSV rows AND writes ``BENCH_shard.json``.

The measurement runs in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the 4-shard legs
exercise a real 4-device mesh even though the benchmark suite itself must
keep a single-device view (tests/conftest.py).  Reading the numbers on this
CPU container: forced host devices share the same cores, so the 4-shard
``step_us`` measures *overhead* of the sharded path (shard_map + collectives),
not speedup — the decode-row columns are the scaling axis on real multi-host
hardware.  Per run: ``frontier_rows_per_device`` is the local frontier
block (``frontier_cap``, padding included), ``unique_rows_per_device`` the
measured mean per-shard unique count, and ``rows_decoded_per_device`` the
rows each device's decoder actually runs per step — a STATIC padded shape
under the same accounting for every run: the full local block for the
local-decode runs, the per-owner decode capacity (``owner_unique_cap``)
for the owner run, whose measured post-dedup floor rides along as
``owned_unique_rows_per_device`` (hubs decode once on their owner instead
of once per shard — the reclaim the ``--bench`` smoke asserts can't
regress).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmarks.common import emit, steps

ROOT = Path(__file__).resolve().parents[1]
OUT_PATH = ROOT / "BENCH_shard.json"

_WORKER = """
import json, sys, time
import jax, numpy as np
from repro.configs.paper_gnn import paper_gnn_config
from repro.graph.engine import default_frontier_cap
from repro.graph.runtime import GraphRuntime, GraphSource, RuntimeSpec
from repro.optim import AdamWConfig

N_NODES, N_CLASSES, BATCH, FANOUT = 8000, 8, 256, 10
n_steps = int(sys.argv[1])

spec = RuntimeSpec(
    graph=GraphSource(kind="powerlaw", seed=0, n_nodes=N_NODES,
                      n_classes=N_CLASSES, avg_degree=10, homophily=0.9),
    model=paper_gnn_config("sage", n_nodes=N_NODES, n_classes=N_CLASSES,
                           fanout=FANOUT),
    optimizer=AdamWConfig(lr=1e-2, weight_decay=0.0),
    batch_size=BATCH, data_seed=1, prefetch_depth=2,
).with_updates(c=16, m=8, d_c=128, d_m=64, lookup_impl="sharded:gather")
graph = spec.graph.build()

def run(n_shards, impl=None):
    # fix the per-shard frontier cap at its worst case so every step keeps
    # one jit shape (a varying round-up cap would recompile mid-measurement)
    cap = default_frontier_cap(BATCH // n_shards, spec.model.fanouts,
                               spec.pad_to, N_NODES)
    sp = spec.with_updates(n_shards=n_shards, frontier_cap=cap)
    if impl is not None:
        sp = sp.with_updates(lookup_impl=impl)
    rt = GraphRuntime.from_spec(sp, graph=graph)
    state, step = rt.state, rt.jitted_step
    losses, uniq, decoded, owned, t0 = [], [], [], [], None
    try:
        for i in range(n_steps):
            batch = rt.data_iter.next_batch()
            fb = batch["frontier"]
            uniq.append(int(np.asarray(fb.n_unique)))
            # rows each device's decoder actually runs per step (STATIC
            # padded shapes, same accounting for every run): the owner
            # plan's per-owner decode capacity, else the full local block.
            # The owner run additionally reports its measured owned-unique
            # mean — the floor the capacity is padded up from.
            plan = getattr(fb, "plan", None)
            if plan is not None:
                decoded.append(plan.owned_src.shape[1])
                owned.append(int(np.asarray(plan.n_owned).sum()) / n_shards)
            else:
                decoded.append(fb.unique.shape[0] // n_shards)
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))   # blocks
            if i == 0:
                t0 = time.perf_counter()            # first step pays compile
    finally:
        rt.close()
    per_step = (time.perf_counter() - t0) / max(n_steps - 1, 1) * 1e6
    rows_total = batch["frontier"].unique.shape[0]
    out = {"n_shards": n_shards,
           "lookup_impl": sp.model.embedding.lookup_impl,
           # every BENCH entry carries mode + dtype (tools/ci.sh gate);
           # the worker runs on forced CPU host devices
           "mode": "cpu", "dtype": sp.model.compute_dtype,
           "step_us": per_step, "losses": losses,
           "frontier_rows_total": rows_total,
           "frontier_rows_per_device": rows_total // n_shards,
           "unique_rows_per_device": sum(uniq) / len(uniq) / n_shards,
           "rows_decoded_per_device": sum(decoded) / len(decoded)}
    if owned:
        out["owned_unique_rows_per_device"] = sum(owned) / len(owned)
    return out

out = {"device_count": jax.device_count(),
       "workload": {"n_nodes": N_NODES, "batch": BATCH,
                    "fanouts": [FANOUT, FANOUT], "steps": n_steps,
                    "lookup_impl": spec.model.embedding.lookup_impl},
       "runs": {"1shard": run(1), "4shard": run(4),
                "owner": run(4, impl="owner:gather")}}
out["step0_loss_bit_identical"] = (
    out["runs"]["1shard"]["losses"][0] == out["runs"]["4shard"]["losses"][0]
    == out["runs"]["owner"]["losses"][0])
print("BENCH_JSON:" + json.dumps(out))
"""


def run():
    n_steps = steps(12)
    env = dict(os.environ)
    # a CPU rehearsal of the mesh by design: the child never takes the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src") + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-c", _WORKER, str(n_steps)],
        capture_output=True, text=True, env=env, cwd=str(ROOT), timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded_pipeline worker failed:\n{proc.stdout}\n{proc.stderr}")
    payload = [l for l in proc.stdout.splitlines() if l.startswith("BENCH_JSON:")]
    report = json.loads(payload[-1][len("BENCH_JSON:"):])

    for label, r in report["runs"].items():
        owned = ("" if "owned_unique_rows_per_device" not in r else
                 f"owned_unique/device={r['owned_unique_rows_per_device']:.0f} ")
        emit(f"sharded_pipeline/{label}/step", r["step_us"],
             f"rows/device={r['frontier_rows_per_device']} "
             f"unique/device={r['unique_rows_per_device']:.0f} "
             f"decoded/device={r['rows_decoded_per_device']:.0f} "
             f"{owned}loss0={r['losses'][0]:.6f}")
    ident = report["step0_loss_bit_identical"]
    emit("sharded_pipeline/step0_bit_identical", 0.0, str(ident))
    if not ident:
        raise AssertionError(
            "1-shard vs 4-shard vs owner step-0 forward loss diverged: "
            f"{report['runs']['1shard']['losses'][0]} vs "
            f"{report['runs']['4shard']['losses'][0]} vs "
            f"{report['runs']['owner']['losses'][0]}")
    # the owner run's whole point: cross-shard dedup must actually reclaim
    # decode rows (asserted in --bench smoke so it can't silently regress)
    own = report["runs"]["owner"]
    if not own["rows_decoded_per_device"] < own["frontier_rows_per_device"]:
        raise AssertionError(
            "owner run decoded "
            f"{own['rows_decoded_per_device']:.0f} rows/device, expected "
            f"< frontier_rows_per_device={own['frontier_rows_per_device']} "
            "(cross-shard dedup regressed — did the owner plan fall back?)")

    # smoke runs exercise the code path but must not clobber the committed
    # real-measurement datapoint with 2-step throwaway numbers
    from benchmarks import common
    if common.SMOKE:
        emit("sharded_pipeline/json", 0.0,
             f"smoke: skipped writing {OUT_PATH.name}")
    else:
        OUT_PATH.write_text(json.dumps(report, indent=2) + "\n")
        emit("sharded_pipeline/json", 0.0, f"wrote {OUT_PATH.name}")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
