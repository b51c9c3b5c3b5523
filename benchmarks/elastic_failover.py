"""Elastic failover benchmark (ISSUE 9): kill → detect → transfer → rescale.

Drives a 4-shard ``GraphRuntime`` through an ``ElasticManager`` with a
deterministic ``FailurePlan`` (shard 2 dies at step 10, one transfer chunk
arrives corrupted) and reports what recovery actually costs in the units
that transfer to a fleet: **steps lost** to detection latency and **bytes
moved** over the peer wire (chunks, retransmits, payload) — plus the
post-recovery bitwise-equality flag against a never-failed run rescaled
from the same state.  Recovery wall-clock rides along as a non-headline
column (``recovery_wall_s_cpu``): forced host devices share cores, so on
this container it measures interpreter overhead, not fleet behaviour
(ROADMAP "CPU timings lie").

Runs in a SUBPROCESS with ``XLA_FLAGS=--xla_force_host_platform_device_
count=8`` so the 4-shard mesh is real while the benchmark suite keeps its
single-device view (tests/conftest.py).  Emits the usual CSV rows AND
writes ``BENCH_elastic.json`` (smoke mode exercises the path but never
clobbers the committed datapoint).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmarks.common import emit, steps

ROOT = Path(__file__).resolve().parents[1]
OUT_PATH = ROOT / "BENCH_elastic.json"

_WORKER = """
import dataclasses, json, sys, time
import jax, numpy as np
from repro.configs.paper_gnn import paper_gnn_config
from repro.elastic import ElasticManager, ElasticSpec, FailurePlan
from repro.graph.runtime import GraphRuntime, GraphSource, RuntimeSpec
from repro.optim import AdamWConfig

N_NODES, N_CLASSES, BATCH, FANOUT = 4000, 8, 48, 5
total_steps = int(sys.argv[1])
kill_at = int(sys.argv[2])

spec = RuntimeSpec(
    graph=GraphSource(kind="powerlaw", seed=0, n_nodes=N_NODES,
                      n_classes=N_CLASSES, avg_degree=10, homophily=0.9),
    model=paper_gnn_config("sage", n_nodes=N_NODES, n_classes=N_CLASSES,
                           fanout=FANOUT),
    optimizer=AdamWConfig(lr=1e-2, weight_decay=0.0),
    batch_size=BATCH, data_seed=1, prefetch_depth=2, n_shards=4,
    elastic=ElasticSpec(lease_steps=1, chunk_bytes=1 << 16),
).with_updates(c=16, m=8, d_c=128, d_m=64, lookup_impl="sharded:gather")
graph = spec.graph.build()

plan = FailurePlan(kill=((2, kill_at),), corrupt_chunks=(1,))
rt = GraphRuntime.from_spec(spec, graph=graph)
mgr = ElasticManager(rt, plan=plan)

t0 = time.perf_counter()
res = mgr.run(total_steps)
total_wall = time.perf_counter() - t0
rep = res.reports[0]
res.runtime.close()

# reference: never-failed run to the interrupt point, same exact rescale to
# the survivor count, same remaining steps — the post-recovery curve must
# be bitwise this one (the core elastic invariant, tests/test_elastic.py)
recovered_at = rep.detected_at_step + 1
rt4 = GraphRuntime.from_spec(spec, graph=graph)
head = rt4.train(recovered_at)
t1 = time.perf_counter()
rt3 = rt4.rescale(rep.n_after)
rescale_wall = time.perf_counter() - t1
rt4.close()
tail = rt3.train(total_steps - recovered_at)
rt3.close()
bitwise = res.losses == head.losses + tail.losses

out = {
    "device_count": jax.device_count(),
    "workload": {"n_nodes": N_NODES, "batch": BATCH,
                 "fanouts": [FANOUT, FANOUT], "steps": total_steps,
                 "kill": {"shard": 2, "step": kill_at},
                 "lease_steps": mgr.spec.lease_steps,
                 "chunk_bytes": mgr.spec.chunk_bytes,
                 "lookup_impl": spec.model.embedding.lookup_impl},
    # the worker runs on forced CPU host devices; wall-clock columns
    # are CPU numbers and explicitly non-headline
    "mode": "cpu", "dtype": spec.model.compute_dtype,
    "topology": {"before": rep.n_before, "after": rep.n_after},
    "steps_lost": rep.steps_lost,
    "detected_at_step": rep.detected_at_step,
    "payload_bytes": rep.payload_bytes,
    "bytes_transferred": rep.bytes_transferred,
    "chunks": rep.chunks,
    "retransmits": rep.retransmits,
    "post_recovery_bitwise": bitwise,
    "recovery_wall_s_cpu": rescale_wall,
    "run_wall_s_cpu": total_wall,
    "history": res.history,
}
print("BENCH_JSON:" + json.dumps(out))
"""


def run():
    # smoke compresses the schedule (kill at step 1, 4 total) so the full
    # kill/transfer/rescale path runs in seconds
    total, kill_at = (14, 10) if steps(14) == 14 else (4, 1)
    env = dict(os.environ)
    # a CPU rehearsal of the mesh by design: the child never takes the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src") + (
        ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-c", _WORKER, str(total), str(kill_at)],
        capture_output=True, text=True, env=env, cwd=str(ROOT), timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"elastic_failover worker failed:\n{proc.stdout}\n{proc.stderr}")
    payload = [l for l in proc.stdout.splitlines()
               if l.startswith("BENCH_JSON:")]
    report = json.loads(payload[-1][len("BENCH_JSON:"):])

    topo = report["topology"]
    emit("elastic_failover/recovery", 0.0,
         f"shards={topo['before']}->{topo['after']} "
         f"steps_lost={report['steps_lost']} "
         f"bytes_transferred={report['bytes_transferred']} "
         f"chunks={report['chunks']} retransmits={report['retransmits']}")
    emit("elastic_failover/post_recovery_bitwise", 0.0,
         str(report["post_recovery_bitwise"]))
    if not report["post_recovery_bitwise"]:
        raise AssertionError(
            "post-recovery loss curve diverged from the never-failed "
            "rescaled reference — the exact-rescale invariant regressed")
    if report["retransmits"] < 1:
        raise AssertionError(
            "the corrupted transfer chunk was not retransmitted — CRC "
            "verification on the peer wire regressed")

    from benchmarks import common
    if common.SMOKE:
        emit("elastic_failover/json", 0.0,
             f"smoke: skipped writing {OUT_PATH.name}")
    else:
        OUT_PATH.write_text(json.dumps(report, indent=2) + "\n")
        emit("elastic_failover/json", 0.0, f"wrote {OUT_PATH.name}")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
