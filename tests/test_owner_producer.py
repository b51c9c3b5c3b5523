"""The owner plan on the producer's clock and in its counters: a profiler
trace of a few owner-decode steps on four virtual CPU devices (a child
process, since the device count is fixed when JAX starts) holds one
``repro.producer.owner_plan`` span per batch, each inside the
``repro.producer.sample`` span of the same batch; ``stats()`` counts the
plans' owned rows and the batches built without a plan."""

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import dataclasses, json, tempfile, warnings
from collections import defaultdict
from pathlib import Path

import jax
import numpy as np
from jax.profiler import ProfileData

from repro.configs.paper_gnn import paper_gnn_config
from repro.graph.engine import ShardedSageBatchSource
from repro.graph.runtime import GraphRuntime, GraphSource, RuntimeSpec

N = 1200
cfg = paper_gnn_config("sage", n_nodes=N, n_classes=8, fanout=5)
cfg = dataclasses.replace(cfg, embedding=dataclasses.replace(
    cfg.embedding, c=16, m=8, d_c=64, d_m=64, lookup_impl="owner:gather"))
spec = RuntimeSpec(graph=GraphSource(kind="powerlaw", seed=0, n_nodes=N, n_classes=8,
                                     avg_degree=8, homophily=0.9),
                   model=cfg, batch_size=64, n_shards=4, prefetch_depth=2)
# every plan the producer builds, from the first batch on (the producer
# starts with the runtime)
owned = []
build = ShardedSageBatchSource.next_batch

def recording(self):
    batch = build(self)
    owned.append(int(np.asarray(batch["frontier"].plan.n_owned).sum()))
    return batch

ShardedSageBatchSource.next_batch = recording
logdir = tempfile.mkdtemp()
with jax.profiler.trace(logdir):
    # built inside the trace, so the trace holds every batch the producer
    # builds; the loop closes the producer before it returns
    rt = GraphRuntime.from_spec(spec)
    res = rt.train(4)
src = rt.source
stats = rt.data_iter.stats()

spans = defaultdict(list)
path = max(Path(logdir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
for plane in ProfileData.from_file(str(path)).planes:
    if plane.name.startswith("/host:"):
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro.producer."):
                    spans[ev.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))

# caps forced too small: the next batch is built without a plan
ShardedSageBatchSource.next_batch = build
src.owner_cap, src.owner_unique_cap = 2, 8
with warnings.catch_warnings(record=True) as caught:
    warnings.simplefilter("always")
    plan = src.next_batch()["frontier"].plan
after = rt.data_iter.stats()
print(json.dumps({"losses": len(res.losses), "stats": stats, "owned": owned,
                  "spans": {k: sorted(v) for k, v in spans.items()},
                  "overflow_plan": plan is not None,
                  "warned": any("owner plan overflow" in str(w.message) for w in caught),
                  "after": after}))
"""


def test_owner_plan_span_and_counters_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(CHILD)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    stats, spans = out["stats"], out["spans"]
    n = stats["n_produced"]
    assert out["losses"] == 4 and n >= 4 and len(out["owned"]) == n

    # one plan span a batch, each inside its batch's sample span
    plans, samples = spans["repro.producer.owner_plan"], spans["repro.producer.sample"]
    assert len(plans) == n
    for lo, hi in plans:
        assert any(s <= lo and hi <= e for s, e in samples), (lo, hi)

    # the counters: the plans' owned rows, the plan time, no overflow
    assert stats["owned_rows"] == sum(out["owned"]) > 0
    assert stats["owner_plan_overflows"] == 0
    traced_us = sum(hi - lo for lo, hi in plans) / 1e3
    assert 0 < stats["owner_plan_us"] <= stats["sample_us"]
    assert abs(traced_us - stats["owner_plan_us"]) <= 0.1 * stats["owner_plan_us"] + 50

    # a batch built with caps too small: no plan, a warning, one overflow,
    # and no owned rows added
    after = out["after"]
    assert out["warned"] and not out["overflow_plan"]
    assert after["owner_plan_overflows"] == 1
    assert after["owned_rows"] == stats["owned_rows"]
