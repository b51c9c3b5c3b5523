#!/usr/bin/env bash
# Single CI entry point (ISSUE 2 satellite; multidevice leg from ISSUE 3).
#
#   tools/ci.sh                import gate + docs drift gate (check_docs.py:
#                              every registry backend / spec field must be
#                              documented) + tier-1 pytest
#   tools/ci.sh --bench        ... plus the paper-experiment scripts under
#                              benchmarks/ in --smoke mode (2 steps per
#                              module, so they can't silently rot) and a
#                              gate on the committed quality record
#                              BENCH_compression.json (>= 2 budgets x 3
#                              families, dtype + quality columns on every
#                              entry).  Speed is measured on the chip by
#                              bench/ (BENCHMARK.json), never here.
#   tools/ci.sh --bench-only   import gate + benchmark smoke, WITHOUT the
#                              tier-1 pytest — the CI matrix runs tier-1 in
#                              its own leg, so the bench leg shouldn't pay
#                              for the suite twice
#   tools/ci.sh --multidevice  import gate + the `multidevice`-marked tests
#                              under XLA_FLAGS=--xla_force_host_platform_
#                              device_count=8, so sharded code paths see 8
#                              devices on a CPU-only container, plus an
#                              owner-decode GraphRuntime smoke
#                              (lookup_impl="owner:gather", 4 shards, 2
#                              steps).  Runs ONLY the marked tests: the
#                              tier-1 suite must keep its single-device view
#                              (tests/conftest.py).
#   tools/ci.sh --examples     import gate + examples smoke, WITHOUT the
#                              tier-1 pytest: runs the GraphRuntime front
#                              door end to end — `train_gnn_hash.py --steps
#                              2` (train + val/test eval + checkpoint) and a
#                              2-request `GraphInferenceEngine` serve via
#                              `serve_gnn.py` — so the examples can't rot.
#   tools/ci.sh --elastic      import gate + a forced-8-host-device elastic
#                              kill/rescale smoke (FailurePlan kills a shard,
#                              peer transfer + exact rescale recover it,
#                              post-recovery curve asserted bitwise),
#                              WITHOUT the tier-1 pytest.
#
# Mirrors ROADMAP "Tier-1 verify": import/collection health is a gate that
# runs BEFORE the suite, so a broken optional dep fails loudly here instead
# of erroring collection of unrelated test modules.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

RUN_BENCH=0
RUN_MULTI=0
RUN_EXAMPLES=0
RUN_ELASTIC=0
RUN_SUITE=1
for arg in "$@"; do
    case "$arg" in
        --bench)       RUN_BENCH=1 ;;
        --bench-only)  RUN_BENCH=1; RUN_SUITE=0 ;;
        --multidevice) RUN_MULTI=1 ;;
        --examples)    RUN_EXAMPLES=1; RUN_SUITE=0 ;;
        --elastic)     RUN_ELASTIC=1; RUN_SUITE=0 ;;
        *) echo "usage: tools/ci.sh [--bench|--bench-only] [--multidevice] [--examples] [--elastic]" >&2
           exit 2 ;;
    esac
done

echo "== [1/2] import-health + docs drift gate =="
python tools/check_imports.py
python tools/check_docs.py

if [[ "$RUN_MULTI" == 1 ]]; then
    echo "== [2/3] multidevice pytest (8 forced host devices) =="
    XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
        python -m pytest -q -m multidevice
    echo "== [3/3] owner-decode runtime smoke (lookup_impl=owner:gather) =="
    XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
        python - <<'PY'
import math

from repro.configs.paper_gnn import paper_gnn_config
from repro.graph.runtime import GraphRuntime, GraphSource, RuntimeSpec

# n_nodes=1000 + fanout 10 puts the workload firmly in the owner regime:
# the frontier cap rounds to 1024, so owner_unique_cap=512 while any owner
# can own at most 1000/4 = 250 distinct ids — the plan can never overflow
spec = RuntimeSpec(
    graph=GraphSource(kind="powerlaw", seed=0, n_nodes=1000, n_classes=8),
    model=paper_gnn_config("sage", n_nodes=1000, n_classes=8, fanout=10),
    batch_size=64, n_shards=4, total_steps=2, log_every=1,
).with_updates(c=16, m=8, d_c=64, d_m=64, lookup_impl="owner:gather")
rt = GraphRuntime.from_spec(spec)
try:
    batch = rt.data_iter.next_batch()
    assert batch["frontier"].plan is not None, "owner plan missing"
    res = rt.train(2)
    assert all(math.isfinite(l) for l in res.losses), \
        f"non-finite loss: {res.losses}"
    print("owner-decode smoke OK:", res.losses)
finally:
    rt.close()
PY
elif [[ "$RUN_SUITE" == 1 ]]; then
    echo "== [2/2] tier-1 pytest =="
    python -m pytest -x -q
fi

if [[ "$RUN_BENCH" == 1 ]]; then
    echo "== [extra] benchmark smoke =="
    python -m benchmarks.run --smoke
    echo "== [extra] BENCH_compression.json quality-record gate =="
    python - <<'PY'
import json
from pathlib import Path

doc = json.loads(Path("BENCH_compression.json").read_text())
budgets = doc["budgets"]
assert len(budgets) >= 2, f"need >= 2 matched budgets, got {budgets.keys()}"
checked = 0
for bname, row in budgets.items():
    fams = row["families"]
    assert set(fams) == {"paper", "hashemb", "tt"}, (bname, fams.keys())
    for e in fams.values():
        for key in ("table_bytes", "trainable_params", "val_accuracy",
                    "val_loss", "final_train_loss"):
            assert isinstance(e.get(key), (int, float)), (bname, key, e)
        assert isinstance(e.get("dtype"), str) and e["dtype"], (bname, e)
        checked += 1
print(f"BENCH_compression.json gate OK ({checked} entries)")
PY
fi

if [[ "$RUN_ELASTIC" == 1 ]]; then
    echo "== [2/2] elastic kill/rescale smoke (8 forced host devices) =="
    XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
        python - <<'PY'
import dataclasses

from repro.configs.paper_gnn import paper_gnn_config
from repro.elastic import (DEGRADED, HEALTHY, RESCALING, ElasticManager,
                           ElasticSpec, FailurePlan)
from repro.graph.runtime import GraphRuntime, GraphSource, RuntimeSpec

# compressed schedule: shard 2 of 4 dies at step 2 (lease grace 1 -> detect
# at step 3), one transfer chunk arrives corrupted, rescale to 3 shards,
# and the continued curve must be bitwise the never-failed rescaled run
N = 1000
spec = RuntimeSpec(
    graph=GraphSource(kind="powerlaw", seed=0, n_nodes=N, n_classes=8),
    model=paper_gnn_config("sage", n_nodes=N, n_classes=8, fanout=5),
    batch_size=48, n_shards=4, prefetch_depth=2,
    elastic=ElasticSpec(lease_steps=1, chunk_bytes=1 << 16),
).with_updates(c=16, m=8, d_c=64, d_m=64, lookup_impl="sharded:gather")
graph = spec.graph.build()

mgr = ElasticManager(GraphRuntime.from_spec(spec, graph=graph),
                     plan=FailurePlan(kill=((2, 2),), corrupt_chunks=(1,)))
res = mgr.run(6)
assert res.history == [HEALTHY, DEGRADED, RESCALING, HEALTHY], res.history
(rep,) = res.reports
assert rep.n_after == 3 and rep.retransmits >= 1, rep
res.runtime.close()

rt4 = GraphRuntime.from_spec(spec, graph=graph)
head = rt4.train(rep.detected_at_step + 1)
rt3 = rt4.rescale(3)
rt4.close()
tail = rt3.train(6 - rep.detected_at_step - 1)
rt3.close()
assert res.losses == head.losses + tail.losses, "post-recovery curve diverged"
print(f"elastic smoke OK: {rep.n_before}->{rep.n_after} shards, "
      f"steps_lost={rep.steps_lost}, "
      f"bytes_transferred={rep.bytes_transferred}, "
      f"retransmits={rep.retransmits}, bitwise continuation")
PY
fi

if [[ "$RUN_EXAMPLES" == 1 ]]; then
    echo "== [2/2] examples smoke (GraphRuntime train/eval/serve) =="
    CKPT_DIR="$(mktemp -d)"
    python examples/train_gnn_hash.py --steps 2 --nodes 2000 --classes 8 \
        --ckpt-dir "$CKPT_DIR"
    rm -rf "$CKPT_DIR"
    python examples/serve_gnn.py --nodes 2000 --steps 2 --requests 2 \
        --batch 64
fi

echo "CI OK"
