"""The comparison that decides ``correct`` fails what it must: the control
(the reference in the program's place, one precision step down: three
bfloat16 passes for float32 at HIGHEST) and each planted fault, driven
through the rest of a run on the CPU at tiny widths."""

import pytest

from bench.tests.rehearsal import candidate_root, run_tiny
from bench.tests.test_bench_rehearsal import CANDIDATES

CASES = [
    ("sage-products.train", "sage-products", "control"),
    ("sage-products.train", "sage-products", "fault_frozen"),
    ("sage-products.train", "sage-products", "fault_half"),
    ("merchant.serve-zipf", "merchant-sage", "control"),
    ("merchant.serve-zipf", "merchant-sage", "fault_altered"),
]


@pytest.mark.parametrize("workload,config,system", CASES,
                         ids=[f"{w}-{s}" for w, _, s in CASES])
def test_broken_timed_path_is_not_correct(workload, config, system, tmp_path):
    cand = {w["name"]: w["candidate"] for w in CANDIDATES}
    root = candidate_root(tmp_path, cand[workload]) if workload in cand else None
    line = run_tiny(workload, config, system=system, seconds=1.0, root=root)
    assert line["correct"] is False, line["checks"]
    failed = [k for k, c in line["checks"].items() if c["value"] > c["limit"]]
    assert failed and all(k not in ("non_edges", "compiles_in_window") for k in failed)
