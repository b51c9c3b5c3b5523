"""The four-chip owner-decode configuration (``bench/tests/data/
sage-products-owner4.json``; not a cell of BENCHMARK.json) rehearsed through the harness on four virtual
CPU devices (a child process, since the device count is fixed when JAX
starts): the sound run is correct, and the run whose exchange between
chips is left out is not."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CHILD = """
import json, shutil, sys
from pathlib import Path
sys.path.insert(0, {root!r})
from bench.tests.rehearsal import run_tiny
root = Path({tmp!r})
shutil.copytree(Path({root!r}) / "bench", root / "bench",
                ignore=shutil.ignore_patterns(".cache", "__pycache__"))
shutil.copy(root / "bench" / "tests" / "data" / "sage-products-owner4.json",
            root / "bench" / "configs" / "sage-products-owner4.json")
spec = json.loads((Path({root!r}) / "BENCHMARK.json").read_text())
spec["configs"].append({{"name": "sage-products-owner4", "source": "https://ogb.stanford.edu/",
                         "file": "bench/configs/sage-products-owner4.json",
                         "reduced": ["n_nodes"], "why": "owner decode"}})
spec["workloads"].append({{"name": "sage-products.train-owner4", "config": "sage-products-owner4",
                           "traffic": "train", "chips": 4, "why": "four chips"}})
(root / "BENCHMARK.json").write_text(json.dumps(spec))
out = {{}}
for system in ("program", "fault_local"):
    line = run_tiny("sage-products.train-owner4", "sage-products", system=system, root=root,
                    seconds=1.0, extra={{"runtime": {{"batch_size": 256,
                                                      "owner_unique_cap": 768}}}})
    out[system] = {{"correct": line["correct"], "count": line["device"]["count"],
                    "checks": line["checks"], "overflow": "owner plan overflow" in line["stderr"]}}
print(json.dumps(out))
"""


def test_four_chip_cell_on_virtual_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", CHILD.format(root=str(ROOT), tmp=str(tmp_path))],
                          env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["program"]["correct"] is True, out["program"]["checks"]
    assert out["program"]["count"] == 4 and not out["program"]["overflow"]
    assert out["fault_local"]["correct"] is False, out["fault_local"]["checks"]
