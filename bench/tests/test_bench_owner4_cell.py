"""The four-chip cell ``sage-products.train-owner4``, as BENCHMARK.json and
``bench/configs/sage-products-owner4.json`` have it, rehearsed through the
harness on four virtual CPU devices (a child process, since the device
count is fixed when JAX starts): under the accepted configuration's
``loss_gap`` and ``change_gap`` limits (``grad_gap`` takes the tiny size's
own, ``bench/tests/rehearsal.py``) the sound run is correct with no owner plan overflow, and the runs whose
batch is halved or whose step is the reference at three bfloat16 passes
(the control) are not.  The run whose exchange between chips is left out
is rehearsed by ``test_bench_four_chips.py``, on its copy of this
configuration under ``bench/tests/data/``."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SYSTEMS = ("program", "fault_half", "control")
CHILD = """
import json, sys
sys.path.insert(0, {root!r})
from bench.tests.rehearsal import run_tiny
out = {{}}
for system in {systems!r}:
    line = run_tiny("sage-products.train-owner4", "sage-products", system=system,
                    seconds=1.0, extra={{"runtime": {{"batch_size": 256,
                                                      "owner_unique_cap": 768}}}})
    out[system] = {{"correct": line["correct"], "count": line["device"]["count"],
                    "checks": line["checks"], "overflow": "owner plan overflow" in line["stderr"]}}
print(json.dumps(out))
"""


def test_accepted_four_chip_cell_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", CHILD.format(root=str(ROOT), systems=SYSTEMS)],
                          env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    sound = out["program"]
    assert sound["correct"] is True, sound["checks"]
    assert sound["count"] == 4 and not sound["overflow"]
    for other in ("fault_half", "control"):
        assert out[other]["correct"] is False, out[other]["checks"]
        assert out[other]["count"] == 4
