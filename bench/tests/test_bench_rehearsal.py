"""Every cell of BENCHMARK.json, rehearsed end to end on the CPU at tiny
widths; a cell added from files alone; no result off the TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests.rehearsal import candidate_root, run_tiny

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
ONE_CHIP = [w for w in SPEC["workloads"] if w["chips"] == 1]
# cells prepared in files but not yet in BENCHMARK.json (bench/tests/data/*-cell.json)
CANDIDATES = [dict(w, candidate=f.stem)
              for f in sorted((ROOT / "bench" / "tests" / "data").glob("*-cell.json"))
              for w in json.loads(f.read_text())["workloads"]]


def check_line(line, cell, spec=SPEC):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert list(line)[-2] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0
    wanted = {m["name"] for m in spec["end_to_end"]
              if cell["name"] in m.get("workloads", [cell["name"]])}
    assert set(line["metrics"]) == wanted
    units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name] and m["value"] == m["value"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert line["checks"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("cell", ONE_CHIP + CANDIDATES,
                         ids=[w["name"] for w in ONE_CHIP + CANDIDATES])
def test_cell_rehearsal(cell, tmp_path):
    spec, root = SPEC, None
    if cell in CANDIDATES:
        root = candidate_root(tmp_path, cell["candidate"])
        spec = json.loads((root / "BENCHMARK.json").read_text())
    line = run_tiny(cell["name"], cell["config"], root=root)
    check_line(line, cell, spec)
    for k, v in line["checks"].items():
        assert f"check {k}=" in line["stderr"]


def test_cell_from_files_alone(tmp_path):
    """A later PR adds a cell, a traffic mix and a per-layer metric as new
    files and entries; the harness runs it with no code edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    (tmp_path / "bench" / "traffic" / "train-small.json").write_text(
        json.dumps({"driver": "train"}))
    (tmp_path / "bench" / "metrics" / "steps_in_window.train.py").write_text(
        "def read(r):\n    return float(r.counters['steps'])\n")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "sage-products.train-small",
                              "config": "sage-products", "traffic": "train-small",
                              "chips": 1, "why": "a dummy cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    line = run_tiny("sage-products.train-small", "sage-products", root=tmp_path,
                    extra={"runtime": {"batch_size": 32}})
    check_line(line, spec["workloads"][-1], spec)
    reader_spec = {"name": "steps_in_window.train"}
    from bench import run
    from bench.drivers import Reading
    read = run.metric_reader(tmp_path, reader_spec["name"])
    assert read(Reading(kind="train", cfg={}, peak={}, chips=1,
                        counters={"steps": 7})) == 7.0


def test_no_result_off_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", ONE_CHIP[0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_no_result_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", ONE_CHIP[0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
