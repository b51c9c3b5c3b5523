"""Run one cell of the benchmark once and print its result line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's root:
the cell names its configuration (``bench/configs/<file>``) and its traffic
mix (``bench/traffic/<traffic>.json``); the mix names the traffic driver that
generates it (``bench/drivers/<driver>.py``); each per-layer metric is read
by ``bench/metrics/<metric>.py``.  A new cell, mix or metric is new files
and entries, never an edit.

With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
measured window.  Every run checks what the timed path produced against the
plain reference (``bench/reference/``) and prints each compared number
beside its limit, on standard error and as the line's last key.

There is no CPU fallback: a run that finds no TPU, or fewer chips than the
cell asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Fail(RuntimeError):
    """A run that cannot produce a result."""


def load_cell(root: Path, workload: str):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Fail(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return spec, cell, cfg, traffic


def metric_reader(root: Path, name: str):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{abs(hash(name))}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts executables built (compiled or loaded from the cache)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1


def setup_jax(cfg: Dict, cache_dir: Path):
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the precision every float32 product runs at, as the configuration states
    jax.config.update("jax_default_matmul_precision", cfg["model"]["matmul_precision"])


def device_info(chips: int, allow_cpu: bool) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise Fail(f"JAX found platform {devs[0].platform!r} "
                   f"({devs[0].device_kind}), not 'tpu'; there is no CPU fallback")
    if len(devs) < chips:
        raise Fail(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def main(argv: Optional[List[str]] = None, *, root: Path = ROOT,
         system: str = "program", overrides: Optional[Dict] = None,
         allow_cpu: bool = False) -> Dict:
    """One run.  ``system`` replaces the timed path for the control and the
    planted faults (``bench.control``); ``overrides`` and ``allow_cpu``
    serve the tests' tiny CPU rehearsal only.  Returns the result line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec, cell, cfg, traffic = load_cell(root, args.workload)
    for section, values in (overrides or {}).items():
        (traffic if section == "traffic" else cfg[section]).update(values)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise Fail(f"the system under test is not in this checkout ({src})")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    setup_jax(cfg, root / "bench" / ".cache" / "jax")
    device = device_info(cell["chips"], allow_cpu)
    from bench import peaks
    peak = peaks.lookup(device["kind"], allow_cpu=allow_cpu)

    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    ctx = driver.Context(cfg=cfg, traffic=traffic, cell=cell, seed=args.seed,
                         seconds=args.seconds, trace=bool(args.trace),
                         system=system, t0=T0, chips=cell["chips"],
                         compiles=CompileCounter(), peak=peak,
                         trace_dir=root / "bench" / ".cache" / "trace" / cell["name"])
    out = driver.run(ctx)
    for k, v in sorted(out.diagnostics.items()):
        print(f"[bench] {k}={v}", file=sys.stderr)
    if out.reading.breakdown:
        print(f"[bench] breakdown={json.dumps(out.reading.breakdown)}", file=sys.stderr)
    sys.stderr.flush()

    device["memory_peak_bytes"] = out.memory_peak_bytes
    metrics: Dict[str, Dict[str, Any]] = {}
    if args.trace:
        wanted = [m for m in spec["per_layer"]
                  if cell["name"] in m.get("workloads", [cell["name"]])]
        for m in wanted:
            value = metric_reader(root, m["name"])(out.reading)
            if value is None:
                raise Fail(f"metric {m['name']} found nothing to read in "
                           f"{cell['name']}, which lists it")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = out.reading.busy_s
        device["window_s"] = out.reading.window_s
    else:
        for m in spec["end_to_end"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            if m["name"] not in out.end_to_end:
                raise Fail(f"{cell['name']} did not measure {m['name']}")
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]], "unit": m["unit"]}

    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if args.trace and out.reading.breakdown:
        line["breakdown"] = out.reading.breakdown
    line["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in out.checks}
    for k, v, lim in out.checks:
        print(f"check {k}={v!r} limit={lim!r} {'ok' if v <= lim else 'FAIL'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    try:
        main()
    except Fail as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
