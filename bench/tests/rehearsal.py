"""Tiny CPU rehearsal of the benchmark's cells, for the tests.

Shrinks a cell's configuration and traffic to widths and sizes the CPU
runs in seconds (Pallas in interpret mode, which ``GraphRuntime`` picks off
the TPU) and runs ``bench.run.main`` in-process, restoring the JAX settings
a run changes so that other tests in the process see none of them.
"""

from __future__ import annotations

import contextlib
import gc
import io
from pathlib import Path
from typing import Dict, Optional

import jax

TINY_MODEL = dict(c=16, m=8, d_c=128, d_m=128, d_e=32, hidden=32, fanout=4)
# The worst leaf's first-gradient gap at this size, on the CPU, over six
# seeds: the program reads at most 3.1e-8, the control at least 1.0e-6.  At
# the cell's size it cannot part them (a ReLU whose input lies within
# rounding of zero flips its derivative on some seeds, PERF.md), so the
# cell's limit is set against the half-batch fault and this size has its own.
TINY_LIMITS = dict(grad_gap=2.5e-7)
TINY = {
    "sage-products": {"model": TINY_MODEL,
                      "graph": dict(n_nodes=3000, mean_degree=10.0),
                      "runtime": dict(batch_size=64, frontier_cap=1280),
                      "limits": TINY_LIMITS},
    "merchant-sage": {"model": TINY_MODEL,
                      "graph": dict(n_consumers=3000, n_merchants=400,
                                    tx_per_consumer=6),
                      "runtime": dict(serve_batch=16),
                      "serve": dict(request_pool=[3000, 3400],
                                    batching=dict(max_batch=2, max_delay_ms=2.0,
                                                  queue_depth=16)),
                      "traffic": dict(rate_per_s=15, warmup_requests=8,
                                      sample_requests=6, size_max=16)},
}
SETTINGS = ("jax_compilation_cache_dir", "jax_default_matmul_precision",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")


def run_tiny(workload: str, config: str, *, system: str = "program",
             seed: int = 2 ** 31 + 11, seconds: float = 2.0, trace: int = 0,
             root: Optional[Path] = None, extra: Optional[Dict] = None) -> Dict:
    from bench import run
    overrides = {k: dict(v) for k, v in TINY[config].items()}
    for k, v in (extra or {}).items():
        overrides.setdefault(k, {}).update(v)
    saved = {k: getattr(jax.config, k) for k in SETTINGS}
    err = io.StringIO()
    try:
        with contextlib.redirect_stderr(err):
            line = run.main(["--workload", workload, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace", str(trace)],
                            root=root or run.ROOT, system=system,
                            overrides=overrides, allow_cpu=True)
    finally:
        gc.unfreeze()              # a run freezes its set-up's objects
        for k, v in saved.items():
            jax.config.update(k, v)
    line["stderr"] = err.getvalue()
    return line


def candidate_root(tmp: Path, name: str) -> Path:
    """A checkout under ``tmp`` whose BENCHMARK.json also holds the entries
    of the candidate cell ``bench/tests/data/<name>.json`` (a cell prepared
    in files but not yet measured on the chip)."""
    import json
    import shutil
    from bench import run
    shutil.copytree(run.ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    extra = json.loads((run.ROOT / "bench" / "tests" / "data" / f"{name}.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        spec[key] += extra[key]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
