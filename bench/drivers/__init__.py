"""Drivers: one per kind of traffic, each general over configurations.

A driver builds the system under test from a configuration, warms every
shape its traffic uses (set-up), measures for ``seconds``, checks what the
timed path produced against the plain reference, and returns an
``Outcome``.  What the per-layer metric readers see is a ``Reading``.
"""

from __future__ import annotations

import dataclasses
import gc
import resource
import shutil
import threading
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Context:
    cfg: Dict
    traffic: Dict
    cell: Dict
    seed: int
    seconds: float
    trace: bool
    system: str
    t0: float
    chips: int
    compiles: Any
    peak: Dict[str, float]
    trace_dir: Path


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader gets: the traffic driver's counters over the
    measured window, the configuration, the chip's peaks and, in a traced
    run, the reduced trace and the window's span on its clock."""
    kind: str
    cfg: Dict
    peak: Dict[str, float]
    chips: int
    counters: Dict[str, float]
    trace: Any = None
    window: Optional[Tuple[float, float]] = None
    busy_s: float = 0.0
    window_s: float = 0.0
    breakdown: Optional[Dict[str, List]] = None

    def device_ids(self) -> List[int]:
        return self.trace.devices[: self.chips] if self.trace else []


@dataclasses.dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: List[Tuple[str, float, float]]
    memory_peak_bytes: int
    reading: Reading
    diagnostics: Dict[str, Any]


def runtime_spec(cfg: Dict, seed: int, n_nodes: int):
    """The program's ``RuntimeSpec`` for a configuration and a seed."""
    from repro.configs.base import EmbeddingSpec, GNNConfig
    from repro.graph.runtime import GraphSource, RuntimeSpec
    from repro.optim import AdamWConfig
    mc, rc, gc_ = cfg["model"], cfg["runtime"], cfg["graph"]
    model = GNNConfig(
        name=cfg["name"], model="sage", n_nodes=n_nodes,
        n_classes=mc["n_classes"], d_e=mc["d_e"], hidden=mc["hidden"],
        n_gnn_layers=2, fanouts=(mc["fanout"], mc["fanout"]), task="node",
        embedding=EmbeddingSpec(kind="hash_full", c=mc["c"], m=mc["m"],
                                d_c=mc["d_c"], d_m=mc["d_m"],
                                n_layers=mc["n_layers"],
                                lookup_impl=mc["lookup_impl"]),
        compute_dtype=mc["dtype"])
    oc = cfg["optimizer"]
    return RuntimeSpec(
        graph=GraphSource(kind="external", n_nodes=n_nodes),
        model=model,
        optimizer=AdamWConfig(lr=oc["lr"], b1=oc["b1"], b2=oc["b2"],
                              eps=oc["eps"], weight_decay=oc["weight_decay"]),
        data_seed=seed, init_seed=seed % (2 ** 31), split_seed=gc_["graph_seed"],
        split_frac=tuple(gc_["split"]), ckpt_dir=None,
        log_every=1 << 30, **rc)


def program_graph(graph):
    """The benchmark's graph as the program's CSR adjacency and labels."""
    from repro.graph.csr import CSRMatrix
    adj = CSRMatrix(np.ones(graph.nnz, np.float32), graph.indices,
                    graph.indptr.astype(np.int32), (graph.n_nodes, graph.n_nodes))
    return adj, graph.labels


def non_edges(graph, levels) -> int:
    """Sampled (parent, child) pairs that are not edges of the graph; an
    isolated parent may sample itself."""
    keys = graph.edge_keys()
    deg = graph.degrees()
    bad = 0
    for parent, child in zip(levels[:-1], levels[1:]):
        p = np.broadcast_to(np.asarray(parent)[..., None], child.shape).ravel().astype(np.int64)
        c = np.asarray(child).ravel().astype(np.int64)
        k = p * graph.n_nodes + c
        pos = np.minimum(np.searchsorted(keys, k), keys.shape[0] - 1)
        ok = (keys[pos] == k) | ((deg[p] == 0) & (c == p))
        bad += int((~ok).sum())
    return bad


@contextmanager
def traced(ctx: Context):
    """Profiler trace of the block when the run is traced, with the
    benchmark's ``bench.window`` span around it."""
    import jax
    if not ctx.trace:
        yield
        return
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    ctx.trace_dir.mkdir(parents=True, exist_ok=True)
    with jax.profiler.trace(str(ctx.trace_dir)):
        with jax.profiler.TraceAnnotation("bench.window"):
            yield


def read_trace(ctx: Context, reading: Reading) -> None:
    """Fill the trace-derived parts of a reading from the traced window."""
    from bench import trace as tr
    t = tr.load(tr.newest_xplane(ctx.trace_dir))
    win = t.window()
    reading.trace, reading.window = t, win
    devs = reading.device_ids()
    if not devs:
        return
    reading.window_s = win[1] - win[0]
    reading.busy_s = float(np.mean([t.busy(d, win) for d in devs]))
    reading.breakdown = {
        "device_ops": [[n, s] for n, s in t.top_ops(devs[0], win)],
        "idle_gaps": [[n, s] for n, s in t.idle_gaps(devs[0], win)]}
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)


def free(*objs) -> None:
    """Drop the program's state so the reference runs on a clean chip."""
    import jax
    for o in objs:
        close = getattr(o, "close", None)
        if callable(close):
            close()
    del objs
    gc.unfreeze()                  # set-up's objects may be collected again
    gc.collect()
    jax.clear_caches()


def settle() -> None:
    """End of set-up: collect, then move every object set-up made into the
    collector's permanent generation, so a full collection in the window
    scans only what the window makes, not all that set-up left behind
    (``free`` undoes it)."""
    gc.collect()
    gc.freeze()


class HostWatch:
    """What held the host up during a block: the collector's pauses (from
    ``gc.callbacks``), and the longest a 10 ms timer thread overslept, with
    the process's CPU time over that stall.  CPU time near the stall's
    length means a thread of this process ran (a collection, or C code
    holding the interpreter); near 0, the process was off the CPU.

    ``tick=False`` leaves the timer thread out (stall readings are None):
    in a traced run its sleeps would be the host spans that name the
    device's idle gaps."""

    TICK_S = 0.01

    def __init__(self, tick: bool = True):
        self.tick = tick

    def __enter__(self) -> "HostWatch":
        self.gc_n = [0, 0, 0]
        self.gc_s = [0.0, 0.0, 0.0]
        self.gc_max_s = 0.0
        self.stall_s = self.stall_cpu_s = 0.0 if self.tick else None
        self._gc_t = 0.0
        self._ru = resource.getrusage(resource.RUSAGE_SELF)
        self._stop = threading.Event()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="bench-hostwatch")
        if self.tick:
            self._thread.start()
        return self

    def _on_gc(self, phase, info):
        t = time.perf_counter()
        if phase == "start":
            self._gc_t = t
            return
        d, g = t - self._gc_t, info["generation"]
        self.gc_n[g] += 1
        self.gc_s[g] += d
        self.gc_max_s = max(self.gc_max_s, d)

    def _watch(self):
        while not self._stop.is_set():
            w, c = time.perf_counter(), time.process_time()
            time.sleep(self.TICK_S)
            over = time.perf_counter() - w - self.TICK_S
            if over > self.stall_s:
                self.stall_s, self.stall_cpu_s = over, time.process_time() - c

    def __exit__(self, *exc):
        self._stop.set()
        if self.tick:
            self._thread.join()
        gc.callbacks.remove(self._on_gc)
        ru = resource.getrusage(resource.RUSAGE_SELF)
        self.summary = {
            "gc_collections": self.gc_n, "gc_pause_s": self.gc_s,
            "gc_pause_max_s": self.gc_max_s,
            "stall_max_s": self.stall_s, "stall_cpu_s": self.stall_cpu_s,
            "involuntary_switches": ru.ru_nivcsw - self._ru.ru_nivcsw,
            "major_faults": ru.ru_majflt - self._ru.ru_majflt,
            "gc_tracked_objects": len(gc.get_objects()),
            "gc_frozen_objects": gc.get_freeze_count(),
        }
        return False


def peak_memory_bytes(chips: int) -> int:
    """``peak_bytes_in_use`` of the fullest of the cell's chips."""
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()[:chips]]
    return max(peaks)


def now() -> float:
    return time.perf_counter()
