"""Reduction of a JAX profiler trace to the numbers the metrics read.

``load(newest_xplane(logdir))`` reads a profiler trace with
``jax.profiler.ProfileData`` and keeps two kinds of events:

* device ops: the events of each TPU plane's ``XLA Ops`` line, by device,
  named by their HLO instruction's name (``hash_decode.1``, ``fusion.48``);
* host spans: every event of the host plane, by thread line.

``Trace`` then gives busy time (the union of a device's op intervals), the
device time of ops whose names match, the longest idle gaps labelled by
what the host was doing in them, and the top ops by time.  All times are
in seconds; a window is the ``(start, end)`` of the benchmark's own
``bench.window`` span, so ops outside it are not counted.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

Interval = Tuple[float, float]

COLLECTIVE = re.compile(r"^(all-to-all|all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute)")
KERNEL = re.compile(r"^hash_decode(\.\d+)?$")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.window"


class Trace:
    def __init__(self, ops: Dict[int, List[Tuple[str, float, float]]],
                 host: List[Tuple[str, str, float, float]]):
        # ops[device] = [(name, start_s, end_s)], host = [(line, name, start, end)]
        self.ops = {d: sorted(v, key=lambda e: e[1]) for d, v in ops.items()}
        self.host = host

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def window(self) -> Interval:
        spans = [(s, e) for _, n, s, e in self.host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        return spans[0]

    def _ops_in(self, device: int, window: Interval):
        lo, hi = window
        for name, s, e in self.ops.get(device, ()):
            if e > lo and s < hi:
                yield name, max(s, lo), min(e, hi)

    def busy(self, device: int, window: Interval) -> float:
        return sum(e - s for s, e in union(
            (s, e) for _, s, e in self._ops_in(device, window)))

    def op_time(self, device: int, window: Interval, pattern) -> float:
        """Summed device time of ops whose name matches ``pattern``."""
        rx = re.compile(pattern) if isinstance(pattern, str) else pattern
        return sum(e - s for n, s, e in self._ops_in(device, window)
                   if rx.search(n))

    def op_count(self, device: int, window: Interval, pattern) -> int:
        rx = re.compile(pattern) if isinstance(pattern, str) else pattern
        return sum(1 for n, _, _ in self._ops_in(device, window) if rx.search(n))

    def top_ops(self, device: int, window: Interval, k: int = 10):
        tot: Dict[str, float] = defaultdict(float)
        for n, s, e in self._ops_in(device, window):
            tot[n] += e - s
        return sorted(tot.items(), key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, device: int, window: Interval, k: int = 10):
        """The ``k`` longest gaps in which no op ran, each named by the
        shortest host span that covers at least half of it (the most
        specific thing the host was doing; ``idle`` where none does)."""
        busy = union((s, e) for _, s, e in self._ops_in(device, window))
        gaps, t = [], window[0]
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if window[1] > t:
            gaps.append((t, window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for lo, hi in gaps[:k]:
            best, name = float("inf"), "idle"
            for _, n, s, e in self.host:
                if (min(e, hi) - max(s, lo) >= 0.5 * (hi - lo) and e - s < best
                        and n != WINDOW_SPAN):
                    best, name = e - s, n
            out.append((name, hi - lo))
        return out


def op_name(event_name: str) -> str:
    """The HLO instruction's own name: a device event is named by the whole
    instruction text (``%hash_decode.1 = f32[...] custom-call(...)``), whose
    operands name other ops."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def newest_xplane(logdir: Path) -> Path:
    files = sorted(Path(logdir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    return files[-1]


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    ops: Dict[int, List[Tuple[str, float, float]]] = defaultdict(list)
    host: List[Tuple[str, str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops[dev].append((op_name(ev.name), s, s + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    host.append((line.name, ev.name, s, s + ev.duration_ns * 1e-9))
    return Trace(dict(ops), host)
