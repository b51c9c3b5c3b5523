"""The train loop's own spans on the profiler's clock, and the device's idle
time split by which of them the loop was in.

``repro.train.run_training`` marks each iteration with a
``repro.train.step`` span holding ``repro.train.next_batch``,
``.dispatch``, ``.sync``, ``.fence`` and ``.checkpoint``.  They are found by
name, never by thread line: the loop's and the prefetch producer's threads
are both lines called ``python``.  Every interval is clipped to the
benchmark's window.  A trace without ``repro.train.step`` spans (a program
that writes none) has nothing to read: the readers return None.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from bench import trace as tr

STEP = "repro.train.step"
NEXT_BATCH = "repro.train.next_batch"
SYNC = "repro.train.sync"


def spans(t: tr.Trace, name: str, window: tr.Interval) -> List[tr.Interval]:
    """Union of the host spans called ``name``, clipped to ``window``."""
    lo, hi = window
    return tr.union((max(s, lo), min(e, hi)) for _, n, s, e in t.host
                    if n == name and e > lo and s < hi)


def length(a: List[tr.Interval]) -> float:
    return sum(e - s for s, e in a)


def overlap(a: List[tr.Interval], b: List[tr.Interval]) -> float:
    """Length of the intersection of two unions (sorted and disjoint)."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def loop_traced(r) -> bool:
    """A traced training reading whose trace holds the loop's step spans."""
    return (r.kind == "train" and r.trace is not None
            and any(n == STEP for _, n, _, _ in r.trace.host))


def share(r, name: str) -> Optional[float]:
    """The union of the spans called ``name`` over the window, %."""
    if not loop_traced(r):
        return None
    lo, hi = r.window
    return 100.0 * length(spans(r.trace, name, r.window)) / (hi - lo)


def idle_split(r) -> Optional[Dict[str, float]]:
    """Shares (%) of the window in which no op ran on the device, by where
    the loop was: ``input`` inside ``next_batch``, ``sync`` inside
    ``sync``, ``loop`` anywhere else (dispatch, fence, bookkeeping,
    between steps).  Averaged over the chips as ``device_idle_share`` is,
    so the three add up to it."""
    devs = r.device_ids()
    if not loop_traced(r) or not devs:
        return None
    lo, hi = r.window
    batch = spans(r.trace, NEXT_BATCH, r.window)
    held = tr.union(batch + spans(r.trace, SYNC, r.window))
    idle = {"input": 0.0, "sync": 0.0, "loop": 0.0}
    for d in devs:
        busy = tr.union((max(s, lo), min(e, hi)) for _, s, e in r.trace.ops.get(d, ())
                        if e > lo and s < hi)
        idle_batch = length(batch) - overlap(batch, busy)
        idle_held = length(held) - overlap(held, busy)
        idle["input"] += idle_batch
        idle["sync"] += idle_held - idle_batch
        idle["loop"] += (hi - lo) - length(busy) - idle_held
    return {k: 100.0 * v / len(devs) / (hi - lo) for k, v in idle.items()}
