"""The reduction from a profiler trace to busy time, idle share, kernel
time and collective share: on a small trace recorded on a TPU v5e chip
(``record_trace.py``: four steps of the Pallas ``hash_decode`` kernel with
10 ms host sleeps between them), and on hand-built traces."""

from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data" / "one_chip.xplane.pb"


@pytest.fixture(scope="module")
def chip():
    return tr.load(DATA)


def test_recorded_trace_busy_kernel_and_idle(chip):
    assert chip.devices == [0]
    win = chip.window()
    assert np.isclose(win[1] - win[0], 0.047448218, rtol=1e-6)
    busy = chip.busy(0, win)
    assert np.isclose(busy, 0.001130595, rtol=1e-6)
    kernel = chip.op_time(0, win, tr.KERNEL)
    assert np.isclose(kernel, 0.001092977, rtol=1e-6)
    assert chip.op_count(0, win, tr.KERNEL) == 4
    # the fusion that reads the kernel's output is not kernel time
    assert chip.op_time(0, win, "hash_decode") == kernel
    assert chip.op_time(0, win, tr.COLLECTIVE) == 0.0
    idle = 1.0 - busy / (win[1] - win[0])
    assert 0.97 < idle < 0.98
    gaps = chip.idle_gaps(0, win, 3)
    assert [n for n, _ in gaps] == ["$time sleep"] * 3     # inside bench.host_wait
    assert all(0.0105 < g < 0.0125 for _, g in gaps)
    assert chip.top_ops(0, win)[0][0] == "hash_decode.1"


def test_op_names_are_the_instruction_names():
    ev = ("%fusion.3 = f32[] fusion(f32[2048,512]{1,0} %hash_decode.1, "
          "f32[16,256,512]{2,1,0} %b.1), kind=kOutput")
    assert tr.op_name(ev) == "fusion.3"
    assert tr.KERNEL.search(tr.op_name(ev)) is None
    assert tr.KERNEL.search("hash_decode.12")
    assert tr.COLLECTIVE.search("all-to-all.2") and tr.COLLECTIVE.search("all-reduce-start")
    assert tr.COLLECTIVE.search("fusion.all-reduce") is None


def test_busy_is_a_union_and_counts_only_the_window():
    t = tr.Trace({0: [("a", 0.0, 2.0), ("b", 1.0, 3.0), ("all-reduce.1", 5.0, 6.0),
                      ("c", 9.0, 12.0)]},
                 [("python", "bench.window", 0.5, 10.0),
                  ("python", "sampling", 3.0, 5.0), ("python", "main", 0.0, 20.0)])
    win = t.window()
    assert win == (0.5, 10.0)
    assert np.isclose(t.busy(0, win), 2.5 + 1.0 + 1.0)
    assert np.isclose(t.op_time(0, win, tr.COLLECTIVE), 1.0)
    gaps = t.idle_gaps(0, win, 2)
    assert gaps[0] == ("main", 3.0)           # 6 -> 9: only `main` covers it
    assert gaps[1] == ("sampling", 2.0)       # 3 -> 5: the most specific span
    assert tr.union([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]
