"""Share of device 0's busy time in training spent in collectives
(all-to-all, all-gather, all-reduce and their kin)."""

from bench import trace as tr


def read(r):
    devs = r.device_ids()
    if r.kind != "train" or r.trace is None or len(devs) < 2:
        return None
    busy = r.trace.busy(devs[0], r.window)
    coll = r.trace.op_time(devs[0], r.window, tr.COLLECTIVE)
    if busy <= 0 or coll <= 0:
        return None
    return 100.0 * coll / busy
