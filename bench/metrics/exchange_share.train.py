"""Share of the chips' busy time in training spent in cross-chip exchange,
averaged over the cell's chips: every collective instruction by the name
the TPU gives it, with ``-`` or ``_`` (``all_to_all.13``, ``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute``) and the
``async-collective-start``/``-done`` pairs and ``async_collective_fusion``
that run a chunked all-gather.  With the owner-computes decode this holds
the owner exchange (the codes, rows and cotangent all_to_alls and the
codebook psum) and the collectives GSPMD adds to the step (the gradient
all-reduce, the gathers of the decoded table and of its cotangent)."""

import re

EXCHANGE = re.compile(r"^(all[-_]to[-_]all|all[-_]gather|all[-_]reduce|"
                      r"reduce[-_]scatter|collective[-_]permute|async[-_]collective)")


def read(r):
    devs = r.device_ids()
    if r.kind != "train" or r.trace is None or len(devs) < 2:
        return None
    shares = []
    for d in devs:
        busy = r.trace.busy(d, r.window)
        if busy <= 0:
            return None
        shares.append(r.trace.op_time(d, r.window, EXCHANGE) / busy)
    return 100.0 * sum(shares) / len(shares)
