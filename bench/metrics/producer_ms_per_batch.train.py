"""Host time the prefetch producer spends per batch (sampling, code gather
and device put, from ``PrefetchIterator.stats()``) over the window."""


def read(r):
    c = r.counters
    if r.kind != "train" or not c.get("produced"):
        return None
    return c["producer_us"] / c["produced"] / 1e3
