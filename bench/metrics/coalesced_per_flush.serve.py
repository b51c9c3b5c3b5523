"""Requests the batcher coalesced per engine call in the window
(``ServingBatcher.stats()``'s completed over microbatches)."""


def read(r):
    c = r.counters
    if r.kind != "serve" or not c.get("microbatches"):
        return None
    return c["completed"] / c["microbatches"]
