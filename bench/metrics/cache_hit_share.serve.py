"""Share of frontier rows the hot-node cache served in the window
(``GraphInferenceEngine.stats()`` hits over hits + misses)."""


def read(r):
    c = r.counters
    if r.kind != "serve" or not (c.get("hits", 0) + c.get("misses", 0)):
        return None
    return 100.0 * c["hits"] / (c["hits"] + c["misses"])
