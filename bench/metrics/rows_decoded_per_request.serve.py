"""Decoder rows the engine paid per request in the window, miss buckets
included (``GraphInferenceEngine.stats()``)."""


def read(r):
    c = r.counters
    if r.kind != "serve" or not c.get("engine_requests"):
        return None
    return c["rows_decoded"] / c["engine_requests"]
