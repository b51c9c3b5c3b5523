"""Share of the traced serving window in which no op ran on the device:
1 - union of op intervals / window, averaged over the chips."""


def read(r):
    if r.kind != "serve" or r.trace is None or not r.device_ids() or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
