"""The Pallas ``hash_decode`` kernel's share of its roofline in serving:
the least time of the rows the engine handed it in the traced window
(each call reads every codebook once) over the kernel's device time."""

from bench import model
from bench import trace as tr


def read(r):
    c, t = r.counters, r.trace
    devs = r.device_ids()
    if r.kind != "serve" or t is None or not devs or not c.get("rows_decoded"):
        return None
    kernel = sum(t.op_time(d, r.window, tr.KERNEL) for d in devs)
    calls = sum(t.op_count(d, r.window, tr.KERNEL) for d in devs)
    if kernel <= 0 or calls == 0:
        return None
    mc = r.cfg["model"]
    rows = c["rows_decoded"]
    byts = model.decode_bytes(mc, rows) + (calls - 1) * model.decode_bytes(mc, 0)
    least = max(byts / r.peak["hbm_bytes_per_s"],
                rows * model.decode_flops_per_row(mc) / r.peak["bf16_flops_per_s"])
    return 100.0 * least / kernel
