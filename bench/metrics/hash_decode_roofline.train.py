"""The Pallas ``hash_decode`` kernel's share of its roofline in training:
the least time the decode's required work takes on the chip over the
kernel's summed device time in the traced window, over the chips.  The
required work is that of the distinct frontier rows each step decodes (not
the padded rows the kernel is handed): packed codes in, each codebook once
a step, rows out, m row additions per row; the larger of bytes over HBM
bandwidth and additions over peak."""

from bench import model
from bench import trace as tr


def read(r):
    c, t = r.counters, r.trace
    devs = r.device_ids()
    if r.kind != "train" or t is None or not devs or not c.get("steps") \
            or not c.get("unique_rows"):
        return None
    kernel = sum(t.op_time(d, r.window, tr.KERNEL) for d in devs)
    if kernel <= 0:
        return None
    mc = r.cfg["model"]
    rows = c["unique_rows"] / c["steps"] / len(devs)
    least = max(model.decode_bytes(mc, rows) / r.peak["hbm_bytes_per_s"],
                rows * model.decode_flops_per_row(mc) / r.peak["bf16_flops_per_s"])
    return 100.0 * least * c["steps"] * len(devs) / kernel
