"""The Pallas ``hash_decode`` kernel's share of its roofline in training
with the owner-computes decode (``lookup_impl`` ``owner:pallas``).  Each
kernel call on a chip decodes ``owner_unique_cap`` rows: the padded rows
the owner plan hands the kernel, not the distinct rows the step requires
(the plans' ``n_owned``, which the training harness does not count).  A call's least
time is the larger of its bytes (packed codes in, each codebook once, rows
out) over HBM bandwidth and its m row additions a row over the bf16 peak;
summed over the calls of every chip, over the kernel's summed device time."""

from bench import model
from bench import trace as tr


def read(r):
    t, devs = r.trace, r.device_ids()
    rows = r.cfg.get("runtime", {}).get("owner_unique_cap")
    if r.kind != "train" or t is None or not devs or not rows:
        return None
    calls = sum(t.op_count(d, r.window, tr.KERNEL) for d in devs)
    kernel = sum(t.op_time(d, r.window, tr.KERNEL) for d in devs)
    if calls <= 0 or kernel <= 0:
        return None
    mc = r.cfg["model"]
    least = max(model.decode_bytes(mc, rows) / r.peak["hbm_bytes_per_s"],
                rows * model.decode_flops_per_row(mc) / r.peak["bf16_flops_per_s"])
    return 100.0 * least * calls / kernel
