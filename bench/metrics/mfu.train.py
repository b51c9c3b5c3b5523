"""Model FLOP/s of training over the chips' peak for the configuration's
precision: three times the forward work (decode and MLP of each distinct
frontier row the window's steps decoded, SAGE and head of each target)
over the window."""

from bench import model

PEAK = {"highest": "f32_highest_flops_per_s", "default": "bf16_flops_per_s"}


def read(r):
    c = r.counters
    if r.kind != "train" or not c.get("steps") or not c.get("unique_rows"):
        return None
    mc = r.cfg["model"]
    flops = 3 * model.forward_flops(mc, c["unique_rows"], c["batch"] * c["steps"])
    peak = r.peak[PEAK[mc["matmul_precision"]]] * r.chips
    return 100.0 * flops / c["window_s"] / peak
