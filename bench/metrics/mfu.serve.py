"""Model FLOP/s of serving over the chip's peak for the configuration's
precision: decode and MLP of every cache miss, SAGE and head of every
requested id, over the window."""

from bench import model

PEAK = {"highest": "f32_highest_flops_per_s", "default": "bf16_flops_per_s"}


def read(r):
    c = r.counters
    if r.kind != "serve" or not c.get("engine_requests"):
        return None
    mc = r.cfg["model"]
    flops = model.forward_flops(mc, c["misses"], c["target_rows"])
    return 100.0 * flops / c["window_s"] / (r.peak[PEAK[mc["matmul_precision"]]] * r.chips)
