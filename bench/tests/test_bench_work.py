"""The work functions behind the rooflines and MFU, against hand counts."""

import numpy as np

from bench import model, peaks
from bench.drivers import Reading

MC = dict(c=4, m=2, d_c=8, d_m=8, n_layers=3, d_e=4, hidden=6, fanout=3,
          n_classes=5, matmul_precision="highest")
PEAK = {"bf16_flops_per_s": 600.0, "f32_highest_flops_per_s": 100.0,
        "hbm_bytes_per_s": 1000.0}


def test_decoder_and_sage_flops_by_hand():
    assert model.decode_flops_per_row(MC) == 2 * 8
    assert model.mlp_flops_per_row(MC) == 2 * (8 * 8 + 8 * 8 + 8 * 4)
    # layer 1 on the target and its 3 neighbours: [agg, self] (8) -> 6, plus
    # the 4-row means; layer 2 on the target: (12) -> 6 plus a 3-row mean; head
    l1 = 4 * (2 * 8 * 6 + 4 * 4)
    l2 = 2 * 12 * 6 + 3 * 6
    assert model.sage_flops_per_target(MC) == l1 + l2 + 2 * 6 * 5
    assert model.forward_flops(MC, 10, 2) == 10 * (16 + 320) + 2 * model.sage_flops_per_target(MC)


def test_decode_bytes_by_hand():
    # one 32-bit word of codes and 8 f32 outputs per row, the 2x4x8 codebooks once
    assert model.decode_bytes(MC, 3) == 3 * (4 + 32) + 4 * 2 * 4 * 8


def test_shapes_cover_every_trainable_leaf():
    sh = model.shapes(MC)
    assert sh["codebooks"] == (2, 4, 8)
    assert [sh[f"mlp_w{i}"] for i in range(3)] == [(8, 8), (8, 8), (8, 4)]
    assert sh["sage_w1"] == (8, 6) and sh["sage_w2"] == (12, 6) and sh["out_w"] == (6, 5)
    assert set(sh) == set(model.program_paths(MC))


class FakeTrace:
    devices = [0]

    def op_time(self, d, w, pattern):
        return 0.5

    def op_count(self, d, w, pattern):
        return 2


def _read(name, reading):
    from bench import run
    return run.metric_reader(run.ROOT, name)(reading)


def test_mfu_train_by_hand():
    c = {"steps": 4, "window_s": 2.0, "batch": 2, "unique_rows": 40.0}
    r = Reading(kind="train", cfg={"model": MC}, peak=PEAK, chips=1, counters=c)
    want = 100.0 * 3 * model.forward_flops(MC, 10, 2) * 4 / 2.0 / 100.0
    assert np.isclose(_read("mfu.train", r), want)


def test_roofline_train_by_hand():
    # 400 distinct rows over 4 steps: 100 a step, whatever the padded cap
    c = {"steps": 4, "unique_rows": 400.0}
    r = Reading(kind="train", cfg={"model": MC}, peak=PEAK, chips=1, counters=c,
                trace=FakeTrace(), window=(0.0, 1.0))
    least = max(model.decode_bytes(MC, 100) / 1000.0, 100 * 16 / 600.0)
    assert np.isclose(_read("hash_decode_roofline.train", r), 100.0 * least * 4 / 0.5)


def test_roofline_serve_by_hand():
    c = {"rows_decoded": 300}
    r = Reading(kind="serve", cfg={"model": MC}, peak=PEAK, chips=1, counters=c,
                trace=FakeTrace(), window=(0.0, 1.0))
    byts = model.decode_bytes(MC, 300) + model.decode_bytes(MC, 0)
    least = max(byts / 1000.0, 300 * 16 / 600.0)
    assert np.isclose(_read("hash_decode_roofline.serve", r), 100.0 * least / 0.5)


def test_readers_find_nothing_in_the_other_kind_of_cell():
    r = Reading(kind="serve", cfg={"model": MC}, peak=PEAK, chips=1, counters={})
    for name in ("mfu.train", "hash_decode_roofline.train", "producer_ms_per_batch.train",
                 "input_wait_share.train", "device_idle_share.train",
                 "collective_share.train"):
        assert _read(name, r) is None


def test_peaks_table_refuses_unknown_devices():
    p = peaks.lookup("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert np.isclose(p["f32_highest_flops_per_s"] * 6, 197e12, rtol=1e-4)
    try:
        peaks.lookup("TPU v9 imaginary")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device must be an error")
