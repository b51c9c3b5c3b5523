"""The readers of the four-chip owner-decode cell: the kernel's roofline
over the rows each owner is handed (``owner_decode_roofline.train``), the
chips' share of busy time in cross-chip exchange
(``exchange_share.train``) and the producer's owner-plan share
(``owner_plan_share.train``, not listed in BENCHMARK.json), on hand-built
traces."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import model, run
from bench import trace as tr
from bench.drivers import Reading

ROOT = Path(__file__).resolve().parents[2]
CELL = "sage-products.train-owner4"
MC = dict(c=4, m=2, d_c=8, d_m=8, n_layers=3, d_e=4, hidden=6, fanout=3,
          n_classes=5, matmul_precision="highest")
PEAK = {"bf16_flops_per_s": 600.0, "f32_highest_flops_per_s": 100.0,
        "hbm_bytes_per_s": 1000.0}
CFG = {"model": MC, "runtime": {"owner_unique_cap": 30}}
WINDOW = ("python", "bench.window", 0.0, 10.0)


def read(name, reading):
    return run.metric_reader(run.ROOT, name)(reading)


def reading(ops, host=(WINDOW,), cfg=CFG, chips=2, kind="train"):
    t = tr.Trace(ops, list(host))
    return Reading(kind=kind, cfg=cfg, peak=PEAK, chips=chips, counters={},
                   trace=t, window=t.window())


def test_owner_roofline_counts_each_call_at_the_owner_cap():
    # chip 0 runs the kernel twice (0.25 s and 0.5 s), chip 1 once (0.25 s);
    # a fusion named after the kernel is not kernel time
    ops = {0: [("hash_decode.1", 1.0, 1.25), ("fusion.3", 1.25, 2.0),
               ("hash_decode.1", 3.0, 3.5)],
           1: [("hash_decode.1", 1.0, 1.25), ("all-to-all.2", 1.25, 1.5)]}
    least = max(model.decode_bytes(MC, 30) / 1000.0, 30 * 16 / 600.0)
    assert np.isclose(read("owner_decode_roofline.train", reading(ops)),
                      100.0 * least * 3 / 1.0)
    # only the cell's chips count
    assert np.isclose(read("owner_decode_roofline.train", reading(ops, chips=1)),
                      100.0 * least * 2 / 0.75)


def test_owner_roofline_reads_nothing_where_it_cannot():
    ops = {0: [("hash_decode.1", 1.0, 1.25)]}
    one_chip_cfg = {"model": MC, "runtime": {"batch_size": 8}}
    assert read("owner_decode_roofline.train", reading(ops, cfg=one_chip_cfg)) is None
    assert read("owner_decode_roofline.train", reading({0: [("fusion.1", 1.0, 2.0)]})) is None
    assert read("owner_decode_roofline.train", reading(ops, kind="serve")) is None
    untraced = Reading(kind="train", cfg=CFG, peak=PEAK, chips=2, counters={})
    assert read("owner_decode_roofline.train", untraced) is None


def test_owner_plan_share_is_the_union_of_its_spans_in_the_window():
    host = [WINDOW, ("python", "repro.train.step", 0.0, 10.0),
            ("python", "repro.producer.sample", -1.0, 4.0),
            ("python", "repro.producer.owner_plan", -1.0, 1.0),
            ("python", "repro.producer.sample", 6.0, 11.0),
            ("python", "repro.producer.owner_plan", 9.0, 11.0)]
    r = reading({0: [("fusion.1", 0.0, 1.0)]}, host)
    assert np.isclose(read("owner_plan_share.train", r), 100.0 * (1.0 + 1.0) / 10.0)


@pytest.mark.parametrize("host", [
    [WINDOW, ("python", "repro.train.step", 0.0, 10.0),
     ("python", "repro.producer.sample", 1.0, 2.0)],
    [WINDOW, ("python", "repro.producer.owner_plan", 1.0, 2.0)],
], ids=["no-owner-plan-spans", "no-loop-spans"])
def test_owner_plan_share_reads_nothing_without_its_spans(host):
    assert read("owner_plan_share.train", reading({0: [("fusion.1", 0.0, 1.0)]}, host)) is None


def test_exchange_share_counts_every_collective_as_the_tpu_names_it():
    # chip 0: the owner exchange's all_to_alls, GSPMD's all-reduce and the
    # chunked all-gather's async pair; fusions and copies are not exchange
    ops = {0: [("all_to_all.13", 0.0, 1.0), ("fusion.13", 1.0, 3.0),
               ("all-reduce", 3.0, 4.0), ("async-collective-start", 4.0, 4.5),
               ("async-collective-done", 4.5, 5.0), ("copy-done.2", 5.0, 6.0)],
           1: [("all-to-all.2", 0.0, 2.0), ("all-gather.6", 2.0, 3.0),
               ("fusion.7", 3.0, 8.0)]}
    share0, share1 = 3.0 / 6.0, 3.0 / 8.0
    assert np.isclose(read("exchange_share.train", reading(ops)),
                      100.0 * (share0 + share1) / 2)
    # the accepted reader's pattern misses the TPU's all_to_all.N and the
    # async pair: on chip 0 it sees the all-reduce alone
    assert np.isclose(read("collective_share.train", reading(ops)), 100.0 * 1.0 / 6.0)


def test_exchange_share_reads_nothing_where_it_cannot():
    ops = {0: [("all_to_all.13", 0.0, 1.0)], 1: [("all_to_all.13", 0.0, 1.0)]}
    assert read("exchange_share.train", reading(ops, chips=1)) is None
    assert read("exchange_share.train", reading(ops, kind="serve")) is None
    untraced = Reading(kind="train", cfg=CFG, peak=PEAK, chips=2, counters={})
    assert read("exchange_share.train", untraced) is None
    idle_chip = {0: [("all_to_all.13", 0.0, 1.0)], 1: [("fusion.1", 20.0, 21.0)]}
    assert read("exchange_share.train", reading(idle_chip)) is None


def test_owner_cell_entries():
    """The cell lists at least the metrics that measure its cross-chip work,
    each listed metric has a reader, and the configuration states the
    four-chip layout."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}[CELL]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    assert cell["chips"] == 4 and cell["traffic"] == "train"
    listed = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert listed >= {"train_nodes_per_s", "peak_hbm_gb", "setup_s",
                      "exchange_share.train", "owner_decode_roofline.train",
                      "device_idle_share.train", "producer_ms_per_batch.train"}
    for m in spec["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    assert cfg["model"]["lookup_impl"] == "owner:pallas"
    rc = cfg["runtime"]
    assert rc["n_shards"] == 4 and rc["batch_size"] == 4 * 1024
    assert set(conf["reduced"]) == set(cfg["reduced"]) == {"n_nodes"}
