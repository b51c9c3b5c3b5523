"""EXACT reproduction of the paper's memory arithmetic (Tables 2, 4, 6)."""

import pytest

from repro.core import memory as M


@pytest.mark.parametrize("n,ref", list(M.PAPER_TABLE4_GLOVE.items()))
def test_table4_glove(n, ref):
    assert abs(M.compression_ratio(n, 300, 2, 128) - ref) < 0.011


@pytest.mark.parametrize("n,ref", list(M.PAPER_TABLE4_M2V.items()))
def test_table4_metapath2vec(n, ref):
    assert abs(M.compression_ratio(n, 128, 2, 128) - ref) < 0.011


@pytest.mark.parametrize("cm", list(M.PAPER_TABLE6_GLOVE))
def test_table6_glove(cm):
    c, m = cm
    for n, ref in M.PAPER_TABLE6_GLOVE[cm].items():
        assert abs(M.compression_ratio(n, 300, c, m) - ref) < 0.011, (cm, n)


@pytest.mark.parametrize("cm", list(M.PAPER_TABLE6_M2V))
def test_table6_metapath2vec(cm):
    c, m = cm
    for n, ref in M.PAPER_TABLE6_M2V[cm].items():
        assert abs(M.compression_ratio(n, 128, c, m) - ref) < 0.011, (cm, n)


def test_table2_exact():
    t = M.PAPER_TABLE2
    light = M.memory_breakdown(t["n"], t["d_e"], 256, 16, 512, 512, 3, "light")
    full = M.memory_breakdown(t["n"], t["d_e"], 256, 16, 512, 512, 3, "full")
    assert abs(light.raw_table_bytes / M.MiB - t["raw_gpu_mib"]) < 0.01
    assert abs(light.binary_code_bytes / M.MiB - t["binary_code_mib"]) < 0.01
    assert abs(light.trainable_decoder_bytes / M.MiB - t["light_decoder_gpu_mib"]) < 0.01
    assert abs(light.frozen_decoder_bytes / M.MiB - t["light_codebooks_cpu_mib"]) < 0.01
    assert abs(full.trainable_decoder_bytes / M.MiB - t["full_decoder_gpu_mib"]) < 0.01
    # GPU-only compression ratio 43.75 (raw + GNN) / (full decoder + GNN)
    gnn = t["gnn_mib"] * M.MiB
    ratio = (full.raw_table_bytes + gnn) / (full.trainable_decoder_bytes + gnn)
    assert abs(ratio - t["full_ratio_gpu"]) < 0.02


def test_ratio_grows_with_entities():
    r = [M.compression_ratio(n, 300, 2, 128) for n in (5000, 50000, 500000)]
    assert r[0] < r[1] < r[2]


def test_musicgen_marginality_note():
    """At n=2048/codebook the gain is marginal (~1.2x, vs the
    paper's ~40x at products scale) — compression not worth the lossiness
    for a 16 MB table, hence musicgen defaults to dense."""
    r = M.compression_ratio(2048, 2048, 256, 16)
    assert 1.0 < r < 2.0, r
