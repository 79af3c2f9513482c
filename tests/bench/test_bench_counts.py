"""FLOP and byte counts against hand counts at the cells' shapes, and the
table of peaks."""

import json

import pytest

from bench_tiny import ROOT
from bench.harness import counts, peaks

MODEL = json.loads((ROOT / "bench/configs/dpr-bert-base-contaccum.json").read_text())["model"]


def workload(name):
    return json.loads((ROOT / "bench/workloads" / f"{name}.json").read_text())


def per_token(seq):  # QKV, output, two FFN products, QK^T and AV, per layer
    d, ff = 768, 3072
    return 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * ff + 2 * 2 * seq * d


@pytest.mark.parametrize("cell, bank, total", [
    ("train-paper", 2048, 3.767e13),
    ("train-bigbank", 16384, 5.718e13),
])
def test_update_flops_by_hand(cell, bank, total):
    towers = 128 * 32 * 12 * per_token(32) + 256 * 256 * 12 * per_token(256)
    sim = 16 * 2 * (8 + bank) * (16 + bank) * 768
    want = 3 * (towers + sim)
    got = counts.contaccum_update_flops(workload(cell), MODEL)
    assert got == want
    assert got == pytest.approx(total, rel=1e-3)


@pytest.mark.parametrize("cell, share", [("train-paper", 0.0083), ("train-bigbank", 0.3467)])
def test_similarity_share(cell, share):
    wl = workload(cell)
    rows, cols = counts.contaccum_chunk_shapes(wl, 768)
    sim = 3 * 16 * counts.similarity_flops(rows, cols, 768)
    assert sim / counts.contaccum_update_flops(wl, MODEL) == pytest.approx(share, abs=1e-4)


def test_infonce_least_time_bigbank():
    # per chunk: 8 query rows and 16,384 bank rows against 16,400 columns,
    # each a forward, dQ and dP product; the big call is compute-bound
    wl = workload("train-bigbank")
    t = counts.infonce_least_s(wl, MODEL, lambda f, b: peaks.least_time_s(f, b, "TPU v5 lite"))
    big = 3 * 16 * 2 * 16384 * 16400 * 768 / 197e12
    small = 3 * 16 * 2 * (8 + 16400) * 768 / 819e9
    assert t == pytest.approx(big + small)


def test_search_work_msmarco():
    rows = 135 * 65536
    flops, bytes_ = counts.search_work(32, 32, rows, MODEL)
    layers = 12 * (4 * 768 * 768 + 2 * 768 * 3072 + 9 * 768 + 3072) * 4
    assert bytes_ == rows * 768 * 2 + layers
    assert flops == 32 * 32 * 12 * per_token(32) + 2 * 32 * rows * 768
    # bandwidth-bound: 13.9 GB at 819 GB/s
    assert peaks.least_time_s(flops, bytes_, "TPU v5 lite") == pytest.approx(bytes_ / 819e9)


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_peaks_of_v5e(kind):
    p = peaks.peaks(kind)
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert p["ici_bits_per_s"] == 1600e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "TPU v5p"])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks(kind)
