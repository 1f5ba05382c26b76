"""Ops and bytes per layer of sparse-cnn-s, against counts made by hand."""
import json

import pytest

import costs
from conftest import BENCH

# (name, Ho·Wo, K = kh·kw·C, F) of every layer; the stem (C=3) is dense
LAYERS = [("l0", 64 * 64, 27, 64), ("l1", 64 * 64, 576, 64),
          ("l2", 32 * 32, 576, 128), ("l3", 32 * 32, 1152, 128),
          ("l4", 16 * 16, 1152, 256), ("l5", 16 * 16, 2304, 256),
          ("l6", 8 * 8, 2304, 512), ("l7", 8 * 8, 4608, 512),
          ("l8", 1, 512, 1000)]


def config(nnz):
    return json.loads((BENCH / "configs" / f"sparse-cnn-s.d{nnz}of8.json").read_text())


@pytest.mark.parametrize("nnz", [3, 1])
def test_ops_per_image_by_hand(nnz):
    got = {l["name"]: costs.ops_per_image(l) for l in costs.layers(config(nnz))}
    want = {name: 2 * m * (k if name == "l0" else k * nnz // 8) * f
            for name, m, k, f in LAYERS}
    assert got == want
    if nnz == 3:
        assert got["l1"] == 113_246_208  # 64·64 · 216 · 64 · 2
        assert sum(got.values()) == 637_393_920


@pytest.mark.parametrize("nnz", [3, 1])
def test_bytes_per_image_by_hand(nnz):
    layers = {l["name"]: l for l in costs.layers(config(nnz))}
    # l1: int8 64·64·64 in and out; 576·nnz/8 kept rows of 64 int8 values,
    # one int8 index per kept row (patterns shared by all columns), scale
    # and bias rows
    kept = 576 * nnz // 8
    assert costs.bytes_per_call(layers["l1"], 1) == (
        2 * 64 * 64 * 64 + kept * 64 + kept + 2 * 64 * 4)
    # l7 flushes fp32 into pooling
    kept = 4608 * nnz // 8
    assert costs.bytes_per_call(layers["l7"], 1) == (
        8 * 8 * 512 + kept * 512 + kept + 2 * 512 * 4 + 8 * 8 * 512 * 4)
    # the stem reads fp32 images, holds dense fp32 weights, writes int8
    assert costs.bytes_per_call(layers["l0"], 2) == (
        2 * 64 * 64 * 3 * 4 + 27 * 64 * 4 + 64 * 4 + 2 * 64 * 64 * 64)


def test_least_time_is_the_larger_bound():
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    l1 = costs.layers(config(3))[1]
    t = costs.least_time_s(l1, 128, peaks)
    assert t == pytest.approx(costs.bytes_per_call(l1, 128) / 819e9)
    assert t > 128 * 113_246_208 / 393e12  # bytes-bound at 3/8
