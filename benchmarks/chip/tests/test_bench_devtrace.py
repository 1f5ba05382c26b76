"""The trace reduction, on five batches of sparse-cnn-s.d3of8 bucket 128
recorded on a TPU v5e (trimmed) and on intervals made by hand."""
import gzip
import json
import types

import pytest

import costs
import devtrace
from conftest import BENCH

DATA = BENCH / "tests" / "data" / "trace_offline_d3of8.json.gz"


@pytest.fixture(scope="module")
def tr():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def test_merged_and_gaps_by_hand():
    ev = [["a", 10, 5], ["b", 12, 10], ["c", 30, 5], ["d", 0, 3]]
    assert devtrace.merged(ev, 5, 40) == [[10, 22], [30, 35]]
    t = {"window": [5, 40], "devices": {"/device:TPU:0": ev},
         "host": [["main", "bench.window", 5, 35], ["main", "bench.wait", 22, 8]]}
    assert devtrace.busy_s(t) == pytest.approx(17e-9)
    assert devtrace.gaps(t) == [[5, 10], [22, 30], [35, 40]]
    assert devtrace.idle_gaps(t) == [["bench.window", 10e-9], ["main:bench.wait", 8e-9]]


def test_custom_call_shapes():
    name = ('%quant_conv.7 = s8[128,64,64,64]{3,2,1,0:T(8,128)(4,1)} custom-call('
            's8[128,1,66,66,64]{4,3,2,1,0} %p, s8[9,24,64]{2,1,0} %w), '
            'custom_call_target="tpu_custom_call"')
    assert devtrace.custom_call_shapes(name) == ((128, 64, 64, 64), (128, 1, 66, 66, 64))
    assert devtrace.custom_call_shapes("%copy.5 = f32[128]{0} copy(f32[128]{0} %x)") is None


def test_recorded_trace(tr):
    ops = tr["devices"]["/device:TPU:0"]
    kernels = [devtrace.custom_call_shapes(n) for n, _, _ in ops]
    kernels = [k for k in kernels if k]
    assert len(kernels) == 5 * 9  # five batches: stem, 7 convs, head
    assert 0.05 < devtrace.idle_share(tr) < 0.5
    top = devtrace.device_ops(tr)
    assert len(top) == devtrace.TOP
    assert top[0][0].startswith("%fused_im2col_conv")  # the fp32 stem
    assert sum(s for _, s in devtrace.idle_gaps(tr)) == pytest.approx(
        devtrace.window_s(tr) - devtrace.busy_s(tr), rel=1e-6)


def test_conv_roofline_reads_the_seven_compressed_convs(tr):
    import importlib.util

    spec = importlib.util.spec_from_file_location("m", BENCH / "metrics" / "conv_roofline.py")
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    config = json.loads((BENCH / "configs" / "sparse-cnn-s.d3of8.json").read_text())
    peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    run = types.SimpleNamespace(trace=tr, config=config, peaks=peaks)
    share = m.read(run)
    convs = [l for l in costs.layers(config) if l["kind"] == "conv"]
    least = 5 * sum(costs.least_time_s(l, 128, peaks) for l in convs)
    spent = sum(d for n, _, d in tr["devices"]["/device:TPU:0"]
                if n.split(" ")[0].startswith("%quant_conv")) / 1e9
    assert share == pytest.approx(100 * least / spent)
    assert 0 < share < 100
    assert m.read(types.SimpleNamespace(trace=None)) is None
