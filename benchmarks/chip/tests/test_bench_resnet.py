"""The ResNet-50 cell's own pieces: ``costs_resnet`` against counts made by
hand for one layer of each kind, the three readers that only this cell
reports on a small trace made by hand (the 56² 1×1 and 3×3 calls share
output and input shapes; their weight operands tell them apart), and a
whole run, and its control, at a smoke size on the CPU."""
import argparse
import json
import types

import pytest

import control
import costs_resnet
import rehearse
import run
from conftest import BENCH

PEAKS = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
CELL = "resnet50.d4of8.offline"


def config():
    return json.loads((BENCH / "configs" / "resnet50.d4of8.json").read_text())


def layer(name):
    return {l["name"]: l for l in costs_resnet.layers(config())}[name]


def test_layer_count_and_total_ops():
    layers = costs_resnet.layers(config())
    assert len(layers) == 54  # stem, 52 compressed convs, head
    assert sum(l["kind"] == "conv1x1" for l in layers) == 36
    assert sum(costs_resnet.ops_per_image(l) for l in layers) == 4_207_198_208


# (layer, ops per image, bytes of one image's call) counted by hand at 4/8:
# kept rows = K/2, one int8 index per kept row (patterns shared by all
# columns), fp32 scale and bias rows
BY_HAND = [
    # 7×7/2 stem, padding 3: 224² fp32 in, 112² int8 out, dense fp32 weights
    ("stem", 2 * 112 * 112 * 147 * 64,
     224 * 224 * 3 * 4 + 147 * 64 * 4 + 64 * 4 + 112 * 112 * 64),
    # 1×1 256→64 at 56²
    ("s1b2.c1", 2 * 56 * 56 * 128 * 64,
     56 * 56 * 256 + (128 * 64 + 128 + 2 * 64 * 4) + 56 * 56 * 64),
    # 1×1/2 projection 256→512, 56² in, 28² out
    ("s2b1.proj", 2 * 28 * 28 * 128 * 512,
     56 * 56 * 256 + (128 * 512 + 128 + 2 * 512 * 4) + 28 * 28 * 512),
    # 3×3/2 128→128, 56² in, 28² out: K = 1152, 576 kept
    ("s2b1.c2", 2 * 28 * 28 * 576 * 128,
     56 * 56 * 128 + (576 * 128 + 576 + 2 * 128 * 4) + 28 * 28 * 128),
    # residual c3 128→512 at 28²: the shortcut's int8 codes read beside the input
    ("s2b1.c3", 2 * 28 * 28 * 64 * 512,
     28 * 28 * 128 + (64 * 512 + 64 + 2 * 512 * 4) + 28 * 28 * 512 + 28 * 28 * 512),
    # the last c3 flushes fp32 into pooling
    ("s4b3.c3", 2 * 7 * 7 * 256 * 2048,
     7 * 7 * 512 + (256 * 2048 + 256 + 2 * 2048 * 4) + 7 * 7 * 2048 * 4 + 7 * 7 * 2048),
    # head 2048→1000: int8 pooled codes in, fp32 logits out
    ("fc", 2 * 1024 * 1000, 2048 + (1024 * 1000 + 1024 + 2 * 1000 * 4) + 1000 * 4),
]


@pytest.mark.parametrize("name,ops,nbytes", BY_HAND, ids=[b[0] for b in BY_HAND])
def test_costs_by_hand(name, ops, nbytes):
    l = layer(name)
    assert costs_resnet.ops_per_image(l) == ops
    assert costs_resnet.bytes_per_call(l, 1) == nbytes


def test_peak_time_per_image():
    t = costs_resnet.peak_time_per_image_s(config(), PEAKS)
    stem = 2 * 112 * 112 * 147 * 64
    assert t == pytest.approx(stem / 197e12 + (4_207_198_208 - stem) / 393e12)
    assert 11.2e-6 < t < 11.4e-6


def call(name, out, operands):
    """A trace event of a Pallas call: the HLO instruction as the profiler
    names it."""
    args = ", ".join(f"{t}{{2,1,0:T(8,128)(4,1)}} %a{i}" for i, t in enumerate(operands))
    return (f"%{name} = {out}{{3,2,1,0:T(8,128)(4,1)}} custom-call({args}), "
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}, '
            "frontend_attributes={kernel_metadata={}}")


MS = 1_000_000  # ns
ONE = call("vdbb_im2col_conv_tc_1x1.3", "s8[128,56,56,64]",
           ["s8[128,1,56,56,64]", "s8[1,32,64]", "s32[1,32,1]", "f32[1,64]", "f32[1,64]",
            "f32[1,64]"])
THREE = call("vdbb_im2col_conv_tc.4", "s8[128,56,56,64]",
             ["s8[128,1,58,58,64]", "s8[9,32,64]", "s32[9,32,1]", "f32[1,64]", "f32[1,64]",
              "f32[1,64]"])
RES = call("vdbb_im2col_conv_tc_1x1_res.5", "s8[128,28,28,512]",
           ["s8[128,1,28,28,128]", "s8[1,64,512]", "s32[1,64,1]", "f32[1,512]", "f32[1,512]",
            "s8[128,28,28,512]", "f32[1,512]", "f32[1,512]"])
# a 1×1 of the right output whose input channels match no layer
STRAY = call("vdbb_im2col_conv_tc_1x1.9", "s8[128,56,56,64]",
             ["s8[128,1,56,56,32]", "s8[1,16,64]", "s32[1,16,1]"])


def trace_run(events, config_=None):
    tr = {"window": [0, 100 * MS], "devices": {"/device:TPU:0": events},
          "host": [["m", "bench.window", 0, 100 * MS]]}
    window = types.SimpleNamespace(n=[128] * 40, done={i: 1.0 for i in range(40)},
                                   error={}, t_end=2.0, seconds=2.0)
    return types.SimpleNamespace(config=config_ or config(), trace=tr, peaks=PEAKS,
                                 chips=1, window=window)


def reader(name):
    return run.load(BENCH / "metrics" / f"{name}.py", f"m_{name}").read


def test_match_tells_1x1_from_3x3_by_the_weight_operand():
    convs = [l for l in costs_resnet.layers(config()) if l["kind"].startswith("conv")]
    one, three = costs_resnet.match_conv(ONE, convs), costs_resnet.match_conv(THREE, convs)
    assert one[0]["kind"] == "conv1x1" and one[0]["cin"] == 64 and one[1] == 128
    assert three[0]["kind"] == "conv3x3" and three[0]["name"].endswith(".c2")
    assert costs_resnet.match_conv(RES, convs)[0]["res_bytes"] == 1
    assert costs_resnet.match_conv(STRAY, convs) is None
    assert costs_resnet.match_conv("%fusion.4 = f32[128,112,112,64]{...} fusion()", convs) is None


def test_roofline_readers_on_a_trace_made_by_hand():
    events = [[ONE, 10 * MS, 2 * MS], [THREE, 20 * MS, 4 * MS], [RES, 30 * MS, 1 * MS],
              [STRAY, 40 * MS, 5 * MS], ["%fusion.4 = f32[128,112,112,64]", 50 * MS, 9 * MS],
              [ONE, 99 * MS, 2 * MS]]  # runs past the window's end: not counted
    r = trace_run(events)
    least = {n: costs_resnet.least_time_s(layer(n), 128, PEAKS)
             for n in ("s1b1.c1", "s1b1.c2", "s2b1.c3")}
    assert reader("conv_roofline.resnet")(r) == pytest.approx(
        100 * sum(least.values()) / 7e-3)
    assert reader("conv1x1_roofline.resnet")(r) == pytest.approx(
        100 * (least["s1b1.c1"] + least["s2b1.c3"]) / 3e-3)
    # the 56² 1×1 (64→64) is bytes-bound at bucket 128; nothing passes 100 %
    assert least["s1b1.c1"] == pytest.approx(
        costs_resnet.bytes_per_call(layer("s1b1.c1"), 128) / 819e9)
    assert 0 < reader("conv_roofline.resnet")(r) < 100


def test_readers_find_nothing_where_nothing_ran():
    assert reader("conv_roofline.resnet")(trace_run([[THREE, 0, MS]])) is not None
    nothing = trace_run([["%fusion = f32[8]", 0, MS]])
    assert reader("conv_roofline.resnet")(nothing) is None
    assert reader("conv1x1_roofline.resnet")(trace_run([[THREE, 0, MS]])) is None
    untraced = dict(vars(nothing), trace=None)
    assert reader("conv_roofline.resnet")(types.SimpleNamespace(**untraced)) is None
    # a configuration without bottleneck blocks (sparse-cnn-s) reads nothing
    cnn = json.loads((BENCH / "configs" / "sparse-cnn-s.d3of8.json").read_text())
    for name in ("conv_roofline.resnet", "conv1x1_roofline.resnet", "mfu.resnet"):
        assert reader(name)(trace_run([[ONE, 0, MS]], cnn)) is None


def test_mfu_is_the_rate_times_the_time_at_peak():
    r = trace_run([])
    rate = 40 * 128 / 2.0
    assert reader("mfu.resnet")(r) == pytest.approx(
        100 * rate * costs_resnet.peak_time_per_image_s(config(), PEAKS))


# ---------------------------------------------------------------------------
# a whole run and its control at a smoke size (CPU, Pallas interpreted)
# ---------------------------------------------------------------------------


def smoke(config_, traffic):
    """The cell at a size the CPU's kernel interpreter gets through: 32²
    images, two stages of bottleneck widths 16 and 32 (two blocks, then one
    strided), ten classes; buckets of 8."""
    config_ = dict(config_, image_size=32, stage_channels=[16, 32], stage_blocks=[2, 1],
                   num_classes=10, calibration_images=4)
    return config_, dict(traffic, pool_images=64, request_images=8, buckets=[8])


def test_smoke_run_is_correct(capsys):
    args = argparse.Namespace(workload=CELL, seed=2**31 + 77, seconds=1.0, trace=0)
    assert run.measure(args, find_devices=rehearse.cpu_devices, resize=smoke) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["retraces_after_warmup"] == 0
    assert out["checks"]["logit_rel_l2_max"]["value"] < 0.05


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_int4_control_reads_above_the_limit(seed):
    import jax

    traffic = json.loads((BENCH / "traffic" / "offline.json").read_text())
    config_, traffic = smoke(config(), traffic)
    family = run.load(BENCH / "families" / "sparse_resnet.py", "fam_resnet")
    reference = run.load(BENCH / "references" / "sparse_resnet.py", "ref_resnet")
    correct, checks = control.reading(jax, config_, traffic, family, reference, seed)
    assert correct is False
    assert checks["logit_rel_l2_max"]["value"] > checks["logit_rel_l2_max"]["limit"]
