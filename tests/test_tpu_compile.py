"""The serving-path kernels compile for a TPU v5e chip, at the shapes of
sparse-cnn-s and of ResNet-50 v1.5 (bucket 128, 224×224, 4/8 DBB).

Interpret mode (every other kernel test) accepts block shapes and kernel
bodies the TPU compiler refuses. These tests hand each kernel to that
compiler, for a described v5e chip that is not attached, and check that
the compiled program holds the Pallas kernel (``tpu_custom_call``). No
chip is needed; nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""
import importlib
import json
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant import QuantDBBWeight
from repro.core.vdbb import DBBFormat, DBBWeight
from repro.kernels import core, ops

FMT = DBBFormat(8, 3, "matrix")  # sparse-cnn-s: 3/8 DBB, shared patterns
FMT_BW = DBBFormat(8, 3, None)   # paper-faithful per-column patterns
FMT_R50 = DBBFormat(8, 4, "matrix")  # ResNet-50: 4/8 DBB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _weight(k, n, dtype, sharding, fmt=FMT):
    nb, ng = k // fmt.bz, (1 if fmt.group == "matrix" else n)
    idx = _spec((nb, fmt.nnz, ng), jnp.int8, sharding)
    if dtype == jnp.int8:
        return QuantDBBWeight(_spec((nb, fmt.nnz, n), jnp.int8, sharding), idx,
                              _spec((n,), jnp.float32, sharding), fmt, (k, n))
    return DBBWeight(_spec((nb, fmt.nnz, n), dtype, sharding), idx, fmt, (k, n))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _assert_kernel(fn, *args):
    return _compile(fn, *args).as_text()


@pytest.mark.parametrize("n", [8, 128])  # 128: the benchmark's bucket
def test_stem_dense_conv(one_chip, n):
    """The fp32 stem (3→64, 64×64) with the fused bias/ReLU/requantize,
    its 9 taps × 3 channels packed into one contraction."""
    s = one_chip
    hlo = _assert_kernel(
        lambda x, w, b, o: ops.fused_im2col_conv(
            x, w, bias=b, relu=True, out_scale=o, interpret=False),
        _spec((n, 64, 64, 3), jnp.float32, s), _spec((3, 3, 3, 64), jnp.float32, s),
        _spec((64,), jnp.float32, s), _spec((), jnp.float32, s))
    assert re.search(r"%im2col_conv_packed[.\d]* = ", hlo)


# (H, C, F, stride): dense convs whose packed contraction K = 9·C spans
# more than one lane tile: unaligned (C=16), two row chunks (C=64), aligned
@pytest.mark.parametrize("h,c,f,stride", [(32, 16, 64, 1), (32, 64, 128, 1),
                                          (16, 128, 128, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8], ids=["fp32", "int8"])
def test_wide_dense_conv(one_chip, h, c, f, stride, dtype):
    s = one_chip
    hlo = _assert_kernel(
        lambda x, w: ops.fused_im2col_conv(x, w, stride=stride, interpret=False),
        _spec((8, h, h, c), dtype, s), _spec((3, 3, c, f), dtype, s))
    assert re.search(r"%im2col_conv_packed[.\d]* = ", hlo)


# (H, C, F, stride): l3 (stride 1) and l2 (stride 2) of sparse-cnn-s
@pytest.mark.parametrize("h,c,f,stride", [(32, 128, 128, 1), (64, 64, 128, 2)],
                         ids=["stride1", "stride2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8], ids=["fp32", "int8"])
def test_tc_sparse_conv(one_chip, h, c, f, stride, dtype):
    s = one_chip
    w = _weight(9 * c, f, dtype, s)
    if dtype == jnp.int8:  # the int8-resident serving layer, epilogue fused
        fn = lambda x, w, a, b, o: ops.quant_conv(  # noqa: E731
            x, w, 3, 3, a, bias=b, relu=True, out_scale=o, stride=stride,
            interpret=False)
        args = (_spec((), jnp.float32, s), _spec((f,), jnp.float32, s),
                _spec((), jnp.float32, s))
    else:
        fn = lambda x, w: ops.sparse_conv(  # noqa: E731
            x, w, 3, 3, stride=stride, interpret=False)
        args = ()
    _assert_kernel(fn, _spec((8, h, h, c), dtype, s), w, *args)


# sparse-cnn-s's compressed 3×3 convs l1–l7 (H, C, F, stride), bucket 128
CNN_CONVS = {"l1": (64, 64, 64, 1), "l2": (64, 64, 128, 2), "l3": (32, 128, 128, 1),
             "l4": (32, 128, 256, 2), "l5": (16, 256, 256, 1), "l6": (16, 256, 512, 2),
             "l7": (8, 512, 512, 1)}


def _cnn_conv3x3(one_chip, layer, nnz):
    """sparse-cnn-s's ``layer`` at ``nnz``/8 compiled as the plan serves
    it: int8 in, the epilogue fused, all of F in one block."""
    h, c, f, stride = CNN_CONVS[layer]
    s = one_chip
    w = _weight(9 * c, f, jnp.int8, s, DBBFormat(8, nnz, "matrix"))
    assert core.default_bf(f, 9 * c // 8 * nnz) == f
    return _compile(
        lambda x, w, a, b, o: ops.quant_conv(
            x, w, 3, 3, a, bias=b, relu=True, out_scale=o, stride=stride, bf=f,
            interpret=False),
        _spec((B, h, h, c), jnp.int8, s), w, _spec((), jnp.float32, s),
        _spec((f,), jnp.float32, s), _spec((), jnp.float32, s))


@pytest.mark.parametrize("nnz", [3, 1], ids=["d3of8", "d1of8"])
@pytest.mark.parametrize("layer", list(CNN_CONVS))
def test_cnn_conv3x3(one_chip, layer, nnz):
    assert _kernels(_cnn_conv3x3(one_chip, layer, nnz).as_text()) == {"vdbb_im2col_conv_tc"}


def test_int8_head_quant_matmul(one_chip):
    """The 512→1000 classifier head at M=8 (N pads to 1024 lanes)."""
    s = one_chip
    _assert_kernel(
        lambda x, w, a, b: ops.quant_matmul(x, w, a, bias=b, interpret=False),
        _spec((8, 512), jnp.float32, s), _weight(512, 1000, jnp.int8, s),
        _spec((), jnp.float32, s), _spec((1000,), jnp.float32, s))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8], ids=["fp32", "int8"])
def test_bw_vdbb_matmul(one_chip, dtype):
    """Per-column patterns: the in-VMEM expand at 256×512→512."""
    s = one_chip
    w = _weight(512, 512, dtype, s, FMT_BW)
    if dtype == jnp.int8:
        w = w.as_dbb()
    _assert_kernel(lambda a, w: ops.vdbb_matmul(a, w, interpret=False),
                   _spec((256, 512), dtype, s), w)


# ---------------------------------------------------------------------------
# ResNet-50 v1.5, bucket 128: every distinct launch of the served plan
# ---------------------------------------------------------------------------

B = 128
P0, P1 = ((0, 0), (0, 0)), ((1, 1), (1, 1))


def _kernels(hlo):
    """Names of the Pallas kernels in a compiled program."""
    return set(re.findall(r"%([a-z0-9_]+?)(?:\.\d+)? = \S+ custom-call\(", hlo))


def test_resnet50_stem(one_chip):
    """The 7×7/2 fp32 stem at 224² (padding 3) with its bias, ReLU and
    requantize, then the 3×3/2 max-pool on the int8 codes: XLA's own conv
    at full fp32 precision, as the model serves it (a Pallas conv would
    read the 3-channel input padded to 128 lanes)."""
    from repro.core.sparse_conv import DBBConv2d
    from repro.models.resnet import SparseResNet, max_pool

    from repro.configs import get_cnn_config

    stem = SparseResNet(get_cnn_config("sparse-resnet50", sparsity=0.5)).stem()
    assert stem.kernel_mode == "ref" and isinstance(stem, DBBConv2d)
    s = one_chip

    def fn(x, w, b, o):
        run, _ = stem.make_plan({"w": w, "b": b}, batch=B, h=224, w=224, relu=True,
                                out_scale=o, fused=True)
        return max_pool(run(x))

    compiled = jax.jit(fn).lower(
        _spec((B, 224, 224, 3), jnp.float32, s), _spec((7, 7, 3, 64), jnp.float32, s),
        _spec((64,), jnp.float32, s), _spec((), jnp.float32, s)).compile()
    hlo = compiled.as_text()
    assert "convolution(" in hlo and "s8[128,56,56,64]" in hlo


# (H, C, F, stride): c1 at each stage's map, and the projections (the first
# at stride 1, the rest at stride 2)
@pytest.mark.parametrize("h,c,f,stride", [
    (56, 256, 64, 1), (28, 512, 128, 1), (14, 1024, 256, 1), (7, 2048, 512, 1),
    (56, 64, 256, 1), (56, 256, 512, 2), (28, 512, 1024, 2), (14, 1024, 2048, 2)],
    ids=["c1_56", "c1_28", "c1_14", "c1_7", "proj_56", "proj_56s2", "proj_28s2",
         "proj_14s2"])
def test_resnet50_conv1x1(one_chip, h, c, f, stride):
    s = one_chip
    hlo = _assert_kernel(
        lambda x, w, a, b, o: ops.quant_conv(
            x, w, 1, 1, a, bias=b, relu=stride == 1 and f < c, out_scale=o,
            stride=stride, padding=P0, interpret=False),
        _spec((B, h, h, c), jnp.int8, s), _weight(c, f, jnp.int8, s, FMT_R50),
        _spec((), jnp.float32, s), _spec((f,), jnp.float32, s), _spec((), jnp.float32, s))
    assert _kernels(hlo) == {"vdbb_im2col_conv_tc_1x1"}


# (H, C, stride): c2 at each stage's map, stride 1, and the strided first
# block of stages 2–4 (its input map twice the output's)
@pytest.mark.parametrize("h,c,stride", [
    (56, 64, 1), (28, 128, 1), (14, 256, 1), (7, 512, 1),
    (56, 128, 2), (28, 256, 2), (14, 512, 2)],
    ids=["56", "28", "14", "7", "56s2", "28s2", "14s2"])
def test_resnet50_conv3x3(one_chip, h, c, stride):
    s = one_chip
    hlo = _assert_kernel(
        lambda x, w, a, b, o: ops.quant_conv(
            x, w, 3, 3, a, bias=b, relu=True, out_scale=o, stride=stride,
            padding=P1, interpret=False),
        _spec((B, h, h, c), jnp.int8, s), _weight(9 * c, c, jnp.int8, s, FMT_R50),
        _spec((), jnp.float32, s), _spec((c,), jnp.float32, s), _spec((), jnp.float32, s))
    assert _kernels(hlo) == {"vdbb_im2col_conv_tc"}


# (H, C, F): each stage's c3 with the shortcut added in its flush; the last
# flushes fp32 into pooling
@pytest.mark.parametrize("h,c,f", [(56, 64, 256), (28, 128, 512), (14, 256, 1024),
                                   (7, 512, 2048)], ids=["56", "28", "14", "7"])
def test_resnet50_residual_conv1x1(one_chip, h, c, f):
    s = one_chip
    last = h == 7
    hlo = _assert_kernel(
        lambda x, w, a, b, r, rs, o: ops.quant_conv(
            x, w, 1, 1, a, bias=b, relu=True, out_scale=None if last else o,
            residual=r, residual_scale=rs, padding=P0, interpret=False),
        _spec((B, h, h, c), jnp.int8, s), _weight(c, f, jnp.int8, s, FMT_R50),
        _spec((), jnp.float32, s), _spec((f,), jnp.float32, s),
        _spec((B, h, h, f), jnp.int8, s), _spec((), jnp.float32, s),
        _spec((), jnp.float32, s))
    assert _kernels(hlo) == {"vdbb_im2col_conv_tc_1x1_res"}
    assert re.search(rf"= {'f32' if last else 's8'}\[128,{h},{h},{f}\]", hlo)


def test_resnet50_head(one_chip):
    """The 2048→1000 int8 head at M = 128 (N pads to 1024 lanes)."""
    s = one_chip
    hlo = _assert_kernel(
        lambda x, w, a, b: ops.quant_matmul(x, w, a, bias=b, interpret=False),
        _spec((B, 2048), jnp.float32, s), _weight(2048, 1000, jnp.int8, s, FMT_R50),
        _spec((), jnp.float32, s), _spec((1000,), jnp.float32, s))
    assert _kernels(hlo) == {"vdbb_matmul_tc"}


# ---------------------------------------------------------------------------
# the benchmark's roofline readers find the compiled calls
# ---------------------------------------------------------------------------

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


def _call_line(compiled, name):
    """The HLO instruction of the Pallas call ``name`` as a device trace
    names the kernel's event: operands printed with their shapes."""
    from jax._src.lib import xla_client

    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    hlo = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    return re.search(rf"^\s*(?:ROOT )?(%{name}(?:\.\d+)? = .*custom-call\(.*)$",
                     hlo, re.M).group(1)


@pytest.mark.parametrize("case", ["cnn.l1.d3of8", "cnn.l7.d1of8", "r50.c2.56", "r50.c2.7"])
def test_rooflines_match_compiled_conv(one_chip, case, monkeypatch):
    """``conv_roofline`` (output map and input channels) and
    ``costs_resnet.match_conv`` (and the weight operand (kh·kw, C·nnz/bz,
    F)) still find the compiled k×k call, so neither roofline goes silent."""
    monkeypatch.syspath_prepend(str(BENCH))
    family, layer, tag = case.split(".")
    if family == "cnn":
        line = _call_line(_cnn_conv3x3(one_chip, layer, int(tag[1])), "vdbb_im2col_conv_tc")
        config = json.loads((BENCH / "configs" / f"sparse-cnn-s.{tag}.json").read_text())
        spec = importlib.util.spec_from_file_location("conv_roofline",
                                                      BENCH / "metrics" / "conv_roofline.py")
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        peaks = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
        run = types.SimpleNamespace(config=config, peaks=peaks, trace={
            "window": [0, 10**7], "devices": {"tpu0": [[line, 0, 10**6]]}})
        assert reader.read(run) is not None
        return
    h = int(tag)
    s = one_chip
    c = {56: 64, 7: 512}[h]
    compiled = _compile(
        lambda x, w, a, b, o: ops.quant_conv(
            x, w, 3, 3, a, bias=b, relu=True, out_scale=o, padding=P1, interpret=False),
        _spec((B, h, h, c), jnp.int8, s), _weight(9 * c, c, jnp.int8, s, FMT_R50),
        _spec((), jnp.float32, s), _spec((c,), jnp.float32, s), _spec((), jnp.float32, s))
    costs_resnet = importlib.import_module("costs_resnet")
    config = json.loads((BENCH / "configs" / "resnet50.d4of8.json").read_text())
    convs = [l for l in costs_resnet.layers(config) if l["kind"].startswith("conv")]
    hit = costs_resnet.match_conv(_call_line(compiled, "vdbb_im2col_conv_tc"), convs)
    assert hit is not None and hit[0]["kind"] == "conv3x3" and hit[1] == B
