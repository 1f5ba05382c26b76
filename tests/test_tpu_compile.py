"""The serving-path kernels compile for a TPU v5e chip, at sparse-cnn-s shapes.

Interpret mode (every other kernel test) accepts block shapes and kernel
bodies the TPU compiler refuses. These tests hand each kernel to that
compiler, for a described v5e chip that is not attached, and check that
the compiled program holds the Pallas kernel (``tpu_custom_call``). No
chip is needed; nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quant import QuantDBBWeight
from repro.core.vdbb import DBBFormat, DBBWeight
from repro.kernels import ops

FMT = DBBFormat(8, 3, "matrix")  # sparse-cnn-s: 3/8 DBB, shared patterns
FMT_BW = DBBFormat(8, 3, None)   # paper-faithful per-column patterns


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


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled.as_text()


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
