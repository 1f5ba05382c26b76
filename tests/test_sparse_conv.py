"""Sparse-conv path validation: the fused IM2COL × VDBB kernel vs
``lax.conv_general_dilated(x, dbb_decode(w))``, conv edge cases for the
generalized dense kernel, the DBBConv2d layer lifecycle, and the
grouped-pattern encode/decode round-trip.

Pallas kernels run in interpret mode on CPU (the kernel body executes in
Python), so these validate the exact code that compiles for TPU.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import QuantDBBWeight, quantize_dbb
from repro.core.sparse_conv import DBBConv2d
from repro.core.vdbb import (
    DBBFormat,
    dbb_conv_costs,
    dbb_decode,
    dbb_decode_conv,
    dbb_encode,
    dbb_encode_conv,
    satisfies_dbb,
)
from repro.kernels import ops, ref
from repro.kernels.im2col_conv import im2col_conv
from repro.kernels.vdbb_im2col_conv import vdbb_im2col_conv

TOLS = {jnp.float32: dict(rtol=1e-4, atol=1e-4), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _mk_conv(n, h, w, c, f, kh, kw, nnz, group, dtype=jnp.float32, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (n, h, w, c), jnp.float32).astype(dtype)
    w4 = jax.random.normal(k2, (kh, kw, c, f), jnp.float32)
    fmt = DBBFormat(8, nnz, group)
    dw = dbb_encode_conv(w4, fmt, prune=True)
    dw = jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, dw
    )
    return x, dw, fmt


class TestDenseConvEdgeCases:
    """Generalized im2col_conv vs lax for every lifted restriction."""

    @pytest.mark.parametrize(
        "n,h,w,c,f,kh,kw,stride,padding,tiles",
        [
            (1, 8, 8, 8, 16, 3, 3, 1, "SAME", None),      # baseline
            (2, 9, 7, 4, 8, 3, 3, 2, "SAME", None),       # stride 2, odd map
            (1, 8, 8, 8, 8, 2, 2, 2, "VALID", None),      # even 2x2 kernel
            (1, 10, 10, 24, 8, 3, 3, 1, "SAME", None),    # non-128 channels
            (1, 12, 12, 8, 16, 5, 3, 1, "SAME", (6, 4)),  # spatial tiling
            (2, 16, 16, 3, 8, 3, 3, 2, "SAME", (4, 4)),   # tiling + stride
            (1, 7, 7, 5, 8, 4, 4, 3, ((1, 2), (2, 1)), None),  # explicit pad
            (2, 9, 9, 1, 8, 3, 3, 1, "SAME", None),       # C=1, packed taps
            (1, 11, 11, 1, 8, 3, 3, 2, "VALID", None),    # C=1, stride 2
            (1, 34, 34, 3, 16, 3, 3, 1, "VALID", None),   # the stem's C=3, 2 row chunks
            (1, 64, 64, 3, 16, 3, 3, 2, "SAME", None),    # C=3, stride 2, 2 row chunks
            (1, 48, 48, 3, 8, 3, 3, 1, "SAME", (24, 48)), # tiled, 3 chunks of 8 rows
            (1, 8, 8, 16, 8, 3, 3, 1, "SAME", None),      # K = 3·3·16 = 144 > 128
            (1, 12, 12, 15, 8, 3, 3, 1, "SAME", None),    # K = 135, just over a lane tile
            (1, 8, 8, 33, 8, 2, 2, 1, "VALID", None),     # K = 132, even kernel
            (1, 10, 10, 9, 8, 5, 3, 1, "SAME", None),     # K = 135, 5x3 kernel
            (1, 32, 32, 3, 8, 7, 7, 2, "SAME", None),     # 7x7x3 stem, K = 147
            (1, 32, 32, 64, 8, 3, 3, 1, "SAME", None),    # K = 576, 2 row chunks
            (1, 8, 8, 128, 8, 3, 3, 1, "SAME", None),     # K = 1152, lane-aligned taps
            (2, 12, 12, 32, 8, 3, 3, 2, "SAME", None),    # K = 288, stride 2
            (1, 16, 16, 14, 8, 3, 3, 1, "SAME", (8, 16)), # K = 126, tiled
        ],
    )
    def test_allclose_vs_lax(self, n, h, w, c, f, kh, kw, stride, padding, tiles):
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        x = jax.random.normal(k1, (n, h, w, c), jnp.float32)
        wk = jax.random.normal(k2, (kh, kw, c, f), jnp.float32)
        th, tw = tiles or (None, None)
        got = im2col_conv(
            x, wk, stride=stride, padding=padding, bf=8, tile_h=th, tile_w=tw
        )
        want = ref.conv_lax_ref(x, wk, stride=stride, padding=padding)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), **TOLS[jnp.float32]
        )

    @pytest.mark.parametrize(
        "c,stride,padding,h",
        [(1, 1, "SAME", 10), (1, 2, "VALID", 10), (3, 1, "VALID", 10),
         (3, 2, "SAME", 10), (3, 1, "SAME", 40),  # 40: 4 chunks of 10 rows
         (16, 1, "SAME", 10)],  # 3·3·16 = 144: K over one lane tile
    )
    def test_fused_epilogue_int8_vs_lax(self, c, stride, padding, h):
        """bias + ReLU + requantize, as the stem runs it. Summation order may
        differ from the oracle's, so a code may be one off, but only where
        the fp32 value sits within 1e-5 of a rounding boundary."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
        x = jax.random.normal(k1, (2, h, h, c), jnp.float32)
        wk = jax.random.normal(k2, (3, 3, c, 16), jnp.float32)
        bias = jax.random.normal(k3, (16,), jnp.float32)
        y = ref.conv_lax_ref(x, wk, stride=stride, padding=padding) + bias
        out_scale = float(jnp.max(y)) / 127  # calibrated to the max
        got = im2col_conv(x, wk, bias=bias, relu=True, out_scale=out_scale,
                          stride=stride, padding=padding, bf=8)
        assert got.dtype == jnp.int8
        v = np.asarray(jnp.maximum(y, 0) / out_scale)
        off = np.abs(np.asarray(got, np.int32) - np.clip(np.round(v), -127, 127))
        near = np.abs(np.abs(v - np.floor(v)) - 0.5) < 1e-5
        assert off.max() <= 1 and np.all(near[off == 1])

    @pytest.mark.parametrize(
        "stride,kh", [(2, 3), (1, 2)]  # strided + even kernel, bf16 numerics
    )
    def test_allclose_vs_lax_bf16(self, stride, kh):
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        x = jax.random.normal(k1, (1, 8, 8, 8), jnp.float32).astype(jnp.bfloat16)
        wk = jax.random.normal(k2, (kh, kh, 8, 8), jnp.float32).astype(jnp.bfloat16)
        got = im2col_conv(x, wk, stride=stride, bf=8)
        want = ref.conv_lax_ref(x, wk, stride=stride)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **TOLS[jnp.bfloat16]
        )

    def test_explicit_im2col_ref_matches_lax(self):
        k1, k2 = jax.random.split(jax.random.PRNGKey(2))
        x = jax.random.normal(k1, (2, 9, 9, 4), jnp.float32)
        wk = jax.random.normal(k2, (3, 3, 4, 8), jnp.float32)
        got = ref.im2col_conv_ref(x, wk, stride=2, padding="SAME")
        want = ref.conv_lax_ref(x, wk, stride=2, padding="SAME")
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TestFusedSparseConv:
    """Acceptance sweep: vdbb_im2col_conv == lax.conv(dbb_decode(w)) across
    pattern-sharing modes × nnz × strided and spatially-tiled shapes."""

    @pytest.mark.parametrize("group", ["matrix", None])
    @pytest.mark.parametrize("nnz", [1, 4, 8])
    @pytest.mark.parametrize(
        "n,h,w,c,f,kh,kw,stride,tiles",
        [
            (1, 8, 8, 8, 16, 3, 3, 1, None),      # baseline SAME stride-1
            (2, 9, 9, 16, 8, 3, 3, 2, None),      # strided
            (1, 12, 12, 8, 16, 3, 3, 1, (4, 6)),  # spatially tiled
        ],
    )
    def test_allclose_vs_decode_conv(self, group, nnz, n, h, w, c, f, kh, kw, stride, tiles):
        x, dw, fmt = _mk_conv(n, h, w, c, f, kh, kw, nnz, group)
        th, tw = tiles or (None, None)
        got = vdbb_im2col_conv(x, dw, kh, kw, stride=stride, bf=8, tile_h=th, tile_w=tw)
        want = ref.conv_lax_ref(
            x, dbb_decode_conv(dw, kh, kw).astype(x.dtype), stride=stride
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
        )

    @pytest.mark.parametrize("group", ["matrix", None, 4])
    def test_even_kernel_valid_bf16(self, group):
        x, dw, fmt = _mk_conv(1, 8, 8, 8, 8, 2, 2, 3, group, dtype=jnp.bfloat16)
        got = ops.sparse_conv(x, dw, 2, 2, stride=2, padding="VALID", bf=8, interpret=True)
        want = ref.sparse_conv_ref(x, dw, 2, 2, stride=2, padding="VALID")
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **TOLS[jnp.bfloat16]
        )

    @pytest.mark.slow
    def test_tiling_sweep(self):
        """Interpret-mode sweep over tile shapes (DESIGN.md §6 tiling)."""
        x, dw, fmt = _mk_conv(1, 12, 12, 16, 16, 3, 3, 4, "matrix", seed=7)
        want = ref.sparse_conv_ref(x, dw, 3, 3)
        for th, tw in [(2, 2), (3, 12), (12, 4), (6, 6)]:
            got = vdbb_im2col_conv(x, dw, 3, 3, bf=8, tile_h=th, tile_w=tw)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4,
                err_msg=f"tile {(th, tw)}",
            )

    def test_rejects_block_straddling_taps(self):
        x, dw, _ = _mk_conv(1, 8, 8, 8, 8, 3, 3, 4, "matrix")
        bad = dataclasses.replace(dw, shape=(9 * 4, 8))  # C=4 not % bz=8
        with pytest.raises(ValueError, match="straddle"):
            vdbb_im2col_conv(x, bad, 3, 3)


def _int8_conv(n, h, c, f, k, nnz, seed):
    """int8 codes and a 3/8-style int8 compressed weight with power-of-two
    scales, so every fp32 flush below is exact whatever order its
    multiply-adds run in."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.randint(k1, (n, h, h, c), -127, 128).astype(jnp.int8)
    w4 = jax.random.normal(k2, (k, k, c, f), jnp.float32)
    qw = quantize_dbb(dbb_encode_conv(w4, DBBFormat(8, nnz, "matrix"), prune=True))
    scales = 2.0 ** -jax.random.randint(k3, (f,), 6, 10).astype(jnp.float32)
    bias = jax.random.randint(k4, (f,), -64, 64).astype(jnp.float32) / 4
    return x, QuantDBBWeight(qw.values, qw.indices, scales, qw.fmt, qw.shape), bias


class TestPackedTcConv:
    """The tc conv muxes each tap once per chunk of output rows and contracts
    the packed compressed patch in one dot over all of F: on int8 operands
    it equals the exact int32 oracle bit for bit, for every flush."""

    @pytest.mark.parametrize(
        "n,h,c,f,k,stride,nnz,flush,bf",
        [
            (2, 8, 64, 64, 3, 1, 3, "raw", None),         # Ck = 24
            (2, 9, 64, 128, 3, 2, 1, "requant", None),    # Ck = 8, stride 2
            (1, 32, 64, 64, 3, 1, 3, "raw", None),        # two 512-pixel chunks
            (1, 8, 128, 128, 3, 1, 3, "requant", None),   # Ck = 48
            (2, 8, 128, 64, 3, 2, 4, "raw", None),        # Ck = 64, stride 2
            (1, 4, 512, 128, 3, 1, 3, "requant", None),   # Ck = 192
            (1, 6, 512, 128, 3, 2, 1, "requant", None),   # Ck = 64, stride 2
            (1, 24, 64, 64, 3, 1, 4, "res_requant", None),  # residual over 2 chunks
            (1, 8, 128, 128, 3, 2, 4, "res_fp32", None),  # the fp32-out _res flush
            (1, 8, 64, 128, 3, 1, 3, "fp32", None),       # dequant + bias, fp32 out
            (1, 32, 64, 128, 1, 1, 4, "res_requant", None),  # 1×1 _res, one chunk
            (1, 8, 64, 256, 3, 1, 3, "requant", 128),     # explicit bf < F
        ],
        ids=["c64_ck24", "c64_ck8_s2", "c64_chunks", "c128_ck48", "c128_ck64_s2",
             "c512_ck192", "c512_ck64_s2", "res_q_chunks", "res_fp32", "fp32",
             "1x1_res", "bf128_of_256"],
    )
    def test_int8_bit_exact_vs_int32_oracle(self, n, h, c, f, k, stride, nnz, flush, bf):
        x, qw, bias = _int8_conv(n, h, c, f, k, nnz, seed=h + c + nnz)
        pad = (k // 2, k // 2)
        padding = (pad, pad)
        acc = ref.sparse_conv_int_ref(x, qw.as_dbb(), k, k, stride=stride, padding=padding)
        if flush == "raw":
            got = ops.sparse_conv(x, qw.as_dbb(), k, k, stride=stride, padding=padding,
                                  bf=bf, interpret=True)
            assert got.dtype == jnp.int32
            np.testing.assert_array_equal(np.asarray(got), np.asarray(acc))
            return
        act = jnp.float32(0.25)
        res = res_scale = None
        if flush.startswith("res"):
            res = jax.random.randint(jax.random.PRNGKey(5), acc.shape, -127, 128).astype(jnp.int8)
            res_scale = jnp.float32(0.5)
        out_scale = None
        if flush.endswith("requant"):  # a power of two near the 99th percentile / 127
            y = ref.quant_epilogue_ref(acc, act * qw.scales, bias=bias, residual=res,
                                       residual_scale=res_scale)
            out_scale = float(2.0 ** np.round(np.log2(np.percentile(np.abs(y), 99) / 127)))
        got = ops.quant_conv(x, qw, k, k, act, bias=bias, relu=flush != "fp32",
                             out_scale=out_scale, residual=res, residual_scale=res_scale,
                             stride=stride, padding=padding, bf=bf, interpret=True)
        want = ref.quant_epilogue_ref(acc, act * qw.scales, bias=bias, relu=flush != "fp32",
                                      out_scale=out_scale, residual=res,
                                      residual_scale=res_scale)
        assert got.dtype == want.dtype == (jnp.float32 if out_scale is None else jnp.int8)
        if out_scale is not None:  # the codes span the int8 range, few clipped
            codes = np.abs(np.asarray(want, np.int32))
            assert codes.max() == 127 and np.mean(codes == 127) < 0.05
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestGroupedRoundTrip:
    """dbb_encode/dbb_decode round-trip with grouped (int g) patterns."""

    @pytest.mark.parametrize("group", [2, 4, "matrix", None])
    def test_round_trip(self, group):
        fmt = DBBFormat(8, 3, group)
        k, n = 64, 16
        w = jax.random.normal(jax.random.PRNGKey(0), (k, n))
        from repro.core.vdbb import dbb_prune

        pruned = dbb_prune(w, fmt)
        assert satisfies_dbb(pruned, fmt)
        dw = dbb_encode(pruned, fmt)
        back = dbb_decode(dw)
        np.testing.assert_allclose(np.asarray(back), np.asarray(pruned), rtol=0, atol=0)

    def test_conv_round_trip(self):
        fmt = DBBFormat(8, 4, None)
        w4 = jax.random.normal(jax.random.PRNGKey(3), (3, 3, 16, 8))
        dw = dbb_encode_conv(w4, fmt, prune=True)
        back = dbb_decode_conv(dw, 3, 3)
        assert back.shape == w4.shape
        # decoded weight satisfies the constraint and keeps kept values exact
        assert satisfies_dbb(back.reshape(-1, 8), fmt)
        mask = np.asarray(back) != 0
        np.testing.assert_allclose(np.asarray(back)[mask], np.asarray(w4)[mask])


class TestDBBConv2dLayer:
    @pytest.mark.parametrize("group", ["matrix", None])
    def test_lifecycle_constrain_compress_serve(self, group):
        fmt = DBBFormat(8, 3, group)
        layer = DBBConv2d(16, 8, kernel_size=3, stride=2, fmt=fmt, use_bias=True)
        params = layer.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 9, 16))
        params = layer.constrain(params)
        kh, kw = layer.kh, layer.kw
        w2 = params["w"].reshape(kh * kw * 16, 8)
        assert satisfies_dbb(w2, fmt)
        y_dense = layer(params, x)
        served = layer.compress_params(params)
        y_ref = layer(served, x)
        y_pallas = dataclasses.replace(layer, kernel_mode="pallas")(served, x)
        np.testing.assert_allclose(
            np.asarray(y_ref), np.asarray(y_dense), rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(y_pallas), np.asarray(y_ref), rtol=1e-4, atol=1e-4
        )

    def test_sparse_cnn_end_to_end(self):
        from repro.configs import smoke_cnn_config
        from repro.models.cnn import SparseCNN

        cfg = smoke_cnn_config("sparse-cnn-tiny")
        model = SparseCNN(cfg)
        params = model.constrain(model.init(jax.random.PRNGKey(0)))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, cfg.image_size, cfg.image_size, 3))
        served = model.compress(params)
        y_ref = model(served, x)
        y_pl = SparseCNN(dataclasses.replace(cfg, kernel_mode="pallas"))(served, x)
        assert y_ref.shape == (2, cfg.num_classes)
        np.testing.assert_allclose(
            np.asarray(y_pl), np.asarray(y_ref), rtol=1e-4, atol=1e-4
        )


class TestConvCosts:
    def test_combined_accounting(self):
        fmt = DBBFormat(8, 2, "matrix")
        c = dbb_conv_costs(1, 32, 32, 64, 128, 3, 3, fmt)
        assert c["speedup"] == 4.0
        assert c["im2col_magnification"] == pytest.approx(9.0)
        assert c["combined_reduction"] == pytest.approx(36.0)
        assert c["act_bytes"] == c["act_bytes_raw"]
        stored = dbb_conv_costs(1, 32, 32, 64, 128, 3, 3, fmt, im2col_unit=False)
        assert stored["act_bytes"] == stored["act_bytes_expanded"]
        # strided conv: expansion ratio shrinks with the output map
        s2 = dbb_conv_costs(1, 32, 32, 64, 128, 3, 3, fmt, stride=2)
        assert s2["im2col_magnification"] < c["im2col_magnification"]

    def test_conv_roofline_row(self):
        from benchmarks.roofline import conv_roofline_row

        fmt = DBBFormat(8, 3, "matrix")
        row = conv_roofline_row(8, 32, 32, 64, 128, 3, 3, fmt)
        assert row["bound_reduction"] > 1.0
        assert row["dominant"] in ("compute", "memory")
