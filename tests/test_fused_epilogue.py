"""Fused epilogue + INT8-resident activations (DESIGN.md §9).

Bottom-up: the ``quant_epilogue_ref`` integer oracle; every bias/ReLU/
out_scale combination of the fused epilogue bit-exact against it across
the tc/bw matmul and fused conv kernels (interpret mode — the code that
compiles for TPU); the dense-stem epilogue; ``pick_tile`` default-tile
fallback; the head GEMM following ``cfg.kernel_mode`` with the tiny-M
reference fallback; the int8-resident SparseCNN chain (inter-layer
dtypes + agreement with the PR-3 per-layer-dequant path); and the
``epilogue_fused`` cost accounting.
"""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.quant import QuantDBBWeight
from repro.core.sparse_linear import DBBLinear
from repro.core.vdbb import (
    DBBFormat,
    dbb_conv_costs,
    dbb_encode,
    dbb_encode_conv,
    dbb_gemm_costs,
)
from repro.kernels import core, ops, ref


def _gemm_case(group, m=16, k=64, n=32, nnz=3, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = jax.random.normal(k1, (m, k))
    w = jax.random.normal(k2, (k, n))
    b = jax.random.normal(k3, (n,))
    fmt = DBBFormat(8, nnz, group)
    qw = quant.quantize_dbb(dbb_encode(w, fmt, prune=True))
    s_a = quant.dynamic_act_scale(a)
    return a, quant.quantize(a, s_a), s_a, b, qw


# ---------------------------------------------------------------------------
# the oracle itself
# ---------------------------------------------------------------------------


class TestEpilogueRef:
    def test_dataflow_order_and_dtypes(self):
        acc = jnp.array([[-300, 100], [50, -50]], jnp.int32)
        scale = jnp.array([0.01, 0.02], jnp.float32)
        bias = jnp.array([1.0, -1.0], jnp.float32)
        # dequant only
        y = ref.quant_epilogue_ref(acc, scale)
        np.testing.assert_allclose(np.asarray(y), [[-3.0, 2.0], [0.5, -1.0]])
        # + bias + relu
        y = ref.quant_epilogue_ref(acc, scale, bias=bias, relu=True)
        np.testing.assert_allclose(np.asarray(y), [[0.0, 1.0], [1.5, 0.0]])
        # + requant: int8 codes in ±127
        q = ref.quant_epilogue_ref(acc, scale, bias=bias, relu=True, out_scale=0.5)
        assert q.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(q), [[0, 2], [3, 0]])

    def test_requant_clips_to_qmax(self):
        acc = jnp.array([[10_000_000, -10_000_000]], jnp.int32)
        q = ref.quant_epilogue_ref(acc, jnp.float32(1.0), out_scale=1.0)
        np.testing.assert_array_equal(np.asarray(q), [[127, -127]])


# ---------------------------------------------------------------------------
# fused kernels bit-exact against the oracle, all epilogue combinations
# ---------------------------------------------------------------------------

COMBOS = [
    (has_b, relu, has_q)
    for has_b, relu, has_q in itertools.product([False, True], repeat=3)
    if has_b or relu or has_q  # the bare-scales case is PR-3 coverage
]


def _check(got, want):
    if want.dtype == jnp.int8:
        assert got.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7
        )


class TestMatmulEpilogue:
    @pytest.mark.parametrize("group", ["matrix", None])
    @pytest.mark.parametrize("has_b,relu,has_q", COMBOS)
    def test_bit_exact_vs_oracle(self, group, has_b, relu, has_q):
        a, aq, s_a, b, qw = _gemm_case(group)
        bias = b if has_b else None
        out_s = 0.07 if has_q else None
        got = ops.quant_matmul(
            a, qw, s_a, bias=bias, relu=relu, out_scale=out_s,
            bm=8, bn=16, kb=2, interpret=True,
        )
        acc = quant.int_matmul_ref(aq, ref.dbb_decode(qw.as_dbb()))
        want = ref.quant_epilogue_ref(
            acc, s_a * qw.scales, bias=bias, relu=relu, out_scale=out_s
        )
        _check(got, want)

    def test_int8_resident_input_matches_fp_input(self):
        """Passing the already-quantized codes + scale == quantizing inside."""
        a, aq, s_a, b, qw = _gemm_case("matrix", seed=3)
        kw = dict(bias=b, relu=True, out_scale=0.05, bm=8, bn=16, kb=2,
                  interpret=True)
        np.testing.assert_array_equal(
            np.asarray(ops.quant_matmul(aq, qw, s_a, **kw)),
            np.asarray(ops.quant_matmul(a, qw, s_a, **kw)),
        )

    def test_int8_input_requires_scale(self):
        _, aq, _, _, qw = _gemm_case("matrix")
        with pytest.raises(ValueError, match="act_scale"):
            ops.quant_matmul(aq, qw, interpret=True)

    def test_fp_path_bias_relu_fused(self):
        """The fp (non-quantized) kernels fuse bias/ReLU too."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
        a = jax.random.normal(k1, (16, 64))
        w = jax.random.normal(k2, (64, 32))
        b = jax.random.normal(k3, (32,))
        dw = dbb_encode(w, DBBFormat(8, 4, "matrix"), prune=True)
        got = ops.vdbb_matmul(a, dw, bias=b, relu=True, bm=8, bn=16, kb=2,
                              interpret=True)
        want = jnp.maximum(ref.dbb_matmul_ref(a, dw) + b, 0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


class TestConvEpilogue:
    @pytest.mark.parametrize("group,stride", [("matrix", 1), (None, 2)])
    @pytest.mark.parametrize("has_b,relu,has_q", COMBOS)
    def test_bit_exact_vs_oracle(self, group, stride, has_b, relu, has_q):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
        x = jax.random.normal(k1, (2, 8, 8, 8))
        w4 = jax.random.normal(k2, (3, 3, 8, 16))
        b = jax.random.normal(k3, (16,))
        qw = quant.quantize_dbb(
            dbb_encode_conv(w4, DBBFormat(8, 3, group), prune=True)
        )
        s_a = quant.dynamic_act_scale(x)
        xq = quant.quantize(x, s_a)
        bias = b if has_b else None
        out_s = 0.05 if has_q else None
        got = ops.quant_conv(
            x, qw, 3, 3, s_a, bias=bias, relu=relu, out_scale=out_s,
            stride=stride, bf=8, interpret=True,
        )
        acc = ref.sparse_conv_int_ref(xq, qw.as_dbb(), 3, 3, stride=stride)
        want = ref.quant_epilogue_ref(
            acc, s_a * qw.scales, bias=bias, relu=relu, out_scale=out_s
        )
        _check(got, want)

    def test_dense_stem_epilogue(self):
        """The dense im2col kernel's fused epilogue == its own fp32 output
        pushed through the same (standalone) epilogue ops — bit-exact."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
        x = jax.random.normal(k1, (2, 8, 8, 3))
        w4 = jax.random.normal(k2, (3, 3, 3, 16))
        b = jax.random.normal(k3, (16,))
        base = ops.fused_im2col_conv(x, w4, bf=8, interpret=True)
        got = ops.fused_im2col_conv(
            x, w4, bias=b, relu=True, out_scale=0.04, bf=8, interpret=True
        )
        want = quant.quantize(jnp.maximum(base + b, 0), 0.04)
        assert got.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_sparse_conv_fp_bias_relu(self):
        x, k2 = jax.random.normal(jax.random.PRNGKey(8), (1, 8, 8, 8)), None
        w4 = jax.random.normal(jax.random.PRNGKey(9), (3, 3, 8, 16))
        b = jax.random.normal(jax.random.PRNGKey(10), (16,))
        dw = dbb_encode_conv(w4, DBBFormat(8, 4, "matrix"), prune=True)
        got = ops.sparse_conv(x, dw, 3, 3, bias=b, relu=True, bf=8, interpret=True)
        want = jnp.maximum(ref.sparse_conv_ref(x, dw, 3, 3) + b, 0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# pick_tile: default tiles fall back to the largest dividing size
# ---------------------------------------------------------------------------


class TestPickTile:
    def test_values(self):
        assert core.pick_tile(200, 128) == 100
        assert core.pick_tile(96, 128) == 96
        assert core.pick_tile(128, 128) == 128
        assert core.pick_tile(7, 4) == 1
        assert core.pick_tile(320, 256) == 160
        # prime dim: one full tile, never a pathological 1-wide grid
        assert core.pick_tile(257, 128) == 257

    def test_resolve_tile_stays_strict(self):
        with pytest.raises(ValueError, match="does not tile"):
            core.resolve_tile(48, 32, "bm")

    def test_default_tiles_on_odd_shapes(self):
        """Shapes that used to raise at the default tiles now run."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        a = jax.random.normal(k1, (200, 64))  # bm=128 did not divide 200
        w = jax.random.normal(k2, (64, 320))  # bn=256 did not divide 320
        dw = dbb_encode(w, DBBFormat(8, 4, "matrix"), prune=True)
        got = ops.vdbb_matmul(a, dw, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.dbb_matmul_ref(a, dw)),
            rtol=1e-4, atol=1e-4,
        )

    def test_explicit_bad_tile_pads_at_ops_strict_in_kernel(self):
        """§10 pad-to-tile: a non-dividing explicit tile no longer raises
        at the ops layer — the ragged M edge is zero-padded and sliced
        back off, bit-identically (int8 path: exact int32 accumulation).
        The kernel-level wrappers keep the strict contract."""
        a, aq, _, _, qw = _gemm_case("matrix")
        got = ops.vdbb_matmul(aq, qw.as_dbb(), bm=5, interpret=True)
        want = ops.vdbb_matmul(aq, qw.as_dbb(), interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

        from repro.kernels.vdbb_matmul import vdbb_matmul_tc

        with pytest.raises(ValueError, match="does not tile"):
            vdbb_matmul_tc(aq, qw.values, qw.indices[:, :, 0], qw.fmt, bm=5)


# ---------------------------------------------------------------------------
# ragged shapes: pad-and-slice stays bit-exact, and never fires when the
# shapes already divide (DESIGN.md §12)
# ---------------------------------------------------------------------------


class _SpyJnp:
    """Forwards every attribute to the real jnp, counting ``pad`` calls —
    installed over ``ops.jnp`` so a trace through the dispatch layer
    reveals whether the pad-and-slice escape hatch actually fired."""

    def __init__(self):
        self.pad_calls = 0

    def __getattr__(self, name):
        attr = getattr(jnp, name)
        if name == "pad":
            def counted(*a, **k):
                self.pad_calls += 1
                return attr(*a, **k)
            return counted
        return attr


class TestRaggedShapes:
    @pytest.mark.parametrize("m,n", [(7, 10), (7, 130), (67, 10), (67, 130)])
    def test_fused_matmul_ragged_mn_bit_exact(self, m, n):
        """Non-dividing M and N with the full fused epilogue: the padded
        rows/columns (including the padded out_scale columns) slice away
        bit-exactly against the integer oracle."""
        k = 56  # 7 K-blocks at bz=8: the default kb must handle it too
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
        a = jax.random.normal(k1, (m, k))
        w = jax.random.normal(k2, (k, n))
        b = jax.random.normal(k3, (n,))
        fmt = DBBFormat(8, 3, "matrix")
        qw = quant.quantize_dbb(dbb_encode(w, fmt, prune=True))
        s_a = quant.dynamic_act_scale(a)
        got = ops.quant_matmul(
            a, qw, s_a, bias=b, relu=True, out_scale=0.06,
            bm=16, bn=32, interpret=True,  # neither divides m/n
        )
        acc = quant.int_matmul_ref(quant.quantize(a, s_a),
                                   ref.dbb_decode(qw.as_dbb()))
        want = ref.quant_epilogue_ref(acc, s_a * qw.scales, bias=b,
                                      relu=True, out_scale=0.06)
        assert got.shape == (m, n) and got.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("stride", [1, 2])
    def test_fused_conv_odd_spatial_bit_exact(self, stride):
        """Odd spatial dims (15x15, stride 1/2) through the fused conv
        epilogue: conv tiles resolve to exact divisors (no padding path)
        and stay bit-exact against the oracle."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(12), 3)
        x = jax.random.normal(k1, (2, 15, 15, 8))
        w4 = jax.random.normal(k2, (3, 3, 8, 16))
        b = jax.random.normal(k3, (16,))
        qw = quant.quantize_dbb(
            dbb_encode_conv(w4, DBBFormat(8, 3, "matrix"), prune=True))
        s_a = quant.dynamic_act_scale(x)
        got = ops.quant_conv(x, qw, 3, 3, s_a, bias=b, relu=True,
                             out_scale=0.05, stride=stride, interpret=True)
        acc = ref.sparse_conv_int_ref(quant.quantize(x, s_a), qw.as_dbb(),
                                      3, 3, stride=stride)
        want = ref.quant_epilogue_ref(acc, s_a * qw.scales, bias=b,
                                      relu=True, out_scale=0.05)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_pad_tile_unit_contract(self):
        # dividing: no padding, requested tile honored
        assert core.pad_tile(64, 32, 128) == (32, 64)
        assert core.pad_tile(64, None, 128) == (64, 64)
        assert core.pick_tile_padded(128, 128, 128) == (128, 128)
        # ragged: padded up to the next tile multiple
        assert core.pad_tile(67, 16, 128) == (16, 80)
        # oversized explicit tile clamps to the dimension
        assert core.pad_tile(10, 64, 128) == (10, 10)

    def test_no_pad_when_shapes_divide(self, monkeypatch):
        """When every launch dim divides its tile, the dispatch layer must
        not touch ``jnp.pad`` at all — fresh shapes force a retrace with a
        spy installed over ``ops.jnp``."""
        spy = _SpyJnp()
        monkeypatch.setattr(ops, "jnp", spy)
        k1, k2 = jax.random.split(jax.random.PRNGKey(13))
        a = jax.random.normal(k1, (24, 64))
        w = jax.random.normal(k2, (64, 48))
        fmt = DBBFormat(8, 3, "matrix")
        qw = quant.quantize_dbb(dbb_encode(w, fmt, prune=True))
        s_a = quant.dynamic_act_scale(a)
        y = ops.quant_matmul(a, qw, s_a, bias=jnp.zeros(48), relu=True,
                             out_scale=0.05, bm=8, bn=16, kb=2,
                             interpret=True)
        assert y.shape == (24, 48)
        assert spy.pad_calls == 0

        # positive control on another fresh shape: a ragged M does pad
        a2 = jax.random.normal(k1, (23, 64))
        y2 = ops.quant_matmul(a2, qw, s_a, bm=8, bn=16, kb=2, interpret=True)
        assert y2.shape == (23, 48)
        assert spy.pad_calls > 0


# ---------------------------------------------------------------------------
# model: head kernel mode + the int8-resident chain
# ---------------------------------------------------------------------------


def _model(kernel_mode="ref", batch=8):
    from repro.configs import smoke_cnn_config
    from repro.models.cnn import SparseCNN

    cfg = smoke_cnn_config("sparse-cnn-tiny", sparsity=0.625)
    # two convs per stage so compressed→compressed int8 edges exist
    cfg = dataclasses.replace(cfg, convs_per_stage=2, kernel_mode=kernel_mode)
    model = SparseCNN(cfg)
    params = model.compress(model.init(jax.random.PRNGKey(0)))
    x = jax.random.normal(
        jax.random.PRNGKey(1),
        (batch, cfg.image_size, cfg.image_size, cfg.in_channels),
    )
    return model, params, x


def _unfused_reference(model, qparams, x):
    """The PR-3 per-layer path: fp32 dequant → ReLU between every layer."""
    layers = model.layers()
    for i, m in enumerate(layers[:-1]):
        x = jax.nn.relu(m(qparams[f"l{i}"], x))
    return layers[-1](qparams[f"l{len(layers) - 1}"], x.mean(axis=(1, 2)))


class TestHeadKernelMode:
    def test_head_follows_cfg(self):
        model, _, _ = _model("pallas")
        assert model.layers()[-1].kernel_mode == "pallas"

    def test_tiny_m_falls_back_to_ref(self):
        """Below the MXU sublane the pallas head uses the jnp reference —
        bit-identical to an explicit ref layer."""
        fmt = DBBFormat(8, 3, "matrix")
        ref_layer = DBBLinear(64, 10, fmt=fmt, use_bias=True, kernel_mode="ref")
        pl_layer = dataclasses.replace(ref_layer, kernel_mode="pallas")
        params = ref_layer.compress_params(ref_layer.init(jax.random.PRNGKey(0)))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))  # M=4 < 8
        np.testing.assert_array_equal(
            np.asarray(pl_layer(params, x)), np.asarray(ref_layer(params, x))
        )

    def test_pallas_head_matches_ref_at_mxu_m(self):
        fmt = DBBFormat(8, 3, "matrix")
        ref_layer = DBBLinear(64, 16, fmt=fmt, use_bias=True, kernel_mode="ref")
        pl_layer = dataclasses.replace(ref_layer, kernel_mode="pallas")
        params = ref_layer.compress_params(ref_layer.init(jax.random.PRNGKey(0)))
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
        np.testing.assert_allclose(
            np.asarray(pl_layer(params, x)), np.asarray(ref_layer(params, x)),
            rtol=1e-4, atol=1e-4,
        )


class TestInt8ResidentCNN:
    @pytest.mark.parametrize("mode", ["ref", "pallas"])
    def test_matches_per_layer_dequant_path(self, mode):
        """The one-kernel-per-layer chain agrees with the PR-3 unfused
        path within the documented 1% relative L2 (identical fp32 math →
        in practice bit-near-exact)."""
        model, params, x = _model(mode)
        _, stats = model.apply(params, x, collect_act_stats=True)
        qparams = model.quantize(params, stats)
        got = model.apply(qparams, x)
        want = _unfused_reference(model, qparams, x)
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert rel < 0.01, rel

    def test_inter_layer_activations_are_int8(self):
        """Acceptance: zero standalone fp32 tensors between compressed
        layers — every inter-layer activation (stem→l1, l1→l2, ...) is
        int8 codes; only the last conv flushes fp32 into the pooling."""
        model, params, x = _model("ref")
        _, stats = model.apply(params, x, collect_act_stats=True)
        qparams = model.quantize(params, stats)
        seen = []
        logits = model.apply(qparams, x, intermediates=seen)
        n_convs = len(model.layers()) - 1
        assert len(seen) == n_convs
        for t in seen[:-1]:  # every edge that feeds a compressed conv
            assert t.dtype == jnp.int8, t.dtype
        assert seen[-1].dtype == jnp.float32  # fp32 flush into GAP
        assert logits.dtype == jnp.float32

    def test_uncalibrated_params_fall_back(self):
        """Dynamic quantization (no ``aq``) cannot chain statically — the
        fp per-layer path runs and intermediates stay fp32."""
        model, params, x = _model("ref")
        qdyn = model.quantize(params)  # no calibration
        seen = []
        logits = model.apply(qdyn, x, intermediates=seen)
        assert all(t.dtype == jnp.float32 for t in seen)
        assert bool(jnp.all(jnp.isfinite(logits)))

    def test_chain_matches_fp32_within_tolerance(self):
        """End-to-end sanity at the documented §8 bound."""
        model, params, x = _model("ref")
        logits_fp, stats = model.apply(params, x, collect_act_stats=True)
        logits_q = model.apply(model.quantize(params, stats), x)
        rel = float(
            jnp.linalg.norm(logits_q - logits_fp) / jnp.linalg.norm(logits_fp)
        )
        assert rel < 0.05, rel


# ---------------------------------------------------------------------------
# cost accounting
# ---------------------------------------------------------------------------


class TestEpilogueCosts:
    def test_fused_drops_epilogue_traffic(self):
        fmt = DBBFormat(8, 3, "matrix")
        unfused = dbb_gemm_costs(256, 288, 64, fmt, bits=8, act_bits=8)
        fused = dbb_gemm_costs(256, 288, 64, fmt, bits=8, act_bits=8,
                               epilogue_fused=True)
        assert unfused["epilogue_bytes"] > 0 and not unfused["epilogue_fused"]
        assert fused["epilogue_bytes"] == 0 and fused["epilogue_fused"]
        # int8 flush is a quarter of the fp32/int32 one
        assert fused["out_bytes"] * 4 == unfused["out_bytes"]

    def test_conv_layer_total_reduction(self):
        """Acceptance: ≥25% lower modeled HBM bytes per conv layer."""
        fmt = DBBFormat(8, 3, "matrix")
        kw = dict(bits=8, act_bits=8)
        for shape in [(4, 16, 16, 32, 64, 3, 3), (2, 32, 32, 64, 128, 3, 3)]:
            unf = dbb_conv_costs(*shape, fmt, **kw)
            fus = dbb_conv_costs(*shape, fmt, epilogue_fused=True, **kw)

            def total(c):
                return (c["act_bytes"] + c["weight_bytes"] + c["out_bytes"]
                        + c["epilogue_bytes"])

            assert total(fus) <= 0.75 * total(unf), (total(fus), total(unf))

    def test_conv_workload_surfaces_epilogue_traffic(self):
        """The flag reaches the energy-model tables: conv_workload carries
        out/epilogue bytes and a total that shrinks when fused."""
        from repro.core.energy_model import PARETO_DESIGN, conv_workload

        fmt = DBBFormat(8, 3, "matrix")
        unf = conv_workload(
            PARETO_DESIGN, dbb_conv_costs(4, 16, 16, 32, 64, 3, 3, fmt), fmt
        )
        fus = conv_workload(
            PARETO_DESIGN,
            dbb_conv_costs(4, 16, 16, 32, 64, 3, 3, fmt, epilogue_fused=True),
            fmt,
        )
        assert fus["epilogue_fused"] and not unf["epilogue_fused"]
        assert fus["epilogue_bytes"] == 0 < unf["epilogue_bytes"]
        assert fus["hbm_bytes_total"] < 0.75 * unf["hbm_bytes_total"]
